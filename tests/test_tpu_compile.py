"""Compile every Pallas kernel for a described TPU v5e, without a chip.

Interpret mode (the other kernel tests) cannot show what Mosaic refuses: a
shape cast it cannot lay out, a slice off the (8, 128) tiling, a 64-bit scalar
under `jax_enable_x64`, a block that overflows VMEM. These compiles can, at
the main path's real widths (`chip_smoke.py`: 6M ⋈ 1.5M rows over 64
buckets). The topology is described inside a fixture, never at import, so
only the pytest worker that runs this file loads libtpu.
"""

import os

import pytest

import jax
import jax.numpy as jnp

import hyperspace_tpu.engine  # noqa: F401  (jax_enable_x64, as in production)
from hyperspace_tpu.ops import pallas_probe as pp
from hyperspace_tpu.ops import pallas_sort as ps


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


def _compile(observed, *args):
    compiled = observed._hyperspace_jitted.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize(
    "B,cap_l,cap_r", [(64, 131072, 32768), (200, 65536, 8192), (64, 131072, 32), (64, 4096, 1024)]
)
def test_probe_compiles(shape, B, cap_l, cap_r):
    _compile(
        pp._probe_pallas_call,
        shape((B, cap_l)),
        shape((B, cap_l)),
        shape((B, cap_r)),
        shape((B, cap_r)),
        False,
    )


@pytest.mark.parametrize("bits", [1, 2, 4])
@pytest.mark.parametrize("B,cap_l,cap_r", [(256, 4096, 4096), (64, 256, 32768)])
def test_packed_probe_compiles(shape, bits, B, cap_l, cap_r):
    lpw = 32 // bits
    _compile(
        pp._probe_packed_call,
        shape((B, cap_l // lpw), jnp.uint32),
        shape((B, cap_r // lpw), jnp.uint32),
        bits,
        False,
    )


@pytest.mark.parametrize("bits", [1, 2, 4])
@pytest.mark.parametrize("side", ["left", "right"])
def test_packed_probe_compiles_at_gate_limit(shape, bits, side):
    """The widest caps the auto gate admits over 64 buckets: one side a single
    word, the other B * cap_l * cap_r = the ops budget (cap 524288 at 4
    bits). The kernel's blocks are tiled, so VMEM stays bounded."""
    B, lpw = 64, 32 // bits
    wide = pp._AUTO_MAX_OPS // (B * lpw)
    cap_l, cap_r = (wide, lpw) if side == "left" else (lpw, wide)
    assert pp.packed_shape_supported(B, cap_l, cap_r, bits)
    _compile(
        pp._probe_packed_call,
        shape((B, cap_l // lpw), jnp.uint32),
        shape((B, cap_r // lpw), jnp.uint32),
        bits,
        False,
    )


@pytest.mark.parametrize("B,cap", [(8, 32768), (1, 32768), (256, 4096)])
def test_sort_compiles(shape, B, cap):
    _compile(ps._sort_pallas_call, shape((B, cap)), shape((B, cap)), shape((B, cap)), False)


@pytest.mark.parametrize("B,cap", [(8, 32768), (1, 32768)])
def test_sort_comp_compiles(shape, B, cap):
    _compile(ps.sort_comp_padded, shape((B, cap)), False)


@pytest.mark.parametrize("B,cap", [(256, 4096), (8, 32768)])
def test_code_sort_compiles(shape, B, cap):
    _compile(ps._sort_codes_call, shape((B, cap)), False)
