"""Device mesh helpers.

The framework's distribution axis is "buckets": index data is hash-partitioned into
`num_buckets` buckets, and on a mesh each device owns a contiguous bucket block. Both
the build's all-to-all exchange and the co-bucketed join's zero-communication
execution ride this one axis (ICI within a slice, DCN across slices — the axis order
in `jax.devices()` already reflects the platform's topology).
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

BUCKET_AXIS = "buckets"

#: Minimum rows a device program shape is quantized to (env-tunable). Every
#: mesh program's row dimension — exchange shard rows, send-matrix capacity,
#: probe block width, padded bucket capacity — is ceil'd to a power of two
#: AND floored at this quantum, so each device program compiles exactly once
#: per pow2 workload class and small workloads all share ONE class. 1024 rows
#: of int64 is 8 KiB/lane — noise on any device, and it keeps the warm
#: program set tiny for the persistent compilation cache.
ENV_ROW_QUANTUM = "HYPERSPACE_MESH_ROW_QUANTUM"
_DEFAULT_ROW_QUANTUM = 1024


def mesh_row_quantum() -> int:
    try:
        q = int(os.environ.get(ENV_ROW_QUANTUM, _DEFAULT_ROW_QUANTUM))
    except ValueError:
        return _DEFAULT_ROW_QUANTUM
    if q < 1:
        return _DEFAULT_ROW_QUANTUM
    # The quantum itself must be a power of two (it is a shape class bound).
    return 1 << (q - 1).bit_length()


def quantize_cap(n: int) -> int:
    """Pow2-quantize a per-device capacity, floored at the mesh row quantum."""
    return 1 << (max(int(n), mesh_row_quantum()) - 1).bit_length()


def quantized_rows(num_rows: int, n_dev: int) -> int:
    """The padded GLOBAL row count for `num_rows` rows on an `n_dev` mesh: each
    device's shard is the same pow2-quantized size, so the exchange programs
    (whose traced shapes are the shard sizes) compile once per workload class
    instead of once per exact row count — the fix for the r05 failure mode
    (a 2400 s compile inside an unquantized-shape device program)."""
    per = -(-max(int(num_rows), 1) // n_dev)  # ceil division
    return quantize_cap(per) * n_dev


def force_virtual_cpu(n_devices: int = 8) -> None:
    """Force jax onto a virtual n-device CPU platform BEFORE first backend init.

    Sets both the env defaults and the already-created jax config (jax reads
    the env only when it is first imported). Used by the test harness
    (conftest) and the driver's `dryrun_multichip` entry point.

    The process stays CPU-pinned afterwards (a jax backend cannot be re-selected
    once initialized); the env mutations are reverted after init so child
    processes are unaffected.
    """
    import os
    import re

    old_flags = os.environ.get("XLA_FLAGS")
    old_platforms = os.environ.get("JAX_PLATFORMS")
    stripped = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "", old_flags or ""
    ).strip()
    os.environ["XLA_FLAGS"] = (
        stripped + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
    avail = len(jax.devices())  # initializes the backend under our flags

    for key, old in (("XLA_FLAGS", old_flags), ("JAX_PLATFORMS", old_platforms)):
        if old is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = old

    if avail < n_devices:
        raise RuntimeError(
            f"virtual CPU platform has {avail} devices (need {n_devices}): the jax "
            "backend was already initialized before force_virtual_cpu ran"
        )


def make_mesh(num_devices: Optional[int] = None) -> Mesh:
    devices = jax.devices()
    n = num_devices or len(devices)
    if n > len(devices):
        raise ValueError(f"Requested {n} devices; only {len(devices)} available.")
    return Mesh(np.asarray(devices[:n]), (BUCKET_AXIS,))


def row_sharding(mesh: Mesh) -> NamedSharding:
    """Rows sharded over the mesh (axis 0)."""
    return NamedSharding(mesh, PartitionSpec(BUCKET_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())
