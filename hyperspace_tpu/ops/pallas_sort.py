"""Pallas TPU kernels for the in-bucket sort (hash-mode padded reps).

`bucket_join._pad_and_sort` sorts each padded bucket row with `jnp.argsort`
(XLA variadic sort), a bitonic network whose every stage round-trips HBM.
These kernels keep a whole [TB, cap] bucket group resident in VMEM and run
the complete bitonic network in one `pallas_call` — a single HBM read +
write per element regardless of the network's O(log² cap) stages. That trade
only exists while the block fits VMEM, so the dispatcher gates on cap (pow2
by construction — `_cap_pow2`).

Formulation: the compare-exchange at stride j pairs lane i with lane i ^ j.
The partner row is two lane rotations (by j and by cap - j) and an iota
select, so the network needs no reshape (Mosaic refuses the [TB, m, 2, j]
shape cast). The rotation direction is read off a rotated iota rather than
assumed. Stages run as `fori_loop`s with a dynamic rotate amount, so the
kernel body is one stage whatever the cap (an unrolled network takes minutes
to compile at cap 4096). The state lives in the output refs between stages.

Keys are 64-bit, pre-split OUTSIDE the kernel into the same lexicographic
(hi, lo) int32 pair the probe kernel uses (no 64-bit values on the VPU). The
row-index payload rides the exchanges, so the kernel returns both sorted
keys and the argsort permutation in one pass.

Bitonic networks are NOT stable; equal keys land in arbitrary order. That is
sound here by the same argument as hash collisions: the probe emits the whole
equal-key RANGE and verification compares actual values, so any permutation
within an equal run yields the identical pair set.

Equivalence with `jnp.argsort` is pinned by tests/test_pallas_sort.py
(interpret mode off-TPU); tests/test_tpu_compile.py compiles the kernels for
a described v5e. A wanted kernel that fails raises.
"""

from __future__ import annotations

import os
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ENV_KEY = "HYPERSPACE_PALLAS_SORT"
# VMEM budget: 3 int32 payloads (hi, lo, idx) x in/out, double-buffered, plus
# stage temps. TB=8 rows of cap=32768 fits the default 16 MiB scoped VMEM of
# a v5e (tests/test_tpu_compile.py); 65536 would not, so the gate stops here.
_MAX_CAP = 32768
_MIN_CAP = 256  # below this the dispatch overhead beats any fusion win

from ..telemetry.compile_log import observed_jit as _observed_jit


def _bitonic_refs(refs, less):
    """In-place bitonic network over the LAST axis of the [TB, n] VMEM refs.
    `less(a, b)` orders tuples of loaded rows (keys first; trailing rows are
    payload that rides the exchanges)."""
    tb, n = refs[0].shape
    log_n = n.bit_length() - 1
    pos = jax.lax.broadcasted_iota(jnp.int32, (tb, n), 1)

    def stage(k, j):
        xs = [r[...] for r in refs]
        # Which of the two rotations holds lane i ^ j, decided by rotating
        # the iota itself (independent of the rotate's sign convention).
        from_a = pltpu.roll(pos, j, 1) == (pos ^ j)
        ps = [
            jnp.where(from_a, pltpu.roll(x, j, 1), pltpu.roll(x, n - j, 1))
            for x in xs
        ]
        # Lane i keeps the min of its pair when it is the pair's lower lane
        # of an ascending run, or the upper lane of a descending run.
        want_min = ((pos & j) == 0) == ((pos & k) == 0)
        take = (want_min & less(ps, xs)) | (~want_min & less(xs, ps))
        for r, x, p in zip(refs, xs, ps):
            r[...] = jnp.where(take, p, x)

    # int32 loop bounds: under jax_enable_x64 Python-int bounds give int64
    # induction variables, which Mosaic cannot lower.
    one = jnp.int32(1)

    def merge(kk, carry):
        k = jnp.left_shift(one, kk)

        def step(t, c):
            stage(k, jnp.right_shift(k, t + one))
            return c

        return jax.lax.fori_loop(jnp.int32(0), kk, step, carry)

    jax.lax.fori_loop(one, jnp.int32(log_n + 1), merge, jnp.int32(0))


def _pairs_lt(a, b):
    """64-bit (hi, lo) lexicographic signed compare on row tuples: a < b."""
    return (a[0] < b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))


def _single_lt(a, b):
    return a[0] < b[0]


def _sort_kernel(h_ref, l_ref, i_ref, ho_ref, lo_ref, io_ref):
    ho_ref[...] = h_ref[...]
    lo_ref[...] = l_ref[...]
    io_ref[...] = i_ref[...]
    _bitonic_refs((ho_ref, lo_ref, io_ref), _pairs_lt)


def _bucket_tile(B: int) -> int:
    """Same legality rule as the probe kernel: 8-row groups when divisible,
    whole axis otherwise (equal-to-dimension)."""
    return 8 if B % 8 == 0 else B


def shape_supported(B: int, cap: int) -> bool:
    if B <= 0 or cap < _MIN_CAP or cap > _MAX_CAP:
        return False
    if cap & (cap - 1):
        return False  # bitonic needs pow2 (guaranteed by _cap_pow2 upstream)
    tb = _bucket_tile(B)
    if tb > 8 and B > 8:
        return False  # whole-axis block beyond 8 rows would blow VMEM
    return True


@_observed_jit(label="pallas.sort", static_argnums=(3,))
def _sort_pallas_call(hi, lo, idx, interpret: bool):
    B, cap = hi.shape
    TB = _bucket_tile(B)
    grid = (B // TB,)
    spec = pl.BlockSpec((TB, cap), lambda b: (b, jnp.int32(0)))
    return pl.pallas_call(
        _sort_kernel,
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, cap), jnp.int32),
            jax.ShapeDtypeStruct((B, cap), jnp.int32),
            jax.ShapeDtypeStruct((B, cap), jnp.int32),
        ],
        interpret=interpret,
    )(hi, lo, idx)


@_observed_jit(label="pallas.sort_recombine")
def _recombine(hi, lo):
    """(hi, lo) int32 pair → the original int64 keys (undo `_split_hi_lo`)."""
    h = hi.astype(jnp.int64) << 32
    l = (lo.astype(jnp.int64) + jnp.int64(0x80000000)) & jnp.int64(0xFFFFFFFF)
    return h | l


def sort_padded_with_order(keys_i64) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Drop-in replacement for the argsort+gather inside `_pad_and_sort`:
    returns (sorted_keys int64 [B, cap], order int32 [B, cap]) where
    `sorted[b, s] == keys[b, order[b, s]]`. Equal keys may permute (bitonic
    is unstable) — sound for the join, see the module docstring."""
    from .pallas_probe import _split_hi_lo

    keys_i64 = jnp.asarray(keys_i64)
    B, cap = keys_i64.shape
    hi, lo = _split_hi_lo(keys_i64)
    idx = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32)[None, :], (B, cap))
    interpret = jax.default_backend() != "tpu"
    sh, sl, order = _sort_pallas_call(hi, lo, idx, interpret)
    return _recombine(sh, sl), order


# --- sort on sub-byte code lanes ----------------------------------------------
#
# Sub-byte dictionary codes (`engine/packed_codes.py`) don't need the 64-bit
# (hi, lo, idx) triple: a biased code (< 16) and its slot index (< cap <=
# 32768) TOGETHER fit one int32 composite, comp = (code << log2 cap) | slot.
# Comps are UNIQUE (slot bits), so the unstable bitonic reproduces the STABLE
# argsort of the code matrix exactly — and the network moves one int32 lane
# instead of three, a third of the VMEM traffic of `_sort_kernel`. The words
# are unpacked by XLA before the kernel: an in-kernel unpack materialises a
# [TB, words, lanes-per-word] intermediate whose minor axis pads to 128
# lanes, which overflows VMEM at cap 32768 with 4-bit codes.


def _comp_pallas(v, interpret: bool):
    B, cap = v.shape
    TB = _bucket_tile(B)
    spec = pl.BlockSpec((TB, cap), lambda b: (b, jnp.int32(0)))
    return pl.pallas_call(
        _sort_comp_kernel,
        grid=(B // TB,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, cap), jnp.int32),
        interpret=interpret,
    )(v)


def _sort_comp_kernel(v_ref, o_ref):
    o_ref[...] = v_ref[...]
    _bitonic_refs((o_ref,), _single_lt)


@_observed_jit(label="pallas.sort_comp", static_argnums=(1,))
def sort_comp_padded(v, interpret: bool):
    """Single-lane int32 bitonic over [B, cap] composite rows (build-side
    bucket|code|row composites — `partition.pallas_packed_build_sort`). The
    caller owns the composite encoding; this just sorts rows ascending."""
    return _comp_pallas(v, interpret)


@_observed_jit(label="pallas.sort_codes", static_argnums=(1,))
def _sort_codes_call(lanes, interpret: bool):
    B, cap = lanes.shape
    log2cap = cap.bit_length() - 1
    slot = jax.lax.broadcasted_iota(jnp.int32, (B, cap), 1)
    comp = _comp_pallas((lanes.astype(jnp.int32) << log2cap) | slot, interpret)
    return comp >> log2cap, comp & (cap - 1)


def sort_code_rows(lanes) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sort each padded bucket row of a BIASED-code int32 matrix [B, cap]:
    (sorted_biased_codes int32 [B, cap], order int32 [B, cap]) with
    `sorted[b, s] == codes[b, order[b, s]]`. Pad slots must hold the top lane
    value (2**bits - 1 — `probe_bits_for_cardinality` keeps it above every
    real biased code), so pads sort last like the int64 path's pad key.
    Matches `jnp.argsort` EXACTLY including ties (comp uniqueness => stable)."""
    return _sort_codes_call(jnp.asarray(lanes), jax.default_backend() != "tpu")


def sort_codes_packed(words, bits: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """`sort_code_rows` over a packed biased-code WORD matrix [B, cap/lpw]."""
    from ..engine.packed_codes import unpack_rows_traced

    return sort_code_rows(unpack_rows_traced(jnp.asarray(words), bits))


def pallas_packed_sort_wanted(B: int, cap: int, bits: int) -> bool:
    """Gate for the code-lane sort: the ordinary sort gate plus whole-word
    rows."""
    if cap % (32 // bits):
        return False
    return pallas_sort_wanted(B, cap)


def pallas_sort_wanted(B: int, cap: int) -> bool:
    """Dispatch decision: forced by env (1/0), else auto on TPU within the
    VMEM shape budget."""
    mode = os.environ.get(_ENV_KEY, "auto")
    if mode == "0":
        return False
    if not shape_supported(B, cap):
        return False
    if mode == "1":
        return True
    return jax.default_backend() == "tpu"
