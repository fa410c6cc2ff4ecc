"""Pallas TPU kernel for the co-bucketed range probe.

The XLA path (`bucket_join._probe`) vmaps `jnp.searchsorted` over the bucket
axis. This kernel recasts the probe as the VPU-friendly identity

    searchsorted(sorted_row, key, 'left')  == count(row <  key)
    searchsorted(sorted_row, key, 'right') == count(row <= key)

computed as tiled broadcast-compare + reductions: grid (bucket-group,
left-tile, right-tile), each step compares [TB, TL] left keys against [TB, TR]
right keys as a 3D broadcast and accumulates the two counts. No gathers, no
dynamic shapes — exactly the shape of work Mosaic schedules well; the block
shapes honor Mosaic's (x8, x128)-or-equal-to-dim tiling rule
(tests/test_tpu_compile.py compiles it for a described v5e). The per-bucket
merge this
implements is what the reference gets from SortMergeJoinExec over co-bucketed
index scans (`JoinIndexRule.scala:137-162`).

Key dtype: 64-bit keys (hash mode is int64; value mode is promoted) do not
exist on the TPU VPU, so keys are pre-split OUTSIDE the kernel into a
lexicographic (hi, lo) int32 pair whose signed compare reproduces the 64-bit
order (floats go through the standard order-preserving bit transform first,
with -0.0 canonicalized to +0.0 so searchsorted equality classes survive).

Cost note: the tiled compare is O(cap_l * cap_r) per bucket vs the XLA path's
O(cap_l * log cap_r); it wins on dispatch/fusion for small-to-medium buckets
and loses asymptotically on very large ones, so `probe_ranges` dispatches by
capacity product (override with HYPERSPACE_PALLAS_PROBE=1/0). Equivalence with
the XLA path is pinned by tests/test_pallas_probe.py (interpret mode off-TPU).
A wanted kernel that fails raises.
"""

from __future__ import annotations

import os
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_ENV_KEY = "HYPERSPACE_PALLAS_PROBE"
# Auto-dispatch budget on TOTAL compare ops (B * cap_l * cap_r): the tiled
# compare is quadratic per bucket, so it wins below this (measured on v5e:
# 64x4096x512 = 2^27 ops -> 91 ms Pallas vs 176 ms XLA probe) and loses to
# XLA's log-probe above it; 2^28 gives the measured win point 2x headroom
# without admitting shapes whose linear scaling clearly loses.
_AUTO_MAX_OPS = 1 << 28
from ..telemetry.compile_log import observed_jit as _observed_jit


def _pallas_mode() -> str:
    return os.environ.get(_ENV_KEY, "auto")


def _sortable_i64(x: jnp.ndarray) -> jnp.ndarray:
    """Order-preserving map of any 64-bit key space into signed int64."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(jnp.float64) + 0.0  # canonicalize -0.0
        bits = jax.lax.bitcast_convert_type(x, jnp.int64)
        # Negative floats: flip magnitude bits (reverses their order, keeps
        # sign); positives: unchanged. Signed compare == float total order.
        return bits ^ ((bits >> 63) & jnp.int64(0x7FFFFFFFFFFFFFFF))
    return x.astype(jnp.int64)


def _split_hi_lo(k: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(hi, lo) int32 pair whose lexicographic signed compare == int64 compare."""
    hi = (k >> 32).astype(jnp.int32)
    lo = ((k & jnp.int64(0xFFFFFFFF)) - jnp.int64(0x80000000)).astype(jnp.int32)
    return hi, lo


def _split_hi_lo_float(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Float keys → the kernel's (hi, lo) int32 pair WITHOUT any 64-bit bitcast.

    The order-preserving transform `bits ^ ((bits >> 63) & 0x7FFF…)` is
    computed on the two 32-bit words of `bitcast f64->s32[..,2]` (word 0 = low bits, word 1 = high bits):
    the sign mask comes from the high word's arithmetic shift, the magnitude
    flip applies to the low word in full and to the high word below the sign
    bit, and the lo word gets the same signed-compare bias `_split_hi_lo`
    applies. Equivalence with the 64-bit transform is pinned by
    tests/test_pallas_probe.py."""
    x = x.astype(jnp.float64) + 0.0  # canonicalize -0.0
    words = jax.lax.bitcast_convert_type(x, jnp.int32)
    lo, hi = words[..., 0], words[..., 1]
    mask = hi >> 31  # all-ones for negative floats, zero otherwise
    hi = hi ^ (mask & jnp.int32(0x7FFFFFFF))
    lo = lo ^ mask
    lo = lo ^ jnp.int32(-0x80000000)  # unsigned->signed bias, as a flip
    return hi, lo


def _probe_kernel(lh_ref, ll_ref, rh_ref, rl_ref, lo_ref, hi_ref):
    """One (bucket-group, left-tile, right-tile) step: accumulate lt/le counts.

    Blocks carry TB buckets at once — Mosaic requires the last two block dims
    to be (x8, x128)-divisible or equal to the array dims, so per-bucket
    (1, TL) blocks are illegal; (TB, TL) blocks with the bucket axis widened
    to TB=8 (or the whole axis when B<8) satisfy it. The compare runs as a 3D
    broadcast [TB, TL, 1] x [TB, 1, TR] with a lane-axis reduction."""
    lhv = lh_ref[...][:, :, None]  # [TB, TL, 1]
    llv = ll_ref[...][:, :, None]
    rhv = rh_ref[...][:, None, :]  # [TB, 1, TR]
    rlv = rl_ref[...][:, None, :]
    # r < key  /  r <= key, 64-bit order via the (hi, lo) int32 pair.
    r_lt_k = (rhv < lhv) | ((rhv == lhv) & (rlv < llv))
    r_eq_k = (rhv == lhv) & (rlv == llv)
    lt_counts = jnp.sum(r_lt_k, axis=2, dtype=jnp.int32)  # [TB, TL]
    le_counts = lt_counts + jnp.sum(r_eq_k, axis=2, dtype=jnp.int32)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        lo_ref[...] = jnp.zeros_like(lo_ref)
        hi_ref[...] = jnp.zeros_like(hi_ref)

    lo_ref[...] += lt_counts
    hi_ref[...] += le_counts


def _bucket_tile(B: int) -> int:
    """Bucket-axis block size: 8 when divisible (the min int32 sublane tile),
    else the whole axis (equal-to-dimension is the other legal shape)."""
    return 8 if B % 8 == 0 else B


def _tiles(cap_l: int, cap_r: int):
    """The (TL, TR) tile sizes — ONE home, shared by shape_supported and the
    pallas_call so they cannot drift."""
    return min(cap_l, 256), min(cap_r, 512)


def shape_supported(B: int, cap_l: int, cap_r: int) -> bool:
    """Shapes this kernel can lower: bucket axis tileable, caps tile-multiples
    (guaranteed for _cap_pow2 caps), and a bounded VMEM compare block."""
    if B <= 0:
        return False
    tb = _bucket_tile(B)
    if tb > 8 and B > 8:  # non-multiple-of-8 bucket count > 8: whole-axis
        # block would blow VMEM; let the XLA path take it.
        return False
    tl, tr = _tiles(cap_l, cap_r)
    return cap_l % tl == 0 and cap_r % tr == 0


@_observed_jit(label="pallas.probe", static_argnums=(4,))
def _probe_pallas_call(lh, ll, rh, rl, interpret: bool):
    B, cap_l = lh.shape
    cap_r = rh.shape[1]
    TB = _bucket_tile(B)
    TL, TR = _tiles(cap_l, cap_r)
    # Caps reaching this kernel are _cap_pow2-shaped; guard loudly so a future
    # non-multiple cap cannot silently skip tail tiles (unwritten output blocks).
    assert B % TB == 0 and cap_l % TL == 0 and cap_r % TR == 0, (B, cap_l, cap_r)
    grid = (B // TB, cap_l // TL, cap_r // TR)
    left_spec = pl.BlockSpec((TB, TL), lambda b, i, j: (b, i))
    right_spec = pl.BlockSpec((TB, TR), lambda b, i, j: (b, j))
    out_spec = pl.BlockSpec((TB, TL), lambda b, i, j: (b, i))
    lo, hi = pl.pallas_call(
        _probe_kernel,
        grid=grid,
        in_specs=[left_spec, left_spec, right_spec, right_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, cap_l), jnp.int32),
            jax.ShapeDtypeStruct((B, cap_l), jnp.int32),
        ],
        interpret=interpret,
    )(lh, ll, rh, rl)
    return lo, hi


def probe_pallas(ls, rs, l_len, r_len) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Drop-in replacement for `bucket_join._probe`: (lo, counts) int32, with
    ranges clamped to each right bucket's valid length and counts zeroed for
    left pad slots."""
    ls, rs = jnp.asarray(ls), jnp.asarray(rs)
    if jnp.issubdtype(ls.dtype, jnp.floating):
        lh, ll = _split_hi_lo_float(ls)
        rh, rl = _split_hi_lo_float(rs)
    else:
        lh, ll = _split_hi_lo(_sortable_i64(ls))
        rh, rl = _split_hi_lo(_sortable_i64(rs))
    interpret = jax.default_backend() != "tpu"
    lo, hi = _probe_pallas_call(lh, ll, rh, rl, interpret)
    r_len_b = jnp.asarray(r_len)[:, None]
    lo = jnp.minimum(lo, r_len_b).astype(jnp.int32)
    hi = jnp.minimum(hi, r_len_b)
    valid_left = jnp.arange(ls.shape[1])[None, :] < jnp.asarray(l_len)[:, None]
    counts = jnp.where(valid_left, hi - lo, 0).astype(jnp.int32)
    return lo, counts


# --- probe on PACKED sub-byte code words -------------------------------------
#
# Dictionary codes below int8 ship and persist as big-endian uint32 lane words
# (`engine/packed_codes.py`). A biased code takes at most 2**bits <= 16
# values, so a bucket's probe is a histogram, not a merge: with
# c[v] = #{right lanes == v}, searchsorted-left of v is the prefix sum of c
# below v and searchsorted-right the prefix sum through v. The kernel reads
# the WORD matrices (bits-per-code HBM traffic), extracts lanes with
# shift/mask, and does O(cap) work per bucket. Grid (bucket-group, left
# tile, right tile): the right tiles accumulate the histogram in VMEM scratch
# and the last one emits the left tile's ranges, so every block is bounded
# whatever the caps. Right-side order is irrelevant to a count, so its lanes
# are extracted one lane position at a time; left results are written
# lane-position-major ([lpw, B, words]) because an in-kernel interleave back
# to slot order is a shape cast Mosaic refuses — the wrapper's XLA transpose
# restores slot order.

_PACKED_TILE_WORDS = 1024  # words per left/right tile (a multiple of 128)


def _packed_tiles(wl: int, wr: int):
    """The (left, right) tile widths in words — ONE home, shared by
    packed_shape_supported and the pallas_call."""
    return min(wl, _PACKED_TILE_WORDS), min(wr, _PACKED_TILE_WORDS)


def packed_shape_supported(B: int, cap_l: int, cap_r: int, bits: int) -> bool:
    """Shapes the packed kernel can lower: bucket axis tileable, whole-word
    caps that are tile multiples (guaranteed for _cap_pow2 caps). VMEM use is
    bounded by the tiles, not by the caps."""
    lpw = 32 // bits
    if B <= 0 or cap_l % lpw or cap_r % lpw:
        return False
    if _bucket_tile(B) > 8:
        return False
    wl, wr = cap_l // lpw, cap_r // lpw
    tl, tr = _packed_tiles(wl, wr)
    return wl % tl == 0 and wr % tr == 0


def _probe_packed_kernel(lw_ref, rw_ref, lo_ref, hi_ref, cnt_ref, *, bits, n_r):
    # Every scalar is an explicit 32-bit value: under jax_enable_x64 a Python
    # int in the kernel (a literal, a ref index) traces as int64, which Mosaic
    # cannot lower.
    lpw = 32 // bits
    nv = 1 << bits
    mask = jnp.uint32(nv - 1)
    shifts = [jnp.uint32(32 - bits * (k + 1)) for k in range(lpw)]
    values = [jnp.int32(v) for v in range(nv)]
    j = pl.program_id(2)

    @pl.when(j == jnp.int32(0))
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    rw = rw_ref[...]
    counts = [None] * nv
    for s in shifts:
        lane = ((rw >> s) & mask).astype(jnp.int32)
        for v in range(nv):
            c = jnp.sum(lane == values[v], axis=1, keepdims=True, dtype=jnp.int32)
            counts[v] = c if counts[v] is None else counts[v] + c
    for v in range(nv):
        cnt_ref[jnp.int32(v)] += counts[v]

    @pl.when(j == jnp.int32(n_r - 1))
    def _emit():
        below = [jnp.zeros_like(counts[0])]  # below[v] = #{right lanes < v}
        for v in range(nv):
            below.append(below[-1] + cnt_ref[jnp.int32(v)])
        lw = lw_ref[...]
        for k, s in enumerate(shifts):
            lane = ((lw >> s) & mask).astype(jnp.int32)
            lt = jnp.zeros(lane.shape, jnp.int32)
            le = jnp.zeros(lane.shape, jnp.int32)
            for v in range(nv):
                hit = lane == values[v]
                lt = jnp.where(hit, below[v], lt)
                le = jnp.where(hit, below[v + 1], le)
            lo_ref[jnp.int32(k)] = lt
            hi_ref[jnp.int32(k)] = le


@_observed_jit(label="pallas.probe_packed", static_argnums=(2, 3))
def _probe_packed_call(lw, rw, bits: int, interpret: bool):
    import functools

    from jax.experimental.pallas import tpu as pltpu

    B, wl = lw.shape
    wr = rw.shape[1]
    lpw = 32 // bits
    TB = _bucket_tile(B)
    TLW, TRW = _packed_tiles(wl, wr)
    assert B % TB == 0 and wl % TLW == 0 and wr % TRW == 0, (B, wl, wr)
    n_r = wr // TRW
    out_spec = pl.BlockSpec((lpw, TB, TLW), lambda b, i, j: (jnp.int32(0), b, i))
    out_shape = jax.ShapeDtypeStruct((lpw, B, wl), jnp.int32)
    lo, hi = pl.pallas_call(
        functools.partial(_probe_packed_kernel, bits=bits, n_r=n_r),
        grid=(B // TB, wl // TLW, n_r),
        in_specs=[
            pl.BlockSpec((TB, TLW), lambda b, i, j: (b, i)),
            pl.BlockSpec((TB, TRW), lambda b, i, j: (b, j)),
        ],
        out_specs=[out_spec, out_spec],
        out_shape=[out_shape, out_shape],
        scratch_shapes=[pltpu.VMEM((1 << bits, TB, 1), jnp.int32)],
        interpret=interpret,
    )(lw, rw)
    # [lpw, B, words] -> slot order [B, words * lpw] (slot = word * lpw + k).
    return (
        lo.transpose(1, 2, 0).reshape(B, wl * lpw),
        hi.transpose(1, 2, 0).reshape(B, wl * lpw),
    )


def probe_packed_pallas(l_words, r_words, bits: int, l_len, r_len):
    """`probe_pallas` over packed BIASED-code word matrices: (lo, counts)
    int32. Both sides must share the same bits class and hold sorted biased
    codes with pad slots at the top lane value (2**bits - 1, which
    `probe_bits_for_cardinality` reserves above every real biased code, so
    pads sort last and the r_len clamp excises them)."""
    lo, hi = _probe_packed_call(
        jnp.asarray(l_words),
        jnp.asarray(r_words),
        bits,
        jax.default_backend() != "tpu",
    )
    r_len_b = jnp.asarray(r_len)[:, None]
    lo = jnp.minimum(lo, r_len_b).astype(jnp.int32)
    hi = jnp.minimum(hi, r_len_b)
    cap_l = lo.shape[1]
    valid_left = jnp.arange(cap_l)[None, :] < jnp.asarray(l_len)[:, None]
    counts = jnp.where(valid_left, hi - lo, 0).astype(jnp.int32)
    return lo, counts


def pallas_packed_probe_wanted(
    cap_l: int, cap_r: int, num_buckets: int, bits: int
) -> bool:
    """Dispatch decision for the packed probe: forced on/off by env, else
    on-TPU within the ordinary probe's capacity-product bound. Shapes the
    kernel cannot lower (`packed_shape_supported`) take the XLA path."""
    mode = _pallas_mode()
    if mode == "0":
        return False
    if not packed_shape_supported(num_buckets, cap_l, cap_r, bits):
        return False
    if mode == "1":
        return True
    return (
        jax.default_backend() == "tpu"
        and num_buckets * cap_l * cap_r <= _AUTO_MAX_OPS
    )


def pallas_probe_wanted(cap_l: int, cap_r: int, num_buckets: int) -> bool:
    """Dispatch decision for `probe_ranges`: forced on/off by env, else on-TPU
    with a capacity-product bound (the quadratic-compare budget). Shapes the
    kernel cannot lower (see `shape_supported`) always take the XLA path.
    Float value-mode keys ride the kernel via the pure-32-bit split
    (`_split_hi_lo_float`)."""
    mode = _pallas_mode()
    if mode == "0":
        return False
    if not shape_supported(num_buckets, cap_l, cap_r):
        return False
    if mode == "1":
        return True
    return (
        jax.default_backend() == "tpu"
        and num_buckets * cap_l * cap_r <= _AUTO_MAX_OPS
    )
