"""Batched co-bucketed merge join: ALL bucket pairs joined in one device program.

The co-bucketed sort-merge join (reference `JoinIndexRule.scala:137-162`: equal keys
are co-located in equal-numbered buckets, so no shuffle is needed) must not be executed
as a Python loop over buckets — B small per-bucket dispatches with distinct shapes
defeat XLA. Instead the bucket axis becomes a *batch dimension*:

1. Scatter each side's per-row key64 into a padded [B, cap] matrix (pad = i64 max).
2. One batched sort along the row axis (pads sort to the end).
3. One batched searchsorted probe (vmap), ranges clamped to each bucket's valid length.
4. Two-pass expansion (count → scalar sync → scatter) exactly like the global join.

Static shapes throughout; the bucket axis is also the natural shard axis on a device
mesh (each device owns a contiguous bucket range and never communicates).
"""

from __future__ import annotations

import os
import time

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import device_observatory as _devobs
from ..telemetry.compile_log import observed_jit as _observed_jit

_PAD = jnp.iinfo(jnp.int64).max

#: Size-classed padding (the skew-aware layout) is the default; ``=0`` restores
#: the single global-cap dense layout exactly as it was.
ENV_SIZE_CLASSES = "HYPERSPACE_JOIN_SIZE_CLASSES"
#: A bucket whose larger side exceeds ``factor × median`` of the active
#: buckets' larger sides leaves the padded layout entirely and merges on host
#: (per bucket). ``<=0`` disables the outlier path.
ENV_OUTLIER_FACTOR = "HYPERSPACE_JOIN_OUTLIER_FACTOR"
_DEFAULT_OUTLIER_FACTOR = 8.0
# Cap on the number of capacity classes: beyond this the per-class dispatch
# (and, on the device path, per-shape compiles) start eating the padding win.
_MAX_CLASSES = 8


def size_classes_enabled() -> bool:
    """Default ON; unset defers to the adaptive planner's per-query decision
    when one is ambient — explicit flags always win (`docs/planner.md`)."""
    raw = os.environ.get(ENV_SIZE_CLASSES, "")
    if raw != "":
        return raw != "0"
    from ..plananalysis.planner import decided_value

    decided = decided_value("join_size_classes")
    return True if decided is None else bool(decided)


def _outlier_factor() -> float:
    raw = os.environ.get(ENV_OUTLIER_FACTOR, "")
    try:
        return float(raw) if raw else _DEFAULT_OUTLIER_FACTOR
    except ValueError:
        return _DEFAULT_OUTLIER_FACTOR


def _cap_pow2(n: int) -> int:
    """Quantize a padded-bucket capacity to the next power of two: growing data
    reuses the compiled kernels instead of recompiling per exact max bucket size."""
    return 1 << (max(1, n) - 1).bit_length()


def mesh_probe_skew_safe(l_starts, r_starts) -> bool:
    """Whether the MESH-sharded co-bucketed probe should claim this bucket
    layout. The sharded probe pads every bucket to the GLOBAL max bucket
    length (one [B_local, cap] matrix per device) — a single outlier bucket
    multiplies every device's probe area, exactly the skew blowup JSPIM
    measures and the PR-3 size-classed executor exists to avoid. Reuses the
    classed executor's own outlier criterion (larger side > factor × median
    of active larger sides): skewed layouts stay on the size-classed
    single-device path; balanced layouts take the mesh. Disabled size
    classes (=0) always answer True — with the skew machinery off there is
    no better fallback to protect."""
    if not size_classes_enabled():
        return True
    l_lens = np.diff(np.asarray(l_starts, np.int64))
    r_lens = np.diff(np.asarray(r_starts, np.int64))
    n = min(len(l_lens), len(r_lens))
    l_lens, r_lens = l_lens[:n], r_lens[:n]
    active = np.nonzero((l_lens > 0) & (r_lens > 0))[0]
    if len(active) == 0:
        return True
    factor = _outlier_factor()
    if factor <= 0:
        return True
    mx = np.maximum(l_lens, r_lens)[active]
    return bool(mx.max(initial=0) <= factor * max(float(np.median(mx)), 1.0))


@_observed_jit(label="bucket_join.pad_scatter", static_argnums=(2, 3))
def _pad_scatter(keys, starts, num_buckets: int, cap: int):
    """Scatter per-row keys (concatenated in bucket order) into an UNSORTED
    padded [B, cap] matrix (pad = i64 max) + per-bucket lengths — the input
    shape the Pallas in-VMEM sort consumes."""
    n = keys.shape[0]
    pos = jnp.arange(n)
    b_of_row = jnp.searchsorted(starts, pos, side="right") - 1
    slot = pos - starts[b_of_row]
    padded = jnp.full((num_buckets, cap), _PAD, dtype=jnp.int64)
    padded = padded.at[b_of_row, slot].set(keys)
    lengths = starts[1:] - starts[:-1]
    return padded, lengths


@_observed_jit(label="bucket_join.pad_and_sort", static_argnums=(2, 3))
def _pad_and_sort(keys, starts, num_buckets: int, cap: int):
    """Scatter per-row keys (concatenated in bucket order) into a sorted [B, cap]
    matrix. Returns (sorted_keys [B,cap], order [B,cap] slot→original-slot, lengths).
    ONE scatter implementation: composes `_pad_scatter` (jit nests fine), so the
    Pallas and XLA paths can never diverge on the bucket-mapping semantics."""
    padded, lengths = _pad_scatter(keys, starts, num_buckets, cap)
    order = jnp.argsort(padded, axis=1)
    sorted_keys = jnp.take_along_axis(padded, order, axis=1)
    return sorted_keys, order, lengths


@_observed_jit(label="bucket_join.probe")
def _probe(ls, rs, l_len, r_len):
    """Batched range probe: for each left slot, the [lo, hi) match range in the
    right bucket, clamped to valid rows; counts zeroed for left pad slots.
    int32 outputs (slots/counts are bounded by cap): halves the device→host
    transfer the expansion consumes."""
    lo = jax.vmap(lambda r, l: jnp.searchsorted(r, l, side="left"))(rs, ls)
    hi = jax.vmap(lambda r, l: jnp.searchsorted(r, l, side="right"))(rs, ls)
    r_len_b = r_len[:, None]
    lo = jnp.minimum(lo, r_len_b)
    hi = jnp.minimum(hi, r_len_b)
    valid_left = jnp.arange(ls.shape[1])[None, :] < l_len[:, None]
    counts = jnp.where(valid_left, hi - lo, 0)
    return lo.astype(jnp.int32), counts.astype(jnp.int32)


def _probe_host(L, R, l_len, r_len):
    """Host twin of `_probe` for the CPU backend: per-bucket `np.searchsorted`
    over the valid regions (XLA-CPU's vmap'd searchsorted measured ~4x slower
    at bench shapes — 1.19 s vs 0.31 s at 64x131072 probing 64x16384). Same
    contract: int32 (lo, counts), counts zeroed on left pad slots, ranges
    clamped to the right side's valid length by construction (the build slice
    stops at r_len)."""
    lo = np.zeros(L.shape, np.int32)
    cnt = np.zeros(L.shape, np.int32)
    for b in range(L.shape[0]):
        n, m = int(l_len[b]), int(r_len[b])
        if n == 0 or m == 0:
            continue
        probe, build = L[b, :n], R[b, :m]
        left = np.searchsorted(build, probe, "left")
        lo[b, :n] = left
        cnt[b, :n] = (np.searchsorted(build, probe, "right") - left).astype(
            np.int32
        )
    return lo, cnt


def _expand_np(
    lo: np.ndarray,
    counts: np.ndarray,
    l_starts: np.ndarray,
    r_starts: np.ndarray,
    l_order: np.ndarray = None,
    r_order: np.ndarray = None,
):
    """Expand count ranges into global (left_row, right_row) index pairs.

    Host-side numpy: the expansion is data-dependent-size gather/repeat work that
    the final host gather consumes anyway — running it eagerly op-by-op on device
    costs more in dispatch than the arithmetic (measured 0.5s → ~30ms at 2M rows).
    `l_order`/`r_order` map within-bucket sorted slots back to storage slots; None
    means the matrices were built value-direct (slot == storage position)."""
    B, cap_l = counts.shape
    counts_flat = counts.reshape(-1)
    lo_flat = lo.reshape(-1).astype(np.int64)
    starts_flat = np.cumsum(counts_flat, dtype=np.int64) - counts_flat
    l_flat = np.repeat(np.arange(B * cap_l), counts_flat)
    offset = np.arange(l_flat.shape[0]) - starts_flat[l_flat]
    b = l_flat // cap_l
    l_slot = l_flat % cap_l
    r_slot = lo_flat[l_flat] + offset
    if l_order is not None:
        l_slot = l_order[b, l_slot]
    if r_order is not None:
        r_slot = r_order[b, r_slot]
    return l_starts[b] + l_slot, r_starts[b] + r_slot


@_observed_jit(label="bucket_join.expand_pairs", static_argnums=(0, 1))
def _expand_pairs_dev(
    out_cap: int,
    has_order: bool,
    lo,
    counts,
    a_starts,
    b_starts,
    a_order,
    b_order,
):
    """ON-DEVICE expansion of probe count ranges into global (a_row, b_row)
    index pairs, padded to a static `out_cap` (pow2-quantized so repeat queries
    reuse the compiled program). The host variant (`_expand_np`) materializes
    the ranges with numpy; on a TPU the gathered pairs feed DEVICE consumers
    (count, fused join+aggregate), so expanding on device avoids the
    device->host->device round trip of the probe matrices entirely.

    Standard searchsorted expansion: output position j belongs to the flat left
    slot whose inclusive count prefix first exceeds j. Slots past `total` carry
    garbage and are masked by the returned validity lane (gathers clamp)."""
    cap_l = counts.shape[1]
    counts_flat = counts.reshape(-1).astype(jnp.int64)
    e = jnp.cumsum(counts_flat)  # inclusive prefix
    total = e[-1]
    j = jnp.arange(out_cap, dtype=jnp.int64)
    src = jnp.searchsorted(e, j, side="right")
    src = jnp.minimum(src, counts_flat.shape[0] - 1)
    offset = j - (e[src] - counts_flat[src])
    bkt = src // cap_l
    a_slot = src % cap_l
    b_slot = lo.reshape(-1).astype(jnp.int64)[src] + offset
    if has_order:
        a_slot = a_order[bkt, a_slot]
        b_slot = b_order[bkt, jnp.clip(b_slot, 0, b_order.shape[1] - 1)]
    ai = a_starts[bkt] + a_slot
    bi = b_starts[bkt] + b_slot
    return ai, bi, j < total


@_observed_jit(label="bucket_join.compact_pairs", static_argnums=(0,))
def _compact_pairs_dev(out_cap2: int, ai, bi, keep):
    """Stream-compact verified pairs to a static pow2 size. Pad slots repeat
    the FIRST kept pair (a real, verified pair), so downstream group detection
    over gathered values cannot invent spurious groups — pad contributions are
    masked out of every reduction by the `j < n_keep` lane the caller builds."""
    pos = jnp.cumsum(keep.astype(jnp.int64)) - 1
    idx = jnp.where(keep, pos, out_cap2)  # dropped -> out-of-bounds
    a2 = jnp.zeros(out_cap2, ai.dtype).at[idx].set(ai, mode="drop")
    b2 = jnp.zeros(out_cap2, bi.dtype).at[idx].set(bi, mode="drop")
    a2 = jnp.where(jnp.arange(out_cap2) < pos[-1] + 1, a2, a2[0])
    b2 = jnp.where(jnp.arange(out_cap2) < pos[-1] + 1, b2, b2[0])
    return a2, b2


def _counts_total(counts):
    if isinstance(counts, np.ndarray):  # host probe output: no device hop
        return counts.sum(dtype=np.int64)
    return _counts_total_jit(counts)


@_observed_jit(label="bucket_join.counts_total")
def _counts_total_jit(counts):
    return counts.sum(dtype=jnp.int64)


@_observed_jit(label="bucket_join.pad_only", static_argnums=(2, 3))
def _pad_only(vals, starts, num_buckets: int, cap: int, pad_value):
    """Scatter per-row values (concatenated in bucket order) into a padded [B, cap]
    matrix WITHOUT sorting, plus a per-bucket sortedness check."""
    n = vals.shape[0]
    pos = jnp.arange(n)
    b_of_row = jnp.searchsorted(starts, pos, side="right") - 1
    slot = pos - starts[b_of_row]
    padded = jnp.full((num_buckets, cap), pad_value, dtype=vals.dtype)
    padded = padded.at[b_of_row, slot].set(vals)
    lengths = starts[1:] - starts[:-1]
    valid = jnp.arange(cap - 1)[None, :] < (lengths - 1)[:, None]
    non_decreasing = jnp.where(valid, padded[:, 1:] >= padded[:, :-1], True).all()
    return padded, lengths, non_decreasing


class PaddedBuckets:
    """Device-resident padded representation of one side of a co-bucketed join:
    `keys` [B, cap] sorted within each row (pad = dtype max), `lengths` [B] valid
    counts, `order` [B, cap] host map sorted-slot → storage-slot (None when the
    matrix was built value-direct, i.e. storage order IS sorted order), `starts`
    host bucket offsets. Cacheable across queries — the whole point: a steady-state
    indexed join starts at the probe."""

    __slots__ = ("keys", "lengths", "order", "starts", "mode")

    def __init__(self, keys, lengths, order, starts, mode: str):
        self.keys = keys
        self.lengths = lengths
        self.order = order
        self.starts = starts
        self.mode = mode  # "value" | "hash"

    @property
    def nbytes(self) -> int:
        """Bytes pinned by this rep (device matrices + host maps) — what the
        engine's device-cache byte budget accounts."""
        total = 0
        for a in (self.keys, self.lengths, self.order, self.starts):
            total += int(getattr(a, "nbytes", 0) or 0)
        return total


def pad_buckets_by_value(vals, starts_np: np.ndarray) -> Optional[PaddedBuckets]:
    """Value-direct padded matrices for a side whose buckets are ALREADY sorted by
    the (single, numeric, null-free) key — the covering-index fast path: the sort
    happened once at build time (`ops.partition.bucketize_table` orders each bucket
    by the indexed columns), so queries need no hashing and no argsort. Returns
    None if the buckets turn out unsorted (e.g. multi-file buckets after
    incremental refresh); caller falls back to the hash path."""
    B = len(starts_np) - 1
    lens = np.diff(starts_np)
    if B == 0 or lens.max(initial=0) == 0:
        return None
    cap = _cap_pow2(int(lens.max()))
    vals = jnp.asarray(vals)
    if jnp.issubdtype(vals.dtype, jnp.floating):
        # NaN keys disqualify value mode EXPLICITLY: value-mode probe counts
        # are trusted without verification, but every probe implementation
        # counts NaN as matching NaN while the engine's equality says NaN
        # never equals anything. (Multi-row NaN buckets already fail the
        # non-decreasing check below — NaN >= x is false — but a SINGLETON
        # NaN bucket has zero comparisons and would slip through.) The hash
        # rep canonicalizes NaN and verifies exactly.
        if bool(jnp.isnan(vals).any()):
            return None
        # Canonicalize -0.0 -> +0.0: probe implementations disagree on signed
        # zeros (numpy searchsorted compares IEEE-equal; lax.sort's total
        # order puts -0.0 < +0.0 on some backends), and the engine's equality
        # treats them equal — canonical keys make every probe agree.
        vals = jnp.where(vals == 0, jnp.zeros((), vals.dtype), vals)
        pad = jnp.asarray(jnp.finfo(vals.dtype).max, dtype=vals.dtype)
    else:
        pad = jnp.asarray(jnp.iinfo(vals.dtype).max, dtype=vals.dtype)
    keys, lengths, ok = _pad_only(vals, jnp.asarray(starts_np), B, cap, pad)
    if not bool(ok):
        return None
    _record_bucket_pad(int(starts_np[-1]), B, cap, int(vals.dtype.itemsize))
    return PaddedBuckets(keys, lengths, None, starts_np, "value")


def _record_bucket_pad(rows: int, B: int, cap: int, itemsize: int) -> None:
    """Padding-tax ledger for one padded [B, cap] key matrix: `rows` real
    keys staged inside B×cap slots (the cost the size-classed layout exists
    to shrink — now measured per query, not modeled)."""
    _devobs.record_pad(
        "join_buckets", rows * itemsize, (B * cap - rows) * itemsize
    )


def pad_buckets_by_hash(key64_arr, starts_np: np.ndarray) -> PaddedBuckets:
    """Hash-key padded matrices (argsort within bucket) for the general case:
    multi-column or string keys, nullable keys, or unsorted buckets. Within
    its VMEM shape budget the in-bucket sort dispatches to the Pallas
    single-pass bitonic kernel (`ops.pallas_sort`)."""
    from .backend import pallas_maybe_wanted

    B = len(starts_np) - 1
    lens = np.diff(starts_np)
    cap = _cap_pow2(int(lens.max())) if B else 1
    keys_nudged = jnp.minimum(jnp.asarray(key64_arr), _PAD - 1)
    if pallas_maybe_wanted("HYPERSPACE_PALLAS_SORT"):
        from .pallas_sort import pallas_sort_wanted, sort_padded_with_order

        if pallas_sort_wanted(B, cap):
            padded, lengths = _pad_scatter(keys_nudged, jnp.asarray(starts_np), B, cap)
            keys, order = sort_padded_with_order(padded)
            _record_bucket_pad(int(starts_np[-1]), B, cap, 8)
            return PaddedBuckets(keys, lengths, np.asarray(order), starts_np, "hash")
    keys, order, lengths = _pad_and_sort(keys_nudged, jnp.asarray(starts_np), B, cap)
    _record_bucket_pad(int(starts_np[-1]), B, cap, 8)
    return PaddedBuckets(keys, lengths, np.asarray(order), starts_np, "hash")


def probe_orientation(left, right):
    """Canonical probe orientation — the SMALLER-capacity side probes into the
    larger (search count scales linearly with the probing side's capacity, only
    logarithmically with the other's). Single source of the heuristic, shared by
    `probe_padded`, the sharded `probe_dist_blocks`, and the bench's kernel
    isolation. Returns (probe_side, build_side, swapped)."""
    if left.keys.shape[1] > right.keys.shape[1]:
        return right, left, True
    return left, right, False


def probe_keys_promoted(a_keys, b_keys):
    """Key matrices promoted to a common dtype (value-direct sides may be int32/
    float while hash sides are int64). NUMPY's promotion lattice, matching the
    exact-verification pass (int64 x float32 -> float64 there; JAX's lattice
    would give float32 and a 2^24-magnitude int could falsely probe-match)."""
    if a_keys.dtype != b_keys.dtype:
        common = np.promote_types(np.dtype(a_keys.dtype), np.dtype(b_keys.dtype))
        return a_keys.astype(common), b_keys.astype(common)
    return a_keys, b_keys


def probe_ranges(ls, rs, l_len, r_len):
    """Probe dispatcher: the Pallas tiled-compare kernel when wanted (on-TPU
    within its capacity budget, or HYPERSPACE_PALLAS_PROBE=1), else the XLA
    vmap'd-searchsorted probe; the CPU backend probes on host (numpy
    searchsorted, ~4x the XLA-CPU probe)."""
    from .backend import pallas_maybe_wanted, use_device_path

    # Cheap pre-gate before touching pallas at all: importing
    # jax.experimental.pallas costs ~1 s on first use, and on the plain CPU
    # backend the kernel is never wanted — the import would be pure cold-path
    # tax (measured as the dominant cost of the first 8M indexed count).
    if pallas_maybe_wanted("HYPERSPACE_PALLAS_PROBE"):
        from .pallas_probe import pallas_probe_wanted, probe_pallas

        if pallas_probe_wanted(int(ls.shape[1]), int(rs.shape[1]), int(ls.shape[0])):
            # Checked FIRST: HYPERSPACE_PALLAS_PROBE=1 forces the kernel even
            # on the CPU backend (interpret-mode validation rides this).
            return probe_pallas(ls, rs, l_len, r_len)
    if not use_device_path():
        return _probe_host(
            np.asarray(ls), np.asarray(rs), np.asarray(l_len), np.asarray(r_len)
        )
    return _probe(ls, rs, l_len, r_len)


# ---------------------------------------------------------------------------
# Packed code-mode padded reps (sub-byte dictionary codes)
# ---------------------------------------------------------------------------
#
# A single low-cardinality STRING key doesn't need 64-bit hash keys at all:
# its dictionary codes order-embed the join equality (equal code <=> equal
# string within the shared dictionary), and below int8 they pack into uint32
# lane words (`engine/packed_codes.py`). These reps keep the device-resident
# padded matrices in PACKED form — 8-32x smaller HBM residency than the int64
# hash rep — and the probe computes on the words directly (Pallas packed
# kernel) or unpacks once and reuses the generic probe (the widen-then-probe
# fallback the bench compares against).


class PackedCodeBuckets:
    """Packed-word twin of `PaddedBuckets`: `words` [B, cap/lpw] uint32 rows
    of sorted BIASED codes (code + 1; pad slots hold the top lane value),
    `bits` the lane width, `lengths`/`order`/`starts` as in the hash rep."""

    __slots__ = ("words", "bits", "lengths", "order", "starts", "cap")

    def __init__(self, words, bits: int, lengths, order, starts, cap: int):
        self.words = words
        self.bits = bits
        self.lengths = lengths
        self.order = order
        self.starts = starts
        self.cap = cap

    @property
    def nbytes(self) -> int:
        total = 0
        for a in (self.words, self.lengths, self.order, self.starts):
            total += int(getattr(a, "nbytes", 0) or 0)
        return total


@_observed_jit(label="bucket_join.pad_scatter_codes", static_argnums=(2, 3, 4))
def _pad_scatter_codes(codes, starts, num_buckets: int, cap: int, bits: int):
    """`_pad_scatter` for code lanes: scatter raw codes (null = -1) into an
    UNSORTED padded [B, cap] int32 matrix of BIASED codes (code + 1), pad =
    2**bits - 1 — the top lane value `probe_bits_for_cardinality` reserves, so
    pads sort last exactly like the i64-max pad of the hash rep."""
    n = codes.shape[0]
    pos = jnp.arange(n)
    b_of_row = jnp.searchsorted(starts, pos, side="right") - 1
    slot = pos - starts[b_of_row]
    padded = jnp.full((num_buckets, cap), (1 << bits) - 1, dtype=jnp.int32)
    padded = padded.at[b_of_row, slot].set(codes.astype(jnp.int32) + 1)
    lengths = starts[1:] - starts[:-1]
    return padded, lengths


@_observed_jit(label="bucket_join.pad_and_sort_codes", static_argnums=(2, 3, 4))
def _pad_and_sort_codes(codes, starts, num_buckets: int, cap: int, bits: int):
    """XLA twin of `pallas_sort.sort_code_rows`: scatter + stable
    argsort on the flat biased matrix. Same (sorted, order, lengths) contract."""
    padded, lengths = _pad_scatter_codes(codes, starts, num_buckets, cap, bits)
    order = jnp.argsort(padded, axis=1)
    return jnp.take_along_axis(padded, order, axis=1), order, lengths


@_observed_jit(label="bucket_join.pack_code_rows", static_argnums=(1,))
def _pack_code_rows(mat, bits: int):
    from ..engine.packed_codes import pack_rows_traced

    return pack_rows_traced(mat, bits)


@_observed_jit(label="bucket_join.unpack_code_rows", static_argnums=(1,))
def _unpack_code_rows(words, bits: int):
    from ..engine.packed_codes import unpack_rows_traced

    return unpack_rows_traced(words, bits)


def pad_buckets_by_codes(
    codes, starts_np: np.ndarray, cardinality: int, has_nulls: bool = False
) -> Optional[PackedCodeBuckets]:
    """Packed code-mode rep for a single low-cardinality string key. Returns
    None when the key doesn't qualify (cardinality past the 4-bit compute
    bound, nulls present — like the value-direct rep, null semantics belong
    to the hash path — or degenerate bucket layouts). In-bucket sorting rides
    the Pallas packed-word sort when wanted, else the XLA argsort fallback;
    either way the RESIDENT matrix is packed words."""
    from ..engine.packed_codes import (
        lanes_per_word,
        probe_bits_for_cardinality,
    )
    from .backend import pallas_maybe_wanted

    if has_nulls:
        return None
    bits = probe_bits_for_cardinality(int(cardinality))
    if bits is None:
        return None
    B = len(starts_np) - 1
    lens = np.diff(starts_np)
    if B == 0 or lens.max(initial=0) == 0:
        return None
    cap = max(_cap_pow2(int(lens.max())), lanes_per_word(bits))
    codes = jnp.asarray(codes)
    starts = jnp.asarray(starts_np)
    sorted_codes = order = lengths = None
    if pallas_maybe_wanted("HYPERSPACE_PALLAS_SORT"):
        from .pallas_sort import pallas_packed_sort_wanted, sort_code_rows

        if pallas_packed_sort_wanted(B, cap, bits):
            padded, lengths = _pad_scatter_codes(codes, starts, B, cap, bits)
            sorted_codes, order = sort_code_rows(padded)
    if sorted_codes is None:
        sorted_codes, order, lengths = _pad_and_sort_codes(
            codes, starts, B, cap, bits
        )
    words = _pack_code_rows(sorted_codes, bits)
    rows = int(starts_np[-1])
    _devobs.record_pad(
        "join_buckets", -(-rows * bits // 8), -(-(B * cap - rows) * bits // 8)
    )
    return PackedCodeBuckets(
        words, bits, lengths, np.asarray(order), starts_np, cap
    )


def probe_code_ranges(l: PackedCodeBuckets, r: PackedCodeBuckets):
    """Probe dispatcher for packed code reps: the Pallas packed-word kernel
    when wanted, else widen-then-probe — one device
    unpack to flat int32 matrices feeding the generic probe (`_probe`, or the
    host searchsorted off the device path). Biased codes compare consistently
    on both sides, so ranges are identical across all three paths."""
    from .backend import pallas_maybe_wanted, use_device_path

    if l.bits != r.bits:
        raise ValueError(f"packed rep bits mismatch: {l.bits} != {r.bits}")
    B = l.words.shape[0]
    if pallas_maybe_wanted("HYPERSPACE_PALLAS_PROBE"):
        from .pallas_probe import pallas_packed_probe_wanted, probe_packed_pallas

        if pallas_packed_probe_wanted(l.cap, r.cap, B, l.bits):
            return probe_packed_pallas(l.words, r.words, l.bits, l.lengths, r.lengths)
    ls = _unpack_code_rows(l.words, l.bits)
    rs = _unpack_code_rows(r.words, r.bits)
    if not use_device_path():
        return _probe_host(
            np.asarray(ls),
            np.asarray(rs),
            np.asarray(l.lengths),
            np.asarray(r.lengths),
        )
    return _probe(ls, rs, l.lengths, r.lengths)


# ---------------------------------------------------------------------------
# Size-classed (skew-aware) layout
# ---------------------------------------------------------------------------
#
# The dense layout above pads EVERY bucket to the global max bucket size, so a
# single hot key inflates `num_buckets × cap` — at the 8M CPU bench the padded
# sort alone (`pad_sort_p50`) was the slowest surviving kernel (2.44 s), and a
# skewed key distribution multiplies the padded area by the skew ratio. The
# classed layout (JSPIM-style, PAPERS.md) groups the ACTIVE buckets (non-empty
# on both sides) into a small set of pow2 capacity classes; each class gets its
# own padded matrices and its own probe program (the Pallas tiled-compare
# kernel dispatches per class on TPU, where the smaller per-class capacity
# products fall inside its quadratic-compare budget far more often than the
# global cap did). Oversized outlier buckets skip padding entirely and merge
# on host per bucket (`ops.join.host_merge_pairs`). On the CPU backend the
# class matrices are built with numpy (per-bucket stable argsort over the
# actual rows) — no XLA scatter/argsort over padded slots at all.


class _ClassSide:
    """One side of one capacity class: `keys` [B, cap] sorted within each row,
    `lengths` [B] valid counts, `order` [B, cap] sorted-slot → storage-slot
    (None in value mode), `starts` [B] GLOBAL row offsets of the class's
    buckets (indexable by the class-local bucket row)."""

    __slots__ = ("keys", "lengths", "order", "starts", "cap")

    def __init__(self, keys, lengths, order, starts, cap: int):
        self.keys = keys
        self.lengths = lengths
        self.order = order
        self.starts = starts
        self.cap = cap

    @property
    def nbytes(self) -> int:
        return sum(
            int(getattr(a, "nbytes", 0) or 0)
            for a in (self.keys, self.lengths, self.order, self.starts)
        )


class JoinSegment:
    """One capacity class of a classed join plan: the bucket ids it covers
    (ascending, SHARED by both sides — the partition is joint) and the two
    padded sides."""

    __slots__ = ("ids", "l", "r")

    def __init__(self, ids: np.ndarray, l: _ClassSide, r: _ClassSide):
        self.ids = ids
        self.l = l
        self.r = r


class ClassedJoinPlan:
    """Joint size-classed layout of one co-bucketed join pair. `l_vals`/
    `r_vals` are the HOST key arrays in the joint key space (key64 for hash
    mode, canonicalized actual values for value mode), concatenated in bucket
    order — the outlier merge and the host probe slice them directly.
    Cacheable per table pair (the classed analogue of `PaddedBuckets`)."""

    __slots__ = (
        "mode",
        "segments",
        "outlier_ids",
        "l_vals",
        "r_vals",
        "l_starts",
        "r_starts",
        "num_buckets",
    )

    def __init__(
        self, mode, segments, outlier_ids, l_vals, r_vals, l_starts, r_starts
    ):
        self.mode = mode  # "value" | "hash"
        self.segments = segments
        self.outlier_ids = outlier_ids
        self.l_vals = l_vals
        self.r_vals = r_vals
        self.l_starts = l_starts
        self.r_starts = r_starts
        self.num_buckets = len(l_starts) - 1

    @property
    def nbytes(self) -> int:
        total = int(self.l_vals.nbytes) + int(self.r_vals.nbytes)
        for seg in self.segments:
            total += seg.l.nbytes + seg.r.nbytes
        return total


class ClassedRanges:
    """Probe output of a classed plan: per segment (lo, counts, swapped,
    seg_total) in the segment's own probe orientation, plus the outlier
    buckets' already-expanded GLOBAL candidate pairs. `total` counts every
    candidate pair (exact matches in value mode)."""

    __slots__ = ("segments", "outliers", "total")

    def __init__(self, segments, outliers, total: int):
        self.segments = segments
        self.outliers = outliers
        self.total = total

    @property
    def nbytes(self) -> int:
        total = 0
        for lo, counts, _sw, _tot in self.segments:
            total += int(getattr(lo, "nbytes", 0)) + int(getattr(counts, "nbytes", 0))
        for _b, li, ri in self.outliers:
            total += int(li.nbytes) + int(ri.nbytes)
        return total


def joint_partition(
    l_starts: np.ndarray, r_starts: np.ndarray
) -> Tuple[List[np.ndarray], np.ndarray]:
    """Partition the ACTIVE buckets (rows on BOTH sides — a bucket empty on
    either side produces no pairs and is skipped entirely) into capacity
    classes by the pow2 caps of their two sides, with oversized outliers
    split off for the host merge path. Returns (class id-arrays ascending,
    outlier ids). The partition is a pure function of the two bucket-offset
    arrays, so both sides of a join always agree on it."""
    l_lens = np.diff(np.asarray(l_starts, np.int64))
    r_lens = np.diff(np.asarray(r_starts, np.int64))
    active = np.nonzero((l_lens > 0) & (r_lens > 0))[0]
    if len(active) == 0:
        return [], np.empty(0, np.int64)
    mx = np.maximum(l_lens, r_lens)[active]
    factor = _outlier_factor()
    if factor > 0:
        out_mask = mx > factor * max(float(np.median(mx)), 1.0)
    else:
        out_mask = np.zeros(len(active), bool)
    outliers = active[out_mask]
    rest = active[~out_mask]
    if len(rest) == 0:
        return [], outliers

    def group_by_caps(quantize) -> dict:
        classes: dict = {}
        for b in rest:
            key = (quantize(int(l_lens[b])), quantize(int(r_lens[b])))
            classes.setdefault(key, []).append(int(b))
        return classes

    classes = group_by_caps(_cap_pow2)
    if len(classes) > _MAX_CLASSES:
        # Coarsen to power-of-4 caps (halves the distinct-class count bound).
        def cap_pow4(n: int) -> int:
            bits = (max(1, n) - 1).bit_length()
            return 1 << (bits + (bits & 1))

        classes = group_by_caps(cap_pow4)
    if len(classes) > _MAX_CLASSES:
        classes = {("all", "all"): [int(b) for b in rest]}
    groups = [
        np.asarray(sorted(ids), np.int64)
        for _key, ids in sorted(
            classes.items(), key=lambda kv: (str(kv[0]), kv[1][0])
        )
    ]
    return groups, outliers


def value_mode_vals(data, starts) -> Optional[np.ndarray]:
    """Canonicalized HOST key values for value mode, or None when the column
    disqualifies: NaN keys (probe equality would disagree with SQL's
    NaN != NaN) or buckets not sorted by the key (e.g. multi-file buckets
    after incremental refresh). Same contract as `pad_buckets_by_value`,
    checked on host without building any padded matrix."""
    vals = np.asarray(data)
    if np.issubdtype(vals.dtype, np.floating):
        if bool(np.isnan(vals).any()):
            return None
        # -0.0 -> +0.0: probe implementations must agree on signed zeros.
        vals = np.where(vals == 0, np.zeros((), vals.dtype), vals)
    n = vals.shape[0]
    if n > 1:
        adj = vals[1:] >= vals[:-1]
        # Bucket boundaries are exempt from the non-decreasing check.
        bounds = np.asarray(starts, np.int64)[1:-1] - 1
        bounds = bounds[(bounds >= 0) & (bounds < n - 1)]
        adj[bounds] = True
        if not bool(adj.all()):
            return None
    return vals


def _host_pad_value(dtype) -> np.ndarray:
    if np.issubdtype(dtype, np.floating):
        return np.asarray(np.finfo(dtype).max, dtype=dtype)
    return np.asarray(np.iinfo(dtype).max, dtype=dtype)


def _build_side(
    vals: np.ndarray,
    starts: np.ndarray,
    ids: np.ndarray,
    mode: str,
    device: bool,
) -> Optional[_ClassSide]:
    """Padded matrices of one class of one side. Host build (CPU backend):
    numpy scatter + per-bucket stable argsort over the ACTUAL rows only —
    measured ~2x the XLA-CPU padded argsort at bench shapes, and pad slots are
    never sorted at all. Device build: the class rows re-concatenate and ride
    the existing jitted `_pad_and_sort`/`_pad_only` programs (Pallas sort
    included via `pad_buckets_by_hash`), with the bucket axis pow2-quantized
    by EMPTY virtual buckets so growing class populations reuse compiles."""
    lens = (starts[ids + 1] - starts[ids]).astype(np.int64)
    cap = _cap_pow2(int(lens.max()))
    gstarts = starts[ids].astype(np.int64)
    if device:
        concat = (
            np.concatenate([vals[starts[b] : starts[b + 1]] for b in ids])
            if len(ids)
            else vals[:0]
        )
        b_pad = _cap_pow2(len(ids))
        cstarts = np.zeros(b_pad + 1, np.int64)
        np.cumsum(lens, out=cstarts[1 : len(ids) + 1])
        cstarts[len(ids) + 1 :] = cstarts[len(ids)]
        if mode == "hash":
            rep = pad_buckets_by_hash(jnp.asarray(concat), cstarts)
        else:
            rep = pad_buckets_by_value(jnp.asarray(concat), cstarts)
            if rep is None:
                return None
        gstarts_pad = np.zeros(b_pad, np.int64)
        gstarts_pad[: len(ids)] = gstarts
        return _ClassSide(
            rep.keys, rep.lengths, rep.order, gstarts_pad, int(rep.keys.shape[1])
        )
    B = len(ids)
    # Classed host build stages its own [B, cap] matrix; the device branch's
    # tax is recorded inside `pad_buckets_by_*` (no double counting).
    _devobs.record_pad(
        "join_class",
        int(lens.sum()) * int(vals.dtype.itemsize),
        (B * cap - int(lens.sum())) * int(vals.dtype.itemsize),
    )
    keys = np.full((B, cap), _host_pad_value(vals.dtype), vals.dtype)
    order = np.zeros((B, cap), np.int64) if mode == "hash" else None
    for k, b in enumerate(ids):
        s, e = int(starts[b]), int(starts[b + 1])
        sl = vals[s:e]
        if mode == "hash":
            o = np.argsort(sl, kind="stable")
            keys[k, : e - s] = sl[o]
            order[k, : e - s] = o
        else:
            keys[k, : e - s] = sl
    return _ClassSide(keys, lens, order, gstarts, cap)


def fact_bucket_layout(
    bucket_ids: np.ndarray, num_buckets: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Bucket-order layout of a PROBE-side table for one star dimension:
    `perm` stably reorders rows into ascending-bucket order and `starts`
    (length num_buckets+1) delimits each bucket's slice — the same
    (bucket-ordered rows, starts) contract `build_classed_plan` expects of a
    bucketed index concat, computed on the fly for a fact table that was
    never bucket-partitioned on this dimension's keys. Stability keeps the
    within-bucket order deterministic (table order), so repeated probes and
    the pair memos agree."""
    bid = np.asarray(bucket_ids, np.int64)
    perm = np.argsort(bid, kind="stable")
    counts = np.bincount(bid, minlength=num_buckets)
    starts = np.zeros(num_buckets + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    return perm, starts


def build_classed_plan(
    l_vals: np.ndarray,
    r_vals: np.ndarray,
    l_starts: np.ndarray,
    r_starts: np.ndarray,
    mode: str,
    device: bool = False,
    timings: Optional[list] = None,
) -> Optional[ClassedJoinPlan]:
    """Build the joint size-classed layout for one co-bucketed join pair.
    `l_vals`/`r_vals` are HOST arrays in the joint key space (key64 hashes for
    ``mode="hash"``, `value_mode_vals`-canonicalized values for
    ``mode="value"``), concatenated in bucket order. Returns None when a
    value-mode segment fails the device-side sortedness check (caller retries
    in hash mode). `timings` (a list) receives per-class build records —
    the bench's `pad_sort_classes` breakdown."""
    l_starts = np.asarray(l_starts, np.int64)
    r_starts = np.asarray(r_starts, np.int64)
    if mode == "hash":
        l_vals = np.minimum(np.asarray(l_vals, np.int64), _PAD - 1)
        r_vals = np.minimum(np.asarray(r_vals, np.int64), _PAD - 1)
    else:
        l_vals = np.asarray(l_vals)
        r_vals = np.asarray(r_vals)
    groups, outlier_ids = joint_partition(l_starts, r_starts)
    segments = []
    for ids in groups:
        t0 = time.monotonic()
        l_side = _build_side(l_vals, l_starts, ids, mode, device)
        r_side = _build_side(r_vals, r_starts, ids, mode, device)
        if l_side is None or r_side is None:
            return None
        segments.append(JoinSegment(ids, l_side, r_side))
        if timings is not None:
            timings.append(
                {
                    "cap_l": l_side.cap,
                    "cap_r": r_side.cap,
                    "buckets": int(len(ids)),
                    "build_s": round(time.monotonic() - t0, 5),
                }
            )
    if timings is not None and len(outlier_ids):
        lens = np.maximum(
            np.diff(l_starts)[outlier_ids], np.diff(r_starts)[outlier_ids]
        )
        timings.append(
            {
                "outliers": int(len(outlier_ids)),
                "max_rows": int(lens.max()),
            }
        )
    return ClassedJoinPlan(
        mode, segments, outlier_ids, l_vals, r_vals, l_starts, r_starts
    )


def _outlier_bucket_pairs(plan: ClassedJoinPlan, b: int):
    """Host merge of ONE oversized bucket → GLOBAL candidate (li, ri) pairs
    (exact matches in value mode; hash candidates verified by the caller's
    exact-equality pass, same as every padded candidate)."""
    from .join import host_merge_pairs

    ls, le = int(plan.l_starts[b]), int(plan.l_starts[b + 1])
    rs, re = int(plan.r_starts[b]), int(plan.r_starts[b + 1])
    lv, rv = plan.l_vals[ls:le], plan.r_vals[rs:re]
    lv, rv = probe_keys_promoted(lv, rv)
    li, ri = host_merge_pairs(lv, rv)
    return li + ls, ri + rs


def probe_classed(plan: ClassedJoinPlan) -> ClassedRanges:
    """Range-probe every segment (each class runs its own probe program via
    `probe_ranges` — the Pallas tiled kernel where its per-class shape budget
    admits it, the XLA vmap'd searchsorted or host numpy probe elsewhere) and
    merge the outlier buckets on host."""
    segs = []
    total = 0
    for seg in plan.segments:
        if seg.l.cap > seg.r.cap:
            a, b, swapped = seg.r, seg.l, True
        else:
            a, b, swapped = seg.l, seg.r, False
        ak, bk = probe_keys_promoted(a.keys, b.keys)
        lo, counts = probe_ranges(ak, bk, a.lengths, b.lengths)
        seg_total = int(_counts_total(counts))
        total += seg_total
        segs.append((lo, counts, swapped, seg_total))
    outs = []
    for b in plan.outlier_ids:
        li, ri = _outlier_bucket_pairs(plan, int(b))
        total += len(li)
        outs.append((int(b), li, ri))
    return ClassedRanges(segs, outs, total)


def classed_pairs(
    plan: ClassedJoinPlan, ranges: ClassedRanges
) -> Tuple[np.ndarray, np.ndarray]:
    """Expand a classed probe into HOST candidate (li, ri) pairs in BUCKET-
    MAJOR order (ascending bucket id; within a bucket, probe-side sorted-slot
    order) — one deterministic order regardless of how buckets landed in
    classes, so repeated queries and the materialized fallback agree."""
    per_bucket = np.zeros(plan.num_buckets, np.int64)
    seg_out = []
    for seg, (lo, counts, swapped, seg_total) in zip(plan.segments, ranges.segments):
        counts_np = np.asarray(counts)
        if seg_total == 0:
            continue
        a, b = (seg.r, seg.l) if swapped else (seg.l, seg.r)
        ai, bi = _expand_np(
            np.asarray(lo), counts_np, a.starts, b.starts, a.order, b.order
        )
        li, ri = (bi, ai) if swapped else (ai, bi)
        tots = counts_np.sum(axis=1, dtype=np.int64)[: len(seg.ids)]
        per_bucket[seg.ids] = tots
        seg_out.append((seg.ids, li, ri, tots))
    for b, li_o, ri_o in ranges.outliers:
        per_bucket[b] = len(li_o)
    out_starts = np.zeros(plan.num_buckets + 1, np.int64)
    np.cumsum(per_bucket, out=out_starts[1:])
    total = int(out_starts[-1])
    li_all = np.empty(total, np.int64)
    ri_all = np.empty(total, np.int64)
    for ids, li, ri, tots in seg_out:
        cum = np.cumsum(tots) - tots
        pos = np.repeat(out_starts[ids] - cum, tots) + np.arange(li.shape[0])
        li_all[pos] = li
        ri_all[pos] = ri
    for b, li_o, ri_o in ranges.outliers:
        s = int(out_starts[b])
        li_all[s : s + len(li_o)] = li_o
        ri_all[s : s + len(ri_o)] = ri_o
    return li_all, ri_all


def classed_pairs_dev(plan: ClassedJoinPlan, ranges: ClassedRanges):
    """DEVICE expansion of a classed probe: per-segment `_expand_pairs_dev`
    programs (pow2 out-caps, so repeat shapes reuse compiles) concatenated
    with the host outlier pairs — (li, ri, valid) device lanes for the fused
    join→aggregate / on-device count paths. Pair order is NOT the host
    bucket-major order (device consumers are order-insensitive reductions)."""
    from ..engine.device_cache import device_array

    has_order = plan.mode == "hash"
    dummy = jnp.zeros((1, 1), dtype=jnp.int64)
    parts = []
    for seg, (lo, counts, swapped, seg_total) in zip(plan.segments, ranges.segments):
        if seg_total == 0:
            continue
        a, b = (seg.r, seg.l) if swapped else (seg.l, seg.r)
        ai, bi, valid = _expand_pairs_dev(
            _cap_pow2(seg_total),
            has_order,
            jnp.asarray(lo),
            jnp.asarray(counts),
            device_array(a.starts),
            device_array(b.starts),
            device_array(a.order) if has_order else dummy,
            device_array(b.order) if has_order else dummy,
        )
        li, ri = (bi, ai) if swapped else (ai, bi)
        parts.append((li, ri, valid))
    for _b, li_o, ri_o in ranges.outliers:
        if len(li_o) == 0:
            continue
        parts.append(
            (
                jnp.asarray(li_o),
                jnp.asarray(ri_o),
                jnp.ones(len(li_o), bool),
            )
        )
    if not parts:
        return None
    if len(parts) == 1:
        return parts[0]
    li = jnp.concatenate([p[0] for p in parts])
    ri = jnp.concatenate([p[1] for p in parts])
    valid = jnp.concatenate([p[2] for p in parts])
    return li, ri, valid


def probe_padded(left: PaddedBuckets, right: PaddedBuckets, ranges=None):
    """Batched range probe of two padded sides → host (left_row, right_row) pairs.

    Both sides must be in the SAME mode: value-direct keys and key64 hashes live in
    different spaces, so a mixed probe would silently find nothing. The caller makes
    the mode decision jointly (`_padded_rep` + the mode reconciliation in
    `SortMergeJoinExec._execute_bucketed`). `ranges` optionally supplies
    already-computed (lo, counts) in the canonical probe orientation (the
    engine's probe-range memo), skipping the probe entirely."""
    if left.mode != right.mode:
        raise ValueError(f"mixed padded modes: {left.mode} vs {right.mode}")
    a, b, swapped = probe_orientation(left, right)
    if ranges is not None:
        lo, counts = ranges
    else:
        ak, bk = probe_keys_promoted(a.keys, b.keys)
        lo, counts = probe_ranges(ak, bk, a.lengths, b.lengths)
    counts_np = np.asarray(counts)
    if counts_np.sum() == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    ai, bi = _expand_np(
        np.asarray(lo), counts_np, a.starts, b.starts, a.order, b.order
    )
    return (bi, ai) if swapped else (ai, bi)


