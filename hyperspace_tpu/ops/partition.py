"""On-device bucketize: hash-partition + in-bucket sort in ONE XLA sort.

This is the TPU replacement for the reference's index-build hot path —
`df.repartition(numBuckets, indexedCols)` (a full Spark shuffle) followed by
per-bucket sort in the bucketed writer (`CreateActionBase.scala:119-140`,
`DataFrameWriterExtensions.scala:49-81`). Here both steps collapse into a single
`lax.sort` over the composite key (bucket_id, indexed_cols...): after the sort, rows
are grouped by bucket AND sorted by the indexed columns within each bucket, so bucket
extraction is a contiguous slice. Static shapes throughout; one device sort is the
whole job.

Backend-adaptive: on the CPU backend the permutation comes from a host
`np.lexsort` instead (XLA's CPU variadic sort is single-threaded and ~3x slower
at build sizes); the device `lax.sort` path is the TPU design. Both produce the
identical (bucket, keys...) ordering contract —
`tests/test_engine.py::test_device_sort_perm_matches_lexsort` pins them to each
other.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.table import Column, Table
from ..telemetry.compile_log import observed_jit as _observed_jit
from .hashing import bucket_id


@_observed_jit(label="partition.sort_perm", static_argnums=(2,))
def _sort_perm(bucket, keys: Tuple, n: int):
    """Permutation ordering rows by (bucket, key1, key2, ...)."""
    iota = jnp.arange(n, dtype=jnp.int32)
    operands = (bucket, *keys, iota)
    res = jax.lax.sort(operands, num_keys=1 + len(keys))
    return res[-1], res[0]  # (permutation, sorted bucket ids)


def _sortable(arr: jnp.ndarray) -> jnp.ndarray:
    if arr.dtype == jnp.bool_:
        return arr.astype(jnp.int32)
    return arr


def _composite_sort_host(
    b_host: np.ndarray, cols, num_buckets: int
) -> "np.ndarray | None":
    """Single-lane composite sort for the common single-key case: with one
    non-null integer-or-dictionary key of bounded range, `(bucket * range +
    (key - min)) * n + row` fits int64 and one unstable introsort orders by
    (bucket, key, original row) — measured 0.84 s vs lexsort's 2.58 s at 8M.
    The row-id low bits make every composite UNIQUE, so the unstable introsort
    reproduces the engine's CANONICAL build order — stable (bucket, key) with
    ties in original row order — exactly: the same order `np.lexsort`, the
    stable `lax.sort` device paths, the Pallas composite sort, and the mesh
    exchange's receive-side sort all produce. One canonical order is what
    makes the mesh build's index files byte-identical to single-device ones
    (`HYPERSPACE_DISTRIBUTED=0` oracle). Strings ride their sorted-dictionary
    codes (code order IS value order). None = use the lexsort."""
    if len(cols) != 1:
        return None
    c = cols[0]
    if getattr(c, "validity", None) is not None:
        return None
    data = c.data  # codes for strings
    if data.dtype == np.bool_:
        data = data.astype(np.int64)
    if not np.issubdtype(data.dtype, np.integer):
        return None
    n = data.shape[0]
    if n == 0:
        return np.empty(0, np.int64)
    lo, hi = int(data.min()), int(data.max())
    span = hi - lo + 1
    if span > (1 << 62) // max(num_buckets * n, 1):
        return None
    comp = (
        b_host.astype(np.int64) * span + (data.astype(np.int64) - lo)
    ) * np.int64(n) + np.arange(n, dtype=np.int64)
    return np.argsort(comp)


def host_sort_perm(b_host: np.ndarray, cols, num_buckets: int) -> np.ndarray:
    """The CPU-backend permutation by (bucket, keys...): single-lane composite
    sort when eligible, else lexsort. ONE implementation shared by the serial
    build path and the pipelined build's sort stage — the bit-for-bit
    reproducibility contract between them rides on this being the same code
    over the same arrays (np.argsort/np.lexsort are unstable, so even an
    equivalent reformulation could permute equal-key rows differently)."""
    perm_host = _composite_sort_host(b_host, cols, num_buckets)
    if perm_host is None:
        lanes = tuple(
            c.data.astype(np.int32) if c.data.dtype == np.bool_ else c.data
            for c in reversed(cols)
        ) + (b_host,)
        perm_host = np.lexsort(lanes)
    return perm_host


def bucket_starts(sorted_b_host: np.ndarray, num_buckets: int) -> np.ndarray:
    """Bucket start offsets (length num_buckets+1) of a bucket-sorted id array.
    The needles take the ids' dtype: mixed dtypes would cast the whole id
    array into a fresh buffer first (48 MB at 6 M int32 ids)."""
    return np.searchsorted(
        sorted_b_host, np.arange(num_buckets + 1, dtype=sorted_b_host.dtype)
    )


def bucketize_table(
    table: Table, bucket_columns: Sequence[str], num_buckets: int
) -> Tuple[Table, np.ndarray]:
    """Hash-partition `table` into `num_buckets` by `bucket_columns`, sorted by those
    columns within each bucket. Returns (reordered table, bucket start offsets of
    length num_buckets+1): bucket b = rows[starts[b]:starts[b+1]]."""
    cols = [table.column(c) for c in bucket_columns]
    from ..engine.encoded_device import stage_codes

    # String key lanes stage as NARROW dictionary codes when the cardinality
    # allows (engine/encoded_device.py): the hash gathers dh_table[codes] and
    # the sort compares code VALUES, so both are bit-identical from narrow
    # lanes — only the upload bytes shrink.
    arrs = [stage_codes(c, "partition_build") for c in cols]
    b = bucket_id(cols, arrs, num_buckets)
    from .backend import use_device_path

    if not use_device_path():
        # Backend-adaptive: XLA's CPU variadic sort is single-threaded and ~3x
        # slower than numpy's lexsort at index-build sizes; the one-device-sort
        # design is for the TPU, where lax.sort is the right primitive. The
        # output contract (permutation by (bucket, keys...)) is identical.
        b_host = np.asarray(b)
        perm_host = host_sort_perm(b_host, cols, num_buckets)
        sorted_b_host = b_host[perm_host]
    else:
        res = None
        if (
            len(cols) == 1
            and getattr(cols[0], "is_string", False)
            and cols[0].dictionary is not None
        ):
            # Sub-byte code build: (bucket | biased code | row) packs into ONE
            # int32 composite — same canonical order as the variadic sort,
            # a quarter of the sorted state. None when out of budget.
            res = pallas_packed_build_sort(
                b, arrs[0], len(cols[0].dictionary), table.num_rows, num_buckets
            )
        if res is not None:
            perm_host, sorted_b_host = res
        else:
            perm, sorted_b = _sort_perm(
                b, tuple(_sortable(a) for a in arrs), table.num_rows
            )
            perm_host = np.asarray(perm)
            sorted_b_host = np.asarray(sorted_b)
    starts = bucket_starts(sorted_b_host, num_buckets)
    return table.take(perm_host), starts


# -- fused bucketize+sort for the pipelined build (device path) --------------
#
# The serial device path runs TWO dispatches (bucket-id hash, then the
# variadic sort). The
# pipelined build stages pow2-padded chunk buffers onto the device as files
# decode, then runs hash + concat + sort as ONE jitted program over the whole
# chunk group, with every staging buffer donated (the build owns them; XLA
# reuses their HBM for the sort operands). Numeric keys only — string keys
# need the union-dictionary re-encoding that happens on host anyway.

from functools import lru_cache


@lru_cache(maxsize=64)
def _fused_sort_program(n_keys: int, n_chunks: int, num_buckets: int):
    from .hashing import _SEED1, _mix_combine, fmix32, hash_device_values

    def impl(valid_lens, *flat):
        # flat layout: key column 0's chunks, then column 1's chunks, ...
        # Pad rows ride INSIDE the sort with a sentinel bucket id (they sort
        # last; lax.sort is stable, so real rows keep their relative —
        # i.e. unpadded-concat — order), which keeps the program's compile
        # shapes a function of the pow2-quantized buffer shapes ONLY: the
        # actual row counts are traced operands, not static values.
        cols = []
        for k in range(n_keys):
            cols.append(jnp.concatenate(flat[k * n_chunks : (k + 1) * n_chunks]))
        starts = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(valid_lens).astype(jnp.int32)]
        )
        real_parts, gidx_parts = [], []
        for i in range(n_chunks):
            li = jnp.arange(int(flat[i].shape[0]), dtype=jnp.int32)
            real = li < valid_lens[i]
            real_parts.append(real)
            # Each real row carries its UNPADDED global index; pad rows get a
            # sentinel (they land past the first n outputs anyway).
            gidx_parts.append(jnp.where(real, starts[i] + li, jnp.int32(2**31 - 1)))
        real = jnp.concatenate(real_parts)
        gidx = jnp.concatenate(gidx_parts)
        h = None
        for arr in cols:
            hc = hash_device_values(arr, _SEED1)
            h = hc if h is None else fmix32(_mix_combine(h, hc))
        b = jnp.where(
            real, (h % jnp.uint32(num_buckets)).astype(jnp.int32), jnp.int32(num_buckets)
        )
        operands = (b, *(_sortable(a) for a in cols), gidx)
        res = jax.lax.sort(operands, num_keys=1 + n_keys)
        return res[-1], res[0]  # (permutation, sorted bucket ids) incl. pad tail

    return _observed_jit(
        impl,
        label="partition.fused_bucketize_sort",
        donate_argnums=tuple(range(1, 1 + n_keys * n_chunks)),
    )


def fused_bucketize_sort_perm(
    chunk_arrays: List[List[jnp.ndarray]], valid_lens: Sequence[int], num_buckets: int
) -> Tuple[np.ndarray, np.ndarray]:
    """One-dispatch bucketize+sort over staged device chunks.

    `chunk_arrays[k][i]` = key column k's chunk i (device array, possibly
    pow2-padded beyond `valid_lens[i]`). All chunk buffers are DONATED —
    callers must not reuse them (best-effort: XLA reuses their memory where
    aliasing allows). Returns host (perm, sorted_bucket_ids) of length
    sum(valid_lens); identical ordering to the serial device path (`lax.sort`
    is stable, and the hash math is the same ops `bucket_id` runs)."""
    n_keys = len(chunk_arrays)
    n_chunks = len(chunk_arrays[0])
    n = int(sum(int(v) for v in valid_lens))
    fn = _fused_sort_program(n_keys, n_chunks, int(num_buckets))
    flat = [c for col in chunk_arrays for c in col]
    perm, sorted_b = fn(jnp.asarray(list(valid_lens), dtype=jnp.int32), *flat)
    return np.asarray(perm)[:n], np.asarray(sorted_b)[:n]


@_observed_jit(
    label="partition.packed_build_comp", static_argnums=(2, 3, 4, 5)
)
def _packed_build_comp(b, codes, bits: int, log2np: int, n_pad: int, num_buckets: int):
    """(bucket | biased code | row) int32 composites, padded to [1, n_pad]
    with the supremum composite (bucket field = num_buckets exceeds every real
    bucket, so pads sort last regardless of the remaining bits)."""
    n = codes.shape[0]
    biased = codes.astype(jnp.int32) + 1  # null (-1) -> reserved lane 0
    comp = (
        ((b.astype(jnp.int32) << bits) | biased) << log2np
    ) | jnp.arange(n, dtype=jnp.int32)
    pad_val = jnp.int32((num_buckets << bits) << log2np)
    return jnp.full((1, n_pad), pad_val, dtype=jnp.int32).at[0, :n].set(comp)


def pallas_packed_build_sort(
    b_dev, codes_dev, cardinality: int, n: int, num_buckets: int
) -> "Tuple[np.ndarray, np.ndarray] | None":
    """Sub-byte-key build fast path: for a single dictionary-encoded key whose
    cardinality fits a packed lane class (`engine/packed_codes.py`), the
    (bucket, biased code, row) triple bit-packs into ONE int32 composite —
    sorted by the single-lane Pallas bitonic (`pallas_sort.sort_comp_padded`),
    a QUARTER of the in-VMEM state of the int64 composite path and 1/3 of the
    (hi, lo, idx) network's exchanges. Unique row bits => the unstable bitonic
    reproduces the engine's canonical stable (bucket, code) order exactly
    (same argument as `_composite_sort_host`), so index files stay
    byte-identical whichever sort ran. Biased codes (code + 1) keep the null
    lane (-1 -> 0) ordered first, matching the raw-code variadic sort.
    Returns None when out of budget (flag off, cardinality past the 4-bit
    class, int32 headroom, or sort-gate shapes)."""
    from ..engine.packed_codes import bits_for_cardinality, packed_codes_enabled
    from .pallas_sort import pallas_sort_wanted, sort_comp_padded

    if not packed_codes_enabled():
        return None
    bits = bits_for_cardinality(int(cardinality))
    if bits is None:
        return None
    if n == 0:
        return np.empty(0, np.int32), np.empty(0, np.int32)
    n_pad = 1 << max(int(n) - 1, 1).bit_length()
    log2np = n_pad.bit_length() - 1
    # The pad composite is the largest value the encoding produces: it must
    # fit signed int32.
    if (num_buckets << bits) << log2np >= 1 << 31:
        return None
    if not pallas_sort_wanted(1, n_pad):
        return None
    comp = _packed_build_comp(b_dev, codes_dev, bits, log2np, n_pad, num_buckets)
    sorted_comp = sort_comp_padded(comp, jax.default_backend() != "tpu")
    head = sorted_comp[0, :n]
    perm = np.asarray(head & (n_pad - 1)).astype(np.int32)
    sorted_b = np.asarray(head >> (bits + log2np)).astype(np.int32)
    return perm, sorted_b


def pallas_composite_build_sort(
    b_dev, key_dev, n: int, num_buckets: int
) -> "Tuple[np.ndarray, np.ndarray] | None":
    """Small-build fast path: pack (bucket, key, row) into ONE int64 composite
    and sort it with the Pallas in-VMEM bitonic kernel (`ops/pallas_sort`) —
    the whole O(log² n) network in a single HBM round-trip instead of the
    multi-stage XLA variadic sort. The row-index tiebreaker in the low bits
    makes the unstable bitonic network reproduce the STABLE (bucket, key)
    order exactly, so the output contract matches `_sort_perm` bit-for-bit.
    Returns None when out of budget (shape, dtype, or int64 headroom)."""
    from .pallas_sort import pallas_sort_wanted, sort_padded_with_order

    key_dev = jnp.asarray(key_dev)
    if not jnp.issubdtype(key_dev.dtype, jnp.integer):
        return None
    n_pad = 1 << max(int(n) - 1, 1).bit_length()
    if not pallas_sort_wanted(1, n_pad):
        return None
    if n == 0:
        return np.empty(0, np.int32), np.empty(0, np.int32)
    k64 = key_dev.astype(jnp.int64)
    lo = int(jax.device_get(k64.min()))
    hi = int(jax.device_get(k64.max()))
    span = hi - lo + 1
    # Composite headroom: (num_buckets+1) * span * n_pad must fit signed 64.
    if span > (1 << 62) // max((num_buckets + 1) * n_pad, 1):
        return None
    iota = jnp.arange(n, dtype=jnp.int64)
    comp = (
        b_dev.astype(jnp.int64) * jnp.int64(span) + (k64 - jnp.int64(lo))
    ) * jnp.int64(n_pad) + iota
    pad_val = jnp.int64(num_buckets) * jnp.int64(span) * jnp.int64(n_pad)
    padded = jnp.full((1, n_pad), pad_val, dtype=jnp.int64).at[0, :n].set(comp)
    sorted_keys, order = sort_padded_with_order(padded)
    perm = np.asarray(order[0, :n]).astype(np.int32)
    sorted_b = np.asarray(
        (sorted_keys[0, :n] // jnp.int64(n_pad)) // jnp.int64(span)
    ).astype(np.int32)
    return perm, sorted_b
