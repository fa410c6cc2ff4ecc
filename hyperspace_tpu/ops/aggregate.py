"""Grouped aggregation on device: hash-sort + segment reductions.

The reference's real workloads are aggregation-bearing SQL (every BASELINE TPC-H/DS
config groups the indexed join's output; the reference gets GROUP BY for free from
Spark — `docs/_docs/13-toh-overview.md:33-36`). The engine analogue, TPU-first:

1. key64-hash the group columns on device (`ops.hashing`), argsort once — equal key
   tuples are guaranteed adjacent because equal tuples hash equal (null slots hold
   the canonical fill, so they hash equal too and form one cluster).
2. Detect group boundaries by comparing ADJACENT ACTUAL values (+ validity lanes),
   not hashes — so a 64-bit hash collision between different tuples can only SPLIT
   a group (the colliding tuples interleave within one sorted run), never merge two.
3. Segment-reduce every aggregate in one device pass (`jax.ops.segment_sum/min/max`
   with a static segment count → compiled once per shape class).
4. A host pass dedups representative key tuples; the astronomically-rare split from
   step 2 is repaired by recomputing on host — the exactness contract matches the
   join path's verify step (hash suggests, values decide).

SQL semantics: group-key nulls form one group (GROUP BY groups nulls); sum/min/max/
avg ignore null inputs and are NULL for all-null groups; count(col) counts non-null,
count(*) counts rows.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.schema import BOOL, FLOAT32, FLOAT64, INT32, INT64, STRING
from ..engine.table import Column, Table
from ..exceptions import HyperspaceException
from ..engine.device_cache import device_array
from ..telemetry import device_observatory as _devobs
from ..telemetry.compile_log import observed_jit as _observed_jit
from .hashing import key64

#: (out_name, fn, column|None) — column is None only for count(*).
AggTriple = Tuple[str, str, Optional[str]]


def _group_ids_body(has_valid: tuple, perm, flat, xp=jnp):
    """Boundary detection + group ids from a given sort permutation — the ONE
    home of the adjacent-value (+validity) semantics, used traced (fused
    device program, xp=jnp) and eagerly on HOST arrays (CPU path, xp=np:
    eager jnp ops here were measured at ~0.5 s of device round-trips per 8M
    aggregate on the CPU backend). `has_valid[i]` tells whether key column i
    contributes a validity lane in `flat`."""
    n = perm.shape[0]
    eq = xp.ones(max(n - 1, 0), bool)
    i = 0
    for hv in has_valid:
        a = flat[i]
        i += 1
        sa = a[perm]
        col_eq = sa[1:] == sa[:-1]
        if hv:
            sv = flat[i][perm]
            i += 1
            both_null = (~sv[1:]) & (~sv[:-1])
            col_eq = (col_eq & (sv[1:] == sv[:-1])) | both_null
        eq = eq & col_eq
    boundary = xp.concatenate([xp.ones(1, bool), ~eq])
    gid = xp.cumsum(boundary.astype(xp.int64)) - 1
    return boundary, gid


@_observed_jit(label="aggregate.group_ids", static_argnums=(0,))
def _group_ids_fused(has_valid: tuple, k64, *flat):
    """Device path of the group-id pipeline as ONE compiled program."""
    perm = jnp.argsort(k64)  # stable by default
    boundary, gid = _group_ids_body(has_valid, perm, flat)
    return perm, boundary, gid

_NUMERIC = (INT32, INT64, FLOAT32, FLOAT64, BOOL)


def _minmax_fill(dtype: np.dtype, fn: str):
    """Null-mask fill for min/max: the opposite extreme of the value domain,
    so masked slots never win the reduction. The ONE home of this rule for
    the device program (`_seg_reduce_body`), the CPU fast path
    (`_segment_reduce_host`), and the collision-repair oracle
    (`_host_aggregate`). Bool inputs are converted to int32 by callers first."""
    if np.issubdtype(dtype, np.floating):
        return np.asarray(np.inf if fn == "min" else -np.inf, dtype=dtype)
    info = np.iinfo(dtype)
    return np.asarray(info.max if fn == "min" else info.min, dtype=dtype)


def _acc_dtype(dtype):
    """sum/avg accumulator widening: floats → float64, ints/bools → int64
    (np scalar types are jnp-compatible, so both paths share this)."""
    return np.float64 if np.issubdtype(np.dtype(dtype), np.floating) else np.int64


def result_dtype(fn: str, in_dtype: Optional[str]) -> str:
    """Aggregate result type: count/count_distinct→int64; avg→float64; sum widens
    to int64/float64; min/max preserve the input type (strings included —
    dictionary order is value order because dictionaries are sorted)."""
    if fn == "count":
        return INT64
    if in_dtype is None:
        raise HyperspaceException(f"{fn}() requires a column")
    if fn == "count_distinct":
        return INT64
    if fn == "avg":
        if in_dtype not in _NUMERIC:
            raise HyperspaceException(f"avg() unsupported for {in_dtype}")
        return FLOAT64
    if fn == "sum":
        if in_dtype in (FLOAT32, FLOAT64):
            return FLOAT64
        if in_dtype in (INT32, INT64, BOOL):
            return INT64
        raise HyperspaceException(f"sum() unsupported for {in_dtype}")
    if fn in ("min", "max"):
        return in_dtype
    raise HyperspaceException(f"Unknown aggregate function: {fn}")


def _out_column(
    fn: str, col: Optional[Column], dtype: str, vals: np.ndarray, validity
) -> Column:
    """Package reduced values (+ all-null-group validity) as an output column."""
    v = None if validity is None or bool(np.all(validity)) else np.asarray(validity, bool)
    if dtype == STRING:
        d = col.dictionary if col is not None and len(col.dictionary) else np.array([""], "<U1")
        codes = np.asarray(vals, np.int64)
        if v is not None:
            # All-null groups hold the min/max fill sentinel — not a valid code.
            codes = np.where(v, codes, 0)
        return Column(STRING, codes.astype(np.int32), d, v)
    return Column(dtype, np.asarray(vals).astype(np.dtype(dtype)), None, v)


def _empty_result(table: Table, group_keys, aggs: Sequence[AggTriple]) -> Table:
    out = {}
    for k in group_keys:
        out[k] = table.column(k)
    for out_name, fn, col_name in aggs:
        col = table.column(col_name) if col_name is not None else None
        dtype = result_dtype(fn, None if col is None else col.dtype)
        out[out_name] = _out_column(fn, col, dtype, np.empty(0, np.int64), None)
    return Table(out)


def _distinct_values(data: np.ndarray) -> np.ndarray:
    """Value lane for distinct-dedup: floats canonicalized (all NaNs one value,
    -0.0 == +0.0) and viewed as bit patterns, because structured np.unique
    compares NaN != NaN and would count every NaN occurrence separately."""
    if np.issubdtype(data.dtype, np.floating):
        d = data.astype(np.float64)
        d = np.where(np.isnan(d), np.float64("nan"), d)
        d = np.where(d == 0.0, np.float64(0.0), d)
        return d.view(np.int64)
    return data


def _count_distinct_per_group(
    group_ids: np.ndarray, col: Column, valid: np.ndarray, n_groups: int
) -> np.ndarray:
    """Exact per-group distinct counts via (group, value) pair dedup — the ONE
    implementation behind the grouped device path, the host oracle, and
    (with a single group) the global path. Values are codes for strings."""
    pairs = np.rec.fromarrays(
        [group_ids[valid], _distinct_values(col.data)[valid]]
    )
    uniq_pairs = np.unique(pairs)
    vals = np.zeros(n_groups, np.int64)
    np.add.at(vals, uniq_pairs.f0, 1)
    return vals


def _canon_distinct_traced(x):
    """Traced twin of `_distinct_values`' canonicalization (all NaNs one value,
    -0.0 == +0.0, floats viewed as int64 bit patterns) so the device
    count-distinct compares the same value identities the host oracle does."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        d = x.astype(jnp.float64)
        d = jnp.where(jnp.isnan(d), jnp.float64(np.nan), d)
        d = jnp.where(d == 0.0, jnp.float64(0.0), d)
        return jax.lax.bitcast_convert_type(d, jnp.int64)
    if x.dtype == jnp.bool_:
        return x.astype(jnp.int32)
    return x


@_observed_jit(label="aggregate.count_distinct", static_argnums=(0, 1))
def _count_distinct_dev_jit(n_groups: int, has_valid: bool, gid, perm, x, valid=None):
    """Per-group exact distinct counts ON DEVICE, for rows already run through
    the group-id program (`gid`/`perm` from `_group_ids_fused`): sort each
    group's values adjacent (invalid slots to the back of their group), count
    first-of-run valid slots per group. Exactness matches the host
    `_count_distinct_per_group` (actual canonicalized values, never hashes)."""
    xs = _canon_distinct_traced(x)[perm]
    v = valid[perm] if has_valid else jnp.ones(xs.shape[0], bool)
    # lexsort: LAST key is primary → (value, invalid-last, group).
    order = jnp.lexsort((xs, ~v, gid))
    sg = gid[order]
    sx = xs[order]
    sv = v[order]
    first = jnp.concatenate(
        [jnp.ones(1, bool), (sg[1:] != sg[:-1]) | (sx[1:] != sx[:-1])]
    )
    # Valids sort before invalids within a group, so a valid slot never follows
    # an invalid one of the same group: `first & valid` counts distinct valids.
    return jax.ops.segment_sum(
        (first & sv).astype(jnp.int64), sg, num_segments=n_groups
    )


def _global_aggregate(table: Table, aggs: Sequence[AggTriple]) -> Table:
    """No group keys: one output row (SQL global aggregate; empty input gives
    count=0 and NULL sum/min/max/avg)."""
    out = {}
    n = table.num_rows
    for out_name, fn, col_name in aggs:
        col = table.column(col_name) if col_name is not None else None
        dtype = result_dtype(fn, None if col is None else col.dtype)
        if fn == "count" and col is None:
            out[out_name] = _out_column(fn, col, dtype, np.array([n]), None)
            continue
        valid = col.validity if col.validity is not None else np.ones(n, bool)
        nv = int(valid.sum())
        if fn == "count":
            out[out_name] = _out_column(fn, col, dtype, np.array([nv]), None)
            continue
        if fn == "count_distinct":
            counts = _count_distinct_per_group(
                np.zeros(n, np.int64), col, np.asarray(valid, bool), 1
            )
            out[out_name] = _out_column(fn, col, dtype, counts, None)
            continue
        if nv == 0:
            out[out_name] = _out_column(
                fn, col, dtype, np.zeros(1, np.int64), np.zeros(1, bool)
            )
            continue
        data = col.data[valid]  # codes for strings: min code == min value
        if fn == "min":
            r = data.min()
        elif fn == "max":
            r = data.max()
        else:
            acc = data.astype(np.float64 if dtype == FLOAT64 else np.int64)
            r = acc.sum()
            if fn == "avg":
                r = float(r) / nv
        out[out_name] = _out_column(fn, col, dtype, np.array([r]), None)
    return Table(out)


def _seg_reduce_body(fn: str, n_groups: int, has_valid: bool, gid, perm, x, valid=None):
    """One aggregate's permute + mask + segment reduce — the traced body shared
    by the single-agg program and the all-aggs-fused program. Returns
    (values, n_valid)."""
    n = x.shape[0]
    v = valid[perm] if has_valid else jnp.ones(n, bool)
    n_valid = jax.ops.segment_sum(v.astype(jnp.int64), gid, num_segments=n_groups)
    if fn == "count":
        return n_valid, n_valid
    xs = x[perm]
    if fn in ("sum", "avg"):
        acc = xs.astype(_acc_dtype(xs.dtype))
        s = jax.ops.segment_sum(jnp.where(v, acc, 0), gid, num_segments=n_groups)
        if fn == "sum":
            return s, n_valid
        c = jnp.maximum(n_valid, 1)
        return s.astype(jnp.float64) / c.astype(jnp.float64), n_valid
    # min/max: mask nulls to the opposite extreme; all-null groups are invalid.
    if xs.dtype == jnp.bool_:
        xs = xs.astype(jnp.int32)  # segment_min/iinfo don't take bools
    masked = jnp.where(v, xs, _minmax_fill(np.dtype(xs.dtype), fn))
    reduce = jax.ops.segment_min if fn == "min" else jax.ops.segment_max
    return reduce(masked, gid, num_segments=n_groups), n_valid


@_observed_jit(label="aggregate.seg_reduce", static_argnums=(0, 1, 2))
def _seg_reduce_jit(fn: str, n_groups: int, has_valid: bool, gid, perm, x, valid=None):
    """One aggregate's whole device pipeline as a single compiled program,
    keyed on (fn, n_groups, validity presence, shapes/dtypes)."""
    return _seg_reduce_body(fn, n_groups, has_valid, gid, perm, x, valid)


@_observed_jit(label="aggregate.seg_reduce_multi", static_argnums=(0, 1))
def _seg_reduce_multi_jit(specs: tuple, n_groups: int, gid, perm, *flat):
    """EVERY aggregate's segment reduction in ONE compiled program — on a
    remote PJRT transport each dispatch is a round-trip, so a 4-aggregate
    query pays 1 RTT here instead of 4. `specs[i] = (fn, has_valid)`; `flat`
    carries x [+ valid] per aggregate in order. XLA CSEs the shared permute.
    Returns a flat tuple of (values, n_valid) pairs."""
    out = []
    i = 0
    for fn, has_valid in specs:
        x = flat[i]
        i += 1
        valid = None
        if has_valid:
            valid = flat[i]
            i += 1
        out.extend(_seg_reduce_body(fn, n_groups, has_valid, gid, perm, x, valid))
    return tuple(out)


def _segment_reduce(
    fn: str,
    col: Optional[Column],
    gid: jnp.ndarray,
    perm: jnp.ndarray,
    n_groups: int,
    seg_rows: jnp.ndarray,
):
    """One aggregate over the hash-sorted rows → (values[n_groups], validity|None)."""
    if fn == "count" and col is None:
        return np.asarray(seg_rows), None
    assert col is not None
    has_valid = col.validity is not None
    args = (device_array(col.data),)
    if has_valid:
        args = args + (device_array(col.validity),)
    vals, n_valid = _seg_reduce_jit(fn, int(n_groups), has_valid, gid, perm, *args)
    if fn == "count":
        return np.asarray(n_valid), None
    any_valid = np.asarray(n_valid) > 0
    return np.asarray(vals), any_valid


def _segment_reduce_host(
    fn: str,
    col: Optional[Column],
    perm: np.ndarray,
    starts: np.ndarray,
    seg_rows: np.ndarray,
):
    """Host twin of `_segment_reduce` for the CPU backend: `ufunc.reduceat`
    over the sorted rows at the group-start offsets. The device branch's
    `_seg_reduce_jit` on XLA-CPU pays an upload per 8M-row column plus a
    single-threaded segment scatter — measured ~0.65 s per aggregate at 8M,
    vs ~0.1 s for the gather+reduceat pair here. Returns (values, n_valid):
    the per-group non-null input counts, from which callers derive the
    all-null-group validity (and which the streaming carry accumulates)."""
    if fn == "count" and col is None:
        n_rows = seg_rows.astype(np.int64)
        return n_rows, n_rows
    assert col is not None
    has_valid = col.validity is not None
    sv = col.validity[perm] if has_valid else None
    n_valid = (
        np.add.reduceat(sv.astype(np.int64), starts)
        if has_valid
        else seg_rows.astype(np.int64)
    )
    if fn == "count":
        return n_valid, n_valid
    xs = col.data[perm]
    if fn in ("sum", "avg"):
        acc = xs.astype(_acc_dtype(xs.dtype))
        if has_valid:
            acc = np.where(sv, acc, 0)
        s = np.add.reduceat(acc, starts)
        if fn == "sum":
            return s, n_valid
        return s.astype(np.float64) / np.maximum(n_valid, 1), n_valid
    # min/max: mask nulls to the opposite extreme; all-null groups are invalid.
    if xs.dtype == np.bool_:
        xs = xs.astype(np.int32)
    if has_valid:
        xs = np.where(sv, xs, _minmax_fill(xs.dtype, fn))
    op = np.minimum if fn == "min" else np.maximum
    return op.reduceat(xs, starts), n_valid


def _key_records(table: Table, group_keys) -> np.ndarray:
    """Key tuples as one comparable structured array: per column (data, valid) with
    invalid slots' data masked to the canonical fill, so null == null and
    null != everything-else exactly."""
    fields = []
    for k in group_keys:
        c = table.column(k)
        valid = c.validity if c.validity is not None else np.ones(len(c.data), bool)
        data = np.where(valid, c.data, np.zeros((), dtype=c.data.dtype))
        fields.append(data)
        fields.append(valid)
    return np.rec.fromarrays(fields)


def _host_aggregate(table: Table, group_keys, aggs: Sequence[AggTriple]) -> Table:
    """Exact host groupby — the collision-repair path (and the oracle the tests
    compare the device path against). np.unique group ids + ufunc.at reductions."""
    recs = _key_records(table, group_keys)
    uniq, first_idx, inverse = np.unique(recs, return_index=True, return_inverse=True)
    n_groups = len(uniq)
    out = {}
    rep_rows = table.take(np.sort(first_idx))
    # np.unique sorts groups; keep FIRST-OCCURRENCE order stable instead so the
    # device and host paths are comparable after row sorting.
    order = np.argsort(first_idx, kind="stable")
    remap = np.empty(n_groups, np.int64)
    remap[order] = np.arange(n_groups)
    inverse = remap[inverse]
    for k in group_keys:
        out[k] = rep_rows.column(k)
    for out_name, fn, col_name in aggs:
        col = table.column(col_name) if col_name is not None else None
        dtype = result_dtype(fn, None if col is None else col.dtype)
        if fn == "count" and col is None:
            vals = np.zeros(n_groups, np.int64)
            np.add.at(vals, inverse, 1)
            out[out_name] = _out_column(fn, col, dtype, vals, None)
            continue
        valid = col.validity if col.validity is not None else np.ones(len(col.data), bool)
        nv = np.zeros(n_groups, np.int64)
        np.add.at(nv, inverse[valid], 1)
        if fn == "count":
            out[out_name] = _out_column(fn, col, dtype, nv, None)
            continue
        if fn == "count_distinct":
            vals = _count_distinct_per_group(inverse, col, valid, n_groups)
            out[out_name] = _out_column(fn, col, dtype, vals, None)
            continue
        any_valid = nv > 0
        data = col.data
        if fn in ("sum", "avg"):
            acc = data.astype(_acc_dtype(data.dtype))
            s = np.zeros(n_groups, acc.dtype)
            np.add.at(s, inverse[valid], acc[valid])
            vals = s if fn == "sum" else s.astype(np.float64) / np.maximum(nv, 1)
        else:
            if data.dtype == np.bool_:
                data = data.astype(np.int32)
            vals = np.full(n_groups, _minmax_fill(data.dtype, fn), data.dtype)
            op = np.minimum if fn == "min" else np.maximum
            op.at(vals, inverse[valid], data[valid])
        out[out_name] = _out_column(fn, col, dtype, vals, any_valid)
    return Table(out)


class DevCol:
    """A device-resident virtual column for the fused join→aggregate pipeline:
    jnp value array (codes for strings), host dictionary, optional jnp validity
    lane. Duck-types the attrs `key64`/`_out_column` read from `Column`."""

    __slots__ = ("dtype", "arr", "dictionary", "validity")

    def __init__(self, dtype: str, arr, dictionary=None, validity=None):
        self.dtype = dtype
        self.arr = arr
        self.dictionary = dictionary
        self.validity = validity

    @property
    def is_string(self) -> bool:
        return self.dtype == STRING


def hash_aggregate_device(
    cols: dict,
    row_valid,
    group_keys: Sequence[str],
    aggs: Sequence[AggTriple],
) -> Optional[Table]:
    """GROUP BY over DEVICE-resident virtual columns (`DevCol`) — the aggregate
    core of the fused bucketed-join→aggregate path. Same pipeline as
    `hash_aggregate` (hash-sort, adjacent-ACTUAL-value boundaries, segment
    reductions) but the input never materializes as a host table: only the
    per-group results and representative key rows are pulled (n_groups-sized).

    `row_valid` is an optional global validity lane: the fused join pads its
    compacted pair arrays by REPEATING a real pair to keep shapes static, and
    those pad slots must contribute to no aggregate (they can never form a
    spurious group — they duplicate real key values). Returns None on the
    astronomically-rare 64-bit hash collision split (caller recomputes exactly)."""
    group_keys = list(group_keys)
    key_cols = [cols[k] for k in group_keys]
    k64 = key64(key_cols, [c.arr for c in key_cols])

    flat = []
    has_valid = []
    for c in key_cols:
        flat.append(c.arr)
        has_valid.append(c.validity is not None)
        if c.validity is not None:
            flat.append(c.validity)
    perm, boundary, gid = _group_ids_fused(tuple(has_valid), k64, *flat)
    n_groups = int(gid[-1]) + 1

    # Representative rows: one device compaction + tiny gathers, pulled host-side
    # at n_groups size (never the full pair count).
    rep_rows = perm[jnp.nonzero(boundary, size=n_groups)[0]]
    rep_cols = {}
    for k, c in zip(group_keys, key_cols):
        data = np.asarray(c.arr[rep_rows])
        v = None if c.validity is None else np.asarray(c.validity[rep_rows], bool)
        if c.is_string:
            codes = data.astype(np.int32)
            if v is not None:
                codes = np.where(v, codes, 0).astype(np.int32)
            rep_cols[k] = Column(STRING, codes, c.dictionary, v)
        else:
            if v is not None:
                data = np.where(v, data, np.zeros((), dtype=data.dtype))
            rep_cols[k] = Column(c.dtype, data.astype(np.dtype(c.dtype)), None, v)
    rep_table = Table(rep_cols)
    if len(np.unique(_key_records(rep_table, group_keys))) != n_groups:
        return None  # collision split: caller takes the exact path

    out = dict(rep_cols)
    # ALL aggregates reduce in ONE compiled program (1 dispatch RTT), results
    # pulled host-side in ONE transfer.
    specs, flat, metas = [], [], []
    for out_name, fn, col_name in aggs:
        c = cols[col_name] if col_name is not None else None
        dtype = result_dtype(fn, None if c is None else c.dtype)
        if fn == "count" and c is None:
            # count(*) counts surviving rows: the row_valid lane IS the data.
            specs.append(("count", row_valid is not None))
            flat.append(row_valid if row_valid is not None else k64)
            if row_valid is not None:
                flat.append(row_valid)
            metas.append((out_name, fn, None, dtype))
            continue
        v = c.validity
        if row_valid is not None:
            v = row_valid if v is None else (v & row_valid)
        specs.append((fn, v is not None))
        flat.append(c.arr)
        if v is not None:
            flat.append(v)
        metas.append((out_name, fn, c, dtype))
    results = jax.device_get(
        _seg_reduce_multi_jit(tuple(specs), n_groups, gid, perm, *flat)
    )
    for i, (out_name, fn, c, dtype) in enumerate(metas):
        vals, n_valid = np.asarray(results[2 * i]), np.asarray(results[2 * i + 1])
        if fn == "count":
            out[out_name] = _out_column(fn, None, dtype, n_valid, None)
            continue
        out[out_name] = _out_column(fn, c, dtype, vals, n_valid > 0)
    return Table(out)


# Direct-address aggregation cell budget: 4M cells ≈ 32 MB per int64
# accumulator — bincount is O(n + R), so a bounded R keeps the pass linear.
_DIRECT_CELL_BUDGET = 1 << 22


def _direct_layout(key_cols, aggs: Sequence[AggTriple]):
    """Eligibility + cell layout of the direct-address host aggregation:
    (los, ranges, datas, strides, cells), or None when the shape doesn't
    apply (float or null-able keys, unbounded ranges, min/max aggregates).
    The ONE home of this decision: `_direct_host_aggregate` takes it over the
    full key columns, and the streaming finalizer re-derives it over the
    carried group keys (whose value ranges/dictionaries equal the full
    columns') to reproduce the same output order."""
    for _, fn, _ in aggs:
        if fn in ("min", "max"):
            return None
    los, ranges, datas = [], [], []
    for c in key_cols:
        if c.validity is not None:
            return None
        data = c.data
        if c.is_string:
            lo, hi = 0, max(len(c.dictionary) - 1, 0)
        elif data.dtype == np.bool_:
            data = data.astype(np.int64)
            lo, hi = 0, 1
        elif np.issubdtype(data.dtype, np.integer):
            if len(data) == 0:
                return None
            lo, hi = int(data.min()), int(data.max())
        else:
            return None
        los.append(lo)
        ranges.append(hi - lo + 1)
        datas.append(data)
    cells = 1
    for r in ranges:
        cells *= r
        if cells > _DIRECT_CELL_BUDGET:
            return None
    # Mixed-radix cell id strides: last key fastest (row-major).
    strides = [1] * len(ranges)
    for i in range(len(ranges) - 2, -1, -1):
        strides[i] = strides[i + 1] * ranges[i + 1]
    return los, ranges, datas, strides, cells


def _direct_host_aggregate(
    table: Table, group_keys, key_cols, aggs: Sequence[AggTriple]
) -> Optional[Table]:
    """Sort-free host aggregation for bounded-range integer/dictionary keys:
    each key tuple maps to a dense cell id (mixed-radix over per-key value
    ranges) and every aggregate is one `np.bincount` pass — no 8M-row argsort
    (measured 0.58 s of the 8M CPU Q3 aggregate) and no representative-row
    gather (key values are reconstructed from the cell id). Returns None
    whenever the shape doesn't apply (`_direct_layout`) — the sort path is
    always correct."""
    n = table.num_rows
    layout = _direct_layout(key_cols, aggs)
    if layout is None:
        return None
    los, ranges, datas, strides, cells = layout

    gid0 = np.zeros(n, np.int64)
    for data, lo, st in zip(datas, los, strides):
        gid0 += (data.astype(np.int64) - lo) * st

    counts = np.bincount(gid0, minlength=cells)
    present = np.nonzero(counts)[0]
    n_groups = len(present)
    counts_p = counts[present]
    remap = None  # dense per-row group ids, built only if an agg needs them

    out = {}
    for k, c, lo, rng, st in zip(group_keys, key_cols, los, ranges, strides):
        vals = lo + (present // st) % rng
        if c.is_string:
            out[k] = Column(STRING, vals.astype(np.int32), c.dictionary, None)
        else:
            out[k] = Column(c.dtype, vals.astype(c.data.dtype), None, None)

    # Per-column memo of (valid-cell ids, valid counts): count(v)+sum(v)+avg(v)
    # over one nullable column must not pay three O(n) mask gathers and
    # full-cells bincounts for the same answer.
    nv_cache: dict = {}

    def _valid_stats(col_name, valid):
        if valid is None:
            return gid0, counts_p
        if col_name not in nv_cache:
            g = gid0[valid]
            nv_cache[col_name] = (g, np.bincount(g, minlength=cells)[present])
        return nv_cache[col_name]

    for out_name, fn, col_name in aggs:
        col = table.column(col_name) if col_name is not None else None
        dtype = result_dtype(fn, None if col is None else col.dtype)
        if fn == "count" and col is None:
            out[out_name] = _out_column(fn, col, dtype, counts_p, None)
            continue
        valid = col.validity
        if fn == "count":
            _, nv = _valid_stats(col_name, valid)
            out[out_name] = _out_column(fn, col, dtype, nv, None)
            continue
        if fn == "count_distinct":
            if remap is None:
                remap = np.full(cells, -1, np.int64)
                remap[present] = np.arange(n_groups)
            v = valid if valid is not None else np.ones(n, bool)
            vals = _count_distinct_per_group(remap[gid0], col, v, n_groups)
            out[out_name] = _out_column(fn, col, dtype, vals, None)
            continue
        # sum / avg
        g, nv = _valid_stats(col_name, valid)
        any_valid = nv > 0
        data = col.data
        if np.issubdtype(data.dtype, np.floating):
            w = data.astype(np.float64)
            if valid is not None:
                w = w[valid]
            s = np.bincount(g, weights=w, minlength=cells)[present]
        else:
            # Exact int64 accumulation (bincount weights are float64 and
            # would round sums past 2^53).
            acc = data.astype(np.int64)
            if valid is not None:
                acc = acc[valid]
            s = np.zeros(cells, np.int64)
            np.add.at(s, g, acc)
            s = s[present]
        if fn == "avg":
            s = s.astype(np.float64) / np.maximum(nv, 1)
        out[out_name] = _out_column(fn, col, dtype, s, any_valid)
    return Table(out)


def hash_aggregate(table: Table, group_keys, aggs: Sequence[AggTriple]) -> Table:
    """GROUP BY `group_keys` computing `aggs` = [(out_name, fn, column|None)]."""
    group_keys = list(group_keys)
    if not group_keys:
        return _global_aggregate(table, aggs)
    key_cols = [table.column(k) for k in group_keys]
    if table.num_rows == 0:
        return _empty_result(table, group_keys, aggs)

    n = table.num_rows
    from .backend import use_device_path

    device = use_device_path()
    if not device:
        direct = _direct_host_aggregate(table, group_keys, key_cols, aggs)
        if direct is not None:
            return direct
    from ..engine.encoded_device import stage_codes

    arrs = [stage_codes(c, "agg_keys") for c in key_cols]
    k64 = key64(key_cols, arrs)

    # Group boundaries from ADJACENT ACTUAL VALUES (+ validity), never the
    # hash. ONE host-side lane list (data [+ validity] per key column); the
    # device branch stages each lane through the memoized upload cache
    # (string keys as narrow codes — adjacent equality is value-preserving
    # under narrowing), the host branch consumes the flat lanes directly.
    flat_host = []
    has_valid = []
    flat_dev = [] if device else None
    for c in key_cols:
        flat_host.append(c.data)
        if device:
            flat_dev.append(stage_codes(c, "agg_keys"))
        has_valid.append(c.validity is not None)
        if c.validity is not None:
            flat_host.append(c.validity)
            if device:
                flat_dev.append(device_array(c.validity))
    if device:
        # One fused program for sort + boundary detection + group ids: each
        # eager op would be a dispatch of its own.
        perm, boundary, gid = _group_ids_fused(
            tuple(has_valid), k64, *flat_dev
        )
        n_groups = int(gid[-1]) + 1
        seg_rows = jax.ops.segment_sum(
            jnp.ones(n, jnp.int64), gid, num_segments=n_groups
        )
        perm_np = starts_np = seg_rows_np = None
    else:
        # Host argsort beats XLA-CPU's sort, and the boundary pipeline runs on
        # the HOST key arrays directly (same body, xp=np) — eager jnp ops here
        # are CPU device round-trips per operator. The reductions stay on host
        # too (`_segment_reduce_host`): round-tripping the payload columns
        # through XLA-CPU's segment ops cost ~1.9 s of the 8M aggregate.
        from .join import stable_argsort_host

        perm_np = stable_argsort_host(k64)
        boundary, gid = _group_ids_body(tuple(has_valid), perm_np, flat_host, xp=np)
        perm = perm_np
        starts_np = np.nonzero(boundary)[0]
        n_groups = len(starts_np)
        seg_rows_np = np.diff(np.append(starts_np, n))
    gid_of_row = None
    reduced = []
    for out_name, fn, col_name in aggs:
        col = table.column(col_name) if col_name is not None else None
        dtype = result_dtype(fn, None if col is None else col.dtype)
        if fn == "count_distinct":
            if device:
                # The group-id program already ran on device: keep the distinct
                # dedup there too (sort-adjacent + first-of-run counting on
                # actual values) instead of pulling gid/perm and the column to
                # the host. The host path below stays the pinned oracle.
                has_v = col.validity is not None
                if getattr(col, "is_string", False):
                    from ..engine.encoded_device import widen_for_gather

                    # Narrow/packed staging is distinctness-preserving; widen
                    # back so the jitted program keeps ONE int32 compile class.
                    args = (widen_for_gather(stage_codes(col, "agg_distinct")),)
                else:
                    args = (device_array(col.data),)
                if has_v:
                    args = args + (device_array(col.validity),)
                vals = np.asarray(
                    _count_distinct_dev_jit(int(n_groups), has_v, gid, perm, *args)
                )
                reduced.append((out_name, fn, col, dtype, vals, None))
                continue
            # Exact distinct: dedupe (group, value) pairs on host (same exactness
            # contract as the collision-repair path).
            if gid_of_row is None:
                gid_of_row = np.empty(n, np.int64)
                gid_of_row[np.asarray(perm)] = np.asarray(gid)
            valid = (
                col.validity if col.validity is not None else np.ones(n, bool)
            )
            vals = _count_distinct_per_group(gid_of_row, col, valid, n_groups)
            reduced.append((out_name, fn, col, dtype, vals, None))
            continue
        if device:
            vals, validity = _segment_reduce(fn, col, gid, perm, n_groups, seg_rows)
        else:
            vals, n_valid = _segment_reduce_host(
                fn, col, perm_np, starts_np, seg_rows_np
            )
            validity = None if fn == "count" else n_valid > 0
        reduced.append((out_name, fn, col, dtype, vals, validity))

    # Representative row of each group → materialize the key columns on host.
    reps = (
        perm_np[starts_np]
        if not device
        else np.asarray(perm)[np.nonzero(np.asarray(boundary))[0]]
    )
    rep_rows = table.take(reps).select(group_keys)
    if len(np.unique(_key_records(rep_rows, group_keys))) != n_groups:
        # 64-bit collision interleaved two tuples in one sorted run: recompute
        # exactly on host (rarity ~2^-64; correctness over speed).
        return _host_aggregate(table, group_keys, aggs)

    out = {}
    for k in group_keys:
        out[k] = rep_rows.column(k)
    for out_name, fn, col, dtype, vals, validity in reduced:
        out[out_name] = _out_column(fn, col, dtype, vals, validity)
    return Table(out)


# ---------------------------------------------------------------------------
# Streaming chunk-carry aggregation (the read-side pipeline's reduce stage)
# ---------------------------------------------------------------------------

#: Aggregate functions the chunk-carry stream supports. count_distinct is
#: excluded by design: its state is a per-group value SET, not a scalar.
STREAMING_AGG_FNS = ("count", "sum", "avg", "min", "max")

_STATE_PREFIX = "__hs_"


def streaming_agg_supported(group_keys, aggs: Sequence[AggTriple]) -> bool:
    """Whether this GROUP BY shape can run as a chunk-carry stream: grouped
    (global aggregates keep the one-pass host path), scalar-state functions
    only, and no group key colliding with the internal state-column names."""
    if not group_keys:
        return False
    if any(fn not in STREAMING_AGG_FNS for _, fn, _ in aggs):
        return False
    return not any(str(k).startswith(_STATE_PREFIX) for k in group_keys)


def _pow2_ceil(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length()


def direct_stream_hint(key_cols, aggs: Sequence[AggTriple]):
    """Direct-address layout hint for a `StreamAggregator` whose chunks are
    GATHERS of known source columns (the streamed join→aggregate): when the
    SOURCE key columns qualify for the direct-address layout, every chunk of
    gathered values is guaranteed inside the source's value ranges, so the
    stream can accumulate straight into one dense cell array — no per-chunk
    state tables and no record-keyed carry merge at all. Returns
    (los, ranges, strides, cells, key_meta) or None; key_meta reconstructs
    the output key columns from cell ids exactly like
    `_direct_host_aggregate` does."""
    layout = _direct_layout(key_cols, aggs)
    if layout is None:
        return None
    los, ranges, _datas, strides, cells = layout
    key_meta = [
        (c.dtype, c.dictionary if c.is_string else None, c.data.dtype)
        for c in key_cols
    ]
    return los, ranges, strides, cells, key_meta


def _pad_repeat_first(a: np.ndarray, cap: int) -> np.ndarray:
    """Pad to `cap` rows by REPEATING the first row: pad slots join a real
    group (they duplicate real key values) and are masked out of every
    reduction by the row-validity lane — the same padding contract as the
    fused join→aggregate's compacted pair arrays."""
    if len(a) == cap:
        return a
    return np.concatenate([a, np.broadcast_to(a[:1], (cap - len(a),))])


# Per-arity compiled reducers (the arity is the flat lane count; donation
# wants a static argnum tuple, so each arity gets its own jitted wrapper).
_STREAM_REDUCE_FNS: dict = {}


def _stream_reduce_fn(n_flat: int, donate: bool):
    """ALL of one chunk's segment reductions as ONE compiled program, with a
    row-validity lane ANDed into every aggregate (pad slots and, when a
    caller fuses a filter, masked-out rows contribute nothing). `n_seg` is
    pow2-quantized by the caller so growing group counts share programs.
    With `donate`, the one-shot staged chunk lanes (and gid/perm/row_valid)
    are donated so XLA reuses their buffers across chunks."""
    key = (n_flat, donate)
    fn = _STREAM_REDUCE_FNS.get(key)
    if fn is not None:
        return fn

    def body(specs, n_seg, gid, perm, row_valid, *flat):
        out = []
        i = 0
        for sfn, has_valid in specs:
            x = flat[i]
            i += 1
            v = row_valid
            if has_valid:
                v = flat[i] & row_valid
                i += 1
            out.extend(_seg_reduce_body(sfn, n_seg, True, gid, perm, x, v))
        return tuple(out)

    donate_argnums = tuple(range(2, 5 + n_flat)) if donate else ()
    fn = _observed_jit(
        body,
        label="aggregate.stream_reduce",
        static_argnums=(0, 1),
        donate_argnums=donate_argnums,
    )
    _STREAM_REDUCE_FNS[key] = fn
    return fn


class StreamAggregator:
    """Chunk-carry GROUP BY: feed table chunks with `add_chunk`, read the
    final aggregate with `finalize`.

    Each chunk reduces to per-group PARTIAL STATES through the same machinery
    the one-pass `hash_aggregate` uses (key64 hash-sort, adjacent-ACTUAL-value
    boundaries, segment reductions — host `reduceat` on the CPU backend, the
    fused jitted programs on the device path with pow2-quantized chunk shapes
    and donated staging buffers). States are (value, n_valid) pairs — avg
    carries (sum, count) — packaged as a small state TABLE (group keys + state
    columns), and carried states merge by exact key records (`_key_records`
    over the concatenated state tables, so string codes re-encode through
    union dictionaries and a 64-bit hash collision can never merge two
    groups). Merging is deferred until pending partials outgrow the carry
    (compaction), which keeps memory bounded without re-sorting the carry per
    chunk; the left-to-right chunk fold order is preserved regardless of
    compaction cadence, so results do not depend on prefetch/thread counts.

    Float sum/avg accumulate per chunk then across chunks, which reorders the
    additions relative to the one-pass path — results match it exactly for
    integer/count/min/max outputs and to float-associativity rounding for
    float sums (docs/query-pipeline.md).

    `finalize` emits groups in the one-pass path's output order: the
    direct-address cell order when `hash_aggregate`'s host fast path would
    have taken it (`_direct_layout` on the carried keys reproduces the same
    decision), else ascending key64."""

    def __init__(
        self, group_keys, aggs: Sequence[AggTriple], stages=None, direct_hint=None
    ):
        self.group_keys = list(group_keys)
        self.aggs = [tuple(a) for a in aggs]
        if not streaming_agg_supported(self.group_keys, self.aggs):
            raise HyperspaceException("aggregate shape not streamable")
        self._stages = stages
        self._carry: Optional[Table] = None
        self._pending: list = []
        self._pending_rows = 0
        self._in_dtypes: list = [None] * len(self.aggs)
        self.chunks = 0
        self.rows = 0
        # Direct-address cells mode (`direct_stream_hint`): dense accumulators
        # over the hinted cell space replace the state-table carry entirely.
        self._direct = direct_hint
        self._dcounts: Optional[np.ndarray] = None
        self._dstates: Optional[list] = None

    def _timed(self, stage: str):
        if self._stages is None:
            import contextlib

            return contextlib.nullcontext()
        return self._stages.timed(stage)

    # -- per-chunk partial ---------------------------------------------------

    def add_chunk(self, t: Table) -> None:
        # Dtype tracking BEFORE the empty-chunk return: a mixed-width source
        # whose wider-typed file is empty (or fully filtered) still promotes
        # in the one-pass path's concat, so it must promote here too.
        for i, (_out, fn, cname) in enumerate(self.aggs):
            if cname is not None:
                # Track the PROMOTED input dtype across chunks — a mixed-width
                # multi-file source promotes in the one-pass path's concat, so
                # the streamed result dtype must promote identically.
                cur = self._in_dtypes[i]
                new = t.column(cname).dtype
                if cur is None or cur == new or STRING in (cur, new):
                    self._in_dtypes[i] = new if cur is None else cur
                else:
                    from ..engine.schema import dtype_from_numpy

                    self._in_dtypes[i] = dtype_from_numpy(
                        np.promote_types(np.dtype(cur), np.dtype(new))
                    )
        if t.num_rows == 0:
            return
        if self._direct is not None:
            with self._timed("partial"):
                self._add_chunk_direct(t)
            self.chunks += 1
            self.rows += t.num_rows
            return
        from .backend import use_device_path

        with self._timed("partial"):
            partial = (
                self._partial_device(t) if use_device_path() else self._partial_host(t)
            )
        self.chunks += 1
        self.rows += t.num_rows
        self._pending.append(partial)
        self._pending_rows += partial.num_rows
        carry_rows = self._carry.num_rows if self._carry is not None else 0
        if self._pending_rows >= max(1 << 16, carry_rows):
            with self._timed("merge"):
                self._compact()

    def _direct_gid(self, t: Table) -> np.ndarray:
        los, _ranges, strides, _cells, _meta = self._direct
        gid0 = np.zeros(t.num_rows, np.int64)
        for k, lo, st in zip(self.group_keys, los, strides):
            c = t.column(k)
            if c.validity is not None:
                # The hint promised null-free source keys; a null here means
                # the chunks are NOT gathers of the hinted columns — fail
                # loudly rather than mis-bin.
                raise HyperspaceException("direct-hint chunk carries null keys")
            data = c.data
            if data.dtype == np.bool_:
                data = data.astype(np.int64)
            gid0 += (data.astype(np.int64) - lo) * st
        return gid0

    def _add_chunk_direct(self, t: Table) -> None:
        """Chunk fold in direct-address cells mode: one bincount pass per
        aggregate into persistent dense accumulators — the streamed twin of
        `_direct_host_aggregate`'s passes, with identical per-cell arithmetic
        (exact int64 sums; float64 bincount sums to associativity rounding)."""
        cells = self._direct[3]
        gid0 = self._direct_gid(t)
        if self._dcounts is None:
            self._dcounts = np.zeros(cells, np.int64)
            self._dstates = [
                [None, None] for _ in self.aggs
            ]  # per agg: [nv_cells, val_cells]
        self._dcounts += np.bincount(gid0, minlength=cells)
        for i, (_out, fn, cname) in enumerate(self.aggs):
            col = t.column(cname) if cname is not None else None
            state = self._dstates[i]
            if fn == "count" and col is None:
                continue  # count(*) reads self._dcounts
            valid = col.validity
            if valid is None:
                # All rows valid: the per-cell valid counts stay derivable
                # from _dcounts until some chunk introduces nulls.
                if state[0] is not None:
                    state[0] += np.bincount(gid0, minlength=cells)
            else:
                if state[0] is None:
                    # First null-bearing chunk: every earlier chunk was
                    # all-valid, so their per-cell valid counts equal the row
                    # counts accumulated so far minus THIS chunk's rows
                    # (_dcounts already folded it above).
                    state[0] = self._dcounts - np.bincount(gid0, minlength=cells)
                state[0] += np.bincount(gid0[valid], minlength=cells)
            if fn == "count":
                continue
            data = col.data
            g = gid0
            if valid is not None:
                data, g = data[valid], g[valid]
            if np.issubdtype(data.dtype, np.floating):
                s = np.bincount(g, weights=data.astype(np.float64), minlength=cells)
            else:
                # Exact int64 accumulation (bincount weights are float64 and
                # would round sums past 2^53).
                s = np.zeros(cells, np.int64)
                np.add.at(s, g, data.astype(np.int64))
            if state[1] is None:
                state[1] = s
            else:
                if state[1].dtype != s.dtype:
                    common = np.promote_types(state[1].dtype, s.dtype)
                    state[1] = state[1].astype(common)
                    s = s.astype(common)
                state[1] += s

    def _finalize_direct(self) -> Optional[Table]:
        if self._dcounts is None or self.rows == 0:
            return None
        los, ranges_, strides, _cells, key_meta = self._direct
        present = np.nonzero(self._dcounts)[0]
        counts_p = self._dcounts[present]
        out = {}
        for k, (dtype, dictionary, np_dtype), lo, rng, st in zip(
            self.group_keys, key_meta, los, ranges_, strides
        ):
            vals = lo + (present // st) % rng
            if dtype == STRING:
                out[k] = Column(STRING, vals.astype(np.int32), dictionary, None)
            else:
                out[k] = Column(dtype, vals.astype(np_dtype), None, None)
        for i, (out_name, fn, cname) in enumerate(self.aggs):
            dtype = result_dtype(fn, self._in_dtypes[i])
            nv_cells, val_cells = self._dstates[i]
            nv = counts_p if nv_cells is None else nv_cells[present]
            if fn == "count":
                out[out_name] = _out_column(fn, None, dtype, nv, None)
                continue
            vals = val_cells[present]
            if fn == "avg":
                vals = vals.astype(np.float64) / np.maximum(nv, 1)
            out[out_name] = _out_column(fn, None, dtype, vals, nv > 0)
        return Table(out)

    def _state_table(self, rep_keys: Table, states: list) -> Table:
        """Assemble the state-layout table: group keys + per-agg value/count
        columns (value codes of all-null groups clamped to 0 so string state
        columns always index their dictionaries)."""
        out = dict(rep_keys.columns)
        for i, (vals_col, n_valid) in enumerate(states):
            if vals_col is not None:
                out[f"{_STATE_PREFIX}v{i}"] = vals_col
            out[f"{_STATE_PREFIX}n{i}"] = Column(
                INT64, np.asarray(n_valid, np.int64).copy()
            )
        return Table(out)

    def _pack_state_col(
        self, fn: str, vals: np.ndarray, n_valid: np.ndarray, dictionary
    ) -> Column:
        anyv = n_valid > 0
        if dictionary is not None:
            codes = np.where(anyv, vals, 0).astype(np.int32)
            return Column(STRING, codes, dictionary, anyv.copy())
        data = np.where(anyv, vals, np.zeros((), dtype=np.asarray(vals).dtype))
        from ..engine.schema import dtype_from_numpy

        return Column(dtype_from_numpy(data.dtype), data, None, anyv.copy())

    def _partial_host(self, t: Table) -> Table:
        from .join import stable_argsort_host

        n = t.num_rows
        key_cols = [t.column(k) for k in self.group_keys]
        layout = _direct_layout(key_cols, self.aggs)
        if layout is not None:
            # Bounded-range keys: the chunk partial is a handful of bincount
            # passes instead of a per-chunk hash-sort — the same trade
            # `_direct_host_aggregate` makes for the one-pass path.
            return self._partial_host_direct(t, key_cols, layout)
        from ..engine.encoded_device import stage_codes

        k64 = key64(key_cols, [stage_codes(c, "agg_keys") for c in key_cols])
        perm = stable_argsort_host(k64)
        flat_host, has_valid = [], []
        for c in key_cols:
            flat_host.append(c.data)
            has_valid.append(c.validity is not None)
            if c.validity is not None:
                flat_host.append(c.validity)
        _boundary, gid = _group_ids_body(tuple(has_valid), perm, flat_host, xp=np)
        starts = np.nonzero(_boundary)[0]
        seg_rows = np.diff(np.append(starts, n))
        rep_keys = t.select(self.group_keys).take(perm[starts])
        states = []
        for _out, fn, cname in self.aggs:
            col = t.column(cname) if cname is not None else None
            sfn = "sum" if fn == "avg" else fn
            vals, n_valid = _segment_reduce_host(sfn, col, perm, starts, seg_rows)
            if fn == "count":
                states.append((None, n_valid))
                continue
            states.append(
                (
                    self._pack_state_col(
                        fn, vals, n_valid, col.dictionary if col.is_string else None
                    ),
                    n_valid,
                )
            )
        return self._state_table(rep_keys, states)

    def _partial_host_direct(self, t: Table, key_cols, layout) -> Table:
        """Direct-address chunk partial: dense mixed-radix cells + bincount
        reductions (`_direct_layout` already proved eligibility: null-free
        bounded int/bool/dictionary keys, no min/max). State contract is
        identical to the sort-based partial; only the internal group order of
        the partial differs, which the record-keyed merge erases."""
        n = t.num_rows
        los, ranges, datas, strides, cells = layout
        gid0 = np.zeros(n, np.int64)
        for data, lo, st in zip(datas, los, strides):
            gid0 += (data.astype(np.int64) - lo) * st
        counts = np.bincount(gid0, minlength=cells)
        present = np.nonzero(counts)[0]
        counts_p = counts[present].astype(np.int64)

        rep_cols = {}
        for k, c, lo, rng, st in zip(
            self.group_keys, key_cols, los, ranges, strides
        ):
            vals = lo + (present // st) % rng
            if c.is_string:
                rep_cols[k] = Column(
                    STRING, vals.astype(np.int32), c.dictionary, None
                )
            else:
                rep_cols[k] = Column(c.dtype, vals.astype(c.data.dtype), None, None)

        states = []
        for _out, fn, cname in self.aggs:
            col = t.column(cname) if cname is not None else None
            if fn == "count" and col is None:
                states.append((None, counts_p))
                continue
            valid = col.validity
            if valid is None:
                nv = counts_p
            else:
                nv = np.bincount(gid0[valid], minlength=cells)[present].astype(
                    np.int64
                )
            if fn == "count":
                states.append((None, nv))
                continue
            # sum / avg state (avg carries its sum): exact int64 accumulation
            # for ints (bincount weights are float64 and would round past
            # 2^53), float64 bincount for floats.
            data = col.data
            if np.issubdtype(data.dtype, np.floating):
                w = data.astype(np.float64)
                g = gid0
                if valid is not None:
                    w, g = w[valid], g[valid]
                s = np.bincount(g, weights=w, minlength=cells)[present]
            else:
                acc = data.astype(np.int64)
                g = gid0
                if valid is not None:
                    acc, g = acc[valid], g[valid]
                s = np.zeros(cells, np.int64)
                np.add.at(s, g, acc)
                s = s[present]
            states.append((self._pack_state_col(fn, s, nv, None), nv))
        return self._state_table(Table(rep_cols), states)

    def _partial_device(self, t: Table) -> Table:
        """Device twin of `_partial_host`: pow2-padded staged lanes, the fused
        group-id program, then every reduction in one compiled (and
        buffer-donating, off-CPU) program quantized to pow2 segment counts."""
        n = t.num_rows
        cap = _pow2_ceil(n)
        staged_bytes = [0, 0]  # [payload, pow2 padding] across all lanes

        def _stage(host_arr):
            # One pow2-padded H2D staging lane; the split feeds the padding
            # ledger once all lanes are up (`pad.agg_partials.*`).
            sz = int(np.asarray(host_arr).dtype.itemsize)
            staged_bytes[0] += n * sz
            staged_bytes[1] += (cap - n) * sz
            return jax.device_put(_pad_repeat_first(host_arr, cap))

        key_cols = [t.column(k) for k in self.group_keys]
        from ..engine.encoded_device import column_qualifies, narrow_codes

        enc_split = [0, 0]  # [flat, staged] bytes of narrowed key lanes

        def _key_lane(c):
            # Qualifying string keys stage as narrow codes; the rep
            # materialization below widens back to int32 before any Column
            # is built, and key64/group boundaries are value-preserving.
            if column_qualifies(c):
                narrow = narrow_codes(c)
                if narrow is not c.data:
                    enc_split[0] += int(c.data.nbytes)
                    enc_split[1] += int(narrow.nbytes)
                    return narrow
            return c.data

        staged_keys = [_stage(_key_lane(c)) for c in key_cols]
        k64 = key64(key_cols, staged_keys)
        flat, has_valid = [], []
        staged_valid = []
        for c, arr in zip(key_cols, staged_keys):
            flat.append(arr)
            has_valid.append(c.validity is not None)
            if c.validity is not None:
                sv = _stage(c.validity)
                staged_valid.append(sv)
                flat.append(sv)
            else:
                staged_valid.append(None)
        perm, boundary, gid = _group_ids_fused(tuple(has_valid), k64, *flat)
        n_groups = int(gid[-1]) + 1  # the one scalar sync per chunk
        n_seg = _pow2_ceil(n_groups)
        rep_rows = perm[jnp.nonzero(boundary, size=n_seg, fill_value=0)[0]]

        # Representative key rows (gathered BEFORE the reduce so its donated
        # buffers are never read afterwards).
        rep_cols = {}
        for k, c, arr, sv in zip(
            self.group_keys, key_cols, staged_keys, staged_valid
        ):
            data = _devobs.to_host(arr[rep_rows])[:n_groups]
            v = (
                None
                if sv is None
                else _devobs.to_host(sv[rep_rows])
                .astype(bool, copy=False)[:n_groups]
                .copy()
            )
            if c.is_string:
                codes = data.astype(np.int32)
                if v is not None:
                    codes = np.where(v, codes, 0).astype(np.int32)
                rep_cols[k] = Column(STRING, codes, c.dictionary, v)
            else:
                if v is not None:
                    data = np.where(v, data, np.zeros((), dtype=data.dtype))
                rep_cols[k] = Column(c.dtype, data.astype(c.data.dtype), None, v)

        specs, lanes = [], []
        for _out, fn, cname in self.aggs:
            col = t.column(cname) if cname is not None else None
            sfn = "sum" if fn == "avg" else fn
            if fn == "count" and col is None:
                # count(*): the row-validity lane IS the data.
                specs.append(("count", False))
                lanes.append(jnp.zeros(cap, jnp.int32))
                continue
            specs.append((sfn, col.validity is not None))
            lanes.append(_stage(col.data))
            if col.validity is not None:
                lanes.append(_stage(col.validity))
        _devobs.record_pad("agg_partials", staged_bytes[0], staged_bytes[1])
        _devobs.record_h2d(staged_bytes[0] + staged_bytes[1])
        if enc_split[1]:
            _devobs.record_encoded_stage("agg_partials", enc_split[0], enc_split[1])
        row_valid = jnp.arange(cap) < n
        donate = jax.default_backend() != "cpu"
        results = jax.device_get(
            _stream_reduce_fn(len(lanes), donate)(
                tuple(specs), n_seg, gid, perm, row_valid, *lanes
            )
        )
        _devobs.record_d2h(
            sum(int(getattr(r, "nbytes", 0) or 0) for r in results)
        )
        states = []
        for i, (_out, fn, cname) in enumerate(self.aggs):
            vals = np.asarray(results[2 * i])[:n_groups]
            n_valid = np.asarray(results[2 * i + 1])[:n_groups]
            if fn == "count":
                states.append((None, n_valid))
                continue
            col = t.column(cname)
            states.append(
                (
                    self._pack_state_col(
                        fn, vals, n_valid, col.dictionary if col.is_string else None
                    ),
                    n_valid,
                )
            )
        return self._state_table(Table(rep_cols), states)

    # -- carry merge ---------------------------------------------------------

    def _compact(self) -> None:
        parts = ([self._carry] if self._carry is not None else []) + self._pending
        self._pending = []
        self._pending_rows = 0
        if not parts:
            return
        if len(parts) == 1:
            self._carry = parts[0]
            return
        # Concat re-encodes string keys AND string min/max states over union
        # dictionaries, so codes are comparable across chunks.
        pt = Table.concat(parts)
        recs = _key_records(pt, self.group_keys)
        uniq, first_idx, inverse = np.unique(
            recs, return_index=True, return_inverse=True
        )
        n_groups = len(uniq)
        out = dict(pt.select(self.group_keys).take(first_idx).columns)
        for i, (_out, fn, _cname) in enumerate(self.aggs):
            contrib = pt.column(f"{_STATE_PREFIX}n{i}").data
            nv = np.zeros(n_groups, np.int64)
            np.add.at(nv, inverse, contrib)
            out[f"{_STATE_PREFIX}n{i}"] = Column(INT64, nv)
            if fn == "count":
                continue
            vcol = pt.column(f"{_STATE_PREFIX}v{i}")
            mask = contrib > 0
            sfn = "sum" if fn == "avg" else fn
            if sfn == "sum":
                acc = np.zeros(n_groups, vcol.data.dtype)
                # np.add.at folds in row order (carry first, then chunks in
                # arrival order) — the float fold stays left-to-right across
                # any compaction cadence.
                np.add.at(acc, inverse[mask], vcol.data[mask])
            else:
                acc = np.full(
                    n_groups,
                    _minmax_fill(vcol.data.dtype, sfn),
                    vcol.data.dtype,
                )
                op = np.minimum if sfn == "min" else np.maximum
                op.at(acc, inverse[mask], vcol.data[mask])
            out[f"{_STATE_PREFIX}v{i}"] = self._pack_state_col(
                fn, acc, nv, vcol.dictionary if vcol.is_string else None
            )
        self._carry = Table(out)

    # -- finalize ------------------------------------------------------------

    def _output_order(self, key_cols) -> np.ndarray:
        """Group output order of the ONE-PASS path: the direct-address cell
        order when its host fast path would have applied (the carried keys
        reproduce the same layout decision), ascending key64 otherwise."""
        from .backend import use_device_path

        if not use_device_path():
            layout = _direct_layout(key_cols, self.aggs)
            if layout is not None:
                los, _ranges, datas, strides, _cells = layout
                gid0 = np.zeros(len(key_cols[0]), np.int64)
                for data, lo, st in zip(datas, los, strides):
                    gid0 += (data.astype(np.int64) - lo) * st
                return np.argsort(gid0, kind="stable")
        from ..engine.encoded_device import stage_codes

        k64 = np.asarray(
            key64(key_cols, [stage_codes(c, "agg_keys") for c in key_cols])
        )
        return np.argsort(k64, kind="stable")

    def finalize(self) -> Optional[Table]:
        """The aggregate over everything streamed so far; None when no chunk
        carried rows (the caller owns the empty-input result shape)."""
        if self._direct is not None:
            with self._timed("finalize"):
                return self._finalize_direct()
        with self._timed("merge"):
            self._compact()
        if self._carry is None:
            return None
        carry = self._carry
        key_cols = [carry.column(k) for k in self.group_keys]
        with self._timed("finalize"):
            order = self._output_order(key_cols)
            out = {}
            for k in self.group_keys:
                out[k] = carry.column(k).take(order)
            for i, (out_name, fn, _cname) in enumerate(self.aggs):
                nv = carry.column(f"{_STATE_PREFIX}n{i}").data[order]
                dtype = result_dtype(fn, self._in_dtypes[i])
                if fn == "count":
                    out[out_name] = _out_column(fn, None, dtype, nv, None)
                    continue
                vcol = carry.column(f"{_STATE_PREFIX}v{i}").take(order)
                vals = vcol.data
                if fn == "avg":
                    vals = vals.astype(np.float64) / np.maximum(nv, 1)
                out[out_name] = _out_column(fn, vcol, dtype, vals, nv > 0)
        return Table(out)
