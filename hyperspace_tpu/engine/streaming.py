"""Streaming scan→filter→aggregate executor: the read-side pipeline.

`bench` r05 measured the materialized aggregate path decoding and concatenating
the FULL multi-file table on the host before any reduction starts (cold indexed
reads spent 1.34 s of 1.35 s in I/O; the 8M scan aggregate materialized ~500 MB
it immediately reduced away). This module mirrors `index/build_pipeline.py` on
the query side:

1. **Decode** — `engine.io.iter_file_tables` feeds per-file tables in sorted
   order through the per-column scan cache, with a bounded decode pool
   (shared ``HYPERSPACE_BUILD_DECODE_THREADS`` contract) running up to
   ``HYPERSPACE_QUERY_PREFETCH_FILES`` files ahead of the consumer. With a
   pushdown predicate (`engine.pushdown`, via `ScanExec.pushdown`), each file
   decodes only the row groups its footer zone maps cannot exclude — pruned
   bytes never enter the stream, so they are never staged or filtered either.
2. **Chunk** — each (pruned) file table splits into row slices of at most
   ``HYPERSPACE_QUERY_CHUNK_ROWS`` (numpy views; chunk boundaries never change
   values or output order, and align to the surviving row groups'
   concatenation by construction).
3. **Filter / projections** — `FilterExec`/`ProjectExec`/`WithColumnExec`
   apply per chunk through their `execute_stream` generators, so selective
   filters shrink chunks before any reduction.
4. **Reduce with carry** — `ops.aggregate.StreamAggregator` reduces every
   chunk to per-group partial states (the fused jitted hash/sort/segment
   programs on the device path, `reduceat` on the CPU backend) and carries the
   accumulators across chunks, merging by exact key records. The full concat
   is never materialized.

``HYPERSPACE_QUERY_STREAMING=0`` disables the whole path: every aggregate runs
today's materialized execution byte-for-byte. Streamed results equal the
materialized path's exactly for integer/count/min/max outputs and to
float-associativity rounding for float sum/avg (docs/query-pipeline.md).

`stream_join_aggregate` (below) is the JOIN-side twin: a grouped aggregate
over a bucketed inner join streams verified pair chunks — gather + expression
chain per chunk on the shared decode-pool contract — straight into the same
`StreamAggregator`, so the join output never materializes whole
(docs/join-pipeline.md).

Late materialization rides both streams by construction: chunks carry string
columns as dictionary codes (under encoded execution the decode stage
produces them without ever flattening — docs/encoded-execution.md), filters
and group-bys and pair verification all run on codes, and only the gathered
survivors that reach the output boundary ever decode.

Per-stage busy timings (decode/eval/partial/merge), wall clock, and the
overlap ratio ride `telemetry.profiling.record_query_stages` and surface in
``bench.py``'s ``bench_detail.query_stages``.
"""

from __future__ import annotations

import os
from typing import List, Optional

from .table import Column, Table

ENV_QUERY_STREAMING = "HYPERSPACE_QUERY_STREAMING"
ENV_QUERY_CHUNK_ROWS = "HYPERSPACE_QUERY_CHUNK_ROWS"
_DEFAULT_QUERY_CHUNK_ROWS = 4_000_000
#: Pair-chunk size of the streamed join→aggregate (rows of JOIN OUTPUT per
#: chunk). Smaller than the scan chunk default: each join chunk materializes
#: every payload column of both sides for its pair slice.
ENV_JOIN_CHUNK_ROWS = "HYPERSPACE_JOIN_CHUNK_ROWS"
_DEFAULT_JOIN_CHUNK_ROWS = 2_000_000
#: Multiway star-join gate: ``HYPERSPACE_MULTIWAY=0`` keeps recognized star
#: joins on the cascaded binary execution byte-for-byte (recognition itself
#: is also suppressed at rule time, so the plan class does not change).
ENV_MULTIWAY = "HYPERSPACE_MULTIWAY"


def multiway_enabled() -> bool:
    """Default ON; ``HYPERSPACE_MULTIWAY=0`` is the cascaded fallback
    (preserves pre-star execution exactly). Unset hands the knob to the
    adaptive planner when one decided this query — an explicit flag always
    wins (`docs/planner.md`)."""
    raw = os.environ.get(ENV_MULTIWAY, "")
    if raw != "":
        return raw != "0"
    from ..plananalysis.planner import decided_value

    decided = decided_value("multiway")
    return True if decided is None else bool(decided)


def streaming_enabled() -> bool:
    """Default ON; ``HYPERSPACE_QUERY_STREAMING=0`` is the materialized
    fallback (preserves the pre-streaming execution exactly). Unset hands
    the knob to the adaptive planner when one decided this query — an
    explicit flag always wins (`docs/planner.md`)."""
    raw = os.environ.get(ENV_QUERY_STREAMING, "")
    if raw != "":
        return raw != "0"
    from ..plananalysis.planner import decided_value

    decided = decided_value("streaming")
    return True if decided is None else bool(decided)


def query_chunk_rows() -> int:
    raw = os.environ.get(ENV_QUERY_CHUNK_ROWS, "")
    if raw != "":
        try:
            return max(1, int(raw))
        except ValueError:
            return _DEFAULT_QUERY_CHUNK_ROWS
    from ..plananalysis.planner import decided_value

    decided = decided_value("chunk_rows")
    if decided is not None:
        return max(1, int(decided))
    return _DEFAULT_QUERY_CHUNK_ROWS


def join_chunk_rows() -> int:
    return max(
        1,
        int(
            os.environ.get(ENV_JOIN_CHUNK_ROWS, _DEFAULT_JOIN_CHUNK_ROWS)
            or _DEFAULT_JOIN_CHUNK_ROWS
        ),
    )


def split_chunks(t: Table, chunk_rows: int) -> List[Table]:
    """Row-slice a table into pipeline chunks (numpy views — chunk boundaries
    have no effect on output order or values). Same slicing as the build
    pipeline's `_split_chunks`."""
    if t.num_rows <= chunk_rows:
        return [t]
    out = []
    for lo in range(0, t.num_rows, chunk_rows):
        hi = min(lo + chunk_rows, t.num_rows)
        out.append(
            Table(
                {
                    n: Column(
                        c.dtype,
                        c.data[lo:hi],
                        c.dictionary,
                        None if c.validity is None else c.validity[lo:hi],
                    )
                    for n, c in t.columns.items()
                }
            )
        )
    return out


def compact_mask_indices(mask):
    """Surviving row indices of a chunk's predicate mask. The whole-table
    filter's `nonzero_indices` compiles one program PER SURVIVOR COUNT
    (`jnp.nonzero(size=n)`) — fine once per query, ~0.3 s of XLA-CPU compile
    per CHUNK here, where every chunk survives differently. CPU backend:
    plain numpy (the mask is host-resident anyway). Device path: pow2-capped
    `size` so compiles stay log2-bounded."""
    import jax.numpy as jnp
    import numpy as np

    from ..ops.backend import use_device_path

    if not use_device_path():
        return np.nonzero(np.asarray(mask))[0]
    mask = jnp.asarray(mask)
    n = int(mask.sum())
    if n == 0:
        return np.empty(0, np.int64)
    cap = 1 << max(n - 1, 1).bit_length()
    return np.asarray(jnp.nonzero(mask, size=cap, fill_value=0)[0])[:n]


def timed(stages, name: str):
    """`stages.timed(name)`, or a no-op context when telemetry is off."""
    if stages is None:
        import contextlib

        return contextlib.nullcontext()
    return stages.timed(name)


def stream_aggregate(agg_exec, ctx) -> Optional[Table]:
    """Run a `HashAggregateExec` over its child's chunk stream with the
    chunk-carry aggregator. Returns None only when no chunk arrived (the
    caller owns the fallback); faults mid-stream propagate — the scan cache
    only ever holds successful decodes, so a failed query poisons nothing."""
    from ..ops.aggregate import StreamAggregator, _empty_result
    from ..ops.backend import use_device_path
    from ..telemetry.profiling import StageTimings, record_query_stages

    import numpy as np

    with StageTimings(
        "query", "stream-device" if use_device_path() else "stream-cpu"
    ) as stages:
        agg = StreamAggregator(agg_exec.group_keys, agg_exec.aggs, stages=stages)
        # 0-row schema template accumulated across ALL chunks: the empty-input
        # result must carry the same concat-PROMOTED dtypes (mixed-width files,
        # union dictionaries) the materialized path would produce.
        template: Optional[Table] = None
        none_idx = np.empty(0, np.int64)
        n_chunks = 0
        from .. import resilience

        for chunk in agg_exec.child.execute_stream(ctx, stages):
            # Chunk-boundary cancellation: a deadlined query stops between
            # chunks; nothing partial was cached (only-cache-on-success).
            resilience.check_deadline("query.stream")
            zero = chunk.take(none_idx)
            template = zero if template is None else Table.concat([template, zero])
            n_chunks += 1
            agg.add_chunk(chunk)
        out = agg.finalize()
        if out is None:
            if template is None:
                return None  # nothing streamed: caller falls back
            out = _empty_result(template, agg_exec.group_keys, agg_exec.aggs)
        record_query_stages(
            stages.summary(chunks=n_chunks, rows=agg.rows, groups=out.num_rows)
        )
        return out


# ---------------------------------------------------------------------------
# Streamed bucketed-join → aggregate (the write-side twin: join pair chunks
# flow straight into the chunk-carry aggregator; the joined table never
# materializes whole)
# ---------------------------------------------------------------------------


def _resolve_named_columns(out_names, chain, names):
    """Resolve aggregate names over a join output's name→Column mapping to
    SOURCE Column objects. None when any name is shadowed by a withColumn in
    the chain (computed — no source column) or does not resolve uniquely."""
    from .physical import WithColumnExec

    shadowed = {
        op.col_name.lower() for op in chain if isinstance(op, WithColumnExec)
    }
    cols = []
    for name in names:
        if name.lower() in shadowed:
            return None
        c = out_names.get(name)
        if c is None:
            ci = [k for k in out_names if k.lower() == name.lower()]
            if len(ci) != 1:
                return None
            c = out_names[ci[0]]
        cols.append(c)
    return cols


def _resolve_source_columns(left: Table, right: Table, chain, names):
    """Resolve aggregate names over the join's output naming (left wins the
    unsuffixed name; colliding right columns answer to `<name>_r`, exactly
    `_assemble_join`'s rule) to SOURCE Column objects."""
    out_names = dict(left.columns)
    for n, c in right.columns.items():
        out_names[n if n not in out_names else f"{n}_r"] = c
    return _resolve_named_columns(out_names, chain, names)


def star_output_columns(fact: Table, dim_tables):
    """Column-name → source Column mapping of a star join's output: the
    cascade applies `_assemble_join`'s naming fold-wise (the left side of
    join k is the fact already joined with dims 0..k-1), so a colliding name
    takes `<name>_r` — and a THIRD table colliding on the same name
    OVERWRITES the existing `_r` entry, exactly as the cascaded execution
    does. The streamed star path must replicate that quirk verbatim to stay
    byte-identical."""
    out_names = dict(fact.columns)
    for dt in dim_tables:
        for n, c in dt.columns.items():
            out_names[n if n not in out_names else f"{n}_r"] = c
    return out_names


def _agg_input_dtype(name: str, left: Table, right: Table, chain):
    """Declared dtype of one aggregate input over the join output: the
    shadowing withColumn's DECLARED dtype when the chain computes it, else the
    source column's dtype; None when unresolvable."""
    from .physical import WithColumnExec

    for op in chain:
        if isinstance(op, WithColumnExec) and op.col_name.lower() == name.lower():
            return op.dtype
    cols = _resolve_source_columns(left, right, (), [name])
    return cols[0].dtype if cols is not None else None


def _float_fold_free(agg_exec, left: Table, right: Table, chain) -> bool:
    """True when every sum/avg input is PROVABLY non-float: integer partial
    states accumulate exactly, so the chunked fold is bitwise-equal to the
    one-pass fold regardless of chunk boundaries — the admission condition
    for the RECORD-MERGE carry, whose hash-sorted partials would reorder a
    float fold even within one chunk. Float sums stream only through the
    direct-cells hint, where the per-chunk fold is the one-pass bincount
    verbatim: bitwise-identical to the materialized fallback when the stream
    fits one chunk (every test-scale shape), and within float-associativity
    rounding once multiple chunks fold partial cell sums (the documented
    streaming contract, docs/join-pipeline.md — same contract as PR 2's
    scan-side stream)."""
    for _out, fn, cname in agg_exec.aggs:
        if fn in ("sum", "avg") and cname is not None:
            dtype = _agg_input_dtype(cname, left, right, chain)
            if dtype is None or dtype in ("float32", "float64"):
                return False
    return True


def stream_join_aggregate(agg_exec, join_exec, chain, ctx) -> Optional[Table]:
    """Run a `HashAggregateExec` over a bucketed INNER join as a chunk-carry
    stream: verified pair chunks gather their payload columns and evaluate the
    WithColumn/Project chain PER CHUNK (on a bounded worker pool riding the
    shared decode-pool contract, overlapping the next chunk's verification and
    the aggregator's fold), and reduce into `StreamAggregator` — with the
    direct-address cells fast path when the SOURCE group-key columns qualify.
    The full join output never materializes.

    The verified pairs and classed probe ranges are inserted into the engine
    memos ONLY after every chunk streamed successfully — a mid-stream fault
    (e.g. a failing gather) propagates cleanly and caches nothing partial, so
    the retry recomputes from scratch. Returns None when the shape doesn't
    apply (caller falls back to the materialized path)."""
    import numpy as np

    from ..exceptions import HyperspaceException
    from ..ops import bucket_join as bj
    from ..ops.aggregate import StreamAggregator, _empty_result, direct_stream_hint
    from ..telemetry.profiling import StageTimings, record_join_stages
    from . import io as engine_io
    from . import physical as phys

    try:
        left, l_starts = join_exec.left.execute_concat(ctx)
        right, r_starts = join_exec.right.execute_concat(ctx)
    except HyperspaceException:
        return None
    if left.num_rows == 0 or right.num_rows == 0:
        return None  # the materialized fallback is trivially cheap here
    if (
        ctx.session is not None
        and ctx.session.mesh_for(left.num_rows + right.num_rows) is not None
    ):
        return None  # the sharded probe owns mesh-scale execution

    group_keys = agg_exec.group_keys
    src_keys = _resolve_source_columns(left, right, chain, group_keys)
    hint = (
        direct_stream_hint(src_keys, agg_exec.aggs) if src_keys is not None else None
    )
    if hint is None and not _float_fold_free(agg_exec, left, right, chain):
        # The record-merge carry would reorder a float fold even at one
        # chunk; without the direct-cells hint those shapes stay
        # materialized (always byte-identical).
        return None

    with StageTimings("join", "join-stream") as stages:
        subkey = phys._pair_subkey(
            join_exec.left_keys,
            join_exec.right_keys,
            join_exec.left,
            join_exec.right,
            left,
            right,
        )
        rows_key = phys._pair_rows_key(join_exec.left, join_exec.right, ctx)

        # Warm path: an earlier query (count/collect/materialized aggregate) on
        # these rows already cached the VERIFIED pairs — start at the gathers.
        verified, cached = phys._peek_two_table("pairs", left, right, subkey, rows_key)
        from ..telemetry import tracing

        tracing.set_attr("pairs_memo", "hit" if verified else "miss")
        plan = ranges = None
        ranges_hit = False
        if verified:
            li_all, ri_all = cached
        else:
            with stages.timed("pad"):
                plan = phys._classed_plan_cached(
                    join_exec, left, right, l_starts, r_starts, subkey, rows_key
                )
            ranges_hit, ranges = phys._peek_two_table(
                "pairs", left, right, ("cprobe", plan.mode) + subkey, rows_key
            )
            if not ranges_hit:
                with stages.timed("probe"):
                    ranges = bj.probe_classed(plan)
            with stages.timed("expand"):
                li_all, ri_all = bj.classed_pairs(plan, ranges)

        agg = StreamAggregator(
            group_keys, agg_exec.aggs, stages=stages, direct_hint=hint
        )

        n = int(len(li_all))
        chunk_rows = join_chunk_rows()
        slices = [
            (lo, min(lo + chunk_rows, n)) for lo in range(0, n, chunk_rows)
        ] or [(0, 0)]
        lkeys, rkeys = join_exec.left_keys, join_exec.right_keys
        verified_parts: List[tuple] = []
        template: Optional[Table] = None

        def build_chunk(lo: int, hi: int):
            from .physical import WithColumnExec, _assemble_join, _verify_pairs

            li_c, ri_c = li_all[lo:hi], ri_all[lo:hi]
            if not verified:
                with stages.timed("verify"):
                    li_c, ri_c = _verify_pairs(left, right, lkeys, rkeys, li_c, ri_c)
            with stages.timed("gather"):
                t = _assemble_join(left, right, li_c, ri_c, "inner")
            with stages.timed("eval"):
                for op in reversed(chain):  # innermost (closest to the join) first
                    t = (
                        op._apply(t)
                        if isinstance(op, WithColumnExec)
                        else t.select(op.column_names)
                    )
            return li_c, ri_c, t

        none_idx = np.empty(0, np.int64)

        def consume(res) -> None:
            nonlocal template
            li_c, ri_c, t = res
            if not verified:
                verified_parts.append((li_c, ri_c))
            if template is None:
                template = t.take(none_idx)
            agg.add_chunk(t)

        from .. import resilience

        workers = min(2, engine_io.decode_pool_size(len(slices)))
        if workers <= 1 or len(slices) == 1:
            for lo, hi in slices:
                # Pair-chunk-boundary cancellation: a mid-stream deadline (like a
                # mid-stream fault) propagates cleanly — the memos below are
                # populated only after EVERY chunk streamed successfully.
                resilience.check_deadline("query.join_stream")
                consume(build_chunk(lo, hi))
        else:
            from collections import deque
            from concurrent.futures import ThreadPoolExecutor

            from ..telemetry import accounting as _accounting

            # Workers adopt the submitting query's ledger and deadline scope
            # (the io.py pool contract): without this, chunk work on pool
            # threads — including any XLA compiles its device programs trigger —
            # billed to NOTHING instead of the query that caused it.
            led = _accounting.current_ledger()
            sc = resilience.current_scope()

            def build_chunk_adopted(lo: int, hi: int):
                with _accounting.use_ledger(led), resilience.use_scope(sc):
                    return build_chunk(lo, hi)

            pool = ThreadPoolExecutor(max_workers=workers)
            try:
                pending: "deque" = deque()
                i = 0
                while i < len(slices) or pending:
                    resilience.check_deadline("query.join_stream")
                    # Depth-bounded: at most workers+1 chunks in flight keeps
                    # resident chunk memory bounded while the NEXT chunk's
                    # verify/gather overlaps this one's aggregator fold.
                    while i < len(slices) and len(pending) < workers + 1:
                        pending.append(pool.submit(build_chunk_adopted, *slices[i]))
                        i += 1
                    consume(pending.popleft().result())
            finally:
                pool.shutdown(wait=False, cancel_futures=True)

        # EVERY chunk streamed successfully: NOW (and only now) populate the
        # memos, so warm queries — streamed or materialized, counts included —
        # start from the verified pairs exactly as after a materialized run.
        if not verified:
            if verified_parts:
                li_v = np.concatenate([p[0] for p in verified_parts])
                ri_v = np.concatenate([p[1] for p in verified_parts])
            else:
                li_v = ri_v = np.empty(0, np.int64)
            phys._cached_two_table(
                "pairs", left, right, subkey, lambda: (li_v, ri_v), rows_key=rows_key
            )
            if plan is not None and ranges is not None and not ranges_hit:
                phys._cached_two_table(
                    "pairs",
                    left,
                    right,
                    ("cprobe", plan.mode) + subkey,
                    lambda: ranges,
                    rows_key=rows_key,
                )

        out = agg.finalize()
        if out is None:
            if template is None:
                return None
            out = _empty_result(template, group_keys, agg_exec.aggs)
        summary = stages.summary(
            chunks=agg.chunks,
            pairs=n,
            groups=out.num_rows,
            direct_cells=hint is not None,
            classes=None if plan is None else len(plan.segments),
            outliers=None if plan is None else int(len(plan.outlier_ids)),
            join_mode=None if plan is None else plan.mode,
        )
        record_join_stages(summary)
        return out


# ---------------------------------------------------------------------------
# Streamed multiway star-join → aggregate: probe EVERY dimension's covering
# index per fact chunk and fold survivor compositions straight into the
# aggregator — the cascaded plan's intermediate fact never materializes
# ---------------------------------------------------------------------------


def _star_agg_input_dtype(name: str, out_names, chain):
    """Declared dtype of one aggregate input over the star output: the
    shadowing withColumn's DECLARED dtype when the chain computes it, else
    the source column's dtype; None when unresolvable."""
    from .physical import WithColumnExec

    for op in chain:
        if isinstance(op, WithColumnExec) and op.col_name.lower() == name.lower():
            return op.dtype
    cols = _resolve_named_columns(out_names, (), [name])
    return cols[0].dtype if cols is not None else None


def _star_float_fold_free(agg_exec, out_names, chain) -> bool:
    """Star twin of `_float_fold_free`: every sum/avg input provably
    non-float — integer partial states accumulate exactly, so the chunked
    fold equals the one-pass fold bitwise regardless of chunk boundaries.
    Float sums stream only through the direct-cells hint (same admission and
    same documented rounding contract as the binary streamed join)."""
    for _out, fn, cname in agg_exec.aggs:
        if fn in ("sum", "avg") and cname is not None:
            dtype = _star_agg_input_dtype(cname, out_names, chain)
            if dtype is None or dtype in ("float32", "float64"):
                return False
    return True


def stream_star_aggregate(agg_exec, star_exec, chain, ctx) -> Optional[Table]:
    """Run a `HashAggregateExec` over a recognized N-way star join as a
    chunk-carry stream. Per DIMENSION (once, up front): hash the fact's FK
    columns into that dimension's bucket space, lay the fact out in bucket
    order on the fly (`bucket_join.fact_bucket_layout`), build the joint
    size-classed plan against the dimension's covering-index concat
    (`build_classed_plan` — padding classes and outlier handling intact),
    probe, expand and exactly verify — yielding the dimension's match list
    in fact-major order. Per FACT CHUNK: compose every dimension's match
    counts into the output row count (the product), enumerate compositions
    with a per-row odometer, gather fact + all dimension payloads, evaluate
    the WithColumn/Project chain, and fold into `StreamAggregator` — with
    the direct-address cells fast path when the source group keys qualify.
    The intermediate fact of the cascaded plan never materializes.

    Per-dimension verified matches ride the engine pair memos keyed
    ``("star",) + pair_subkey`` — inserted ONLY after every chunk streamed
    successfully, so a mid-stream fault caches nothing partial. Returns None
    when the shape doesn't apply (the caller falls through and the
    `MultiwayJoinExec` executes its byte-identical cascade)."""
    import time

    import numpy as np

    from ..exceptions import HyperspaceException
    from ..ops import bucket_join as bj
    from ..ops.aggregate import StreamAggregator, _empty_result, direct_stream_hint
    from ..ops.backend import use_device_path
    from ..ops.hashing import bucket_id
    from ..telemetry.profiling import StageTimings, record_join_stages
    from . import physical as phys
    from .encoded_device import stage_codes

    try:
        fact = star_exec.fact.execute(ctx)
        dim_sides = []
        for dim_exec, fkeys, dkeys, index_name, num_buckets in star_exec.dims:
            dt, d_starts = dim_exec.execute_concat(ctx)
            dim_sides.append(
                (dim_exec, fkeys, dkeys, index_name, num_buckets, dt, d_starts)
            )
    except HyperspaceException:
        return None
    if fact.num_rows == 0 or any(s[5].num_rows == 0 for s in dim_sides):
        return None  # the cascaded fallback is trivially cheap here
    total_rows = fact.num_rows + sum(s[5].num_rows for s in dim_sides)
    if ctx.session is not None and ctx.session.mesh_for(total_rows) is not None:
        return None  # the sharded probe owns mesh-scale execution

    group_keys = agg_exec.group_keys
    out_names = star_output_columns(fact, [s[5] for s in dim_sides])
    src_keys = _resolve_named_columns(out_names, chain, group_keys)
    hint = (
        direct_stream_hint(src_keys, agg_exec.aggs) if src_keys is not None else None
    )
    if hint is None and not _star_float_fold_free(agg_exec, out_names, chain):
        # Same admission as the binary streamed join: without the
        # direct-cells hint, a float fold through the record-merge carry
        # would reorder — those shapes stay on the cascade (byte-identical).
        return None

    with StageTimings("join", "star-stream") as stages:
        n_fact = fact.num_rows
        per_dim = []  # (dim_table, counts, match_starts, ri_fact_major) per dim
        dim_stats: List[dict] = []
        memo_todo: List[tuple] = []

        for dim_exec, fkeys, dkeys, index_name, num_buckets, dt, d_starts in dim_sides:
            t0 = time.monotonic()
            subkey = ("star",) + phys._pair_subkey(
                list(fkeys), list(dkeys), star_exec.fact, dim_exec, fact, dt
            )
            rows_key = phys._pair_rows_key(star_exec.fact, dim_exec, ctx)
            hit, cached = phys._peek_two_table("pairs", fact, dt, subkey, rows_key)
            if hit:
                li, ri = cached
                stat = {
                    "index": index_name,
                    "buckets": int(num_buckets),
                    "pairs": int(len(li)),
                    "memo": "hit",
                }
            else:
                with stages.timed("pad"):
                    # The fact was never bucket-partitioned on THIS dimension's
                    # keys: hash its FK columns into the dimension's bucket
                    # space (the exact build-time hash — narrow string codes
                    # hash via dh_table[codes], so values agree) and lay it out
                    # in bucket order on the fly.
                    fk_cols = [fact.column(k) for k in fkeys]
                    bid = np.asarray(
                        bucket_id(
                            fk_cols,
                            [stage_codes(c, "star_probe") for c in fk_cols],
                            num_buckets,
                        )
                    )
                    perm, f_starts = bj.fact_bucket_layout(bid, num_buckets)
                    l_flags, r_flags = phys._joint_float_flags(
                        fact, dt, list(fkeys), list(dkeys)
                    )
                    l_vals = np.asarray(
                        phys._table_key64(fact, list(fkeys), l_flags)
                    )[perm]
                    r_vals = np.asarray(phys._table_key64(dt, list(dkeys), r_flags))
                    plan = bj.build_classed_plan(
                        l_vals,
                        r_vals,
                        f_starts,
                        np.asarray(d_starts, np.int64),
                        "hash",
                        device=use_device_path(),
                    )
                pad_s = time.monotonic() - t0
                t1 = time.monotonic()
                with stages.timed("probe"):
                    ranges = bj.probe_classed(plan)
                with stages.timed("expand"):
                    pli, ri = bj.classed_pairs(plan, ranges)
                li = perm[pli]  # bucket-layout positions → original fact rows
                probe_s = time.monotonic() - t1
                t2 = time.monotonic()
                with stages.timed("verify"):
                    li, ri = phys._verify_pairs(
                        fact, dt, list(fkeys), list(dkeys), li, ri
                    )
                # Fact-major order (stable: within one fact row, matches keep
                # the deterministic bucket-major probe order) — the layout the
                # per-chunk odometer composes from.
                order = np.argsort(li, kind="stable")
                li, ri = li[order], ri[order]
                verify_s = time.monotonic() - t2
                memo_todo.append((dt, subkey, rows_key, li, ri))
                stat = {
                    "index": index_name,
                    "buckets": int(num_buckets),
                    "pairs": int(len(li)),
                    "memo": "miss",
                    "pad_s": round(pad_s, 5),
                    "probe_s": round(probe_s, 5),
                    "verify_s": round(verify_s, 5),
                }
            counts = np.bincount(li, minlength=n_fact).astype(np.int64)
            mstarts = np.zeros(n_fact + 1, np.int64)
            np.cumsum(counts, out=mstarts[1:])
            per_dim.append((dt, counts, mstarts, ri))
            dim_stats.append(stat)

        ndims = len(per_dim)
        # Output rows per fact row = the product of its per-dimension match
        # counts (the star's survivor composition); chunk boundaries slice FACT
        # rows so each chunk's output stays near the join chunk budget.
        K = per_dim[0][1].copy()
        for _dt, counts, _ms, _ri in per_dim[1:]:
            K = K * counts
        out_starts = np.zeros(n_fact + 1, np.int64)
        np.cumsum(K, out=out_starts[1:])
        total_pairs = int(out_starts[-1])
        chunk_rows = join_chunk_rows()
        bounds = [0]
        while bounds[-1] < n_fact:
            lo = bounds[-1]
            hi = (
                int(
                    np.searchsorted(
                        out_starts, out_starts[lo] + chunk_rows, side="right"
                    )
                )
                - 1
            )
            bounds.append(min(max(hi, lo + 1), n_fact))

        agg = StreamAggregator(
            group_keys, agg_exec.aggs, stages=stages, direct_hint=hint
        )

        def build_chunk(lo: int, hi: int) -> Table:
            from .physical import WithColumnExec

            Kc = K[lo:hi]
            nz = np.nonzero(Kc)[0]
            rows = nz + lo
            Kr = Kc[nz]
            tot = int(Kr.sum())
            with stages.timed("gather"):
                if tot == 0:
                    fact_idx = np.empty(0, np.int64)
                    sels = [np.empty(0, np.int64)] * ndims
                else:
                    fact_idx = np.repeat(rows, Kr)
                    ends = np.cumsum(Kr)
                    off = np.arange(tot, dtype=np.int64) - np.repeat(ends - Kr, Kr)
                    # Per-row odometer over the dimensions (last dim varies
                    # fastest): composition j of fact row i selects match
                    # (j // stride_d) % count_d from each dimension's list.
                    strides: List = [None] * ndims
                    stride = np.ones(len(rows), np.int64)
                    for d in range(ndims - 1, -1, -1):
                        strides[d] = stride
                        stride = stride * per_dim[d][1][rows]
                    sels = []
                    for d in range(ndims):
                        st_e = np.repeat(strides[d], Kr)
                        cnt_e = np.repeat(per_dim[d][1][rows], Kr)
                        sels.append((off // st_e) % cnt_e)
                parts = [fact.take(fact_idx)]
                for d in range(ndims):
                    dt_d, _counts, mstarts_d, ri_d = per_dim[d]
                    if tot == 0:
                        dim_idx = np.empty(0, np.int64)
                    else:
                        dim_idx = ri_d[np.repeat(mstarts_d[rows], Kr) + sels[d]]
                    parts.append(dt_d.take(dim_idx))
            with stages.timed("eval"):
                cols = {}
                for p in parts:
                    for n, c in p.columns.items():
                        cols[n if n not in cols else f"{n}_r"] = c
                t = Table(cols)
                for op in reversed(chain):  # innermost (closest to the join) first
                    t = (
                        op._apply(t)
                        if isinstance(op, WithColumnExec)
                        else t.select(op.column_names)
                    )
            return t

        from .. import resilience

        template: Optional[Table] = None
        none_idx = np.empty(0, np.int64)
        n_chunks = 0
        for lo, hi in zip(bounds, bounds[1:]):
            # Chunk-boundary cancellation: a mid-stream deadline (like a
            # mid-stream fault) propagates cleanly — the memos below are
            # populated only after EVERY chunk streamed successfully.
            resilience.check_deadline("query.star_stream")
            t = build_chunk(lo, hi)
            if template is None:
                template = t.take(none_idx)
            n_chunks += 1
            agg.add_chunk(t)

        # EVERY chunk streamed successfully: NOW (and only now) populate the
        # per-dimension pair memos, so warm star queries start at composition.
        for dt_m, subkey, rows_key, li_v, ri_v in memo_todo:
            phys._cached_two_table(
                "pairs",
                fact,
                dt_m,
                subkey,
                lambda li_v=li_v, ri_v=ri_v: (li_v, ri_v),
                rows_key=rows_key,
            )

        out = agg.finalize()
        if out is None:
            if template is None:
                return None
            out = _empty_result(template, group_keys, agg_exec.aggs)
        summary = stages.summary(
            chunks=n_chunks,
            pairs=total_pairs,
            groups=out.num_rows,
            direct_cells=hint is not None,
            join_mode="star",
            star_dims=dim_stats,
        )
        record_join_stages(summary)
        return out
