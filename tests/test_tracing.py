"""Unified query-lifecycle tracing: span tree, JSONL export, metrics registry,
EXPLAIN ANALYZE.

Pins the observability contracts:
- One indexed streamed join→aggregate query = ONE span tree (one query_id)
  covering plan → rule → join stages (probe/verify/gather/…) → aggregate,
  with non-negative durations and parent linkage that resolves, exported as
  JSONL via ``HYPERSPACE_TRACE_FILE`` (the schema the CI smoke leg checks).
- `explain(analyze=True)` renders the SAME tree with measured wall times,
  row counts, cache-hit annotations and the rule decisions.
- Telemetry concurrency: span creation and metric increments hammered from a
  thread pool lose nothing; a crashing worker closes its span with error
  status; trace history is bounded (deque(maxlen=16)).
- `EventLoggerFactory` falls back to NoOpEventLogger (cached, one warning)
  on a bad dotted path instead of raising mid-query.
"""

import json
import os
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from hyperspace_tpu import IndexConfig, IndexConstants
from hyperspace_tpu.engine import HyperspaceSession, col
from hyperspace_tpu.hyperspace import Hyperspace, enable_hyperspace
from hyperspace_tpu.telemetry import metrics, tracing


@pytest.fixture()
def session(tmp_path):
    base = str(tmp_path)
    s = HyperspaceSession(warehouse=base)
    s.conf.set(IndexConstants.INDEX_SYSTEM_PATH, os.path.join(base, "indexes"))
    s.conf.set(IndexConstants.INDEX_NUM_BUCKETS, 8)
    return s


def _indexed_join_agg(s, tmp_path):
    """Two indexed sides + a streamed join→aggregate query over them."""
    rng = np.random.RandomState(7)
    n = 8000
    li = os.path.join(str(tmp_path), "li")
    orr = os.path.join(str(tmp_path), "orders")
    s.write_parquet(
        {
            "lk": rng.randint(0, 300, n).astype(np.int64),
            "v": rng.randint(1, 99, n).astype(np.int64),
        },
        li,
    )
    s.write_parquet(
        {
            "ok": np.arange(300, dtype=np.int64),
            "w": rng.randint(1, 9, 300).astype(np.int64),
        },
        orr,
    )
    hs = Hyperspace(s)
    hs.create_index(s.read.parquet(li), IndexConfig("liIdx", ["lk"], ["v"]))
    hs.create_index(s.read.parquet(orr), IndexConfig("orIdx", ["ok"], ["w"]))
    enable_hyperspace(s)

    def q():
        return (
            s.read.parquet(li)
            .join(s.read.parquet(orr), col("lk") == col("ok"))
            .with_column("rev", col("v") * col("w"))
            .group_by("lk")
            .agg(total=("rev", "sum"))
        )

    return q


def _check_jsonl_schema(spans):
    """The CI smoke leg's schema contract: every span has a query_id, a
    non-negative duration, and a parent that resolves within its trace."""
    assert spans, "trace file is empty"
    ids = {(sp["query_id"], sp["span_id"]) for sp in spans}
    for sp in spans:
        assert sp["query_id"], sp
        assert isinstance(sp["span_id"], int), sp
        assert sp["duration_s"] is not None and sp["duration_s"] >= 0, sp
        assert sp["status"] in ("ok", "error", "unclosed"), sp
        if sp["parent_id"] is not None:
            assert (sp["query_id"], sp["parent_id"]) in ids, sp


def test_indexed_join_agg_single_span_tree(session, tmp_path, monkeypatch):
    # Pin the STREAMED bucket-join executor (the acceptance shape): under
    # HYPERSPACE_FORCE_DEVICE_OPS=1 the fused device join→aggregate wins the
    # dispatch and runs as one program with no stage summaries to bridge.
    monkeypatch.setenv("HYPERSPACE_FORCE_DEVICE_OPS", "0")
    monkeypatch.setenv("HYPERSPACE_QUERY_STREAMING", "1")
    q = _indexed_join_agg(session, tmp_path)
    trace_file = os.path.join(str(tmp_path), "trace.jsonl")
    monkeypatch.setenv(tracing.ENV_TRACE_FILE, trace_file)
    out = q().collect()
    assert out.num_rows == 300
    monkeypatch.delenv(tracing.ENV_TRACE_FILE)

    spans = [json.loads(line) for line in open(trace_file)]
    _check_jsonl_schema(spans)
    # ONE query = ONE query_id across every exported span.
    assert len({sp["query_id"] for sp in spans}) == 1
    names = {sp["name"] for sp in spans}
    assert "query:collect" in names and "plan" in names
    assert "op:HashAggregate" in names
    assert "rule:JoinIndexRule" in names
    # The streamed bucketed join's stage spans: probe/verify/gather at
    # minimum (cold run), riding the join:stages summary span.
    assert "join:stages" in names
    for stage in ("join:probe", "join:verify", "join:gather"):
        assert stage in names, sorted(names)
    # The root is the only parentless span.
    roots = [sp for sp in spans if sp["parent_id"] is None]
    assert len(roots) == 1 and roots[0]["name"] == "query:collect"
    assert roots[0]["attrs"].get("rows_out") == 300
    # The rule span carries the applied decision with the index names.
    rule = next(sp for sp in spans if sp["name"] == "rule:JoinIndexRule")
    decisions = rule["attrs"]["decisions"]
    assert any(
        d["applied"] and d["indexes"] == ["liIdx", "orIdx"] for d in decisions
    )


def test_explain_analyze_annotates_measured_tree(session, tmp_path):
    q = _indexed_join_agg(session, tmp_path)
    q().collect()  # warm: analyze output must reflect cache hits honestly
    s = q().explain(analyze=True)
    assert "EXPLAIN ANALYZE" in s
    assert "query_id=" in s and "wall=" in s
    assert "HashAggregate" in s and "SortMergeJoin" in s
    assert "rows=300" in s
    assert "JoinIndexRule: applied" in s
    assert "liIdx" in s and "orIdx" in s
    assert "bucketed_cache=hit" in s  # warm run reads the concat cache
    assert "Cache/metric deltas" in s
    # The redirect form matches the Hyperspace facade form.
    captured = []
    Hyperspace(session).explain(q(), analyze=True, redirect=captured.append)
    assert captured and "EXPLAIN ANALYZE" in captured[0]


def test_explain_analyze_plain_query(session, tmp_path):
    path = os.path.join(str(tmp_path), "t")
    session.write_parquet(
        {"k": np.arange(100, dtype=np.int64), "v": np.arange(100, dtype=np.int64)},
        path,
    )
    df = (
        session.read.parquet(path)
        .filter(col("k") < 50)
        .group_by("k")
        .agg(total=("v", "sum"))
    )
    s = df.explain(analyze=True)
    assert "HashAggregate" in s and "Filter" in s and "Scan" in s
    assert "rows=50" in s
    # analyze=False returns the plain static tree.
    assert df.explain() == df.explain_string()


def test_tracing_disabled_records_nothing(session, tmp_path, monkeypatch):
    monkeypatch.delenv(tracing.ENV_TRACE_FILE, raising=False)
    monkeypatch.delenv(tracing.ENV_TRACING, raising=False)
    path = os.path.join(str(tmp_path), "t")
    session.write_parquet({"k": np.arange(10, dtype=np.int64)}, path)
    before = len(tracing.recent_traces())
    session.read.parquet(path).collect()
    assert len(tracing.recent_traces()) == before
    # The hooks hand out the shared no-op span.
    with tracing.query_span("query:off") as sp:
        assert sp is tracing.NOOP_SPAN


def test_span_concurrency_and_error_status():
    """Pool-worker-shaped hammer: child spans created from many threads under
    one root all register under one trace; a crashing worker's span closes
    with error status before the exception propagates."""
    n_threads, n_tasks = 8, 200

    with tracing.capture() as cap:
        with tracing.query_span("query:hammer") as root:

            def work(i):
                if i == 137:
                    with pytest.raises(RuntimeError):
                        with tracing.span(f"w{i}", parent=root):
                            raise RuntimeError("worker died")
                    return
                with tracing.span(f"w{i}", parent=root) as sp:
                    sp.set_attr("i", i)

            with ThreadPoolExecutor(max_workers=n_threads) as pool:
                list(pool.map(work, range(n_tasks)))

    trace = cap.trace
    assert trace is not None
    workers = [s for s in trace.spans if s.name.startswith("w")]
    assert len(workers) == n_tasks  # no lost spans
    assert all(s.parent_id == trace.root.span_id for s in workers)
    assert all(s.duration_s is not None and s.duration_s >= 0 for s in workers)
    crashed = [s for s in trace.spans if s.name == "w137"]
    assert len(crashed) == 1 and crashed[0].status == "error"
    assert "worker died" in crashed[0].attrs["error"]
    ok = [s for s in workers if s.name != "w137"]
    assert all(s.status == "ok" for s in ok)


def test_metric_increments_lose_nothing_under_threads():
    c = metrics.counter("test.hammer.counter")
    h = metrics.histogram("test.hammer.hist")
    start = c.value
    n_threads, n_inc = 16, 500

    def work(_):
        for _i in range(n_inc):
            c.inc()
            h.observe(0.5)

    with ThreadPoolExecutor(max_workers=n_threads) as pool:
        list(pool.map(work, range(n_threads)))
    assert c.value - start == n_threads * n_inc
    snap = metrics.snapshot()
    assert snap["counters"]["test.hammer.counter"] == c.value
    assert snap["histograms"]["test.hammer.hist"]["count"] >= n_threads * n_inc
    assert json.dumps(snap)  # bench_detail-serializable


def test_snapshot_hit_rates_derive_from_counter_pairs():
    metrics.counter("test.rate.hits").inc(3)
    metrics.counter("test.rate.misses").inc(1)
    snap = metrics.snapshot()
    assert snap["rates"]["test.rate"] == 0.75


def test_trace_history_is_bounded(monkeypatch):
    monkeypatch.setenv(tracing.ENV_TRACING, "1")
    for i in range(tracing._RECENT.maxlen + 5):
        with tracing.query_span(f"query:bounded{i}"):
            pass
    recent = tracing.recent_traces()
    assert len(recent) == tracing._RECENT.maxlen == 16
    # Newest last; the oldest of the burst aged out.
    assert recent[-1].root.name == f"query:bounded{tracing._RECENT.maxlen + 4}"


def test_nested_collect_stays_one_query(session, tmp_path, monkeypatch):
    """A scalar-subquery-style nested action inside a traced query attaches
    as a child span instead of opening a second query_id."""
    monkeypatch.setenv(tracing.ENV_TRACING, "1")
    path = os.path.join(str(tmp_path), "t")
    session.write_parquet({"k": np.arange(20, dtype=np.int64)}, path)
    with tracing.capture() as cap:
        with tracing.query_span("query:outer"):
            session.read.parquet(path).collect()
    trace = cap.trace
    assert trace.root.name == "query:outer"
    inner = trace.find("query:collect")
    assert len(inner) == 1 and inner[0].parent_id == trace.root.span_id


def test_stage_spans_ride_streaming_scan_aggregate(session, tmp_path, monkeypatch):
    """A multi-file streamed scan→aggregate records query:stages spans under
    its HashAggregate span (the StageTimings→span bridge)."""
    from hyperspace_tpu.engine import io as engine_io
    from hyperspace_tpu.engine.table import Table

    monkeypatch.setenv("HYPERSPACE_QUERY_STREAMING", "1")
    path = os.path.join(str(tmp_path), "multi")
    rng = np.random.RandomState(3)
    for i in range(3):
        engine_io.write_parquet(
            Table.from_pydict(
                {
                    "g": rng.randint(0, 5, 1000).astype(np.int64),
                    "x": rng.randint(0, 100, 1000).astype(np.int64),
                }
            ),
            os.path.join(path, f"part-{i:05d}.parquet"),
        )
    df = session.read.parquet(path).group_by("g").agg(total=("x", "sum"))
    with tracing.capture() as cap:
        df.collect()
    names = {s.name for s in cap.trace.spans}
    assert "query:stages" in names, sorted(names)
    assert any(n.startswith("query:") and n != "query:stages" for n in names)


def test_failing_pallas_probe_raises_out_of_traced_query(session, tmp_path, monkeypatch):
    """A wanted Pallas probe that fails inside an indexed join surfaces as the
    query's error (no silent XLA/host fallback), and stage summaries carry no
    fallback key."""
    from hyperspace_tpu.engine import physical
    from hyperspace_tpu.ops import pallas_probe
    from hyperspace_tpu.telemetry import profiling

    q = _indexed_join_agg(session, tmp_path)
    expected = q().sorted_rows()

    def boom(*a, **k):
        raise RuntimeError("mosaic lowering failed")

    monkeypatch.setenv("HYPERSPACE_PALLAS_PROBE", "1")
    monkeypatch.setattr(pallas_probe, "probe_pallas", boom)
    physical.clear_device_memos()
    with tracing.capture():
        with pytest.raises(RuntimeError, match="mosaic lowering failed"):
            q().collect()
    for summary in (profiling.last_build_stages(), profiling.last_join_stages()):
        assert summary is None or "pallas_fallbacks" not in summary

    monkeypatch.undo()
    physical.clear_device_memos()
    assert q().sorted_rows() == expected


def test_event_logger_bad_path_falls_back_to_noop():
    from hyperspace_tpu.telemetry import EventLoggerFactory, NoOpEventLogger
    from hyperspace_tpu.telemetry.events import HyperspaceEvent

    EventLoggerFactory.reset()
    try:
        logger = EventLoggerFactory.get_logger("no.such.module.NoSuchLogger")
        assert isinstance(logger, NoOpEventLogger)
        logger.log_event(HyperspaceEvent(message="must not raise"))
        # Cached: the broken import is not retried per event.
        assert EventLoggerFactory.get_logger("no.such.module.NoSuchLogger") is logger
        # A bad ATTRIBUTE on a real module falls back the same way.
        assert isinstance(
            EventLoggerFactory.get_logger("hyperspace_tpu.telemetry.NoSuchAttr"),
            NoOpEventLogger,
        )
    finally:
        EventLoggerFactory.reset()


def test_rule_skip_reasons_recorded(session, tmp_path, monkeypatch):
    """An eligible-but-unusable pattern records a skipped decision with a
    reason (here: indexes exist but none covers the query)."""
    path = os.path.join(str(tmp_path), "t")
    session.write_parquet(
        {
            "a": np.arange(50, dtype=np.int64),
            "b": np.arange(50, dtype=np.int64),
            "c": np.arange(50, dtype=np.int64),
        },
        path,
    )
    hs = Hyperspace(session)
    hs.create_index(
        session.read.parquet(path), IndexConfig("abIdx", ["a"], ["b"])
    )
    enable_hyperspace(session)
    # Filter on a non-head column: the index cannot cover the plan.
    df = session.read.parquet(path).filter(col("c") > 10).select("c")
    with tracing.capture() as cap:
        with tracing.query_span("query:skip"):
            session.optimize(df.plan)
    rule_spans = cap.trace.find("rule:FilterIndexRule")
    assert rule_spans
    decisions = rule_spans[0].attrs.get("decisions", [])
    assert any(not d["applied"] and d.get("reason") for d in decisions)


def test_traced_query_equals_untraced(session, tmp_path, monkeypatch):
    """Tracing must observe, never change: identical rows with the trace
    sink on and off."""
    q = _indexed_join_agg(session, tmp_path)
    plain = sorted(map(tuple, q().collect().rows()))
    monkeypatch.setenv(tracing.ENV_TRACE_FILE, os.path.join(str(tmp_path), "t.jsonl"))
    traced = sorted(map(tuple, q().collect().rows()))
    assert traced == plain


# ---------------------------------------------------------------------------
# Real stage spans: every StageTimings bracket of the index build is a span
# ---------------------------------------------------------------------------

N_SRC_FILES, SRC_ROWS = 4, 4000


def _write_build_source(session, tmp_path):
    from hyperspace_tpu.engine import io as engine_io
    from hyperspace_tpu.engine.table import Table

    path = os.path.join(str(tmp_path), "src")
    rng = np.random.RandomState(11)
    per = SRC_ROWS // N_SRC_FILES
    for i in range(N_SRC_FILES):
        engine_io.write_parquet(
            Table.from_pydict(
                {
                    "k": rng.randint(0, 500, per).astype(np.int64),
                    "v": rng.randint(0, 100, per).astype(np.int64),
                    "f": rng.rand(per),
                }
            ),
            os.path.join(path, f"part-{i:05d}.parquet"),
        )
    return path


def _pipelined_build(session, tmp_path, monkeypatch, name="idx"):
    """A pipelined build (2 decode threads, 3 writers) of a 4-file source."""
    monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "2")
    monkeypatch.setenv("HYPERSPACE_BUILD_WRITERS", "3")
    src = _write_build_source(session, tmp_path)
    Hyperspace(session).create_index(
        session.read.parquet(src), IndexConfig(name, ["k"], ["v", "f"])
    )


@pytest.mark.parametrize("device_ops, staging", [("0", "hash"), ("1", "h2d")])
def test_pipelined_build_records_real_stage_spans(
    session, tmp_path, monkeypatch, device_ops, staging
):
    monkeypatch.setenv("HYPERSPACE_FORCE_DEVICE_OPS", device_ops)
    with tracing.capture() as cap:
        _pipelined_build(session, tmp_path, monkeypatch)
    trace = cap.trace
    (stages,) = trace.find("build:stages")
    children = trace.spans_by_parent()[stages.span_id]
    by_stage = {}
    for sp in children:
        by_stage.setdefault(sp.name.split(":", 1)[1], []).append(sp)

    files = [
        f
        for _root, _dirs, fs in os.walk(os.path.join(str(tmp_path), "indexes", "idx"))
        for f in fs
        if f.endswith(".parquet")
    ]
    assert sorted(sp.attrs["file"] for sp in by_stage["decode"]) == list(
        range(N_SRC_FILES)
    )
    assert len(by_stage[staging]) == N_SRC_FILES  # one chunk per file
    assert len(by_stage["sort"]) == 1 and by_stage["sort"][0].attrs["rows"] == SRC_ROWS
    # One take per gather range, each over every column (k, v, f): the
    # ranges' rows cover the source once, their buckets the files written.
    takes = by_stage["take"]
    assert 1 < len(takes) <= 3  # at most one range per writer
    assert all(sp.attrs["columns"] == 3 for sp in takes)
    assert sum(sp.attrs["rows"] for sp in takes) == SRC_ROWS
    writes = by_stage["write"]
    assert len(writes) == len(files) > 1  # one per non-empty bucket
    assert sum(sp.attrs["buckets"] for sp in takes) == len(files)
    (concat,) = by_stage["concat"]
    assert concat.attrs["bytes"] > 0  # 4 source files: every column copied together
    assert len({sp.attrs["bucket"] for sp in writes}) == len(writes)
    assert sum(sp.attrs["rows"] for sp in writes) == SRC_ROWS
    assert sum(sp.attrs["rows"] for sp in by_stage["decode"]) == SRC_ROWS
    assert all(sp.attrs["bytes"] > 0 for sp in writes + by_stage["decode"])
    if staging == "h2d":
        assert all(sp.attrs["bytes"] > 0 for sp in by_stage["h2d"])

    # Real spans: each starts and ends inside the pipeline's wall, under the
    # build's query_id, and they time exactly the brackets the summary sums.
    end = stages.start_s + stages.duration_s
    for sp in children:
        assert sp.query_id == stages.query_id == trace.query_id
        assert sp.status == "ok" and sp.duration_s is not None
        assert stages.start_s <= sp.start_s
        assert sp.start_s + sp.duration_s <= end + 1e-3
    assert set(by_stage) == set(stages.attrs["stage_counts"])
    for stage, spans in by_stage.items():
        assert stages.attrs["stage_counts"][stage] == len(spans)
        busy = sum(sp.duration_s for sp in spans)
        assert busy == pytest.approx(stages.attrs[f"{stage}_s"], abs=2e-3), stage
    assert stages.attrs["rows"] == SRC_ROWS
    assert "synthesized" not in stages.attrs


def test_build_allocates_no_span_with_tracing_off(session, tmp_path, monkeypatch):
    for env in (tracing.ENV_TRACE_FILE, tracing.ENV_TRACING, tracing.ENV_TIMELINE_DIR):
        monkeypatch.delenv(env, raising=False)
    made = []
    real_init = tracing.Span.__init__

    def counting_init(self, trace, name, *a, **k):
        made.append(name)
        real_init(self, trace, name, *a, **k)

    monkeypatch.setattr(tracing.Span, "__init__", counting_init)
    _pipelined_build(session, tmp_path, monkeypatch, name="off")
    assert made == []
    # The count sees spans when a sink is attached (the control).
    with tracing.capture():
        Hyperspace(session).create_index(
            session.read.parquet(os.path.join(str(tmp_path), "src")),
            IndexConfig("on", ["k"], ["v"]),
        )
    assert "build:stages" in made and "build:write" in made


def test_failing_build_closes_stage_spans_with_error(session, tmp_path, monkeypatch):
    from hyperspace_tpu.index.build_pipeline import _BucketWriter

    def boom(self, b, lo, hi):
        raise RuntimeError("writer down")

    monkeypatch.setattr(_BucketWriter, "write_bucket", boom)
    with tracing.capture() as cap:
        with pytest.raises(RuntimeError, match="writer down"):
            _pipelined_build(session, tmp_path, monkeypatch)
    (stages,) = cap.trace.find("build:stages")
    assert stages.status == "error" and "writer down" in stages.attrs["error"]
    assert any(sp.status == "error" for sp in cap.trace.find("build:write"))


MESH_STAGES = ("hash", "h2d", "exchange_counts", "exchange", "gather")


def test_mesh_build_records_exchange_spans(session, tmp_path, monkeypatch):
    """A build on the mesh (every visible device; 8 here) times each step of
    its bucketize as a stage inside `build:sort`, once per build, with the
    counts that step saw; the exchange's bytes are the counters' deltas. The
    pipeline's range writer then gathers the rows after the sort, one
    `build:take` per gather range."""
    session.conf.set(IndexConstants.DISTRIBUTED_MIN_ROWS, 0)
    monkeypatch.setenv("HYPERSPACE_BUILD_WRITERS", "3")
    src = _write_build_source(session, tmp_path)
    before = metrics.snapshot()["counters"]
    with tracing.capture() as cap:
        Hyperspace(session).create_index(
            session.read.parquet(src), IndexConfig("idx", ["k"], ["v", "f"])
        )
    after = metrics.snapshot()["counters"]
    delta = {
        k: after[k] - before.get(k, 0)
        for k in after
        if k.startswith("parallel.exchange.")
    }
    trace = cap.trace
    (stages,) = trace.find("build:stages")
    (sort,) = trace.find("build:sort")
    assert stages.attrs["mode"] == "mesh"
    sort_end = sort.start_s + sort.duration_s
    spans = {}
    for stage in MESH_STAGES:
        (sp,) = trace.find(f"build:{stage}")
        assert sp.parent_id == stages.span_id and sp.status == "ok"
        assert sort.start_s <= sp.start_s
        assert sp.start_s + sp.duration_s <= sort_end + 1e-3
        assert stages.attrs["stage_counts"][stage] == 1
        spans[stage] = sp.attrs
    assert [s.name.split(":", 1)[1] for s in sorted(
        (trace.find(f"build:{st}")[0] for st in MESH_STAGES), key=lambda s: s.start_s
    )] == list(MESH_STAGES)
    assert spans["hash"]["rows"] == SRC_ROWS
    assert spans["hash"]["pad_rows"] == delta["parallel.exchange.pad_rows"] > 0
    assert spans["h2d"]["bytes"] > 0 and spans["gather"]["bytes"] > 0
    counts, exchange = spans["exchange_counts"], spans["exchange"]
    assert 0 < counts["max_count"] <= counts["cap"] == exchange["cap"]
    assert counts["shard_rows"] == -(-SRC_ROWS // 8)  # the real rows spread evenly
    assert exchange["devices"] == 8
    assert delta["parallel.exchange.count"] == 1
    assert exchange["bytes_payload"] == delta["parallel.exchange.bytes_payload"] > 0
    assert exchange["bytes_moved"] == delta["parallel.exchange.bytes_moved"]

    # The reorder is the range writer's: one take per gather range, after the
    # sort and inside the pipeline, over every column (k, v, f); the ranges'
    # rows cover the source once, their buckets the files written.
    files = [
        f
        for _root, _dirs, fs in os.walk(os.path.join(str(tmp_path), "indexes", "idx"))
        for f in fs
        if f.endswith(".parquet")
    ]
    takes = trace.find("build:take")
    writes = trace.find("build:write")
    assert 1 < len(takes) <= 3 and stages.attrs["stage_counts"]["take"] == len(takes)
    end = stages.start_s + stages.duration_s
    for sp in takes:
        assert sp.parent_id == stages.span_id and sp.status == "ok"
        assert sort_end <= sp.start_s and sp.start_s + sp.duration_s <= end + 1e-3
        assert sp.attrs["columns"] == 3
    assert sum(sp.attrs["rows"] for sp in takes) == SRC_ROWS
    assert sum(sp.attrs["buckets"] for sp in takes) == len(files) == len(writes) > 1
    assert sum(sp.attrs["rows"] for sp in writes) == SRC_ROWS
    # The exchange's steps nest in the sort: the busy time the ratio counts
    # holds their seconds once, beside the writer's concurrent brackets.
    nested = sum(stages.attrs[f"{st}_s"] for st in MESH_STAGES)
    busy = sum(
        v for k, v in stages.attrs.items() if k.endswith("_s") and k != "wall_s"
    ) - nested
    assert stages.attrs["overlap_ratio"] == pytest.approx(
        busy / stages.attrs["wall_s"], abs=0.01  # the summary's rounding
    )


@pytest.mark.parametrize("route", ["pipelined", "serial"])
def test_single_device_build_records_no_exchange_spans(
    session, tmp_path, monkeypatch, route
):
    session.conf.set(IndexConstants.DISTRIBUTED_ENABLED, False)
    if route == "serial":
        monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "1")
        src = _write_build_source(session, tmp_path)
        with tracing.capture() as cap:
            Hyperspace(session).create_index(
                session.read.parquet(src), IndexConfig("idx", ["k"], ["v", "f"])
            )
    else:
        with tracing.capture() as cap:
            _pipelined_build(session, tmp_path, monkeypatch)
    names = {sp.name for sp in cap.trace.spans}
    for stage in ("exchange_counts", "exchange", "gather"):
        assert f"build:{stage}" not in names
    if route == "serial":  # decode, sort and write alone
        assert not names & {f"build:{stage}" for stage in MESH_STAGES}
    assert not any("pad_rows" in sp.attrs for sp in cap.trace.spans)


@pytest.mark.parametrize("outcome", ["summary", "fallback", "error"])
def test_stage_timings_closes_its_stages_span(outcome):
    """The stages span ends with the summary, with ``status="fallback"`` when
    the operation returns before it ran, or with the error; only a summary
    renders a stage line in explain(analyze=True)."""
    from hyperspace_tpu.plananalysis.analyze import _stage_lines
    from hyperspace_tpu.telemetry.profiling import StageTimings

    with tracing.capture() as cap:
        with tracing.query_span("query:collect"):
            try:
                with StageTimings("query", "stream-cpu") as stages:
                    with stages.timed("decode", rows=3):
                        pass
                    if outcome == "summary":
                        stages.summary(rows=3)
                    elif outcome == "error":
                        raise ValueError("decode failed")
            except ValueError:
                pass
    trace = cap.trace
    (sp,) = trace.find("query:stages")
    (decode,) = trace.find("query:decode")
    assert decode.parent_id == sp.span_id and decode.attrs["rows"] == 3
    assert sp.duration_s is not None
    assert sp.status == {"summary": "ok"}.get(outcome, outcome)
    lines = _stage_lines(trace.root, trace.spans_by_parent(), 0)
    if outcome == "summary":
        assert sp.attrs["rows"] == 3 and sp.attrs["stage_counts"] == {"decode": 1}
        assert len(lines) == 1 and "query stages: decode=" in lines[0]
    else:
        assert "stage_counts" not in sp.attrs and lines == []


@pytest.mark.parametrize(
    "action, inner",
    [
        ("create", ("build:stages", "build:commit")),
        ("delete", ()),
        ("vacuum", ()),
    ],
)
def test_lifecycle_actions_record_spans(session, tmp_path, monkeypatch, action, inner):
    """Each lifecycle call is one root span whose action writes the operation
    log under `log:begin`/`log:commit` around the body's `action:op`."""
    hs = Hyperspace(session)
    if action != "create":
        _pipelined_build(session, tmp_path, monkeypatch)
    if action == "vacuum":
        hs.delete_index("idx")
    with tracing.capture() as cap:
        if action == "create":
            _pipelined_build(session, tmp_path, monkeypatch)
        else:
            getattr(hs, f"{action}_index")("idx")
    trace = cap.trace
    assert trace.root.name == f"build:{action}_index"
    assert trace.root.attrs["index_name"] == "idx"
    top = [s.name for s in trace.spans_by_parent()[trace.root.span_id]]
    assert top == ["log:begin", "action:op", "log:commit"]
    (op,) = trace.find("action:op")
    for name in inner:
        (sp,) = trace.find(name)
        assert op.start_s <= sp.start_s <= op.start_s + op.duration_s


def test_explain_analyze_stage_line_reads_summary(session, tmp_path, monkeypatch):
    monkeypatch.setenv("HYPERSPACE_FORCE_DEVICE_OPS", "0")
    monkeypatch.setenv("HYPERSPACE_QUERY_STREAMING", "1")
    q = _indexed_join_agg(session, tmp_path)
    txt = q().explain(analyze=True)
    line = next(ln for ln in txt.splitlines() if "join stages:" in ln)
    for stage in ("probe", "verify", "gather"):
        assert f" {stage}=" in line and f" {stage}=?" not in line, line
    assert "wall=" in line and "overlap=" in line
