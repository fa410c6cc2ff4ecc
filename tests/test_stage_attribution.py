"""Stage-level cost attribution (ISSUE 19 tentpole).

The contract under test: `stage_scope` labels the ambient stage on a
contextvar that pool workers inherit, every cost-vector counter bills the
ambient stage (or the visible ``<unlabeled>`` bucket, so per-stage totals
reconcile with the whole-query ledger BY CONSTRUCTION), the mesh exchange
and H2D upload own dedicated lanes, and the closed ledger's ``stages`` key
feeds the planner's stage-grain learning — a mispriced knob flips on its
stage-local subtotal even when an unrelated stage dominates the wall,
which whole-wall learning cannot do. ``HYPERSPACE_STAGE_ATTRIBUTION=0`` is
zero-cost-off: no stage ledger is ever touched and results are
byte-identical. v1 (pre-stage) planner outcome records keep folding
wall-only; the Chrome-trace conversion gives each stage its own lane.
"""

import glob
import importlib.util
import json
import os
import time

import numpy as np
import pytest

from hyperspace_tpu import resilience
from hyperspace_tpu.engine import HyperspaceSession, col
from hyperspace_tpu.engine import io as engine_io
from hyperspace_tpu.engine.table import Table
from hyperspace_tpu.plananalysis import attribution, costmodel, planner
from hyperspace_tpu.telemetry import accounting, history, stage_ledger, tracing

CLEAN_ENVS = (
    planner.ENV_PLANNER,
    planner.ENV_PLANNER_DIR,
    planner.ENV_MIN_SAMPLES,
    planner.ENV_DRIFT_X,
    stage_ledger.ENV_STAGE_ATTRIBUTION,
    stage_ledger.ENV_TIMELINE_DIR,
    engine_io.ENV_DECODE_THREADS,
    "HYPERSPACE_HISTORY",
    "HYPERSPACE_HISTORY_DIR",
    "HYPERSPACE_ACCOUNTING",
)


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for k in CLEAN_ENVS + tuple(costmodel.KNOB_ENV.values()):
        monkeypatch.delenv(k, raising=False)
    planner.reset()
    history.reset_stores()
    yield
    planner.reset()
    history.reset_stores()


@pytest.fixture()
def session(tmp_path):
    return HyperspaceSession(warehouse=str(tmp_path))


def _write_parts(path: str, parts: int = 4, rows: int = 400) -> None:
    """Key-sorted parts with 4 row groups each: an `isin` point filter
    prunes 3 of 4 groups per file, so pruned decodes (-> bytes_decoded)
    actually happen, one decode job per file (-> the pool engages)."""
    for j in range(parts):
        engine_io.write_parquet(
            Table.from_pydict(
                {
                    "k": (np.arange(rows, dtype=np.int64) + j * rows),
                    "v": np.arange(rows, dtype=np.float64),
                }
            ),
            os.path.join(path, f"part-{j:05d}.parquet"),
            row_group_rows=max(rows // 4, 1),
        )


def _scan_agg(session, path):
    return session.read.parquet(path).group_by("k").agg(total=("v", "sum"))


def _pruned_scan(session, path, parts: int = 4, rows: int = 400):
    return session.read.parquet(path).filter(
        col("k").isin([j * rows + 7 for j in range(parts)])
    )


# ---------------------------------------------------------------------------
# stage_scope: walls bank on the ambient query scope
# ---------------------------------------------------------------------------


def test_stage_scope_banks_walls_on_query_scope():
    with resilience.query_scope("q-walls"):
        assert stage_ledger.query_stage_walls() is None  # nothing labeled yet
        with stage_ledger.stage_scope("probe"):
            assert stage_ledger.current_stage() == "probe"
            time.sleep(0.01)
        with stage_ledger.stage_scope("pad"):
            pass
        walls = stage_ledger.query_stage_walls()
        assert walls is not None and set(walls) == {"probe", "pad"}
        assert walls["probe"] >= 0.01
    assert stage_ledger.current_stage() is None


def test_stage_scope_nested_innermost_wins():
    with resilience.query_scope("q-nest"):
        with stage_ledger.stage_scope("outer"):
            with stage_ledger.stage_scope("inner"):
                assert stage_ledger.current_stage() == "inner"
            assert stage_ledger.current_stage() == "outer"
        walls = stage_ledger.query_stage_walls()
        assert set(walls) == {"outer", "inner"}


def test_no_scope_means_no_walls():
    with stage_ledger.stage_scope("orphan"):
        pass  # no ambient query scope: nowhere to bank, must not raise
    assert stage_ledger.query_stage_walls() is None


# ---------------------------------------------------------------------------
# Pool workers inherit the submitting stage; counters reconcile
# ---------------------------------------------------------------------------


def test_threaded_decode_workers_bill_decode_stage(session, tmp_path, monkeypatch):
    monkeypatch.setenv("HYPERSPACE_ACCOUNTING", "1")
    monkeypatch.setenv(engine_io.ENV_DECODE_THREADS, "2")
    path = os.path.join(str(tmp_path), "t")
    _write_parts(path)
    out = _pruned_scan(session, path).collect()
    assert out.num_rows == 4
    d = accounting.recent_ledgers()[-1].to_dict()
    stages = d.get("stages")
    assert isinstance(stages, dict) and stages
    # The decode pool's workers billed the decode lane, not <unlabeled>.
    assert "decode" in stages
    assert stages["decode"]["bytes_decoded"] > 0
    assert stages["decode"]["wall_s"] > 0


def test_stage_totals_reconcile_with_ledger_counters(session, tmp_path, monkeypatch):
    monkeypatch.setenv("HYPERSPACE_ACCOUNTING", "1")
    path = os.path.join(str(tmp_path), "t")
    _write_parts(path)
    _pruned_scan(session, path).collect()
    d = accounting.recent_ledgers()[-1].to_dict()
    stages = d.get("stages")
    assert isinstance(stages, dict)
    # Every stage-attributed counter sums (across stages INCLUDING the
    # <unlabeled> bucket) to the whole-query ledger counter exactly — the
    # by-construction reconciliation the <unlabeled> bucket exists for.
    for counter_key, field in stage_ledger._COUNTER_VECTOR.items():
        total = d.get(counter_key) or 0
        if not total:
            continue
        staged = sum(vec.get(field, 0) for vec in stages.values())
        assert staged == pytest.approx(total, rel=1e-6), (counter_key, d)
    assert d["bytes_decoded"] > 0  # the loop above exercised at least bytes


# ---------------------------------------------------------------------------
# Zero-cost-off: counting oracle + byte-identical results
# ---------------------------------------------------------------------------


def test_attribution_off_is_zero_cost_and_byte_identical(
    session, tmp_path, monkeypatch
):
    monkeypatch.setenv("HYPERSPACE_ACCOUNTING", "1")
    path = os.path.join(str(tmp_path), "t")
    _write_parts(path)

    calls = []
    real = stage_ledger._scope_ledger

    def counting(create):
        calls.append(create)
        return real(create)

    monkeypatch.setattr(stage_ledger, "_scope_ledger", counting)

    monkeypatch.setenv(stage_ledger.ENV_STAGE_ATTRIBUTION, "0")
    rows_off = _scan_agg(session, path).collect().rows()
    assert calls == []  # the counting oracle: off never touches the ledger
    d_off = accounting.recent_ledgers()[-1].to_dict()
    assert "stages" not in d_off

    monkeypatch.setenv(stage_ledger.ENV_STAGE_ATTRIBUTION, "1")
    rows_on = _scan_agg(session, path).collect().rows()
    assert calls  # on: the same query labels stages
    assert rows_on == rows_off  # byte-identical results in both states


# ---------------------------------------------------------------------------
# Dedicated lanes: mesh exchange
# ---------------------------------------------------------------------------


def test_mesh_exchange_bills_exchange_stage():
    import jax.numpy as jnp

    from hyperspace_tpu.engine.table import Column
    from hyperspace_tpu.ops.hashing import _SEED1, column_hash_u32
    from hyperspace_tpu.parallel import distributed_bucketize, make_mesh

    mesh = make_mesh(8)
    rng = np.random.RandomState(5)
    keys = rng.randint(0, 1000, size=512).astype(np.int64)
    kcol = Column.from_values(keys)
    h1 = np.asarray(column_hash_u32(kcol, jnp.asarray(keys), _SEED1))
    bucket = jnp.asarray((h1 % np.uint32(32)).astype(np.int8))
    valid = jnp.ones(len(keys), dtype=jnp.int8)
    with resilience.query_scope("q-mesh"):
        distributed_bucketize(
            mesh, bucket, [jnp.asarray(keys)], [jnp.asarray(keys)], 32, valid, len(keys)
        )
        walls = stage_ledger.query_stage_walls()
    assert walls is not None and walls.get("exchange", 0) > 0


# ---------------------------------------------------------------------------
# Planner: v1 records fold, stage-grain flips beat whole-wall
# ---------------------------------------------------------------------------


def test_v1_outcome_records_fold_wall_only(tmp_path, monkeypatch):
    store_dir = os.path.join(str(tmp_path), "store")
    os.makedirs(store_dir)
    v1 = {
        "kind": "planner_outcome",
        "ts": 1.0,
        "fingerprint": "fp-v1",
        "outcomes": {"streaming": {"arm": "on", "wall_s": 0.05, "predicted_s": 0.01}},
    }
    v2 = {
        "schema_version": 2,
        "kind": "planner_outcome",
        "ts": 2.0,
        "fingerprint": "fp-v1",
        "outcomes": {
            "streaming": {
                "arm": "on",
                "wall_s": 0.07,
                "predicted_s": 0.01,
                "stage_s": 0.002,
            }
        },
    }
    with open(os.path.join(store_dir, "planner-old.jsonl"), "w") as fh:
        fh.write(json.dumps(v1) + "\n")
        fh.write(json.dumps(v2) + "\n")
    monkeypatch.setenv(planner.ENV_PLANNER_DIR, store_dir)
    planner.reset()
    st = planner._outcome_store().stat("fp-v1", "streaming", "on")
    # Both versions fold walls; only the v2 record carries stage stats.
    assert st.n == 2 and st.mean_wall() == pytest.approx(0.06)
    assert st.stage_n == 1 and st.mean_stage() == pytest.approx(0.002)


def test_v2_records_round_trip_through_observe(tmp_path, monkeypatch):
    store_dir = os.path.join(str(tmp_path), "store")
    monkeypatch.setenv(planner.ENV_PLANNER_DIR, store_dir)
    store = planner._outcome_store()
    store.observe(
        "fp-rt",
        {"streaming": {"arm": "off", "wall_s": 0.5, "predicted_s": 0.1, "stage_s": 0.02}},
    )
    recs = []
    for f in glob.glob(os.path.join(store_dir, "planner-*.jsonl")):
        recs += [json.loads(line) for line in open(f)]
    assert recs and recs[0]["schema_version"] == 2
    planner.reset()  # restart: the persisted stage_s must fold back
    st = planner._outcome_store().stat("fp-rt", "streaming", "off")
    assert st.stage_n == 1 and st.mean_stage() == pytest.approx(0.02)


def _mispriced_streaming(stats, cal):
    est = {k: (True, False, 0.0, 0.0) for k in costmodel.KNOBS}
    est["streaming"] = (False, True, 0.01, 0.011)  # model prefers OFF
    est["chunk_rows"] = (4_000_000, 4_000_000, 0.0, 0.0)
    est["hash_quantize"] = (False, True, 0.0, 0.0)
    return est


def test_stage_grain_flip_beats_whole_wall(session, tmp_path, monkeypatch):
    """Equal walls (an unrelated stage dominates both arms), decisive stage
    subtotals: stage-grain learning flips to the measured-better arm while
    the identical wall-only history stays on the model arm."""
    monkeypatch.setenv(planner.ENV_MIN_SAMPLES, "2")
    monkeypatch.setattr(costmodel, "estimate", _mispriced_streaming)
    src = os.path.join(str(tmp_path), "t")
    _write_parts(src, parts=1, rows=50)
    phys = _scan_agg(session, src).physical_plan()

    # Wall-only history: both arms identical at 1.0s -> no flip margin.
    monkeypatch.setenv(planner.ENV_PLANNER_DIR, os.path.join(str(tmp_path), "w"))
    planner.reset()
    store = planner._outcome_store()
    for _ in range(2):
        store.observe("fp-g", {"streaming": {"arm": "off", "wall_s": 1.0, "predicted_s": 0.01}})
        store.observe("fp-g", {"streaming": {"arm": "on", "wall_s": 1.0, "predicted_s": 0.011}})
    pd = planner.decide(phys, "fp-g")
    assert pd.decisions["streaming"].source == "model"
    assert pd.decisions["streaming"].value is False

    # Same walls PLUS stage subtotals: on's streaming-governed stages are
    # 4x cheaper -> measured flip despite indistinguishable walls.
    monkeypatch.setenv(planner.ENV_PLANNER_DIR, os.path.join(str(tmp_path), "s"))
    planner.reset()
    store = planner._outcome_store()
    for _ in range(2):
        store.observe(
            "fp-g",
            {"streaming": {"arm": "off", "wall_s": 1.0, "predicted_s": 0.01, "stage_s": 0.08}},
        )
        store.observe(
            "fp-g",
            {"streaming": {"arm": "on", "wall_s": 1.0, "predicted_s": 0.011, "stage_s": 0.02}},
        )
    pd = planner.decide(phys, "fp-g")
    assert pd.decisions["streaming"].source == "measured"
    assert pd.decisions["streaming"].value is True


def test_observe_records_knob_stage_subtotals(session, tmp_path, monkeypatch):
    monkeypatch.setenv(planner.ENV_PLANNER_DIR, os.path.join(str(tmp_path), "s"))
    src = os.path.join(str(tmp_path), "t")
    _write_parts(src, parts=1, rows=50)
    phys = _scan_agg(session, src).physical_plan()
    pd = planner.decide(phys, "fp-obs")
    planner.observe(
        pd, 0.5, stages={"decode": 0.1, "filter": 0.02, "pad": 0.01, "h2d": 0.03}
    )
    st = planner._outcome_store().stat(
        "fp-obs", "streaming", planner.arm_label(pd.decisions["streaming"].value)
    )
    # streaming governs decode/filter/partial/merge -> 0.12 of the snapshot.
    assert st.stage_n == 1 and st.mean_stage() == pytest.approx(0.12)
    # pushdown governs decode only.
    stp = planner._outcome_store().stat(
        "fp-obs", "pushdown", planner.arm_label(pd.decisions["pushdown"].value)
    )
    assert stp.mean_stage() == pytest.approx(0.1)


# ---------------------------------------------------------------------------
# attribution units
# ---------------------------------------------------------------------------


def test_knob_stage_seconds_subtotals_and_fallback():
    walls = {"pad": 0.01, "probe": 0.02, "verify": 0.03, "decode": 9.0}
    assert attribution.knob_stage_seconds("join_size_classes", walls) == pytest.approx(
        0.06
    )
    assert attribution.knob_stage_seconds("pushdown", walls) == pytest.approx(9.0)
    # None -> whole-wall fallback: no snapshot, unknown knob, or no overlap.
    assert attribution.knob_stage_seconds("join_size_classes", None) is None
    assert attribution.knob_stage_seconds("no_such_knob", walls) is None
    assert attribution.knob_stage_seconds("packed_codes", {"decode": 1.0}) is None


def test_knob_stages_cover_every_costmodel_knob():
    assert set(attribution.KNOB_STAGES) == set(costmodel.KNOBS)


# ---------------------------------------------------------------------------
# Chrome-trace conversion: one lane per stage
# ---------------------------------------------------------------------------


def _span(name, span_id, parent, start, dur):
    return {
        "query_id": "q-trace",
        "span_id": span_id,
        "parent_id": parent,
        "name": name,
        "start_s": start,
        "duration_s": dur,
        "status": "ok",
        "attrs": {},
    }


def test_chrome_trace_gives_each_stage_its_own_lane():
    spans = [
        _span("query:collect", "r", None, 0.0, 1.0),
        _span("join:stages", "s", "r", 0.1, 0.8),
        _span("join:pad", "p1", "s", 0.1, 0.2),
        _span("join:probe", "p2", "s", 0.3, 0.3),
        _span("join:verify", "p3", "s", 0.6, 0.2),
        _span("op:scan", "o1", "r", 0.0, 0.1),
        _span("worker:decode", "w1", "r", 0.0, 0.05),
    ]
    doc = stage_ledger.chrome_trace(spans)
    lanes = doc["otherData"]["lanes"]
    stage_lanes = [ln for ln in lanes if ln.startswith("stage:")]
    assert sorted(stage_lanes) == ["stage:pad", "stage:probe", "stage:verify"]
    assert "query" in lanes and "ops" in lanes and "workers" in lanes
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {m["args"]["name"] for m in metas} == set(lanes)
    assert len(xs) == len(spans)
    by_name = {e["name"]: e for e in xs}
    # Events on the same lane share a tid; different stages never do.
    tids = {by_name[n]["tid"] for n in ("join:pad", "join:probe", "join:verify")}
    assert len(tids) == 3
    assert by_name["join:probe"]["ts"] == pytest.approx(0.3e6)
    assert by_name["join:probe"]["dur"] == pytest.approx(0.3e6)
    assert json.dumps(doc)


def test_live_timeline_capture_writes_per_query_file(session, tmp_path, monkeypatch):
    tdir = os.path.join(str(tmp_path), "timelines")
    monkeypatch.setenv(stage_ledger.ENV_TIMELINE_DIR, tdir)
    path = os.path.join(str(tmp_path), "t")
    _write_parts(path, parts=2)
    with tracing.capture() as cap:
        _scan_agg(session, path).collect()
    f = os.path.join(tdir, f"timeline-{cap.trace.query_id}.json")
    assert os.path.exists(f)
    doc = json.load(open(f))
    assert doc["otherData"]["query_id"] == cap.trace.query_id
    assert any(e["ph"] == "X" for e in doc["traceEvents"])


# ---------------------------------------------------------------------------
# Surfacing: explain, exporter, hsreport
# ---------------------------------------------------------------------------


def test_explain_analyze_renders_attribution_section(session, tmp_path, monkeypatch):
    monkeypatch.setenv("HYPERSPACE_ACCOUNTING", "1")
    path = os.path.join(str(tmp_path), "t")
    _write_parts(path)
    txt = _scan_agg(session, path).explain(analyze=True)
    assert "Attribution (per-stage cost vectors):" in txt
    assert "<unlabeled>" in txt or "decode" in txt
    assert "[knobs:" in txt
    # Attribution off: the section disappears, the rest of explain survives.
    monkeypatch.setenv(stage_ledger.ENV_STAGE_ATTRIBUTION, "0")
    txt_off = _scan_agg(session, path).explain(analyze=True)
    assert "Attribution (per-stage cost vectors):" not in txt_off
    assert "Resource ledger (this query):" in txt_off


def test_exporter_frame_carries_planner_activity(session, tmp_path, monkeypatch):
    from hyperspace_tpu.telemetry.exporter import MetricsExporter

    monkeypatch.setenv(planner.ENV_PLANNER_DIR, os.path.join(str(tmp_path), "s"))
    src = os.path.join(str(tmp_path), "t")
    _write_parts(src, parts=1, rows=50)
    _scan_agg(session, src).collect()  # at least one planner decision
    ex = MetricsExporter(os.path.join(str(tmp_path), "m.jsonl"), interval_s=60)
    frame = ex._frame()
    assert "planner" in frame
    assert frame["planner"].get("streaming", {}).get("decisions", 0) >= 1


def _load_hsreport():
    path = os.path.join(os.path.dirname(__file__), "..", "tools", "hsreport.py")
    if not os.path.exists(path):
        pytest.skip("tools/hsreport.py not present (installed-wheel run)")
    spec = importlib.util.spec_from_file_location("hsreport", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_hsreport_stage_drift_and_fingerprint_filter(session, tmp_path, monkeypatch):
    hdir = os.path.join(str(tmp_path), "hist")
    monkeypatch.setenv("HYPERSPACE_HISTORY", "1")
    monkeypatch.setenv("HYPERSPACE_HISTORY_DIR", hdir)
    monkeypatch.setenv("HYPERSPACE_ACCOUNTING", "1")
    path = os.path.join(str(tmp_path), "t")
    _write_parts(path)
    for _ in range(3):
        _scan_agg(session, path).collect()
    mod = _load_hsreport()
    report = mod.build_report(hdir, top=10, recent_k=2)
    rows = report.get("stage_drift")
    assert rows, "stage drift table empty despite staged ledgers"
    row = rows[0]
    assert {
        "fingerprint",
        "stage",
        "baseline_n",
        "expected_wall_s",
        "recent_n",
        "actual_wall_s",
        "ratio",
    } <= set(row)
    assert "stage drift" in mod.render(report)

    fp = row["fingerprint"]
    filt = mod.build_report(hdir, top=10, recent_k=2, fingerprint=fp[:8])
    assert filt["fingerprint_filter"] == fp[:8]
    assert all(r["fingerprint"].startswith(fp[:8]) for r in filt["stage_drift"])
    miss = mod.build_report(hdir, top=10, recent_k=2, fingerprint="zzzz-no-such")
    assert not miss.get("stage_drift") and not miss.get("classes")


# ---------------------------------------------------------------------------
# Satellite: multiway star joins render per-dimension stage walls
# ---------------------------------------------------------------------------


def test_star_explain_renders_per_dimension_walls(tmp_path, monkeypatch):
    from hyperspace_tpu import IndexConfig, IndexConstants
    from hyperspace_tpu.engine import physical as phys
    from hyperspace_tpu.hyperspace import Hyperspace, enable_hyperspace

    monkeypatch.setenv("HYPERSPACE_QUERY_STREAMING", "1")
    phys.clear_device_memos()
    s = HyperspaceSession(warehouse=str(tmp_path))
    s.conf.set(IndexConstants.INDEX_SYSTEM_PATH, str(tmp_path / "indexes"))
    s.conf.set(IndexConstants.INDEX_NUM_BUCKETS, 8)
    hs = Hyperspace(s)
    rng = np.random.RandomState(3)
    n = 2000
    s.write_parquet(
        {
            "k1": rng.randint(0, 40, n).astype(np.int64),
            "k2": rng.randint(0, 20, n).astype(np.int64),
            "v": rng.randint(0, 100, n).astype(np.int64),
        },
        str(tmp_path / "fact"),
    )
    for name, card, grp in (("dim1", 40, "g1"), ("dim2", 20, "g2")):
        s.write_parquet(
            {
                f"d{name[-1]}": np.arange(card, dtype=np.int64),
                grp: rng.randint(0, 5, card).astype(np.int64),
            },
            str(tmp_path / name),
        )
        hs.create_index(
            s.read.parquet(str(tmp_path / name)),
            IndexConfig(f"star_{name}", [f"d{name[-1]}"], [grp]),
        )
    enable_hyperspace(s)
    q = (
        s.read.parquet(str(tmp_path / "fact"))
        .join(s.read.parquet(str(tmp_path / "dim1")), col("k1") == col("d1"))
        .join(s.read.parquet(str(tmp_path / "dim2")), col("k2") == col("d2"))
        .group_by("g1")
        .agg(t=("v", "sum"))
    )
    pp = q.physical_plan()
    assert any(isinstance(nd, phys.MultiwayJoinExec) for nd in pp.collect_nodes())
    txt = q.explain(analyze=True)
    assert "join stages:" in txt
    assert "dim[star_dim1]:" in txt or "dim[0]:" in txt
    assert "probe=" in txt
