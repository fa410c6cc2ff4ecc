"""The readers of the build's span split (`decode_ms`, `sort_ms`, `write_ms`,
`outside_pipeline_ms`): on a span file written by hand, whose answers can be
worked out on paper, and on the spans this program records in a window of
build cycles on the CPU."""

import json
import os
from types import SimpleNamespace

import pytest

import harness
from conftest import small_cell

SPLIT = ("decode_ms", "sort_ms", "write_ms")
SEED = 2**31 + 101


def _ctx(workdir, spans, records):
    os.makedirs(workdir, exist_ok=True)
    with open(os.path.join(workdir, "spans.jsonl"), "w") as f:
        for s in spans:
            f.write(json.dumps(s) + "\n")
    return SimpleNamespace(run=SimpleNamespace(workdir=workdir, records=records))


def _span(qid, sid, parent, name, start, dur, **attrs):
    return {"query_id": qid, "span_id": sid, "parent_id": parent, "name": name,
            "start_s": start, "duration_s": dur, "status": "ok", "attrs": attrs}


def _cycle(qid, t0, decode, sort, write, outside):
    """One cycle's root, pipeline and sort spans: the pipeline starts 5 ms
    into the cycle and the sort `decode` seconds after it."""
    start = t0 + 0.005
    return [_span(qid, 0, None, "build:create_index", t0, decode + sort + write + outside),
            _span(qid, 1, 0, "build:stages", start, decode + sort + write),
            _span(qid, 2, 1, "build:decode", start, decode, file=0),
            _span(qid, 3, 1, "build:sort", start + decode, sort, rows=10)]


def _read(name, ctx):
    return harness.metric_reader(name + ".build").read(ctx)


@pytest.mark.parametrize("n", [3, 4])
def test_hand_written_spans(workdir, n):
    # (decode, sort, write, outside) seconds of each cycle; pipeline walls
    # 0.740, 0.750, 0.745 (and 0.760): the median build is the third, whose
    # parts are not each part's median (300, 45, 400, 25 ms)
    cycles = [(0.300, 0.040, 0.400, 0.020), (0.320, 0.050, 0.380, 0.030), (0.280, 0.045, 0.420, 0.025),
              (0.330, 0.050, 0.380, 0.030)][:n]
    spans, records = [], []
    for i, (d, s, w, o) in enumerate(cycles):
        t0 = 1000.0 + i  # wall clock of the spans; the records are on another clock
        spans += _cycle(f"q{i}", t0, d, s, w, o)
        records.append({"op": "build", "t_start": 50.0 + i, "t_done": 50.0 + i + d + s + w + o})
    ctx = _ctx(workdir, spans, records)
    got = {name: _read(name, ctx) for name in SPLIT + ("outside_pipeline_ms",)}
    assert got == pytest.approx({"decode_ms": 280.0, "sort_ms": 45.0, "write_ms": 420.0, "outside_pipeline_ms": 25.0})
    assert sum(got[name] for name in SPLIT) == pytest.approx(745.0)  # the median pipeline wall


@pytest.mark.parametrize("case", ["no_file", "no_stages", "synthesized", "records_differ"])
def test_nothing_to_read_gives_none(workdir, case):
    spans = _cycle("q0", 1000.0, 0.3, 0.04, 0.4, 0.02)
    records = [{"op": "build", "t_start": 5.0, "t_done": 5.76}]
    if case == "no_stages":
        spans = [s for s in spans if s["name"] != "build:stages"]
    elif case == "synthesized":
        spans[1]["attrs"]["synthesized"] = True
    elif case == "records_differ":
        records = records * 2
    ctx = _ctx(workdir, spans, records)
    if case == "no_file":
        os.remove(os.path.join(workdir, "spans.jsonl"))
    assert _read("outside_pipeline_ms", ctx) is None
    if case != "records_differ":
        assert all(_read(name, ctx) is None for name in SPLIT)


def test_spans_of_build_cycles_on_cpu(workdir):
    """Build cycles of the cell at a tiny size, with the program's spans
    written where run.py has them written: every reader finds its spans, and
    per build decode + sort + write is the pipeline's wall."""
    run = harness.Run(small_cell("tpch_sf1.build"), SEED, workdir)
    os.makedirs(workdir)
    run.make_data()
    run.make_session()
    driver = harness.DRIVERS[run.traffic["kind"]](run)
    driver.warmup(1.0)
    os.environ["HYPERSPACE_TRACE_FILE"] = os.path.join(workdir, "spans.jsonl")
    try:
        driver.window(1.0)
    finally:
        os.environ.pop("HYPERSPACE_TRACE_FILE")
    ctx = SimpleNamespace(run=run)

    import build_spans

    pairs = build_spans.builds(ctx)
    assert len(pairs) == len(run.records) >= 1
    for stages, sort in pairs:
        parts = [sort["start_s"] - stages["start_s"], sort["duration_s"], build_spans.end_s(stages) - build_spans.end_s(sort)]
        assert min(parts) >= 0
        assert sum(parts) == pytest.approx(stages["duration_s"], abs=1e-5)
    values = {name: _read(name, ctx) for name in SPLIT + ("outside_pipeline_ms",)}
    assert all(v is not None and v >= 0 for v in values.values()), values
    walls = sorted(1e3 * stages["duration_s"] for stages, _ in pairs)
    assert sum(values[n] for n in SPLIT) == pytest.approx(walls[(len(walls) - 1) // 2], abs=1e-2)
    cycles = [1e3 * (r["t_done"] - r["t_start"]) for r in run.records]
    assert min(cycles) <= sum(values.values()) <= max(cycles) + 1e-2
