"""The readers of the mesh build's metrics (`exchange_ms`, `exchange_padding`,
`mesh_sort_roofline`) on span files, counters and a four-chip trace written by
hand, whose answers can be worked out on paper."""

import json
import os
from types import SimpleNamespace

import pytest

import harness
import trace_reduce as tr

MS = 1e6  # ns


def _span(qid, sid, parent, name, start, dur, **attrs):
    return {"query_id": qid, "span_id": sid, "parent_id": parent, "name": name,
            "start_s": start, "duration_s": dur, "status": "ok", "attrs": attrs}


def _mesh_build(qid, t0, pipeline, counts, exchange):
    """One build's stages, sort and exchange spans: the pipeline `pipeline`
    seconds long, its sort from 0.1 s in to 0.1 s before its end, the count
    pass and the exchange inside the sort."""
    return [_span(qid, 0, None, "build:create_index", t0, pipeline + 0.05),
            _span(qid, 1, 0, "build:stages", t0, pipeline),
            _span(qid, 2, 1, "build:decode", t0, 0.1),
            _span(qid, 3, 1, "build:sort", t0 + 0.1, pipeline - 0.2, rows=10),
            _span(qid, 4, 1, "build:exchange_counts", t0 + 0.2, counts, cap=1024, max_count=900),
            _span(qid, 5, 1, "build:exchange", t0 + 0.2 + counts, exchange, devices=4)]


def _ctx(workdir, spans, records=None, counters=None, trace=None):
    os.makedirs(workdir, exist_ok=True)
    with open(os.path.join(workdir, "spans.jsonl"), "w") as f:
        for s in spans:
            f.write(json.dumps(s) + "\n")
    cfg = {"indexes": {"li": {"table": "t", "indexed": ["k"], "included": ["a", "b", "c"]}}}
    run = SimpleNamespace(workdir=workdir, records=records or [], cfg=cfg, traffic={"index": "li"},
                          cell={"chips": 4}, queries=SimpleNamespace(source_rows=lambda run, index: 1_000_000))
    return SimpleNamespace(run=run, counters=counters or {}, trace=trace, device_kind="TPU v5 lite")


def _read(name, ctx):
    return harness.metric_reader(name).read(ctx)


def test_exchange_ms_of_the_median_build(workdir):
    # pipeline walls 2.0, 2.2, 2.1: the median build is the third, whose
    # count pass and exchange take 30 + 400 ms
    spans = (_mesh_build("q0", 100.0, 2.0, 0.020, 0.300) + _mesh_build("q1", 103.0, 2.2, 0.050, 0.500)
             + _mesh_build("q2", 106.0, 2.1, 0.030, 0.400))
    records = [{"op": "build", "t_start": i, "t_done": i + 2.5} for i in range(3)]
    assert _read("exchange_ms", _ctx(workdir, spans, records)) == pytest.approx(430.0)


def test_exchange_ms_none_without_exchange(workdir):
    spans = [s for s in _mesh_build("q0", 100.0, 2.0, 0.02, 0.3) if "exchange" not in s["name"]]
    assert _read("exchange_ms", _ctx(workdir, spans, [{"op": "build", "t_start": 0, "t_done": 2.5}])) is None
    os.remove(os.path.join(workdir, "spans.jsonl"))
    assert _read("exchange_ms", _ctx(workdir, [], [])) is None


@pytest.mark.parametrize("counters, want", [
    ({"parallel.exchange.bytes_moved": 4000, "parallel.exchange.bytes_payload": 1000}, 75.0),
    ({"parallel.exchange.bytes_moved": 4000, "parallel.exchange.bytes_payload": 4000}, 0.0),
    ({"parallel.exchange.bytes_moved": 0, "parallel.exchange.bytes_payload": 0}, None),
    ({}, None),
])
def test_exchange_padding(workdir, counters, want):
    got = _read("exchange_padding", _ctx(workdir, [], counters=counters))
    assert got == (None if want is None else pytest.approx(want))


def _four_chip_trace():
    """Window 0..100 ms on 4 chips. Each chip runs the count pass (20-22 ms),
    the exchange program (30-50 ms) and a hash program (10-15 ms); chip 3's
    exchange is renamed, and the hash program's middle lies outside both
    spans."""
    planes = {}
    for d in range(4):
        exchange = "jit_renamed(9)" if d == 3 else "jit_shmap_fn(7)"
        planes[f"/device:TPU:{d}"] = {tr.MODULE_LINE: [
            [tr.START_MARK + "(1)", -1 * MS, 1 * MS], ["jit_hash(3)", 10 * MS, 5 * MS],
            ["jit_count_fn(5)", 20 * MS, 2 * MS], [exchange, 30 * MS, 20 * MS],
            [tr.END_MARK + "(2)", 100 * MS, 1 * MS]]}
    return {"device": planes, "host": {}}


def test_mesh_sort_roofline_counts_programs_by_span(workdir):
    # spans on the wall clock; t_mark 1000.0 is the trace's 0
    spans = [{"name": "build:exchange_counts", "start_s": 1000.019, "duration_s": 0.004},
             {"name": "build:exchange", "start_s": 1000.025, "duration_s": 0.030}]
    red = tr.reduce(_four_chip_trace(), 4, spans=spans, t_mark=1000.0)
    ctx = _ctx(workdir, [], records=[{"op": "build"}] * 2, trace=red)
    # per chip 2 + 20 ms of device time; 2 builds × 2 × 1 M rows × 32 bytes
    # over 4 chips at 819 GB/s
    want = 100.0 * (2 * 2 * 1_000_000 * 32 / 4) / 819e9 / 0.022
    assert _read("mesh_sort_roofline", ctx) == pytest.approx(want)


def test_mesh_sort_roofline_none_without_exchange(workdir):
    red = tr.reduce(_four_chip_trace(), 4, spans=[], t_mark=1000.0)
    assert _read("mesh_sort_roofline", _ctx(workdir, [], records=[{"op": "build"}], trace=red)) is None
