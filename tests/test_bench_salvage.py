"""The bench's metric record: it degrades to the best measurement a partial
run has, and never reports a fabricated 0.0."""

import importlib.util
import os
import sys

import pytest

_BENCH_PATH = os.path.join(os.path.dirname(__file__), "..", "bench.py")


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location("bench_under_test", _BENCH_PATH)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench_under_test"] = mod
    spec.loader.exec_module(mod)
    return mod


def test_metric_full(bench):
    m = bench._metric_from(
        {"rows": 8, "build_s": 5.0, "indexed_join_p50_s": 0.2, "scan_join_p50_s": 4.0}
    )
    assert m["metric"].startswith("tpch(8) index-build+join-p50")
    assert "(partial)" not in m["metric"]
    assert m["value"] == 5.2
    assert m["vs_baseline"] == 20.0


def test_metric_build_only(bench):
    # A failed indexed-join phase leaves build-only partials: report them,
    # not 0.0.
    m = bench._metric_from({"rows": 8, "build_s": 5.0, "aborted_at": "x"})
    assert m["metric"] == "tpch(8) index-build (partial)"
    assert m["value"] == 5.0
    assert m["vs_baseline"] is None


def test_metric_degrades_to_indexed_then_scan(bench):
    m = bench._metric_from({"rows": 8, "indexed_join_p50_s": 0.2, "aborted_at": "x"})
    assert m["metric"] == "tpch(8) indexed-join-p50 (partial)"
    assert m["value"] == 0.2
    # Scan-only: value must be the scan number, never a fabricated 0.0.
    m = bench._metric_from({"rows": 8, "scan_join_p50_s": 6.7, "aborted_at": "x"})
    assert m["metric"] == "tpch(8) scan-join-p50 (partial)"
    assert m["value"] == 6.7
    assert m["vs_baseline"] is None


def test_metric_partial_marker_from_skips(bench):
    m = bench._metric_from(
        {"rows": 8, "build_s": 1.0, "indexed_join_p50_s": 0.1, "skipped_phases": ["x"]}
    )
    assert "(partial)" in m["metric"]


def test_bench_refuses_a_run_without_a_tpu(bench, monkeypatch, capsys):
    monkeypatch.delenv("BENCH_FORCE_CPU", raising=False)
    monkeypatch.delenv("BENCH_CHILD", raising=False)
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert "no TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""


def test_peak_table_is_keyed_by_device_kind(bench):
    assert bench._peak_bw("TPU v5 lite") == 819e9
    with pytest.raises(KeyError):
        bench._peak_bw("cpu")


def test_device_label_names_platform_kind_and_count(bench):
    label = bench._device_label()
    assert set(label) == {"platform", "kind", "count"}
    assert label["platform"] == "cpu" and label["count"] >= 1
