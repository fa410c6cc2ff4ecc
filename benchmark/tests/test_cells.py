"""Each cell driven end to end at a tiny size on the CPU, through the
harness's own functions: the system's output passes the plain reference, and
every control of the comparison fails it."""

import pytest

import control
import harness
import run as bench_run
from conftest import small_cell

CELLS = ["tpch_sf1.build"]
SEED = 2**31 + 99


@pytest.mark.parametrize("name", CELLS)
def test_cell_correct_on_cpu(name, workdir):
    res = bench_run.run_cell(small_cell(name), SEED, 2.0, False, workdir, log=lambda *_: None)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert "setup_s" in res["metrics"] and len(res["metrics"]) == 2


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", sorted(control.FAULTS))
def test_control_fails(name, fault, workdir):
    cell = small_cell(name)
    limits = harness.config_module("reference", cell["config_name"]).LIMITS
    numbers = control.control_numbers(cell, SEED, workdir)[fault]
    assert any(v > limits[k] for k, v in numbers.items()), numbers


def test_refuses_cpu(capsys):
    assert bench_run.main(["--workload", "tpch_sf1.build", "--seed", "1", "--seconds", "1"]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


def test_unknown_device_has_no_peak():
    import readers

    assert readers.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        readers.peak("TPU v9 imaginary", "hbm_bytes_per_s")


def test_metric_readers_found_by_base_name():
    import json
    import os

    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for m in spec["per_layer"]:
        assert callable(harness.metric_reader(m["name"]).read), m["name"]
