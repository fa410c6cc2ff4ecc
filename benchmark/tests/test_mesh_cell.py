"""The four-chip cell `tpch_sf10.build4` driven end to end at a tiny size on
a virtual CPU mesh of 4 devices, through the harness's own functions: the
build takes the mesh route, its output passes the plain reference, and every
control of the comparison fails it. The SF 10 reference's shortcut gives the
SF 1 check's numbers."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import control
import harness

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "tpch_sf10.build4"
# Lineitem above the mesh's row threshold (hyperspace.distributed.minRows,
# 65,536): about 128,000 rows in the configuration's 32 files.
TINY = {"orders": 32000, "part": 2000}
SEED = 2**31 + 113

_DRIVE = """
import json, sys
from hyperspace_tpu.parallel.mesh import force_virtual_cpu
force_virtual_cpu(4)
sys.path.insert(0, {bench!r})
import harness, run
from hyperspace_tpu.telemetry import metrics, profiling
cell = harness.load_cell({cell!r})
cell["config"].update({tiny!r})
before = metrics.snapshot()["counters"]
res = run.run_cell(cell, {seed!r}, 2.0, False, {workdir!r}, log=lambda *_: None)
after = metrics.snapshot()["counters"]
delta = {{k: after[k] - before.get(k, 0) for k in after if k.startswith("parallel.exchange.")}}
print(json.dumps({{"result": res, "exchange": delta, "mode": profiling.last_build_stages()["mode"]}}))
"""


def tiny_cell() -> dict:
    cell = harness.load_cell(CELL)
    cell["config"].update(TINY)
    return cell


def test_cell_takes_mesh_route_and_is_correct(workdir):
    root = os.path.dirname(harness.BENCH)
    script = _DRIVE.format(bench=harness.BENCH, cell=CELL, tiny=TINY, seed=SEED, workdir=workdir)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
    proc = subprocess.run([sys.executable, "-c", script], cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    res = out["result"]
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"build_rows_per_s", "setup_s"}
    assert out["mode"] == "mesh"
    # One exchange per build: the warm-up's and the window's.
    assert out["exchange"]["parallel.exchange.count"] == res["attempted"] + 1
    assert out["exchange"]["parallel.exchange.pad_rows"] > 0
    assert out["exchange"]["parallel.exchange.bytes_moved"] > out["exchange"]["parallel.exchange.bytes_payload"] > 0


@pytest.mark.parametrize("fault", sorted(control.FAULTS))
def test_control_fails(fault, workdir):
    cell = tiny_cell()
    limits = harness.config_module("reference", cell["config_name"]).LIMITS
    numbers = control.control_numbers(cell, SEED, workdir)[fault]
    assert any(v > limits[k] for k, v in numbers.items()), numbers


def _write_index(data, spec, nb, out_dir, order):
    import pyarrow as pa
    import pyarrow.parquet as pq

    ref = harness.config_module("reference", "tpch_sf1")
    cols = [spec["indexed"][0]] + list(spec["included"])
    src = data[spec["table"]]
    bucket = ref.bucket_of(src[cols[0]][order], nb)
    os.makedirs(out_dir)
    for b in np.unique(bucket):
        rows = order[bucket == b]
        pq.write_table(pa.table({c: src[c][rows] for c in cols}), os.path.join(out_dir, f"part-{b:05d}.parquet"))


@pytest.mark.parametrize("ties", ["source_order", "reversed", "one_row_lost"])
def test_shortcut_gives_the_sf1_numbers(ties, tmp_path):
    """A sound index in the build's order passes the shortcut; one whose equal
    keys lie in another order is still sound, counted by the SF 1 check; a
    lost row is counted there too."""
    cell = tiny_cell()
    cfg = cell["config"]
    data = harness.config_module("datagen", "tpch_sf10").generate(cfg, SEED)
    spec = cfg["indexes"]["li_orderkey"]
    key = data[spec["table"]][spec["indexed"][0]]
    n = len(key)
    if ties == "reversed":  # by key, equal keys last row first
        order = (n - 1 - np.argsort(key[::-1], kind="stable"))
    else:
        order = np.argsort(key, kind="stable")
    if ties == "one_row_lost":
        order = order[1:]
    index_dir = str(tmp_path / "index")
    _write_index(data, spec, int(cfg["num_buckets"]), index_dir, order)
    sf10 = harness.config_module("reference", "tpch_sf10")
    sf1 = harness.config_module("reference", "tpch_sf1")
    args = (data, spec, int(cfg["num_buckets"]), index_dir)
    assert sf10._sound(*args) == (ties == "source_order")
    assert sf10.check_index(*args) == sf1.check_index(*args)
    assert (sf10.check_index(*args)["index_rows_mismatched"] > 0) == (ties == "one_row_lost")
