"""Hyperspace-TPU worked walkthrough — the full index lifecycle on one dataset.

The long-form companion to `quickstart.py`, mirroring the reference's worked
example app + Hitchhiker's-Guide notebooks (`examples/scala/.../App.scala:23-103`,
`notebooks/python/`): every step prints what changed on the lake and in the plan,
and asserts the invariant it demonstrates, so it doubles as a CI smoke test.

  1.  Create dept/emp parquet sources.
  2.  Build a covering index on each side.
  3.  EXPLAIN: the join rewrite (shuffle-free bucketed join) with a plan diff.
  4.  Enable/disable round-trip: identical results either way.
  5.  Append source files -> index goes stale; Hybrid Scan unions the appended
      rows into the bucketed join on the fly.
  6.  refresh_index(mode="incremental"): only the appended rows are indexed.
  7.  optimizeIndex: compact the accumulated small files.
  8.  Delete -> restore -> vacuum lifecycle with the operation log on display.

Run:  python examples/walkthrough.py
"""

import glob
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Runs on whatever device JAX finds (JAX_PLATFORMS=cpu picks the CPU).
from hyperspace_tpu.util.compile_cache import enable_compile_cache

enable_compile_cache(
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")
)

import numpy as np

from hyperspace_tpu import IndexConfig, IndexConstants
from hyperspace_tpu.engine import HyperspaceSession, col
from hyperspace_tpu.hyperspace import Hyperspace, disable_hyperspace, enable_hyperspace


def banner(step: str) -> None:
    print(f"\n=== {step} " + "=" * max(0, 70 - len(step)))


def log_states(system_path: str, name: str):
    entries = []
    for p in glob.glob(os.path.join(system_path, name, "_hyperspace_log", "*")):
        if os.path.basename(p).isdigit():
            with open(p) as f:
                entries.append((int(os.path.basename(p)), json.load(f).get("state")))
    return sorted(entries)


def main() -> None:
    base = tempfile.mkdtemp(prefix="hs_walkthrough_")
    sysdir = os.path.join(base, "indexes")
    try:
        s = HyperspaceSession(warehouse=base)
        s.conf.set(IndexConstants.INDEX_SYSTEM_PATH, sysdir)
        s.conf.set(IndexConstants.INDEX_NUM_BUCKETS, 8)
        s.conf.set(IndexConstants.INDEX_HYBRID_SCAN_ENABLED, True)
        hs = Hyperspace(s)

        banner("1. Source data: departments + employees")
        n = 2000
        rng = np.random.RandomState(0)
        s.write_parquet(
            {
                "deptId": np.arange(50, dtype=np.int64),
                "deptName": np.array([f"dept-{i:02d}" for i in range(50)]),
                "location": np.array(["NYC", "SEA", "SF", "ATX", "CHI"] * 10),
            },
            os.path.join(base, "departments"),
        )
        s.write_parquet(
            {
                "empId": np.arange(n, dtype=np.int64),
                "empDept": rng.randint(0, 50, n).astype(np.int64),
                "salary": (rng.rand(n) * 100000).round(2),
            },
            os.path.join(base, "employees"),
        )
        print(f"wrote {n} employees / 50 departments under {base}")

        def emp():
            return s.read.parquet(os.path.join(base, "employees"))

        def dept():
            return s.read.parquet(os.path.join(base, "departments"))

        def join_query():
            return (
                emp()
                .join(dept(), col("empDept") == col("deptId"))
                .select("empId", "salary", "deptName")
            )

        banner("2. Create covering indexes (bucketed by the join key)")
        hs.create_index(emp(), IndexConfig("empIdx", ["empDept"], ["empId", "salary"]))
        hs.create_index(dept(), IndexConfig("deptIdx", ["deptId"], ["deptName"]))
        for row in hs.indexes().rows():
            print("  ", row)
        print("log:", log_states(sysdir, "empIdx"))
        assert log_states(sysdir, "empIdx")[-1][1] == "ACTIVE"

        banner("3. EXPLAIN: the rewrite eliminates the shuffle")
        enable_hyperspace(s)
        captured = []
        hs.explain(join_query(), verbose=True, redirect=captured.append)
        explained = captured[0]
        print(explained)
        assert "empIdx" in explained and "deptIdx" in explained

        banner("4. Enable/disable round-trip: identical results")
        on_rows = join_query().sorted_rows()
        disable_hyperspace(s)
        off_rows = join_query().sorted_rows()
        assert on_rows == off_rows and len(on_rows) == n
        print(f"identical {len(on_rows)} rows with indexing on vs off")
        enable_hyperspace(s)

        banner("5. Append source data -> Hybrid Scan")
        from hyperspace_tpu.engine import io as eio
        from hyperspace_tpu.engine.table import Table

        eio.write_parquet(
            Table.from_pydict(
                {
                    "empId": np.arange(n, n + 100, dtype=np.int64),
                    "empDept": rng.randint(0, 50, 100).astype(np.int64),
                    "salary": (rng.rand(100) * 100000).round(2),
                }
            ),
            os.path.join(base, "employees", "part-00001.parquet"),
        )
        plan = join_query().explain_string()
        print(plan)
        assert "empIdx" in plan, "hybrid scan keeps using the index"
        assert join_query().count() == n + 100
        print(f"appended 100 rows; indexed join sees all {n + 100} without a rebuild")

        def latest_version_files() -> list:
            vdirs = glob.glob(os.path.join(sysdir, "empIdx", "v__=*"))
            latest = max(vdirs, key=lambda p: int(p.rsplit("=", 1)[1]))
            return glob.glob(os.path.join(latest, "part-*"))

        banner('6. refresh_index(mode="incremental")')
        hs.refresh_index("empIdx", mode="incremental")
        print("log:", log_states(sysdir, "empIdx"))
        print(f"{len(latest_version_files())} data files in the latest version")
        assert join_query().count() == n + 100

        banner("7. optimizeIndex: compact small files")
        before = len(latest_version_files())
        hs.optimize_index("empIdx")
        after = len(latest_version_files())
        print(f"{before} files -> {after} after compaction")
        assert after <= before
        assert join_query().count() == n + 100
        assert "empIdx" in join_query().explain_string()

        banner("8. Lifecycle: delete -> restore -> delete -> vacuum")
        hs.delete_index("empIdx")
        assert "empIdx" not in join_query().explain_string()
        hs.restore_index("empIdx")
        assert "empIdx" in join_query().explain_string()
        hs.delete_index("empIdx")
        hs.vacuum_index("empIdx")
        print("log:", log_states(sysdir, "empIdx"))
        remaining = glob.glob(os.path.join(sysdir, "empIdx", "v__=*", "part-*"))
        assert not remaining, "vacuum removed the data files"
        print("vacuumed: data files gone, tombstone log remains")

        print("\nWALKTHROUGH_OK")
    finally:
        shutil.rmtree(base, ignore_errors=True)


if __name__ == "__main__":
    main()
