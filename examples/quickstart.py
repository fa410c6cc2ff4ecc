"""Hyperspace-TPU quickstart — the worked example from the reference's sample app
(`examples/scala/src/main/scala/App.scala:23-103`): departments/employees data,
index CRUD, a filter query and a join query accelerated by covering indexes, and
`explain` showing what the rewrite changed.

Run:  python examples/quickstart.py          (uses ./quickstart_data, cleaned up)
"""

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

from hyperspace_tpu import IndexConfig, IndexConstants
from hyperspace_tpu.engine import HyperspaceSession, col
from hyperspace_tpu.hyperspace import Hyperspace, disable_hyperspace, enable_hyperspace


def main() -> None:
    base = tempfile.mkdtemp(prefix="hs_quickstart_")
    try:
        session = HyperspaceSession(warehouse=base)
        session.conf.set(IndexConstants.INDEX_SYSTEM_PATH, os.path.join(base, "indexes"))
        session.conf.set(IndexConstants.INDEX_NUM_BUCKETS, 8)

        # -- Sample data (the reference app's departments/employees) ----------
        departments = {
            "deptId": [10, 20, 30, 40],
            "deptName": ["Accounting", "Research", "Sales", "Operations"],
            "location": ["Seattle", "New York", "Chicago", "Boston"],
        }
        employees = {
            "empId": list(range(1, 9)),
            "empName": ["Clark", "Dave", "Ava", "Josh", "Kim", "Raj", "Lee", "Mia"],
            "empDeptId": [10, 20, 20, 30, 30, 30, 40, 10],
        }
        session.write_parquet(departments, os.path.join(base, "departments"))
        session.write_parquet(employees, os.path.join(base, "employees"))

        hs = Hyperspace(session)

        # -- Create covering indexes ------------------------------------------
        dept_df = session.read.parquet(os.path.join(base, "departments"))
        emp_df = session.read.parquet(os.path.join(base, "employees"))
        hs.create_index(dept_df, IndexConfig("deptIndex1", ["deptId"], ["deptName"]))
        hs.create_index(dept_df, IndexConfig("deptIndex2", ["location"], ["deptName"]))
        hs.create_index(emp_df, IndexConfig("empIndex", ["empDeptId"], ["empName"]))

        print("=== indexes ===")
        for row in hs.indexes().rows():
            print(row)

        # -- Filter query (FilterIndexRule) -----------------------------------
        def filter_query():
            return (
                session.read.parquet(os.path.join(base, "departments"))
                .filter(col("location") == "Seattle")
                .select("deptName", "location")
            )

        enable_hyperspace(session)
        print("\n=== filter query (indexed) ===")
        print(filter_query().collect().rows())
        print("\n=== explain ===")
        hs.explain(filter_query(), verbose=True)

        # -- Join query (JoinIndexRule: co-bucketed, shuffle-free) ------------
        def join_query():
            d = session.read.parquet(os.path.join(base, "departments"))
            e = session.read.parquet(os.path.join(base, "employees"))
            return (
                d.join(e, col("deptId") == col("empDeptId"))
                .select("deptName", "empName")
                .order_by("deptName", "empName")
            )

        print("\n=== join query (indexed, no exchange) ===")
        print(join_query().collect().rows())
        hs.explain(join_query())

        # -- Aggregation over the indexed join --------------------------------
        def agg_query():
            d = session.read.parquet(os.path.join(base, "departments"))
            e = session.read.parquet(os.path.join(base, "employees"))
            return (
                d.join(e, col("deptId") == col("empDeptId"))
                .group_by("deptName")
                .agg(headcount=("empName", "count"))
                .order_by(("headcount", False))
            )

        print("\n=== headcount by department ===")
        print(agg_query().collect().rows())

        # Oracle check: identical results with indexing off.
        indexed = join_query().collect().rows()
        disable_hyperspace(session)
        assert join_query().collect().rows() == indexed
        print("\nresults identical with indexing on/off — OK")

        # -- Lifecycle: delete / restore / vacuum -----------------------------
        hs.delete_index("deptIndex2")
        hs.restore_index("deptIndex2")
        hs.delete_index("deptIndex2")
        hs.vacuum_index("deptIndex2")
        print("after vacuum:", [r[0] for r in hs.indexes().rows()])
    finally:
        shutil.rmtree(base, ignore_errors=True)


if __name__ == "__main__":
    main()
