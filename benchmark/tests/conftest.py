import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import pytest  # noqa: E402

# Each configuration at a size a test run holds; shapes as configured.
SMALL = {"tpch_sf1": {"orders": 16000, "part": 2000, "num_buckets": 8}}


def small_cell(name: str) -> dict:
    """The cell of BENCHMARK.json, with its configuration cut to SMALL."""
    import harness

    cell = harness.load_cell(name)
    cell["config"].update(SMALL[cell["config_name"]])
    return cell


@pytest.fixture
def workdir(tmp_path):
    return str(tmp_path / "work")
