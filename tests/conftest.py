"""Test harness: force JAX onto a virtual 8-device CPU platform.

Mirrors the reference's test strategy of running everything on Spark local[4] in-process
(`SparkInvolvedSuite.scala:30-46`): no real cluster/TPU needed; sharding and collectives
are exercised on a virtual 8-device CPU mesh. The chip is reached only through
`chip_smoke.py`; tests/test_tpu_compile.py compiles the kernels for a described
v5e without one.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from hyperspace_tpu.parallel.mesh import force_virtual_cpu  # noqa: E402

force_virtual_cpu(8)

import pytest  # noqa: E402


@pytest.fixture()
def tmp_system_path(tmp_path):
    """Per-test index system path (the reference's per-suite systemPath fixture,
    `HyperspaceSuite.scala:25-89`)."""
    p = tmp_path / "indexes"
    return str(p)
