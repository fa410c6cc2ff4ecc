"""What a TPC-H SF 10 cell asks of the system under test beyond its builds:
the SF 1 adaptor (`queries/tpch_sf1.py`), whose answers depend only on the
configuration."""

import harness

_SF1 = harness.config_module("queries", "tpch_sf1")
source_rows = _SF1.source_rows
drop_host_caches = _SF1.drop_host_caches
