"""TPC-H SF 10 tables from a seed: the SF 1 generator (`datagen/tpch_sf1.py`),
which reads every size from the configuration (`configs/tpch_sf10.json`)."""

import harness

generate = harness.config_module("datagen", "tpch_sf1").generate
