"""The benchmark's general machinery, driven by data.

A cell of `BENCHMARK.json` names a configuration and a traffic mix. The
configuration's JSON sits at its `file`; beside it, found by the configuration's
name, are `datagen/<config>.py` (data from the seed), `queries/<config>.py`
(what the system under test is asked) and `reference/<config>.py` (the plain
check). The traffic mix is `traffic/<traffic>.json`, whose `kind` picks one of
the drivers below. A per-layer metric `<base>.<cell kind>` is read by
`metrics/<base>.py`. Adding a cell, a mix or a metric adds files; it edits none.
"""

import importlib.util
import json
import os
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


# -- finding things by name ---------------------------------------------------


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """The cell `workload` with its configuration, traffic and metric entries."""
    spec = _read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {
        "name": workload,
        "chips": w["chips"],
        "config_name": w["config"],
        "config": _read_json(os.path.join(ROOT, conf["file"])),
        "traffic": _read_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json")),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
    }


def config_module(kind: str, config_name: str):
    return load_module(os.path.join(BENCH, kind, config_name + ".py"), f"bench_{kind}_{config_name}")


def metric_reader(name: str):
    """The reader of a per-layer metric: `metrics/<base>.py`, where the base is
    the name before its first `.`."""
    base = name.split(".", 1)[0]
    return load_module(os.path.join(BENCH, "metrics", base + ".py"), "metric_" + base)


# -- data on disk ---------------------------------------------------------------


def write_tables(data: dict, src: str, files: dict) -> None:
    """Each table as `files[table]` snappy parquet files under src/<table>/."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    for table, cols in data.items():
        n = len(next(iter(cols.values())))
        os.makedirs(os.path.join(src, table), exist_ok=True)
        bounds = np.linspace(0, n, files.get(table, 1) + 1).astype(np.int64)
        for i in range(len(bounds) - 1):
            lo, hi = bounds[i], bounds[i + 1]
            t = pa.table({c: pa.array(a[lo:hi]) for c, a in cols.items()})
            pq.write_table(t, os.path.join(src, table, f"part-{i:05d}.parquet"))


# -- the run's context ----------------------------------------------------------


class Run:
    """What one run of a cell builds and records: the data, the session, the
    adaptor to the system under test, and each request's record."""

    def __init__(self, cell: dict, seed: int, workdir: str):
        self.cell = cell
        self.cfg = cell["config"]
        self.traffic = cell["traffic"]
        self.seed = int(seed)
        self.workdir = workdir
        self.src = os.path.join(workdir, "src")
        self.records = []  # one dict per request of the window
        self.queries = config_module("queries", cell["config_name"])
        self.session = None
        self.hs = None
        self.data = None

    def make_data(self) -> None:
        datagen = config_module("datagen", self.cell["config_name"])
        self.data = datagen.generate(self.cfg, self.seed)
        write_tables(self.data, self.src, self.cfg.get("files", {}))

    def make_session(self) -> None:
        from hyperspace_tpu import IndexConstants
        from hyperspace_tpu.engine import HyperspaceSession
        from hyperspace_tpu.hyperspace import Hyperspace, enable_hyperspace

        lake = os.path.join(self.workdir, "lake")
        s = HyperspaceSession(warehouse=lake)
        s.conf.set(IndexConstants.INDEX_SYSTEM_PATH, os.path.join(lake, "indexes"))
        s.conf.set(IndexConstants.INDEX_NUM_BUCKETS, int(self.cfg["num_buckets"]))
        self.session = s
        self.hs = Hyperspace(s)
        enable_hyperspace(s)

    def create_index(self, name: str, as_name: str) -> None:
        from hyperspace_tpu import IndexConfig

        spec = self.cfg["indexes"][name]
        df = self.session.read.parquet(os.path.join(self.src, spec["table"]))
        self.hs.create_index(df, IndexConfig(as_name, spec["indexed"], spec["included"]))


# -- drivers: one per traffic kind ----------------------------------------------


class BuildCycles:
    """Back-to-back index builds: each cycle drops the host scan caches,
    `create_index` builds a fresh copy of the traffic's index over the whole
    source, then `delete_index` and `vacuum_index` remove the previous copy.
    Set-up runs one cycle, which compiles every program a cycle runs. The last
    copy is kept for the check. The rate is the source rows indexed over the
    time from the window's start to the last cycle's end."""

    def __init__(self, run: Run):
        self.run = run
        self.t = run.traffic
        self.n = 0
        self.live = None

    def _cycle(self) -> None:
        run = self.run
        run.queries.drop_host_caches()
        name = f"{self.t['index']}_c{self.n}"
        self.n += 1
        t = time.monotonic()
        run.create_index(self.t["index"], as_name=name)
        if self.live is not None:
            run.hs.delete_index(self.live)
            run.hs.vacuum_index(self.live)
        self.live = name
        run.records.append({"op": "build", "params": name, "t_start": t, "t_done": time.monotonic()})

    def warmup(self, seconds: float) -> None:
        self._cycle()
        self.run.records.clear()

    def window(self, seconds: float) -> dict:
        t0 = time.monotonic()
        while time.monotonic() < t0 + seconds:
            self._cycle()
        return {"t0": t0, "t_end": time.monotonic()}

    def end_to_end(self, win: dict) -> dict:
        rows = self.run.queries.source_rows(self.run, self.t["index"])
        return {"build_rows_per_s": rows * len(self.run.records) / (win["t_end"] - win["t0"])}


DRIVERS = {"build_cycles": BuildCycles}
