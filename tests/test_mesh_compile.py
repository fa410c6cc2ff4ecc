"""Mesh-path compile boundedness + the HYPERSPACE_DISTRIBUTED fallback contract.

The r05 TPU bench died inside a 2400 s compile because device-program shapes
tracked exact table sizes. The rebuilt mesh path quantizes every shape that
reaches a device program (hash row dims, exchange shard rows + capacity, probe
block widths — all pow2, floored at `mesh_row_quantum`), so each labeled
program compiles EXACTLY ONCE per workload class no matter how many builds and
queries run. These tests pin that with the compile observatory, pin
`HYPERSPACE_DISTRIBUTED=0` as a byte-identical fallback (index file bytes AND
query rows), and pin the persistent XLA compilation cache knob end to end.
"""

import hashlib
import os

import numpy as np
import pytest

from hyperspace_tpu import IndexConfig, IndexConstants
from hyperspace_tpu.engine import HyperspaceSession, col
from hyperspace_tpu.hyperspace import Hyperspace, disable_hyperspace, enable_hyperspace
from hyperspace_tpu.telemetry import compile_log, metrics

# Row counts chosen to share ONE pow2 workload class on the 8-device mesh
# (shards of 375..625 rows all quantize to the 1024-row quantum; per-cell
# exchange counts stay far below the 1024 capacity floor).
ROW_COUNTS = (3000, 4000, 5000)
NUM_BUCKETS = 24  # distinct from every other suite: fresh program shapes

MESH_LABELS = ("parallel.exchange_counts", "parallel.exchange", "parallel.probe")


def _session(tmp_path, num_buckets=NUM_BUCKETS):
    s = HyperspaceSession(warehouse=str(tmp_path))
    s.conf.set(IndexConstants.INDEX_SYSTEM_PATH, str(tmp_path / "indexes"))
    s.conf.set(IndexConstants.INDEX_NUM_BUCKETS, num_buckets)
    s.conf.set(IndexConstants.DISTRIBUTED_MIN_ROWS, 0)
    return s


def _write_pair(s, base, n, seed=3, suffix="", key_range=40):
    # key_range trades duplicate-key density (ties — the byte-identity
    # oracles want them) against bucket balance (the compile-boundedness test
    # wants every device block inside ONE pow2 class, so it spreads keys).
    rng = np.random.RandomState(seed)
    s.write_parquet(
        {
            "k": rng.randint(0, key_range, n).astype(np.int64),
            "name": np.array([f"d{i % 40}" for i in range(n)]),
        },
        os.path.join(base, f"dept{suffix}"),
    )
    s.write_parquet(
        {
            "ek": rng.randint(0, key_range, n // 4).astype(np.int64),
            "eid": np.arange(n // 4, dtype=np.int64),
        },
        os.path.join(base, f"emp{suffix}"),
    )


def _dir_hashes(root):
    return {
        f: hashlib.sha256(open(os.path.join(root, f), "rb").read()).hexdigest()
        for f in sorted(os.listdir(root))
        if f.startswith("part-")
    }


def test_mesh_programs_compile_exactly_once_across_row_counts(tmp_path, monkeypatch):
    """Builds + indexed joins at several row counts share ONE compiled program
    per mesh label: the compile observatory sees exactly one backend compile
    for each `parallel.*` program across the whole workload."""
    monkeypatch.setenv("HYPERSPACE_DISTRIBUTED", "1")
    s = _session(tmp_path)
    base = str(tmp_path)
    hs = Hyperspace(s)
    def counts():
        return {
            lbl: compile_log.program_summary().get(lbl, {}).get("compiles", 0)
            for lbl in MESH_LABELS
        }

    before = counts()
    after_first = None
    for i, n in enumerate(ROW_COUNTS):
        _write_pair(s, base, n, seed=3 + i, suffix=str(i), key_range=1000)
        d = s.read.parquet(os.path.join(base, f"dept{i}"))
        e = s.read.parquet(os.path.join(base, f"emp{i}"))
        hs.create_index(d, IndexConfig(f"dIdx{i}", ["k"], ["name"]))
        hs.create_index(e, IndexConfig(f"eIdx{i}", ["ek"], ["eid"]))
        enable_hyperspace(s)
        q = d.join(e, col("k") == col("ek")).select("name", "eid")
        assert len(q.sorted_rows()) > 0
        q.count()  # repeat query: must not add a single compile
        if after_first is None:
            after_first = counts()
    after = counts()
    for lbl in MESH_LABELS:
        assert after[lbl] >= 1, f"{lbl} never compiled (mesh path not taken?)"
        # At most ONE compile for the whole workload — zero when an earlier
        # suite in the same process already compiled this quantized shape
        # class (cross-workload program reuse is the point of the grid).
        delta = after[lbl] - before[lbl]
        assert delta <= 1, (
            f"{lbl} compiled {delta} times across row counts {ROW_COUNTS} "
            f"(quantization broken): {compile_log.program_summary().get(lbl)}"
        )
        # And EVERYTHING after the first build+query pair is compile-free.
        assert after[lbl] == after_first[lbl], (
            f"{lbl} recompiled on a later row count: "
            f"{after_first[lbl]} -> {after[lbl]}"
        )


def test_exchange_traffic_counters_tick(tmp_path, monkeypatch):
    monkeypatch.setenv("HYPERSPACE_DISTRIBUTED", "1")
    s = _session(tmp_path)
    base = str(tmp_path)
    _write_pair(s, base, 2000)
    snap0 = metrics.snapshot()["counters"]
    hs = Hyperspace(s)
    hs.create_index(
        s.read.parquet(os.path.join(base, "dept")), IndexConfig("tIdx", ["k"], ["name"])
    )
    snap = metrics.snapshot()["counters"]
    rows = snap.get("parallel.exchange.rows", 0) - snap0.get("parallel.exchange.rows", 0)
    moved = snap.get("parallel.exchange.bytes_moved", 0) - snap0.get(
        "parallel.exchange.bytes_moved", 0
    )
    payload = snap.get("parallel.exchange.bytes_payload", 0) - snap0.get(
        "parallel.exchange.bytes_payload", 0
    )
    assert rows == 2000
    assert payload > 0
    # The padded all_to_all matrix always carries at least the payload bytes.
    assert moved >= payload


class TestDistributedFlagContract:
    """HYPERSPACE_DISTRIBUTED=0 is the exact single-device fallback, in the
    standing PR-1/PR-2 env-flag oracle style."""

    def test_flag_off_disables_mesh(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_DISTRIBUTED", "1")
        s = _session(tmp_path)
        assert s.mesh_for(10) is not None
        monkeypatch.setenv("HYPERSPACE_DISTRIBUTED", "0")
        assert s.mesh_for(10) is None
        monkeypatch.setenv("HYPERSPACE_DISTRIBUTED", "1")
        assert s.mesh_for(10) is not None

    @pytest.mark.parametrize(
        "writers, lineage", [("1", False), ("3", False), ("8", False), ("3", True)]
    )
    def test_build_outputs_byte_identical(self, tmp_path, monkeypatch, writers, lineage):
        """The mesh build's files are the single-device build's bytes whatever
        the number of the range writer's gather ranges, and with lineage on
        (`_data_file_name`, a string column, per source file)."""
        from hyperspace_tpu.telemetry.profiling import last_build_stages

        monkeypatch.setenv("HYPERSPACE_DISTRIBUTED", "1")
        monkeypatch.setenv("HYPERSPACE_BUILD_WRITERS", writers)
        s = _session(tmp_path)
        s.conf.set(IndexConstants.INDEX_LINEAGE_ENABLED, lineage)
        base = str(tmp_path)
        _write_pair(s, base, 3000)  # duplicate keys: ties exercise the order
        if lineage:  # a second source file: two lineage values
            _write_pair(s, base, 1000, seed=4, suffix="2")
            os.replace(
                os.path.join(base, "dept2", "part-00000.parquet"),
                os.path.join(base, "dept", "part-00001.parquet"),
            )
        hs = Hyperspace(s)
        df = s.read.parquet(os.path.join(base, "dept"))
        hs.create_index(df, IndexConfig("meshIdx", ["k"], ["name"]))
        mesh = last_build_stages()
        assert mesh["mode"] == "mesh"
        assert 1 <= mesh["stage_counts"]["take"] <= int(writers)
        monkeypatch.setenv("HYPERSPACE_DISTRIBUTED", "0")
        hs.create_index(df, IndexConfig("localIdx", ["k"], ["name"]))
        hm = _dir_hashes(os.path.join(base, "indexes", "meshIdx", "v__=0"))
        hl = _dir_hashes(os.path.join(base, "indexes", "localIdx", "v__=0"))
        assert list(hm) == list(hl)
        assert hm == {f.replace("local", "mesh"): h for f, h in hl.items()}
        if lineage:
            import pyarrow.parquet as pq

            part = os.path.join(base, "indexes", "meshIdx", "v__=0", next(iter(hm)))
            names = pq.read_schema(part).names
            assert IndexConstants.DATA_FILE_NAME_COLUMN in names

    @pytest.mark.parametrize("n", [3001, 5])
    def test_uneven_shard_build_byte_identical(self, tmp_path, monkeypatch, n):
        """Row counts the mesh does not divide, and fewer rows than devices:
        the real rows spread unevenly by at most one a shard, and the files
        are still the single-device build's bytes."""
        monkeypatch.setenv("HYPERSPACE_DISTRIBUTED", "1")
        s = _session(tmp_path)
        base = str(tmp_path)
        _write_pair(s, base, n)
        hs = Hyperspace(s)
        df = s.read.parquet(os.path.join(base, "dept"))
        snap0 = metrics.snapshot()["counters"]
        hs.create_index(df, IndexConfig("meshOdd", ["k"], ["name"]))
        snap = metrics.snapshot()["counters"]
        assert snap["parallel.exchange.rows"] - snap0.get("parallel.exchange.rows", 0) == n
        monkeypatch.setenv("HYPERSPACE_DISTRIBUTED", "0")
        hs.create_index(df, IndexConfig("localOdd", ["k"], ["name"]))
        hm = _dir_hashes(os.path.join(base, "indexes", "meshOdd", "v__=0"))
        hl = _dir_hashes(os.path.join(base, "indexes", "localOdd", "v__=0"))
        assert hm and list(hm) == list(hl)
        assert hm == {f.replace("local", "mesh"): h for f, h in hl.items()}

    def test_string_key_build_byte_identical(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_DISTRIBUTED", "1")
        s = _session(tmp_path)
        base = str(tmp_path)
        _write_pair(s, base, 2500)
        hs = Hyperspace(s)
        df = s.read.parquet(os.path.join(base, "dept"))
        hs.create_index(df, IndexConfig("meshStr", ["name"], ["k"]))
        monkeypatch.setenv("HYPERSPACE_DISTRIBUTED", "0")
        hs.create_index(df, IndexConfig("localStr", ["name"], ["k"]))
        hm = _dir_hashes(os.path.join(base, "indexes", "meshStr", "v__=0"))
        hl = _dir_hashes(os.path.join(base, "indexes", "localStr", "v__=0"))
        assert list(hm) == list(hl) and set(hm.values()) == set(hl.values())

    def test_query_results_identical_on_and_off(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_DISTRIBUTED", "1")
        s = _session(tmp_path)
        base = str(tmp_path)
        _write_pair(s, base, 3000)
        hs = Hyperspace(s)
        hs.create_index(
            s.read.parquet(os.path.join(base, "dept")),
            IndexConfig("qd", ["k"], ["name"]),
        )
        hs.create_index(
            s.read.parquet(os.path.join(base, "emp")),
            IndexConfig("qe", ["ek"], ["eid"]),
        )
        enable_hyperspace(s)

        def q():
            d = s.read.parquet(os.path.join(base, "dept"))
            e = s.read.parquet(os.path.join(base, "emp"))
            return d.join(e, col("k") == col("ek")).select("name", "eid")

        mesh_rows = q().collect().rows()  # exact rows INCLUDING order
        monkeypatch.setenv("HYPERSPACE_DISTRIBUTED", "0")
        single_rows = q().collect().rows()
        assert len(mesh_rows) > 0
        assert mesh_rows == single_rows
        # Non-indexed general join: the real exchange vs the host merge join.
        disable_hyperspace(s)
        monkeypatch.setenv("HYPERSPACE_DISTRIBUTED", "1")
        got = q().sorted_rows()
        monkeypatch.setenv("HYPERSPACE_DISTRIBUTED", "0")
        assert got == q().sorted_rows()


class TestPersistentCompileCache:
    @pytest.mark.parametrize("env_set", [True, False])
    def test_entries_land_where_jax_env_says_else_default(
        self, tmp_path, monkeypatch, env_set
    ):
        """`JAX_COMPILATION_CACHE_DIR` stands when set; otherwise the entry
        point's fixed default is used. Entries land there and nowhere else,
        and a re-dispatch after dropping in-memory executables hits."""
        import jax
        import jax.numpy as jnp

        from hyperspace_tpu.util.compile_cache import enable_compile_cache

        env_dir = str(tmp_path / "from_env")
        default_dir = str(tmp_path / "default")
        want = env_dir if env_set else default_dir
        if env_set:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            assert enable_compile_cache(default_dir) == want
            assert jax.config.jax_compilation_cache_dir == want
            f = compile_log.observed_jit(lambda x: x * 5 + 2, label="test.pcache")
            f(jnp.ones(333 + env_set))
            assert os.listdir(want), "no persistent cache entries written"
            other = default_dir if env_set else env_dir
            assert not os.path.exists(other) or not os.listdir(other)
            hits0 = compile_log.compile_cache_summary()["events"].get("cache_hits", 0)
            jax.clear_caches()  # drop in-memory executables: next dispatch
            f(jnp.ones(333 + env_set))  # must come from the PERSISTENT cache
            summary = compile_log.compile_cache_summary()
            assert summary["dir"] == want
            assert summary["events"].get("cache_hits", 0) > hits0
        finally:
            jax.config.update("jax_compilation_cache_dir", None)

    def test_exporter_frame_carries_compile_cache(self, tmp_path, monkeypatch):
        import json

        import jax

        from hyperspace_tpu.telemetry.exporter import MetricsExporter
        from hyperspace_tpu.util.compile_cache import enable_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
        try:
            enable_compile_cache(str(tmp_path / "unused"))
            path = str(tmp_path / "frames.jsonl")
            ex = MetricsExporter(path, interval_s=0.05).start()
            ex.stop()
        finally:
            jax.config.update("jax_compilation_cache_dir", None)
        frames = [json.loads(l) for l in open(path)]
        assert frames and frames[-1].get("final") is True
        assert frames[-1].get("compile_cache", {}).get("dir") == str(tmp_path / "c")


def test_skewed_layout_stays_on_classed_executor(tmp_path, monkeypatch):
    """JSPIM skew guard: an outlier-heavy bucket layout skips the mesh probe
    (whose global-cap padding would multiply every device's probe area) and
    rides the PR-3 size-classed executor — with correct results either way."""
    monkeypatch.setenv("HYPERSPACE_DISTRIBUTED", "1")
    s = _session(tmp_path, num_buckets=16)
    base = str(tmp_path)
    rng = np.random.RandomState(11)
    n = 4000
    hot = rng.rand(n) < 0.6  # one 60%-hot key: a guaranteed outlier bucket
    keys = np.where(hot, 7, rng.randint(0, 500, n)).astype(np.int64)
    s.write_parquet(
        {"k": keys, "v": np.arange(n, dtype=np.int64)}, os.path.join(base, "hotL")
    )
    s.write_parquet(
        {"rk": keys[: n // 2], "w": np.arange(n // 2, dtype=np.int64)},
        os.path.join(base, "hotR"),
    )
    hs = Hyperspace(s)
    hs.create_index(
        s.read.parquet(os.path.join(base, "hotL")), IndexConfig("skL", ["k"], ["v"])
    )
    hs.create_index(
        s.read.parquet(os.path.join(base, "hotR")), IndexConfig("skR", ["rk"], ["w"])
    )
    enable_hyperspace(s)

    from hyperspace_tpu.parallel import table_ops

    calls = {"n": 0}
    real = table_ops.probe_dist_blocks

    def spy(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(table_ops, "probe_dist_blocks", spy)

    def q():
        l = s.read.parquet(os.path.join(base, "hotL"))
        r = s.read.parquet(os.path.join(base, "hotR"))
        return l.join(r, col("k") == col("rk")).select("v", "w")

    got = q().sorted_rows()
    assert calls["n"] == 0, "skewed layout took the mesh probe"
    disable_hyperspace(s)
    expected = q().sorted_rows()
    assert len(got) > 0 and got == expected


def test_jit_compiles_program_sharded_on_the_bucket_mesh(tmp_path):
    """`jax.jit` with sharding annotations runs a program sharded over the
    mesh `make_mesh` builds — the seam new sharded programs are declared
    through."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from hyperspace_tpu.parallel import make_mesh
    from hyperspace_tpu.parallel.mesh import BUCKET_AXIS

    mesh = make_mesh(8)
    sh = NamedSharding(mesh, P(BUCKET_AXIS))
    f = jax.jit(lambda x: x * 2 + 1, in_shardings=(sh,), out_shardings=sh)

    x = jax.device_put(jnp.arange(64, dtype=jnp.int64), sh)
    out = f(x)
    assert (np.asarray(out) == np.arange(64) * 2 + 1).all()
    assert out.sharding == sh
