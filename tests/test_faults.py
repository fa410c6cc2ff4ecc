"""Fault-injection + resilient-execution contracts (docs/reliability.md).

The chaos oracle: injected transient faults at the engine's named fault
points must change NOTHING about results (retries absorb them), permanent
faults and exhausted retries must fail classified, a corrupt index bucket
file must quarantine the index and fall back to a correct source scan, and a
query past its deadline must die with a classified timeout leaving no
partial cache/memo state.
"""

import os
import time

import pytest

from hyperspace_tpu import resilience
from hyperspace_tpu.engine.expr import col
from hyperspace_tpu.engine.session import HyperspaceSession
from hyperspace_tpu.exceptions import (
    CompileTimeoutError,
    ConcurrentWriteError,
    CorruptIndexError,
    HyperspaceException,
    LogCommitError,
    PermanentError,
    QueryTimeoutError,
    RetryBudgetExceededError,
    TransientError,
    is_transient,
)
from hyperspace_tpu.index import quarantine
from hyperspace_tpu.telemetry import faults, metrics


@pytest.fixture(autouse=True)
def _clean_fault_state(monkeypatch):
    """Every test starts with injection off, zeroed per-point counters, a
    fast backoff, and an empty quarantine."""
    monkeypatch.delenv("HYPERSPACE_FAULTS", raising=False)
    monkeypatch.delenv("HYPERSPACE_QUERY_TIMEOUT_S", raising=False)
    monkeypatch.setenv("HYPERSPACE_RETRY_BACKOFF_S", "0.001")
    faults.clear()
    faults.reset_counters()
    quarantine.clear()
    yield
    faults.clear()
    faults.reset_counters()
    quarantine.clear()


def _clear_caches():
    from hyperspace_tpu.engine.physical import clear_device_memos
    from hyperspace_tpu.engine.scan_cache import (
        global_bucketed_cache,
        global_concat_cache,
        global_scan_cache,
    )

    global_scan_cache().clear()
    global_concat_cache().clear()
    global_bucketed_cache().clear()
    clear_device_memos()


def _session(tmp_path, n_files=4, rows_per_file=200):
    from hyperspace_tpu.engine import io as eio

    s = HyperspaceSession(warehouse=str(tmp_path))
    src = str(tmp_path / "src")
    for i in range(n_files):
        base = i * rows_per_file
        eio.write_parquet(
            s.create_table(
                {
                    "k": list(range(base, base + rows_per_file)),
                    "v": [j % 7 for j in range(base, base + rows_per_file)],
                }
            ),
            os.path.join(src, f"part-{i:05d}.parquet"),
        )
    return s, src


def _counter(name: str) -> int:
    return metrics.snapshot()["counters"].get(name, 0)


class TestFaultRegistry:
    def test_env_spec_grammar(self, monkeypatch):
        monkeypatch.setenv(
            "HYPERSPACE_FAULTS", "io.decode:0.5, log.write:1.0:permanent:3:2"
        )
        specs = faults._active_specs()
        assert specs["io.decode"].rate == 0.5
        assert specs["io.decode"].kind == "transient"
        assert specs["log.write"].kind == "permanent"
        assert specs["log.write"].limit == 3
        assert specs["log.write"].after == 2

    def test_unknown_point_rejected(self):
        with pytest.raises(ValueError, match="Unknown fault point"):
            faults.FaultSpec("io.bogus", 1.0)

    def test_hang_kind_parses_seconds(self):
        spec = faults.FaultSpec("storage.write", 1.0, "hang2.5")
        assert spec.kind == "hang" and spec.hang_s == 2.5

    def test_deterministic_under_seed(self, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_FAULTS_SEED", "42")
        draws1 = [faults._decide("io.decode", n, 0.3) for n in range(200)]
        draws2 = [faults._decide("io.decode", n, 0.3) for n in range(200)]
        assert draws1 == draws2
        assert any(draws1) and not all(draws1)
        monkeypatch.setenv("HYPERSPACE_FAULTS_SEED", "43")
        assert [faults._decide("io.decode", n, 0.3) for n in range(200)] != draws1

    def test_inject_scope_counts_and_restores(self):
        before = _counter("faults.io.decode.injected")
        with faults.inject("io.decode", rate=1.0, kind="transient"):
            with pytest.raises(TransientError, match="injected"):
                faults.check("io.decode")
        faults.check("io.decode")  # no-op again after the scope
        assert _counter("faults.io.decode.injected") == before + 1
        assert faults.injected_count("io.decode") >= 1

    def test_limit_and_after(self):
        with faults.inject("io.footer", rate=1.0, limit=1, after=2):
            faults.check("io.footer")  # call 0: skipped (after)
            faults.check("io.footer")  # call 1: skipped (after)
            with pytest.raises(TransientError):
                faults.check("io.footer")  # call 2: injected
            faults.check("io.footer")  # limit reached: no-op


class TestTaxonomy:
    def test_is_transient(self):
        assert is_transient(TransientError("x"))
        assert is_transient(ConnectionError("x"))
        assert is_transient(OSError("flaky nfs"))
        assert not is_transient(PermanentError("x"))
        assert not is_transient(FileNotFoundError("x"))
        assert not is_transient(ValueError("corrupt parquet"))
        assert not is_transient(HyperspaceException("x"))

    def test_retry_io_retries_transient(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise TransientError("blip")
            return "ok"

        assert resilience.retry_io("io.decode", flaky) == "ok"
        assert len(calls) == 3

    def test_retry_io_fails_fast_on_permanent(self):
        calls = []

        def broken():
            calls.append(1)
            raise PermanentError("gone")

        before = _counter("io.retries.attempts")
        with pytest.raises(PermanentError):
            resilience.retry_io("io.decode", broken)
        assert len(calls) == 1
        assert _counter("io.retries.attempts") == before


class TestChaosOracle:
    """Results under injected transient faults are byte-identical to clean
    runs, with retries observed in the metrics snapshot."""

    def test_collect_identical_under_decode_faults(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "1")
        # At rate 0.4 the default 2 retries can exhaust on consecutive draws;
        # the chaos contract raises the bound like the CI leg does.
        monkeypatch.setenv("HYPERSPACE_IO_RETRIES", "6")
        s, src = _session(tmp_path)
        _clear_caches()
        clean = s.read.parquet(src).collect().sorted_rows()
        retries_before = _counter("io.retries.attempts")
        with faults.inject("io.decode", rate=0.4, kind="transient"):
            for _ in range(3):
                _clear_caches()
                assert s.read.parquet(src).collect().sorted_rows() == clean
        assert _counter("io.retries.attempts") > retries_before
        assert _counter("faults.injected") > 0

    def test_streamed_aggregate_identical_under_faults(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "1")
        monkeypatch.setenv("HYPERSPACE_QUERY_STREAMING", "1")
        s, src = _session(tmp_path)

        def q():
            return (
                s.read.parquet(src)
                .group_by("v")
                .agg(total=("k", "sum"), n=("*", "count"))
                .collect()
                .sorted_rows()
            )

        _clear_caches()
        clean = q()
        with faults.inject("io.decode", rate=0.4, kind="transient"):
            _clear_caches()
            assert q() == clean

    def test_exhausted_retries_fail_classified(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "1")
        s, src = _session(tmp_path)
        _clear_caches()
        before = _counter("io.retries.exhausted")
        with faults.inject("io.decode", rate=1.0, kind="transient"):
            with pytest.raises(TransientError, match="injected"):
                s.read.parquet(src).collect()
        assert _counter("io.retries.exhausted") > before
        # Nothing poisoned: the same query succeeds once the fault clears.
        _clear_caches()
        assert s.read.parquet(src).count() == 800

    def test_permanent_fault_not_retried(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "1")
        s, src = _session(tmp_path)
        _clear_caches()
        before = _counter("io.retries.attempts")
        with faults.inject("io.decode", rate=1.0, kind="permanent"):
            with pytest.raises(PermanentError):
                s.read.parquet(src).collect()
        assert _counter("io.retries.attempts") == before

    def test_retry_budget_exceeded(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "1")
        monkeypatch.setenv("HYPERSPACE_QUERY_RETRY_BUDGET", "1")
        s, src = _session(tmp_path)
        _clear_caches()
        with faults.inject("io.decode", rate=1.0, kind="transient"):
            with pytest.raises(RetryBudgetExceededError, match="retry budget"):
                s.read.parquet(src).collect()

    @pytest.mark.parametrize("route", ["pipelined", "mesh"])
    def test_build_identical_under_faults(self, tmp_path, monkeypatch, route):
        """The chaos contract covers the BUILD too: an index built under
        injected transient decode/write faults is byte-identical to a clean
        build, on the pipeline and on the mesh (8 devices here), which both
        write through the range writer."""
        from hyperspace_tpu import Hyperspace, IndexConfig, IndexConstants
        from hyperspace_tpu.telemetry.profiling import last_build_stages

        monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "2")
        # At rate 0.3 the default 2 retries exhaust for one write in 37, and a
        # build makes several; the chaos contract raises the bound like the
        # CI leg does (exhaustion: test_exhausted_retries_fail_classified).
        monkeypatch.setenv("HYPERSPACE_IO_RETRIES", "6")
        s, src = _session(tmp_path)
        if route == "mesh":
            s.conf.set(IndexConstants.DISTRIBUTED_MIN_ROWS, 0)
        s.conf.set("hyperspace.system.path", str(tmp_path / "idx_clean"))
        s.conf.set("hyperspace.index.num.buckets", 4)
        _clear_caches()
        hs = Hyperspace(s)
        hs.create_index(s.read.parquet(src), IndexConfig("idx", ["k"], ["v"]))
        clean_dir = str(tmp_path / "idx_clean" / "idx" / "v__=0")
        clean = {
            f: open(os.path.join(clean_dir, f), "rb").read()
            for f in sorted(os.listdir(clean_dir))
        }
        s.conf.set("hyperspace.system.path", str(tmp_path / "idx_chaos"))
        _clear_caches()
        with faults.inject("storage.write", rate=0.3, kind="transient"):
            Hyperspace(s).create_index(
                s.read.parquet(src), IndexConfig("idx", ["k"], ["v"])
            )
        assert last_build_stages()["mode"].startswith(
            "mesh" if route == "mesh" else "pipelined"
        )
        chaos_dir = str(tmp_path / "idx_chaos" / "idx" / "v__=0")
        chaos = {
            f: open(os.path.join(chaos_dir, f), "rb").read()
            for f in sorted(os.listdir(chaos_dir))
        }
        assert clean == chaos


class TestLogWriteClassification:
    def test_transient_log_fault_retried_to_success(self, tmp_path, monkeypatch):
        from hyperspace_tpu import Hyperspace, IndexConfig

        monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "1")
        s, src = _session(tmp_path, n_files=2, rows_per_file=50)
        s.conf.set("hyperspace.system.path", str(tmp_path / "indexes"))
        s.conf.set("hyperspace.index.num.buckets", 2)
        _clear_caches()
        before = _counter("io.retries.log.write")
        with faults.inject("log.write", rate=1.0, kind="transient", limit=1):
            Hyperspace(s).create_index(
                s.read.parquet(src), IndexConfig("idx", ["k"], ["v"])
            )
        assert _counter("io.retries.log.write") > before
        mgr = Hyperspace(s)._manager
        assert [e.state for e in mgr.get_indexes(["ACTIVE"])] == ["ACTIVE"]

    def test_failed_stable_pointer_raises_classified(self):
        """Satellite: a failed latestStable refresh no longer silently
        proceeds — the action raises `LogCommitError` (the numbered entry IS
        committed; readers fall back to the id scan)."""
        from tests.test_actions import FakeBuilder, FakeLogManager

        from hyperspace_tpu import IndexConfig
        from hyperspace_tpu.actions.create import CreateAction

        class PointerLossManager(FakeLogManager):
            def create_latest_stable_log(self, log_id):
                super().create_latest_stable_log(log_id)
                return False

        mgr = PointerLossManager()
        action = CreateAction(
            "df", IndexConfig("idx", ["a"]), FakeBuilder(), mgr, "/i", "/i/v__=0"
        )
        with pytest.raises(LogCommitError, match="latestStable"):
            action.run()
        # The numbered final entry DID commit before the pointer failure.
        assert mgr.entries[1].state == "ACTIVE"

    def test_occ_conflict_is_concurrent_write_error(self):
        from tests.test_actions import FakeLogManager

        from hyperspace_tpu.actions.lifecycle import DeleteAction
        from hyperspace_tpu.actions import states as st
        from tests.test_actions import make_entry

        mgr = FakeLogManager({0: make_entry(state=st.ACTIVE)})
        mgr.entries[1] = make_entry(state=st.DELETING)  # the contested id
        action = DeleteAction(mgr)
        action._base_id = 0
        with pytest.raises(ConcurrentWriteError, match="in progress"):
            action.begin()


class TestQuarantine:
    def _indexed_session(self, tmp_path, monkeypatch):
        from hyperspace_tpu import Hyperspace, IndexConfig
        from hyperspace_tpu.hyperspace import enable_hyperspace

        monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "1")
        s, src = _session(tmp_path, n_files=2, rows_per_file=100)
        s.conf.set("hyperspace.system.path", str(tmp_path / "indexes"))
        s.conf.set("hyperspace.index.num.buckets", 3)
        _clear_caches()
        hs = Hyperspace(s)
        hs.create_index(s.read.parquet(src), IndexConfig("fidx", ["k"], ["v"]))
        enable_hyperspace(s)
        return s, src, hs

    def test_corrupt_bucket_file_quarantines_and_falls_back(
        self, tmp_path, monkeypatch
    ):
        s, src, hs = self._indexed_session(tmp_path, monkeypatch)

        def q():
            # A range filter on the head indexed column: covered by the index
            # (rewritten to an index scan over EVERY part-<bucket> file).
            return (
                s.read.parquet(src)
                .filter(col("k") > 42)
                .select("k", "v")
                .collect()
                .sorted_rows()
            )

        _clear_caches()
        clean = q()
        # Truncate/corrupt one index bucket file on the lake.
        idx_dir = str(tmp_path / "indexes" / "fidx" / "v__=0")
        victim = sorted(os.listdir(idx_dir))[0]
        with open(os.path.join(idx_dir, victim), "wb") as f:
            f.write(b"not a parquet file")
        _clear_caches()
        before = _counter("index.quarantine.events")
        with pytest.warns(RuntimeWarning, match="quarantined"):
            rows = q()
        assert rows == clean  # correct via source-scan fallback
        assert quarantine.is_quarantined("fidx")
        assert _counter("index.quarantine.events") == before + 1
        # Subsequent queries skip the index at candidate selection.
        _clear_caches()
        rq_before = _counter("rule.FilterIndexRule.quarantined")
        assert q() == clean
        assert _counter("rule.FilterIndexRule.quarantined") > rq_before

    def test_refresh_lifts_quarantine(self, tmp_path, monkeypatch):
        s, src, hs = self._indexed_session(tmp_path, monkeypatch)
        quarantine.mark("fidx", reason="test")
        hs.refresh_index("fidx", mode="full")
        assert not quarantine.is_quarantined("fidx")

    def test_engine_bug_never_quarantines(self, tmp_path, monkeypatch):
        """The corruption guard is decode-layer-typed: a TypeError (engine
        bug) during an index scan surfaces raw instead of masquerading as a
        corrupt index."""
        from hyperspace_tpu.engine import io as engine_io

        s, src, hs = self._indexed_session(tmp_path, monkeypatch)
        _clear_caches()

        def boom(*a, **k):
            raise TypeError("engine bug, not corruption")

        monkeypatch.setattr(engine_io, "_read_one", boom)
        with pytest.raises(TypeError, match="engine bug"):
            s.read.parquet(src).filter(col("k") > 42).collect()
        assert not quarantine.is_quarantined("fidx")

    def test_malformed_fault_spec_is_classified(self, monkeypatch):
        """A bad HYPERSPACE_FAULTS value raises a HyperspaceException (config
        error), never a raw ValueError the corruption guard could misread."""
        monkeypatch.setenv("HYPERSPACE_FAULTS", "io.decode")  # missing rate
        with pytest.raises(HyperspaceException, match="Bad HYPERSPACE_FAULTS"):
            faults.check("io.decode")

    def test_transient_faults_never_quarantine(self, tmp_path, monkeypatch):
        """An injected transient fault exhausting its retries is NOT
        corruption: the query fails classified, the index stays usable."""
        s, src, hs = self._indexed_session(tmp_path, monkeypatch)
        _clear_caches()
        with faults.inject("io.decode", rate=1.0, kind="transient"):
            with pytest.raises(TransientError):
                s.read.parquet(src).filter(col("k") > 42).collect()
        assert not quarantine.is_quarantined("fidx")


class TestDeadlines:
    def test_query_timeout_classified_and_clean(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "1")
        s, src = _session(tmp_path, n_files=4)
        _clear_caches()
        monkeypatch.setenv("HYPERSPACE_QUERY_TIMEOUT_S", "0.1")
        before = _counter("query.timeouts")
        with faults.inject("io.decode", rate=1.0, kind="hang0.06"):
            with pytest.raises(QueryTimeoutError, match="HYPERSPACE_QUERY_TIMEOUT_S"):
                s.read.parquet(src).collect()
        assert _counter("query.timeouts") > before
        # No partial cache/memo entries: with the deadline lifted the query
        # returns the full, correct result.
        monkeypatch.delenv("HYPERSPACE_QUERY_TIMEOUT_S")
        faults.clear()
        assert len(s.read.parquet(src).collect().rows()) == 800

    def test_streamed_aggregate_timeout(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "1")
        monkeypatch.setenv("HYPERSPACE_QUERY_STREAMING", "1")
        s, src = _session(tmp_path, n_files=4)
        _clear_caches()
        monkeypatch.setenv("HYPERSPACE_QUERY_TIMEOUT_S", "0.1")
        with faults.inject("io.decode", rate=1.0, kind="hang0.06"):
            with pytest.raises(QueryTimeoutError):
                s.read.parquet(src).group_by("v").agg(total=("k", "sum")).collect()
        monkeypatch.delenv("HYPERSPACE_QUERY_TIMEOUT_S")
        faults.clear()
        _clear_caches()
        out = s.read.parquet(src).group_by("v").agg(total=("k", "sum")).collect()
        assert out.num_rows == 7

    def test_no_scope_no_deadline(self):
        resilience.check_deadline("anywhere")  # no ambient scope: no-op

    def test_nested_scope_shares_deadline(self, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_QUERY_TIMEOUT_S", "60")
        with resilience.query_scope("outer") as outer:
            with resilience.query_scope("inner") as inner:
                assert inner is outer


class TestCompileDeadline:
    def test_slow_trace_raises_classified(self, monkeypatch):
        from hyperspace_tpu.telemetry.compile_log import observed_jit

        def slow(x):
            time.sleep(0.5)  # runs during TRACING (= inside the watchdog)
            return x + 1

        wrapped = observed_jit(slow, label="test.slow_compile")
        monkeypatch.setenv("HYPERSPACE_COMPILE_TIMEOUT_S", "0.05")
        before = _counter("xla.compiles.deadline_exceeded")
        with pytest.raises(CompileTimeoutError, match="test.slow_compile"):
            wrapped(1)
        assert _counter("xla.compiles.deadline_exceeded") == before + 1

    def test_fast_call_unaffected(self, monkeypatch):
        import numpy as np

        from hyperspace_tpu.telemetry.compile_log import observed_jit

        wrapped = observed_jit(lambda x: x * 2, label="test.fast")
        monkeypatch.setenv("HYPERSPACE_COMPILE_TIMEOUT_S", "30")
        assert int(np.asarray(wrapped(21))) == 42

    def test_device_compile_fault_point(self):
        from hyperspace_tpu.telemetry.compile_log import observed_jit

        wrapped = observed_jit(lambda x: x + 0, label="test.faulted")
        with faults.inject("device.compile", rate=1.0, kind="transient"):
            with pytest.raises(TransientError, match="device.compile"):
                wrapped(1)


class TestCrashRecoveryInProcess:
    """Simulated dead-writer states (the subprocess SIGKILL twins live in
    tests/test_crash_recovery.py)."""

    def _orphan_transient_entry(self, tmp_path, state):
        from hyperspace_tpu.index.log_manager import IndexLogManagerImpl

        idx_path = str(tmp_path / "indexes" / "idx")
        mgr = IndexLogManagerImpl(idx_path)
        from tests.test_actions import make_entry

        entry = make_entry(name="idx", state=state)
        assert mgr.write_log(0, entry)
        return idx_path

    def test_create_over_dead_creating_entry(self, tmp_path, monkeypatch):
        from hyperspace_tpu import Hyperspace, IndexConfig

        monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "1")
        s, src = _session(tmp_path, n_files=2, rows_per_file=50)
        s.conf.set("hyperspace.system.path", str(tmp_path / "indexes"))
        s.conf.set("hyperspace.index.num.buckets", 2)
        self._orphan_transient_entry(tmp_path, "CREATING")
        _clear_caches()
        before = _counter("index.recovered_transient")
        Hyperspace(s).create_index(
            s.read.parquet(src), IndexConfig("idx", ["k"], ["v"])
        )
        assert _counter("index.recovered_transient") > before
        mgr = Hyperspace(s)._manager
        latest = mgr.get_indexes(["ACTIVE"])
        assert [e.name for e in latest] == ["idx"]

    def test_dead_staging_dir_reclaimed(self, tmp_path, monkeypatch):
        import subprocess

        from hyperspace_tpu import Hyperspace, IndexConfig
        from hyperspace_tpu.index.staging import STAGING_PREFIX

        monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "1")
        s, src = _session(tmp_path, n_files=2, rows_per_file=50)
        s.conf.set("hyperspace.system.path", str(tmp_path / "indexes"))
        s.conf.set("hyperspace.index.num.buckets", 2)
        idx_path = tmp_path / "indexes" / "idx"
        idx_path.mkdir(parents=True)
        import socket

        proc = subprocess.Popen(["true"])
        proc.wait()  # a real pid, guaranteed dead
        orphan = (
            idx_path
            / f"{STAGING_PREFIX}v__=0~{socket.gethostname()}~{proc.pid}~deadbeef"
        )
        orphan.mkdir()
        (orphan / "part-00000.parquet").write_bytes(b"partial")
        # A LIVE foreign-host staging dir must survive reclamation (pid
        # liveness is unknowable cross-host; only TTL age reclaims those).
        foreign = idx_path / f"{STAGING_PREFIX}v__=0~otherhost~12345~cafef00d"
        foreign.mkdir()
        _clear_caches()
        Hyperspace(s).create_index(
            s.read.parquet(src), IndexConfig("idx", ["k"], ["v"])
        )
        leftovers = [
            n for n in os.listdir(idx_path) if n.startswith(STAGING_PREFIX)
        ]
        assert leftovers == [foreign.name]  # dead local reclaimed, foreign kept
        # Once stale past the TTL, the foreign dir is reclaimed too.
        monkeypatch.setenv("HYPERSPACE_STAGING_TTL_S", "0")
        from hyperspace_tpu.index.staging import reclaim_orphans

        time.sleep(0.01)
        assert reclaim_orphans(str(idx_path)) == 1

    def test_stage_commit_concurrent_loser_aborts_cleanly(self, tmp_path):
        from hyperspace_tpu.index.staging import STAGING_PREFIX, stage_commit

        final = tmp_path / "v__=0"
        with pytest.raises(ConcurrentWriteError, match="committed"):
            with stage_commit(str(final)) as stage:
                os.makedirs(stage)
                with open(os.path.join(stage, "f.parquet"), "wb") as f:
                    f.write(b"x")
                # The racing winner lands first.
                final.mkdir()
                (final / "f.parquet").write_bytes(b"winner")
        assert (final / "f.parquet").read_bytes() == b"winner"
        leftovers = [
            n for n in os.listdir(tmp_path) if n.startswith(STAGING_PREFIX)
        ]
        assert leftovers == []

    def test_refresh_over_dead_refreshing_entry(self, tmp_path, monkeypatch):
        """A killed refresh leaves REFRESHING as the latest entry; the next
        refresh recovers from the latest STABLE (ACTIVE) entry and completes."""
        from hyperspace_tpu import Hyperspace, IndexConfig

        monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "1")
        s, src = _session(tmp_path, n_files=2, rows_per_file=50)
        s.conf.set("hyperspace.system.path", str(tmp_path / "indexes"))
        s.conf.set("hyperspace.index.num.buckets", 2)
        _clear_caches()
        hs = Hyperspace(s)
        hs.create_index(s.read.parquet(src), IndexConfig("idx", ["k"], ["v"]))
        # Simulate a writer killed mid-refresh: an orphan REFRESHING entry.
        from hyperspace_tpu.index.log_manager import IndexLogManagerImpl

        mgr = IndexLogManagerImpl(str(tmp_path / "indexes" / "idx"))
        import copy

        orphan = copy.deepcopy(mgr.get_latest_log())
        orphan.state = "REFRESHING"
        assert mgr.write_log(mgr.get_latest_id() + 1, orphan)
        hs._manager.clear_cache()
        hs.refresh_index("idx", mode="full")
        stable = mgr.get_latest_stable_log()
        assert stable is not None and stable.state == "ACTIVE"


class TestReliabilitySurfaces:
    def test_exporter_frame_carries_reliability(self, tmp_path):
        from hyperspace_tpu.telemetry.exporter import MetricsExporter

        quarantine.mark("brokenidx", reason="test")
        exp = MetricsExporter(str(tmp_path / "m.jsonl"), interval_s=60.0)
        frame = exp._frame()
        rel = frame["reliability"]
        assert "faults_injected" in rel and "io_retries" in rel
        assert rel["quarantined"] == ["brokenidx"]

    def test_explain_analyze_renders_retries(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "1")
        s, src = _session(tmp_path, n_files=2, rows_per_file=50)
        _clear_caches()
        with faults.inject("io.decode", rate=1.0, kind="transient", limit=1):
            out = s.read.parquet(src).explain(analyze=True)
        assert "io_retries=" in out
