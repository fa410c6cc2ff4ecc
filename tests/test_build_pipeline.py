"""Pipelined index build: determinism, fault handling, telemetry.

The pipeline's whole contract is that overlap NEVER changes output: the
pipelined build (decode pool + chunked hash/transfer + fused sort + writer
pool) must produce byte-identical index files and an identical log-entry
signature to the serial fallback (`HYPERSPACE_BUILD_DECODE_THREADS=1`, the
pre-pipeline code path). These tests pin that, plus the failure contract
(a worker exception fails the build cleanly: no partial index directory, no
committed log entry) and the stage telemetry the bench surfaces.

This file is tier-1 (`-m 'not slow'`): the threads=2 smoke below exercises
the overlap machinery on every run, not only in bench.py.
"""

import hashlib
import json
import os

import numpy as np
import pytest

from hyperspace_tpu import IndexConfig, IndexConstants
from hyperspace_tpu.engine import HyperspaceSession
from hyperspace_tpu.engine import io as eio
from hyperspace_tpu.engine.table import Table
from hyperspace_tpu.hyperspace import Hyperspace


def _write_source(
    src_dir, n=6000, n_files=5, strings=False, nulls=False, mixed_widths=False, seed=3
):
    rng = np.random.RandomState(seed)
    per = n // n_files
    for i in range(n_files):
        d = {
            "k": (
                np.array([f"key-{v:04d}" for v in rng.randint(0, 200, per)])
                if strings
                else rng.randint(0, 200, per).astype(np.int64)
            ),
            "v": rng.randint(0, 100, per).astype(np.int64),
            "f": rng.rand(per),
        }
        if mixed_widths and i == 0:
            d["v"] = d["v"].astype(np.int32)  # promoted to int64 by the concat
        if nulls:
            vals = d["v"].astype(object)
            vals[rng.rand(per) < 0.1] = None
            d["v"] = vals
        eio.write_parquet(
            Table.from_pydict(d), os.path.join(src_dir, f"part-{i:05d}.parquet")
        )


def _build(tmp_path, src_dir, tag, lineage=False, num_buckets=8):
    """One covering-index build in its own warehouse; returns (index data file
    hashes by relative path, the ACTIVE log entry)."""
    base = str(tmp_path / tag)
    s = HyperspaceSession(warehouse=base)
    s.conf.set(IndexConstants.INDEX_SYSTEM_PATH, os.path.join(base, "indexes"))
    s.conf.set(IndexConstants.INDEX_NUM_BUCKETS, num_buckets)
    if lineage:
        s.conf.set(IndexConstants.INDEX_LINEAGE_ENABLED, "true")
    hs = Hyperspace(s)
    hs.create_index(s.read.parquet(src_dir), IndexConfig("idx", ["k"], ["v", "f"]))
    idir = os.path.join(base, "indexes", "idx")
    hashes = {}
    for root, _, fs in os.walk(idir):
        for f in sorted(fs):
            if f.endswith(".parquet"):
                p = os.path.join(root, f)
                hashes[os.path.relpath(p, idir)] = hashlib.sha256(
                    open(p, "rb").read()
                ).hexdigest()
    from hyperspace_tpu.hyperspace import _index_manager_for

    entries = _index_manager_for(s).get_indexes(["ACTIVE"])
    assert len(entries) == 1
    return hashes, entries[0]


def _fresh_caches():
    """Drop all decode/concat caches so a build exercises the cold path."""
    from hyperspace_tpu.engine.scan_cache import (
        global_concat_cache,
        global_scan_cache,
    )

    global_scan_cache().clear()
    cc = global_concat_cache()
    budget = cc.stats()["budget"]
    cc.set_capacity(0)
    cc.set_capacity(budget)


@pytest.mark.parametrize("writers", [None, "1", "3"], ids=["writers_default", "writers1", "writers3"])
@pytest.mark.parametrize(
    "variant",
    [
        {},
        {"strings": True},
        {"nulls": True},
        {"lineage": True},
        {"mixed_widths": True},
    ],
    ids=["ints", "strings", "nulls", "lineage", "mixed_widths"],
)
def test_pipelined_build_is_byte_identical_to_serial(tmp_path, monkeypatch, variant, writers):
    """threads>1 must produce byte-identical index files AND an identical
    IndexLogEntry signature to the serial (threads=1) build, for any writer
    count: one gather range (1 writer), several (3), one per bucket (8)."""
    lineage = variant.pop("lineage", False)
    src = str(tmp_path / "src")
    _write_source(src, **variant)

    monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "1")
    _fresh_caches()
    serial_hashes, serial_entry = _build(tmp_path, src, "serial", lineage=lineage)

    if writers is not None:
        monkeypatch.setenv("HYPERSPACE_BUILD_WRITERS", writers)
    monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "3")
    _fresh_caches()
    piped_hashes, piped_entry = _build(tmp_path, src, "piped", lineage=lineage)
    if writers is not None:  # 1 writer: one gather range; 3: the sort splits in 3
        from hyperspace_tpu.telemetry.profiling import last_build_stages

        assert last_build_stages()["stage_counts"]["take"] == int(writers)

    assert len(serial_hashes) > 0
    assert piped_hashes == serial_hashes
    assert piped_entry.signature().value == serial_entry.signature().value
    assert piped_entry.schema_json == serial_entry.schema_json
    # Inventories live under different warehouses: compare basename + size.
    assert sorted(
        (os.path.basename(f.name), f.size) for f in piped_entry.content.file_infos()
    ) == sorted(
        (os.path.basename(f.name), f.size) for f in serial_entry.content.file_infos()
    )


@pytest.mark.parametrize(
    "counts, writers",
    [
        ([0, 5, 0, 0, 7, 3, 0, 9], 3),  # empty buckets between and at the ends
        ([1, 1000, 1, 1], 4),  # one huge bucket
        ([0, 4, 0, 6, 0, 0], 8),  # fewer non-empty buckets than writers
        ([0] * 8, 4),  # no rows
        ([10] * 64, 8),  # even buckets: one range per writer
        ([3, 1, 4, 1, 5, 9, 2, 6], 1),  # one writer: one range
    ],
    ids=["empty_buckets", "huge_bucket", "few_buckets", "no_rows", "even", "one_writer"],
)
def test_gather_ranges_cover_every_bucket_once(counts, writers):
    """The writer's gather ranges: contiguous, cut at bucket starts, covering
    rows [0, n) with every non-empty bucket in exactly one range, each range
    holding rows, and no more ranges than writers."""
    from hyperspace_tpu.index.build_pipeline import _gather_ranges

    starts = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    n = int(starts[-1])
    ranges = _gather_ranges(starts, writers)
    assert len(ranges) <= writers
    if n == 0:
        assert ranges == []
        return
    assert starts[ranges[0][0]] == 0 and starts[ranges[-1][1]] == n
    for (_, b1), (b0, _) in zip(ranges, ranges[1:]):
        assert b1 == b0  # contiguous, cut at a bucket start
    assert all(starts[b1] > starts[b0] for b0, b1 in ranges)
    held = [b for b0, b1 in ranges for b in range(b0, b1) if counts[b] > 0]
    assert held == [b for b, c in enumerate(counts) if c > 0]
    if counts == [10] * 64:
        assert [(b1 - b0) for b0, b1 in ranges] == [8] * 8


def test_pipelined_build_warm_cache_identical(tmp_path, monkeypatch):
    """The warm-concat shortcut (a prior scan populated the caches) produces
    the same bytes as a cold pipelined build."""
    src = str(tmp_path / "src")
    _write_source(src)
    monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "3")
    _fresh_caches()
    cold_hashes, _ = _build(tmp_path, src, "cold")
    # Warm every cache level with a scan over the exact build projection.
    base = str(tmp_path / "warm")
    s = HyperspaceSession(warehouse=base)
    s.read.parquet(src).select("k", "v", "f").count()
    warm_hashes, _ = _build(tmp_path, src, "warm")
    assert warm_hashes == cold_hashes


def test_pipelined_build_forced_device_ops_identical(tmp_path, monkeypatch):
    """The device program (fused bucketize+sort, staged chunk buffers) matches
    the serial device path bit-for-bit — certified on XLA-CPU via
    HYPERSPACE_FORCE_DEVICE_OPS, the same lever CI uses."""
    src = str(tmp_path / "src")
    _write_source(src)
    monkeypatch.setenv("HYPERSPACE_FORCE_DEVICE_OPS", "1")
    monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "1")
    _fresh_caches()
    serial_hashes, _ = _build(tmp_path, src, "dev_serial")
    monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "3")
    _fresh_caches()
    piped_hashes, _ = _build(tmp_path, src, "dev_piped")
    assert piped_hashes == serial_hashes and len(piped_hashes) > 0


def test_pallas_composite_sort_matches_stable_lax_sort(monkeypatch):
    """The Pallas in-VMEM composite build sort (bucket,key,row packed into one
    int64) must reproduce the STABLE `lax.sort` permutation exactly — the
    row-index tiebreaker makes the unstable bitonic network deterministic.
    Certified in interpret mode off-TPU, like the other Pallas kernels."""
    import jax.numpy as jnp

    from hyperspace_tpu.engine.table import Column
    from hyperspace_tpu.ops.hashing import bucket_id
    from hyperspace_tpu.ops.partition import (
        _sort_perm,
        _sortable,
        pallas_composite_build_sort,
    )

    monkeypatch.setenv("HYPERSPACE_PALLAS_SORT", "1")
    rng = np.random.RandomState(0)
    n, nb = 5000, 16
    key = rng.randint(0, 300, n).astype(np.int64)  # heavy duplicates
    col = Column.from_values(key)
    arr = jnp.asarray(key)
    b = bucket_id([col], [arr], nb)
    res = pallas_composite_build_sort(b, arr, n, nb)
    assert res is not None, "pallas composite path not taken"
    perm_p, sb_p = res
    perm_x, sb_x = _sort_perm(b, (_sortable(arr),), n)
    assert np.array_equal(np.asarray(perm_x), perm_p)
    assert np.array_equal(np.asarray(sb_x), sb_p)


def _failing_session(tmp_path, tag):
    base = str(tmp_path / tag)
    s = HyperspaceSession(warehouse=base)
    s.conf.set(IndexConstants.INDEX_SYSTEM_PATH, os.path.join(base, "indexes"))
    s.conf.set(IndexConstants.INDEX_NUM_BUCKETS, 4)
    return s, base


def _assert_clean_failure(s, base):
    """No partial index data directory; no committed (ACTIVE/stable) entry."""
    idir = os.path.join(base, "indexes", "idx")
    data_dirs = [
        d
        for d in (os.listdir(idir) if os.path.isdir(idir) else [])
        if d.startswith(IndexConstants.INDEX_VERSION_DIR_PREFIX)
    ]
    assert data_dirs == [], data_dirs
    from hyperspace_tpu.hyperspace import _index_manager_for

    assert _index_manager_for(s).get_indexes(["ACTIVE"]) == []


def test_decode_worker_failure_fails_build_cleanly(tmp_path, monkeypatch):
    src = str(tmp_path / "src")
    _write_source(src)
    monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "3")
    _fresh_caches()
    s, base = _failing_session(tmp_path, "decode_fail")

    from hyperspace_tpu.index import build_pipeline

    real = build_pipeline._decode_file

    def boom(path, *a, **k):
        if path.endswith("part-00002.parquet"):
            raise RuntimeError("decode worker down")
        return real(path, *a, **k)

    monkeypatch.setattr(build_pipeline, "_decode_file", boom)
    with pytest.raises(Exception, match="decode worker down"):
        Hyperspace(s).create_index(
            s.read.parquet(src), IndexConfig("idx", ["k"], ["v", "f"])
        )
    _assert_clean_failure(s, base)


def test_write_worker_failure_fails_build_cleanly(tmp_path, monkeypatch):
    src = str(tmp_path / "src")
    _write_source(src)
    monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "3")
    _fresh_caches()
    s, base = _failing_session(tmp_path, "write_fail")

    from hyperspace_tpu.index.build_pipeline import _BucketWriter

    real = _BucketWriter.write_bucket

    def boom(self, b, lo, hi):
        if b == 2:
            raise RuntimeError("writer down")
        return real(self, b, lo, hi)

    monkeypatch.setattr(_BucketWriter, "write_bucket", boom)
    with pytest.raises(Exception, match="writer down"):
        Hyperspace(s).create_index(
            s.read.parquet(src), IndexConfig("idx", ["k"], ["v", "f"])
        )
    _assert_clean_failure(s, base)


def test_serial_build_failure_also_cleans_data_dir(tmp_path, monkeypatch):
    """The failure contract holds on the serial fallback too."""
    src = str(tmp_path / "src")
    _write_source(src)
    monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "1")
    _fresh_caches()
    s, base = _failing_session(tmp_path, "serial_fail")
    monkeypatch.setattr(
        eio, "write_parquet", lambda *a, **k: (_ for _ in ()).throw(RuntimeError("io down"))
    )
    with pytest.raises(Exception, match="io down"):
        Hyperspace(s).create_index(
            s.read.parquet(src), IndexConfig("idx", ["k"], ["v", "f"])
        )
    _assert_clean_failure(s, base)


def test_pipeline_smoke_records_stage_telemetry(tmp_path, monkeypatch):
    """Fast tier-1 smoke (threads=2): the pipelined path runs, and records the
    decode/hash/sort/write stage counters bench.py surfaces."""
    src = str(tmp_path / "src")
    _write_source(src, n=2000, n_files=3)
    monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", "2")
    monkeypatch.setenv("HYPERSPACE_BUILD_WRITERS", "2")
    _fresh_caches()
    _build(tmp_path, src, "smoke")
    from hyperspace_tpu.telemetry.profiling import last_build_stages

    stages = last_build_stages()
    assert stages is not None
    assert stages["mode"].startswith("pipelined")
    assert stages["decode_threads"] == 2 and stages["writers"] == 2
    assert stages["rows"] > 0
    for key in ("decode_s", "sort_s", "write_s", "wall_s", "overlap_ratio"):
        assert key in stages, stages
    assert json.dumps(stages)  # bench_detail-serializable


def test_pipeline_queries_see_identical_data(tmp_path, monkeypatch):
    """End to end: an indexed join over a pipelined build returns the same
    rows as over the serial build."""
    from hyperspace_tpu.engine import col
    from hyperspace_tpu.hyperspace import enable_hyperspace

    src = str(tmp_path / "src")
    _write_source(src)
    dim = str(tmp_path / "dim")
    rng = np.random.RandomState(9)
    eio.write_parquet(
        Table.from_pydict(
            {
                "k2": np.arange(200, dtype=np.int64),
                "w": rng.randint(1, 9, 200).astype(np.int64),
            }
        ),
        os.path.join(dim, "part-00000.parquet"),
    )
    counts = {}
    for threads, tag in (("1", "ser"), ("3", "pip")):
        monkeypatch.setenv("HYPERSPACE_BUILD_DECODE_THREADS", threads)
        _fresh_caches()
        base = str(tmp_path / f"q_{tag}")
        s = HyperspaceSession(warehouse=base)
        s.conf.set(IndexConstants.INDEX_SYSTEM_PATH, os.path.join(base, "indexes"))
        s.conf.set(IndexConstants.INDEX_NUM_BUCKETS, 8)
        hs = Hyperspace(s)
        hs.create_index(s.read.parquet(src), IndexConfig("liIdx", ["k"], ["v"]))
        hs.create_index(s.read.parquet(dim), IndexConfig("dimIdx", ["k2"], ["w"]))
        enable_hyperspace(s)
        q = s.read.parquet(src).join(
            s.read.parquet(dim), col("k") == col("k2")
        ).select("v", "w")
        assert "liIdx" in q.explain_string()
        counts[tag] = q.count()
    assert counts["ser"] == counts["pip"] > 0
