"""Device-resident encoded execution (ISSUE 15 tentpole).

Contract under test: with ``HYPERSPACE_ENCODED_DEVICE`` on (the auto
default, riding the encoded-exec master switch), string key lanes cross the
host→device boundary as NARROW dictionary codes (int8/int16 when the
dictionary fits) and the mesh exchange moves code-space lanes — while every
result (join rows, aggregate groups, index file bytes) stays BYTE-IDENTICAL
to the ``HYPERSPACE_ENCODED_DEVICE=0`` flat-staging fallback, in both
``HYPERSPACE_DISTRIBUTED`` ambients. Code width folds into the jit cache key
as a bounded class set: two cardinalities in the same width class share one
compiled exchange (no per-cardinality shapes).
"""

import hashlib
import os

import numpy as np
import pytest

from hyperspace_tpu import IndexConfig, IndexConstants
from hyperspace_tpu.engine import HyperspaceSession, col
from hyperspace_tpu.engine import encoded_device
from hyperspace_tpu.engine.table import Column, Table
from hyperspace_tpu.hyperspace import Hyperspace, enable_hyperspace
from hyperspace_tpu.telemetry import compile_log, metrics

ENV = encoded_device.ENV_ENCODED_DEVICE

# Distinct from every other suite so mesh program shapes are this file's own.
NUM_BUCKETS = 28


def _session(tmp_path, num_buckets=NUM_BUCKETS):
    s = HyperspaceSession(warehouse=str(tmp_path))
    s.conf.set(IndexConstants.INDEX_SYSTEM_PATH, str(tmp_path / "indexes"))
    s.conf.set(IndexConstants.INDEX_NUM_BUCKETS, num_buckets)
    s.conf.set(IndexConstants.DISTRIBUTED_MIN_ROWS, 0)
    return s


def _clear_caches():
    from hyperspace_tpu.engine.physical import clear_device_memos
    from hyperspace_tpu.engine.scan_cache import (
        global_bucketed_cache,
        global_concat_cache,
        global_filtered_cache,
        global_scan_cache,
    )

    global_scan_cache().clear()
    global_concat_cache().clear()
    global_filtered_cache().clear()
    global_bucketed_cache().clear()
    clear_device_memos()


def _write_str_pair(s, base, n, card=60, seed=7, suffix=""):
    """String-key fact/dim pair; `card` distinct keys (≤127 → int8 codes)."""
    rng = np.random.RandomState(seed)
    s.write_parquet(
        {
            "sk": np.array([f"c{v:04d}" for v in rng.randint(0, card, n)]),
            "val": np.arange(n, dtype=np.int64),
        },
        os.path.join(base, f"fact{suffix}"),
    )
    s.write_parquet(
        {
            "dk": np.array([f"c{v:04d}" for v in rng.randint(0, card, n // 4)]),
            "w": rng.randint(0, 100, n // 4).astype(np.int64),
        },
        os.path.join(base, f"dim{suffix}"),
    )


def _tables_identical(a: Table, b: Table):
    assert a.column_names == b.column_names
    for n in a.column_names:
        ca, cb = a.columns[n], b.columns[n]
        assert ca.dtype == cb.dtype, n
        assert np.array_equal(ca.data, cb.data), n
        if ca.is_string:
            assert np.array_equal(ca.dictionary, cb.dictionary), n
        assert (ca.validity is None) == (cb.validity is None), n
        if ca.validity is not None:
            assert np.array_equal(ca.validity, cb.validity), n


def _on_off(monkeypatch, make_result):
    """(result_on, result_off), each produced COLD (caches cleared)."""
    monkeypatch.setenv(ENV, "1")
    _clear_caches()
    on = make_result()
    monkeypatch.setenv(ENV, "0")
    _clear_caches()
    off = make_result()
    monkeypatch.delenv(ENV, raising=False)
    _clear_caches()
    return on, off


def _dir_hashes(root):
    return {
        f: hashlib.sha256(open(os.path.join(root, f), "rb").read()).hexdigest()
        for f in sorted(os.listdir(root))
        if f.startswith("part-")
    }


# ---------------------------------------------------------------------------
# Width policy units
# ---------------------------------------------------------------------------


class TestWidthPolicy:
    def test_code_dtype_boundaries(self):
        assert encoded_device.code_dtype_for(1) is np.int8
        assert encoded_device.code_dtype_for(127) is np.int8
        assert encoded_device.code_dtype_for(128) is np.int16
        assert encoded_device.code_dtype_for(32767) is np.int16
        assert encoded_device.code_dtype_for(32768) is None

    def test_mode_parsing(self, monkeypatch):
        monkeypatch.delenv(ENV, raising=False)
        assert encoded_device.encoded_device_mode() == "auto"
        monkeypatch.setenv(ENV, "0")
        assert encoded_device.encoded_device_mode() == "off"
        assert not encoded_device.encoded_device_enabled()
        monkeypatch.setenv(ENV, "1")
        assert encoded_device.encoded_device_mode() == "force"
        assert encoded_device.encoded_device_enabled()

    def test_narrow_codes_value_identical_and_memoized(self, monkeypatch):
        monkeypatch.setenv(ENV, "1")
        strings = np.array([f"s{i}" for i in range(50)])
        codes = np.arange(50, dtype=np.int32) % 50
        c = Column("string", codes, np.sort(strings))
        narrow = encoded_device.narrow_codes(c)
        assert narrow.dtype == np.int8
        assert np.array_equal(narrow.astype(np.int32), c.data)
        assert encoded_device.narrow_codes(c) is narrow  # memoized
        assert encoded_device.column_qualifies(c)  # force mode: marker not needed

    def test_wide_dictionary_stays_flat(self, monkeypatch):
        monkeypatch.setenv(ENV, "1")
        card = 40000
        dictionary = np.sort(np.array([f"u{i:05d}" for i in range(card)]))
        c = Column("string", np.arange(card, dtype=np.int32), dictionary)
        assert not encoded_device.narrowable(c)
        assert encoded_device.narrow_codes(c) is c.data

    def test_auto_mode_wants_encoded_read_marker(self, monkeypatch):
        monkeypatch.delenv(ENV, raising=False)
        monkeypatch.delenv("HYPERSPACE_ENCODED_EXEC", raising=False)
        dictionary = np.sort(np.array([f"s{i}" for i in range(30)]))
        c = Column("string", np.zeros(8, np.int32), dictionary)
        assert encoded_device.narrowable(c)  # lane-level: no marker needed
        assert not encoded_device.column_qualifies(c)
        c._encoded_read = True
        assert encoded_device.column_qualifies(c)


# ---------------------------------------------------------------------------
# Flag oracle: byte-identical results, flat vs codes-on-device
# ---------------------------------------------------------------------------


class TestFlagOracle:
    def test_string_key_join_identical(self, tmp_path, monkeypatch):
        s = _session(tmp_path)
        base = str(tmp_path)
        _write_str_pair(s, base, 1200, card=60)

        def q():
            f = s.read.parquet(os.path.join(base, "fact"))
            d = s.read.parquet(os.path.join(base, "dim"))
            return f.join(d, col("sk") == col("dk")).select("sk", "val", "w").collect()

        on, off = _on_off(monkeypatch, q)
        _tables_identical(on, off)
        assert on.num_rows > 0

    def test_int_key_join_identical(self, tmp_path, monkeypatch):
        s = _session(tmp_path)
        base = str(tmp_path)
        rng = np.random.RandomState(11)
        s.write_parquet(
            {"k": rng.randint(0, 50, 900).astype(np.int64), "v": np.arange(900)},
            os.path.join(base, "ifact"),
        )
        s.write_parquet(
            {"ik": rng.randint(0, 50, 200).astype(np.int64), "w": np.arange(200)},
            os.path.join(base, "idim"),
        )

        def q():
            f = s.read.parquet(os.path.join(base, "ifact"))
            d = s.read.parquet(os.path.join(base, "idim"))
            return f.join(d, col("k") == col("ik")).select("k", "v", "w").collect()

        on, off = _on_off(monkeypatch, q)
        _tables_identical(on, off)
        assert on.num_rows > 0

    def test_null_key_join_identical(self, tmp_path, monkeypatch):
        from hyperspace_tpu.engine import io as engine_io

        s = _session(tmp_path)
        base = str(tmp_path)
        lt = Table.from_pydict(
            {"k": ["a", "b", None, "c", "a", None], "lv": [1, 2, 3, 4, 5, 6]}
        )
        rt = Table.from_pydict({"k": ["b", "a", None, "d"], "rv": [10, 20, 30, 40]})
        engine_io.write_parquet(lt, os.path.join(base, "nl", "part-00000.parquet"))
        engine_io.write_parquet(rt, os.path.join(base, "nr", "part-00000.parquet"))

        def q():
            l = s.read.parquet(os.path.join(base, "nl"))
            r = s.read.parquet(os.path.join(base, "nr"))
            return l.join(r, col("k") == col("k")).select("k", "lv", "rv").collect()

        on, off = _on_off(monkeypatch, q)
        _tables_identical(on, off)
        assert sorted(on.rows()) == [("a", 1, 20), ("a", 5, 20), ("b", 2, 10)]

    def test_streamed_aggregate_identical(self, tmp_path, monkeypatch):
        s = _session(tmp_path)
        base = str(tmp_path)
        _write_str_pair(s, base, 1500, card=40, seed=13)

        def q():
            return (
                s.read.parquet(os.path.join(base, "fact"))
                .group_by("sk")
                .agg(n=("*", "count"), tot=("val", "sum"))
                .collect()
            )

        on, off = _on_off(monkeypatch, q)
        _tables_identical(on, off)
        assert on.num_rows == 40


# ---------------------------------------------------------------------------
# Mesh build: byte-identical index files + code-space exchange traffic
# ---------------------------------------------------------------------------


class TestMeshCodedExchange:
    @pytest.mark.parametrize("distributed", ["1", "0"])
    def test_build_byte_identical_across_flag(
        self, tmp_path, monkeypatch, distributed
    ):
        monkeypatch.setenv("HYPERSPACE_DISTRIBUTED", distributed)
        s = _session(tmp_path)
        base = str(tmp_path)
        _write_str_pair(s, base, 2000, card=90, seed=5)
        hs = Hyperspace(s)
        f = s.read.parquet(os.path.join(base, "fact"))

        monkeypatch.setenv(ENV, "1")
        _clear_caches()
        hs.create_index(f, IndexConfig("codedIdx", ["sk"], ["val"]))
        monkeypatch.setenv(ENV, "0")
        _clear_caches()
        hs.create_index(f, IndexConfig("flatIdx", ["sk"], ["val"]))
        monkeypatch.delenv(ENV, raising=False)

        hc = _dir_hashes(os.path.join(base, "indexes", "codedIdx", "v__=0"))
        hf = _dir_hashes(os.path.join(base, "indexes", "flatIdx", "v__=0"))
        assert len(hc) > 0
        assert hc == hf

        # And the indexed query answers identically rows-wise in this ambient.
        enable_hyperspace(s)
        d = s.read.parquet(os.path.join(base, "dim"))
        q = f.join(d, col("sk") == col("dk")).select("val", "w")
        monkeypatch.setenv(ENV, "1")
        _clear_caches()
        rows_on = q.sorted_rows()
        monkeypatch.setenv(ENV, "0")
        _clear_caches()
        rows_off = q.sorted_rows()
        assert rows_on == rows_off and len(rows_on) > 0

    def test_exchange_bytes_moved_shrinks_2x(self, tmp_path, monkeypatch):
        """The exchange's wire lanes (narrow bucket + int8 validity + int32 row
        id + int8 codes) move ≥2× fewer bytes than full-width lanes (uint32
        hash + int32 validity + int64 row id + int32 codes) would at the SAME
        build's capacity."""
        import jax

        from hyperspace_tpu.telemetry import tracing

        monkeypatch.setenv("HYPERSPACE_DISTRIBUTED", "1")
        monkeypatch.setenv(ENV, "1")
        s = _session(tmp_path)
        base = str(tmp_path)
        _write_str_pair(s, base, 3000, card=100, seed=9)
        hs = Hyperspace(s)
        f = s.read.parquet(os.path.join(base, "fact"))

        _clear_caches()
        before = metrics.counter("parallel.exchange.bytes_moved").value
        with tracing.capture() as cap:
            hs.create_index(f, IndexConfig("mcIdx", ["sk"], ["val"]))
        moved = metrics.counter("parallel.exchange.bytes_moved").value - before
        monkeypatch.delenv(ENV, raising=False)
        (counts_sp,) = cap.trace.find("build:exchange_counts")
        n_dev = len(jax.devices())
        full_width = n_dev * n_dev * counts_sp.attrs["cap"] * (4 + 4 + 8 + 4)
        assert moved > 0
        assert full_width / moved >= 2.0, (full_width, moved)

    def test_no_per_cardinality_compile_classes(self, tmp_path, monkeypatch):
        """Two dictionary cardinalities in the SAME width class (both int8)
        share one compiled exchange: the code-width class key mints no
        per-cardinality shapes."""
        monkeypatch.setenv("HYPERSPACE_DISTRIBUTED", "1")
        monkeypatch.setenv(ENV, "1")
        s = _session(tmp_path)
        base = str(tmp_path)
        hs = Hyperspace(s)
        _write_str_pair(s, base, 2000, card=50, seed=21, suffix="a")
        _write_str_pair(s, base, 2000, card=100, seed=22, suffix="b")

        def compiles(lbl):
            return compile_log.program_summary().get(lbl, {}).get("compiles", 0)

        fa = s.read.parquet(os.path.join(base, "facta"))
        hs.create_index(fa, IndexConfig("cardA", ["sk"], ["val"]))
        after_first = compiles("parallel.exchange")
        assert after_first >= 1
        fb = s.read.parquet(os.path.join(base, "factb"))
        hs.create_index(fb, IndexConfig("cardB", ["sk"], ["val"]))
        assert compiles("parallel.exchange") == after_first, (
            "a second cardinality in the same code-width class recompiled "
            "the exchange"
        )


# ---------------------------------------------------------------------------
# Ledgers and cache accounting
# ---------------------------------------------------------------------------


class TestEncodedStagingLedger:
    def test_encoded_hits_and_staged_bytes_tick(self, tmp_path, monkeypatch):
        monkeypatch.setenv(ENV, "1")
        s = _session(tmp_path)
        base = str(tmp_path)
        _write_str_pair(s, base, 1000, card=60, seed=17)
        _clear_caches()
        f = s.read.parquet(os.path.join(base, "fact"))
        d = s.read.parquet(os.path.join(base, "dim"))
        q = f.join(d, col("sk") == col("dk")).select("val", "w")

        flat0 = metrics.counter("device.encoded.bytes_flat").value
        staged0 = metrics.counter("device.encoded.bytes_staged").value
        hits0 = metrics.counter("cache.device_upload.encoded_hits").value
        q.count()
        flat1 = metrics.counter("device.encoded.bytes_flat").value
        staged1 = metrics.counter("device.encoded.bytes_staged").value
        assert flat1 > flat0, "no encoded staging recorded"
        # int8 codes: the staged bytes are a strict fraction of the flat ones.
        assert staged1 - staged0 < flat1 - flat0
        # Warm path: restaging the SAME column serves the memoized narrow lane
        # from the id-keyed upload cache and ticks the encoded-hit counter.
        kc = f.collect().columns["sk"]
        encoded_device.stage_codes(kc, "test_site")
        encoded_device.stage_codes(kc, "test_site")
        assert metrics.counter("cache.device_upload.encoded_hits").value > hits0

    def test_flag_off_records_nothing(self, tmp_path, monkeypatch):
        monkeypatch.setenv(ENV, "0")
        s = _session(tmp_path)
        base = str(tmp_path)
        _write_str_pair(s, base, 800, card=60, seed=19)
        _clear_caches()
        flat0 = metrics.counter("device.encoded.bytes_flat").value
        f = s.read.parquet(os.path.join(base, "fact"))
        d = s.read.parquet(os.path.join(base, "dim"))
        f.join(d, col("sk") == col("dk")).select("val", "w").count()
        assert metrics.counter("device.encoded.bytes_flat").value == flat0
