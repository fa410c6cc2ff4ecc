"""Equivalence: the Pallas tiled-compare probe kernel == the XLA searchsorted
probe, across key dtypes, duplicates, empty buckets, and pad slots.

Off-TPU the kernel runs in Pallas interpret mode (same program, interpreted),
which is how CI certifies the kernel the TPU lowers via Mosaic. The per-bucket
merge under test is the reference's SortMergeJoinExec-over-co-bucketed-scans
equivalent (`JoinIndexRule.scala:137-162`).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from hyperspace_tpu.ops.bucket_join import _PAD, _probe, probe_ranges
from hyperspace_tpu.ops.pallas_probe import probe_pallas


def _padded_from_lists(buckets, cap, dtype, pad):
    B = len(buckets)
    mat = np.full((B, cap), pad, dtype=dtype)
    lens = np.zeros(B, np.int64)
    for i, b in enumerate(buckets):
        b = np.sort(np.asarray(b, dtype=dtype))
        mat[i, : len(b)] = b
        lens[i] = len(b)
    return jnp.asarray(mat), jnp.asarray(lens)


def _assert_equiv(ls, rs, l_len, r_len):
    lo_x, cnt_x = _probe(ls, rs, l_len, r_len)
    lo_p, cnt_p = probe_pallas(ls, rs, l_len, r_len)
    # Counts must agree everywhere; lo must agree wherever a match exists
    # (lo is meaningless where count==0, but the XLA path still clamps it —
    # compare under the same clamp).
    np.testing.assert_array_equal(np.asarray(cnt_x), np.asarray(cnt_p))
    mask = np.asarray(cnt_x) > 0
    np.testing.assert_array_equal(np.asarray(lo_x)[mask], np.asarray(lo_p)[mask])


def test_int64_hash_keys_with_duplicates():
    rng = np.random.RandomState(0)
    buckets_l = [rng.randint(-(2**62), 2**62, size=n) for n in (5, 0, 17, 32)]
    # Force cross-side duplicates: reuse some left keys on the right.
    buckets_r = [
        np.concatenate([rng.choice(bl, size=min(3, len(bl)), replace=True), rng.randint(-(2**62), 2**62, size=m)])
        if len(bl)
        else rng.randint(-(2**62), 2**62, size=m)
        for bl, m in zip(buckets_l, (7, 4, 0, 61))
    ]
    ls, llen = _padded_from_lists(buckets_l, 32, np.int64, _PAD)
    rs, rlen = _padded_from_lists(buckets_r, 64, np.int64, _PAD)
    _assert_equiv(ls, rs, llen, rlen)


def test_int64_value_keys_small_range():
    # Small key range → lots of equal runs on both sides.
    rng = np.random.RandomState(1)
    buckets_l = [rng.randint(0, 5, size=n) for n in (16, 16, 16, 16)]
    buckets_r = [rng.randint(0, 5, size=n) for n in (16, 16, 16, 16)]
    ls, llen = _padded_from_lists(buckets_l, 16, np.int64, np.iinfo(np.int64).max)
    rs, rlen = _padded_from_lists(buckets_r, 16, np.int64, np.iinfo(np.int64).max)
    _assert_equiv(ls, rs, llen, rlen)


def test_float64_value_keys_including_zero_signs():
    rng = np.random.RandomState(2)
    vals = np.concatenate([rng.randn(20), [-0.0, 0.0, 0.0, -1.5, 1e300, -1e300]])
    buckets_l = [vals[:13], vals[13:]]
    buckets_r = [vals[5:20], vals[:6]]
    pad = np.finfo(np.float64).max
    ls, llen = _padded_from_lists(buckets_l, 16, np.float64, pad)
    rs, rlen = _padded_from_lists(buckets_r, 16, np.float64, pad)
    _assert_equiv(ls, rs, llen, rlen)


def test_int32_value_keys():
    rng = np.random.RandomState(3)
    buckets = [rng.randint(-100, 100, size=9) for _ in range(3)]
    ls, llen = _padded_from_lists(buckets, 16, np.int32, np.iinfo(np.int32).max)
    rs, rlen = _padded_from_lists(buckets[::-1], 16, np.int32, np.iinfo(np.int32).max)
    _assert_equiv(ls, rs, llen, rlen)


def test_large_caps_exercise_tiling():
    # cap_l > TL(256) and cap_r > TR(512): multiple grid tiles + accumulation.
    rng = np.random.RandomState(4)
    B, cap_l, cap_r = 3, 512, 2048
    buckets_l = [rng.randint(0, 1000, size=rng.randint(1, cap_l)) for _ in range(B)]
    buckets_r = [rng.randint(0, 1000, size=rng.randint(1, cap_r)) for _ in range(B)]
    ls, llen = _padded_from_lists(buckets_l, cap_l, np.int64, _PAD)
    rs, rlen = _padded_from_lists(buckets_r, cap_r, np.int64, _PAD)
    _assert_equiv(ls, rs, llen, rlen)


def test_probe_ranges_dispatches_to_pallas(monkeypatch):
    monkeypatch.setenv("HYPERSPACE_PALLAS_PROBE", "1")
    calls = []
    import hyperspace_tpu.ops.bucket_join as bj
    import hyperspace_tpu.ops.pallas_probe as pp

    real = pp.probe_pallas

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(pp, "probe_pallas", spy)
    rng = np.random.RandomState(5)
    buckets = [rng.randint(0, 50, size=10) for _ in range(2)]
    ls, llen = _padded_from_lists(buckets, 16, np.int64, _PAD)
    rs, rlen = _padded_from_lists(buckets, 16, np.int64, _PAD)
    lo, cnt = bj.probe_ranges(ls, rs, llen, rlen)
    assert calls, "pallas probe not dispatched under HYPERSPACE_PALLAS_PROBE=1"
    lo_x, cnt_x = _probe(ls, rs, llen, rlen)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_x))


@pytest.mark.parametrize("dtype", [np.int64, np.float64])
def test_failing_wanted_probe_raises(monkeypatch, dtype):
    """A wanted Pallas probe that fails raises, for either key kind: no latch,
    no silent XLA/host run, and the next dispatch still wants the kernel."""
    monkeypatch.setenv("HYPERSPACE_PALLAS_PROBE", "1")
    import hyperspace_tpu.ops.bucket_join as bj
    import hyperspace_tpu.ops.pallas_probe as pp

    def boom(*a, **k):
        raise RuntimeError("mosaic lowering failed")

    monkeypatch.setattr(pp, "probe_pallas", boom)
    rng = np.random.RandomState(6)
    buckets = [rng.randint(0, 50, size=10).astype(dtype) for _ in range(2)]
    ls, llen = _padded_from_lists(buckets, 16, dtype, _PAD)
    rs, rlen = _padded_from_lists(buckets, 16, dtype, _PAD)
    with pytest.raises(RuntimeError, match="mosaic lowering failed"):
        bj.probe_ranges(ls, rs, llen, rlen)
    assert pp.pallas_probe_wanted(16, 16, 2)


def test_shape_gate_refuses_unlowerable_bucket_counts(monkeypatch):
    """Bucket counts that are neither <=8 nor a multiple of 8 cannot lower
    (whole-axis blocks would blow VMEM); the dispatcher must refuse them even
    when forced."""
    import hyperspace_tpu.ops.pallas_probe as pp

    monkeypatch.setenv("HYPERSPACE_PALLAS_PROBE", "1")
    assert pp.shape_supported(8, 256, 512)
    assert pp.shape_supported(64, 256, 512)
    assert pp.shape_supported(3, 64, 64)
    assert not pp.shape_supported(20, 256, 512)  # >8, not a multiple of 8
    assert not pp.pallas_probe_wanted(256, 512, 20)


def test_float_split_32bit_matches_64bit_transform():
    """The pure-32-bit float split (`_split_hi_lo_float`, no 64-bit bitcast)
    must reproduce the
    canonical transform's (hi, lo) pair bit-for-bit, including sign flips,
    -0.0 canonicalization, denormals, and extreme magnitudes."""
    import jax.numpy as jnp

    from hyperspace_tpu.ops.pallas_probe import (
        _sortable_i64,
        _split_hi_lo,
        _split_hi_lo_float,
    )

    rng = np.random.RandomState(17)
    vals = np.concatenate(
        [
            rng.randn(256) * 1e3,
            # NOTE on denormals (±5e-324): XLA flushes f64 denormals to zero
            # (measured on XLA-CPU: x + 0.0 == 0.0 and x == 0 is True), so
            # both the 64-bit and 32-bit transforms map them to the zero key
            # IDENTICALLY — the bit-equality check below covers them, but the
            # order-vs-numpy check can't (numpy doesn't flush).
            np.array([0.0, -0.0, 1e308, -1e308, 1.5, -1.5]),
            rng.randn(64) * 1e-300,
        ]
    )
    x = jnp.asarray(vals)
    hi64, lo64 = _split_hi_lo(_sortable_i64(x))
    hi32, lo32 = _split_hi_lo_float(x)
    np.testing.assert_array_equal(np.asarray(hi64), np.asarray(hi32))
    np.testing.assert_array_equal(np.asarray(lo64), np.asarray(lo32))
    # And the pair really orders like the floats do under the kernel's
    # lexicographic SIGNED compare (hi first, then the biased lo).
    order = np.lexsort((np.asarray(lo32), np.asarray(hi32)))
    np.testing.assert_array_equal(vals[order], np.sort(vals))


def test_float_keys_admitted_on_any_backend(monkeypatch):
    """The 32-bit float split lets the dispatcher admit float value-mode keys
    wherever shapes allow."""
    import hyperspace_tpu.ops.pallas_probe as pp

    monkeypatch.setenv("HYPERSPACE_PALLAS_PROBE", "1")
    calls = []
    real = pp.probe_pallas
    monkeypatch.setattr(pp, "probe_pallas", lambda *a: calls.append(a) or real(*a))
    rng = np.random.RandomState(9)
    buckets = [rng.randn(10) for _ in range(2)]
    ls, llen = _padded_from_lists(buckets, 16, np.float64, _PAD)
    rs, rlen = _padded_from_lists(buckets, 16, np.float64, _PAD)
    lo, counts = probe_ranges(ls, rs, llen, rlen)
    assert len(calls) == 1
    np.testing.assert_array_equal(np.asarray(counts)[:, :10], 1)


def test_host_probe_matches_xla_probe():
    """The CPU backend's host probe (`_probe_host`) must match the XLA probe
    exactly on valid regions: lo wherever counts > 0, counts everywhere."""
    from hyperspace_tpu.ops.bucket_join import _probe, _probe_host

    rng = np.random.RandomState(3)
    B, capL, capR = 6, 256, 64
    L = np.sort(rng.randint(0, 300, (B, capL)).astype(np.int64), axis=1)
    R = np.sort(rng.randint(0, 300, (B, capR)).astype(np.int64), axis=1)
    l_len = rng.randint(0, capL + 1, B).astype(np.int32)
    r_len = rng.randint(0, capR + 1, B).astype(np.int32)
    lo_h, cnt_h = _probe_host(L, R, l_len, r_len)
    lo_x, cnt_x = (np.asarray(a) for a in _probe(L, R, l_len, r_len))
    valid = np.arange(capL)[None, :] < l_len[:, None]
    np.testing.assert_array_equal(cnt_h[valid], cnt_x[valid])
    np.testing.assert_array_equal(cnt_h[~valid], 0)
    m = valid & (cnt_h > 0)
    np.testing.assert_array_equal(lo_h[m], lo_x[m])


def test_value_rep_canonicalizes_negative_zero():
    """pad_buckets_by_value must emit no -0.0 keys (probe implementations
    disagree on signed-zero ordering; the engine's equality treats them
    equal), and a NaN-holding bucket must fall back to the hash rep."""
    import jax.numpy as jnp
    from hyperspace_tpu.ops import bucket_join as bj

    rep = bj.pad_buckets_by_value(
        jnp.asarray(np.array([-0.0, 0.0, 1.5])), np.array([0, 3])
    )
    assert rep is not None and rep.mode == "value"
    keys = np.asarray(rep.keys)[0, :3]
    assert not np.signbit(keys).any()
    np.testing.assert_array_equal(keys, [0.0, 0.0, 1.5])
    assert (
        bj.pad_buckets_by_value(
            jnp.asarray(np.array([1.0, np.nan])), np.array([0, 2])
        )
        is None
    )
    # A SINGLETON NaN bucket has zero sortedness comparisons — the explicit
    # NaN gate (not the non-decreasing check) must reject it.
    assert (
        bj.pad_buckets_by_value(
            jnp.asarray(np.array([np.nan])), np.array([0, 1])
        )
        is None
    )


@pytest.mark.parametrize(
    "bits,B,cap_l,cap_r",
    [(4, 8, 16384, 16384), (2, 3, 32768, 64), (4, 8, 8, 16384)],
)
def test_packed_probe_tiles_match_searchsorted(bits, B, cap_l, cap_r):
    """Caps past one tile (1024 words) on either side: the histogram must
    accumulate over every right tile and each left tile must be emitted."""
    import jax.numpy as jnp

    from hyperspace_tpu.engine.packed_codes import pack_rows_traced
    from hyperspace_tpu.ops import pallas_probe as pp

    assert pp.packed_shape_supported(B, cap_l, cap_r, bits)
    rng = np.random.RandomState(bits + B)
    top = (1 << bits) - 1
    L = np.sort(rng.randint(0, top, (B, cap_l)), axis=1)
    R = np.sort(rng.randint(0, top, (B, cap_r)), axis=1)
    l_len, r_len = np.full(B, cap_l), np.full(B, cap_r)
    lo, cnt = pp.probe_packed_pallas(
        pack_rows_traced(jnp.asarray(L), bits),
        pack_rows_traced(jnp.asarray(R), bits),
        bits,
        l_len,
        r_len,
    )
    for b in range(B):
        want_lo = np.searchsorted(R[b], L[b], "left")
        want_hi = np.searchsorted(R[b], L[b], "right")
        np.testing.assert_array_equal(np.asarray(lo)[b], want_lo)
        np.testing.assert_array_equal(np.asarray(cnt)[b], want_hi - want_lo)


def test_packed_shape_gate():
    """The packed gate admits whole-word pow2 caps of any width (its blocks
    are tiled) and refuses partial words and unlowerable bucket counts."""
    from hyperspace_tpu.ops import pallas_probe as pp

    assert pp.packed_shape_supported(64, 524288, 8, 4)
    assert pp.packed_shape_supported(64, 8, 524288, 4)
    assert pp.packed_shape_supported(3, 32, 32, 1)
    assert not pp.packed_shape_supported(64, 524288, 4, 4)  # half a word
    assert not pp.packed_shape_supported(20, 256, 256, 4)
    assert not pp.packed_shape_supported(8, 1536 * 8, 256, 4)  # not a tile multiple
