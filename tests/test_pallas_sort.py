"""Equivalence: the Pallas in-VMEM bitonic sort == jnp.argsort over padded
bucket matrices. Off-TPU the kernel runs in interpret mode (same program the
TPU lowers via Mosaic). Bitonic is unstable, so equivalence is: sorted keys
identical, and the order is a valid permutation reproducing them."""

import numpy as np
import pytest

import jax.numpy as jnp

from hyperspace_tpu.ops.bucket_join import _PAD, pad_buckets_by_hash
from hyperspace_tpu.ops.pallas_sort import (
    pallas_sort_wanted,
    shape_supported,
    sort_padded_with_order,
)


def _check(keys_np):
    got_sorted, got_order = sort_padded_with_order(jnp.asarray(keys_np))
    ref_sorted = np.sort(keys_np, axis=1)
    np.testing.assert_array_equal(np.asarray(got_sorted), ref_sorted)
    # order is a permutation per row and reproduces the sorted keys
    order = np.asarray(got_order)
    for b in range(keys_np.shape[0]):
        assert sorted(order[b]) == list(range(keys_np.shape[1]))
        np.testing.assert_array_equal(keys_np[b][order[b]], ref_sorted[b])


def test_random_int64_keys_with_pads():
    rng = np.random.RandomState(0)
    B, cap = 8, 256
    keys = rng.randint(-(2**62), 2**62, size=(B, cap)).astype(np.int64)
    # Ragged valid prefixes: pad tails with the sentinel like production.
    for b in range(B):
        keys[b, rng.randint(1, cap):] = np.iinfo(np.int64).max
    _check(keys)


def test_duplicate_heavy_keys():
    rng = np.random.RandomState(1)
    keys = rng.randint(0, 7, size=(8, 256)).astype(np.int64)
    _check(keys)


def test_nonmultiple_bucket_axis_whole_block():
    rng = np.random.RandomState(2)
    keys = rng.randint(-1000, 1000, size=(3, 256)).astype(np.int64)
    _check(keys)


def test_shape_gate():
    assert shape_supported(8, 256)
    assert shape_supported(64, 32768)
    assert not shape_supported(8, 65536)  # beyond the VMEM budget
    assert not shape_supported(8, 128)  # below the dispatch-overhead floor
    assert not shape_supported(8, 300)  # not a pow2
    assert not shape_supported(20, 1024)  # >8 and not a multiple of 8


def test_pad_buckets_by_hash_via_pallas_matches_xla(monkeypatch):
    """End-to-end through pad_buckets_by_hash: forced Pallas sort must yield
    the same sorted key matrices and consistent order maps as the XLA path."""
    import hyperspace_tpu.ops.pallas_sort as ps

    rng = np.random.RandomState(3)
    n = 4000
    key64 = rng.randint(-(2**62), 2**62, n).astype(np.int64)
    starts = np.linspace(0, n, 9).astype(np.int64)

    monkeypatch.setenv("HYPERSPACE_PALLAS_SORT", "0")
    ref = pad_buckets_by_hash(key64, starts)
    monkeypatch.setenv("HYPERSPACE_PALLAS_SORT", "1")
    got = pad_buckets_by_hash(key64, starts)
    np.testing.assert_array_equal(np.asarray(got.keys), np.asarray(ref.keys))
    np.testing.assert_array_equal(np.asarray(got.lengths), np.asarray(ref.lengths))
    # order maps agree up to permutations within equal keys: re-gathering the
    # keys through each order must reproduce the sorted matrices.
    padded_ref = np.full(ref.keys.shape, np.iinfo(np.int64).max, np.int64)
    clipped = np.minimum(key64, np.iinfo(np.int64).max - 1)
    for b in range(8):
        lo, hi = int(starts[b]), int(starts[b + 1])
        padded_ref[b, : hi - lo] = clipped[lo:hi]
    for b in range(8):
        np.testing.assert_array_equal(
            padded_ref[b][np.asarray(got.order)[b]], np.asarray(got.keys)[b]
        )


def test_failing_wanted_sort_raises(monkeypatch):
    """A wanted Pallas sort that fails raises: no latch, no silent XLA run,
    and the next dispatch still wants the kernel."""
    import pytest

    import hyperspace_tpu.ops.pallas_sort as ps

    monkeypatch.setenv("HYPERSPACE_PALLAS_SORT", "1")

    def boom(*a, **k):
        raise RuntimeError("mosaic lowering failed")

    monkeypatch.setattr(ps, "sort_padded_with_order", boom)
    rng = np.random.RandomState(4)
    n = 2048
    key64 = rng.randint(0, 10**9, n).astype(np.int64)
    starts = np.linspace(0, n, 9).astype(np.int64)
    with pytest.raises(RuntimeError, match="mosaic lowering failed"):
        pad_buckets_by_hash(key64, starts)
    assert ps.pallas_sort_wanted(8, 256)
