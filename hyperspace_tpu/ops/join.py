"""Device-side sort-merge join primitive.

TPU-first: both sides' join keys are 64-bit value hashes (`ops.hashing.key64`), so the
merge works on a single comparable integer key regardless of column count or string
dictionaries. The pipeline is sort → searchsorted range probe → two-pass expansion
(count, then scatter), which keeps every step static-shaped for XLA except one scalar
sync for the output size — the classic way around ragged output shapes on TPU
(SURVEY §7 "hard parts": two-pass partitioning).

Equal key tuples always produce equal key64s; unequal tuples that collide (~2^-64) are
eliminated by the caller's exact-equality verification on the gathered rows, so results
are exact.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry.compile_log import observed_jit as _observed_jit


def stable_argsort_host(x) -> np.ndarray:
    """The host branch of the backend-adaptive sort trade, as a NUMPY
    permutation (callers that continue host-side skip the device round-trip)."""
    return np.argsort(np.asarray(x), kind="stable")


def _range_probe_body(l_key64, r_key64, l_order, r_order, xp=jnp):
    """Range probe of sorted views — the ONE home of the lo/hi/count
    semantics, used traced in the fused device program (xp=jnp) and on HOST
    arrays by the CPU branch of `merge_join_pairs` (xp=np; eager jnp ops
    there are per-operator XLA-CPU dispatches)."""
    ls = l_key64[l_order]
    rs = r_key64[r_order]
    lo = xp.searchsorted(rs, ls, side="left")
    hi = xp.searchsorted(rs, ls, side="right")
    return lo, hi - lo


@_observed_jit(label="join.sorted_ranges")
def _merge_phase_a(l_key64, r_key64):
    """Sort both sides + range-probe in ONE compiled program (each eager op
    would be a dispatch of its own)."""
    l_order = jnp.argsort(l_key64)
    r_order = jnp.argsort(r_key64)
    lo, counts = _range_probe_body(l_key64, r_key64, l_order, r_order)
    return l_order, r_order, lo, counts, counts.sum()


def merge_join_pairs(l_key64, r_key64) -> Tuple[np.ndarray, np.ndarray]:
    """All (left_index, right_index) pairs with equal keys, as host numpy arrays.

    Works on unsorted inputs: sorts both sides internally and maps positions back to
    the original row order."""
    from .backend import use_device_path

    l_key64 = jnp.asarray(l_key64)
    r_key64 = jnp.asarray(r_key64)
    if l_key64.shape[0] == 0 or r_key64.shape[0] == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)

    if use_device_path():
        l_order, r_order, lo, counts, total_dev = _merge_phase_a(l_key64, r_key64)
        total = int(total_dev)  # the one scalar sync (dynamic output size)
        if total == 0:
            return np.empty(0, np.int64), np.empty(0, np.int64)
        starts = jnp.cumsum(counts) - counts  # exclusive prefix sum
        l_pos = jnp.repeat(
            jnp.arange(l_key64.shape[0]), counts, total_repeat_length=total
        )
        offset = jnp.arange(total) - starts[l_pos]
        r_pos = lo[l_pos] + offset
        return np.asarray(l_order[l_pos]), np.asarray(r_order[r_pos])
    # CPU backend: the WHOLE merge stays on host — eager jnp sorts/probes/
    # expansions here are per-op XLA-CPU dispatches (the sort ~3x slower than
    # numpy, the expansion a chain of eager gathers). Same probe body as the
    # device program (xp=np), same host sort as every other host path.
    return host_merge_pairs(np.asarray(l_key64), np.asarray(r_key64))


def host_merge_pairs(lk: np.ndarray, rk: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """All equal-key (left_index, right_index) pairs of two HOST arrays,
    always on numpy regardless of backend — the CPU branch of
    `merge_join_pairs`, and the per-bucket merge of the size-classed join's
    OUTLIER path (one oversized bucket must not drag the device-wide padded
    layout along, nor pay a per-bucket device dispatch). Pair order: left
    rows in sorted-key order, each with its matches in the right side's
    sorted order — the same within-bucket order the padded expansion emits."""
    lk, rk = np.asarray(lk), np.asarray(rk)
    if lk.shape[0] == 0 or rk.shape[0] == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    l_order = stable_argsort_host(lk)
    r_order = stable_argsort_host(rk)
    lo, counts = _range_probe_body(lk, rk, l_order, r_order, xp=np)
    total = int(counts.sum())
    if total == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    starts = np.cumsum(counts) - counts
    l_pos = np.repeat(np.arange(lk.shape[0]), counts)
    offset = np.arange(total) - starts[l_pos]
    r_pos = lo[l_pos] + offset
    return l_order[l_pos], r_order[r_pos]


def nonzero_indices(mask) -> np.ndarray:
    """Compact a device boolean mask into host row indices (one scalar sync).

    CPU backend: plain numpy. `jnp.nonzero(mask, size=n)` compiles per
    distinct (shape, n) — and n is the SURVIVOR COUNT, so every new filter
    literal (or index generation's new file shape) minted ~16 eager-op
    compiles ≈ 300 ms on the interactive point-lookup path (the PR-2
    varying-survivor-count lesson, applied to the general filter path)."""
    from .backend import use_device_path

    if not use_device_path():
        return np.nonzero(np.asarray(mask))[0].astype(np.int64, copy=False)
    mask = jnp.asarray(mask)
    n = int(mask.sum())
    if n == 0:
        return np.empty(0, np.int64)
    return np.asarray(jnp.nonzero(mask, size=n)[0])
