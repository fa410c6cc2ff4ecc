"""Stable on-device hashing for bucket assignment and join keys.

TPU-first design: bucket ids and join keys are computed on device with uint32 vector
ops (murmur3 finalizer mixing), so the index build's partitioning step
(the analogue of Spark's `repartition(numBuckets, indexedCols)` hash partitioning,
`CreateActionBase.scala:130-131`) runs on the VPU, not the host.

- Numeric columns hash on device from their bit patterns.
- String columns hash via their dictionary: one host-side blake2b per *unique* value,
  then a device gather through the codes — O(dict) host work, O(n) device work.
  This IS the encoded-execution hash path (docs/encoded-execution.md): keys
  arrive as dictionary codes from the reader and are never decoded to hash —
  the per-column dictionary-hash table is the only place the string bytes
  are ever touched, once per distinct value.
- Multi-column keys combine per-column hashes with a murmur-style mixer.
- Join keys are 64-bit (two independent 32-bit lanes packed), verified exactly at join
  time, so hash collisions can never produce wrong results.

Hash stability matters: the same value must hash identically in any table on any
backend (bucket co-location across independently-built indexes is what makes the
shuffle-free bucketed join sound — reference `JoinIndexRule.scala:144-156`).
"""

from __future__ import annotations

import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import device_observatory as _devobs
from ..telemetry.compile_log import observed_jit as _observed_jit

from typing import TYPE_CHECKING

if TYPE_CHECKING:  # annotation-only: a runtime import would cycle through
    # engine/__init__ -> session -> physical -> ops.hashing when `ops` is
    # imported before `engine`.
    from ..engine.table import Column

_SEED1 = np.uint32(0x9747B28C)
_SEED2 = np.uint32(0x85EBCA6B)

#: Pow2-quantize the ROW dimension of every fused hash program ("1" on, "0"
#: off, unset = auto: on exactly when the DEVICE kernel path is active). The
#: hash is elementwise, so padding the inputs to the next power of two and
#: slicing the output changes NOTHING for real rows — but it bounds the
#: number of distinct shapes each program ever traces to log2(max rows)
#: instead of one per exact table size. The r05 TPU bench died inside a
#: 2400 s compile of `hashing.bucket_id` fed a raw table-sized shape stream;
#: quantization at THIS boundary is the structural fix (every caller
#: inherits it), and it is what lets the persistent XLA compilation cache
#: stay small and hot across processes. The auto default is
#: backend-adaptive because the trade inverts: on a TPU one avoided compile
#: pays for years of pad/slice copies, while
#: on the XLA-CPU backend compiles are ~0.2 s and the two O(n) copies showed
#: up as a measured 45% cold-join regression at 2M — so CPU runs exact
#: shapes unless explicitly opted in. (The MESH path is quantized either
#: way: `parallel/table_ops.py` pads rows onto the mesh grid before the hash
#: regardless of this knob.)
ENV_HASH_QUANTIZE = "HYPERSPACE_HASH_QUANTIZE"


def _hash_quantize_enabled() -> bool:
    env = os.environ.get(ENV_HASH_QUANTIZE)
    if env is not None and env != "":
        return env != "0"
    # Unset: the adaptive planner's calibrated decision replaces the raw
    # device-only heuristic (same prior, but both arms are priced and the
    # per-class outcome store can overturn a wrong guess — the measured 45%
    # CPU regression case lands on the span either way).
    from ..plananalysis.planner import decided_value

    decided = decided_value("hash_quantize")
    if decided is not None:
        return bool(decided)
    from .backend import use_device_path

    return use_device_path()


def _pow2_len(n: int) -> int:
    return 1 << (max(1, int(n)) - 1).bit_length()


def _pad_pow2(arr):
    """Pad a 1-D device array to the next pow2 length (zeros: a valid bit
    pattern for every kind — numeric words hash fine, string CODES index slot
    0 — and the caller slices the padded rows back off)."""
    a = jnp.asarray(arr)
    n = int(a.shape[0])
    n_pad = _pow2_len(n)
    if n_pad == n:
        return a
    return jnp.concatenate([a, jnp.zeros(n_pad - n, dtype=a.dtype)])


def fmix32(h):
    """murmur3 32-bit finalizer — a cheap, well-mixed bijection on uint32."""
    h = h.astype(jnp.uint32)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def _mix_combine(h, k):
    """Combine an accumulated hash with a new lane (murmur-style stream step)."""
    h = h ^ fmix32(k)
    h = (h * jnp.uint32(5)) + jnp.uint32(0xE6546B64)
    return h


def _words_u32(arr, force_float: bool = False):
    """Split an array into two uint32 word arrays from its canonical bit pattern.

    Values canonicalize WITHIN their kind (ints/bools → int64 bits, floats →
    float64 bits) so equal values hash equal regardless of storage width — an
    int32 id column must bucket/join against an int64 one. Integer hashing
    stays EXACT (float64 canonicalization would alias dense ids beyond 2^53 —
    snowflake ids, nanosecond timestamps — into systematic collision runs).

    `force_float` canonicalizes integers through float64 too: the CROSS-KIND
    join case (int key ⋈ float key), where equality is numpy-promoted float64
    equality (Spark casts both sides to double), so both sides must hash in
    that space. The JOIN decides this jointly per key pair; it never applies
    to single-table hashing (builds, group-bys)."""
    x = jnp.asarray(arr)
    if force_float or jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(jnp.float64)
        # Normalize -0.0 to +0.0 so equal floats hash equal.
        x = jnp.where(x == 0, jnp.zeros_like(x), x)
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)  # shape (..., 2)
        return [bits[..., 0], bits[..., 1]]
    x = x.astype(jnp.int64)
    lo = (x & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = ((x >> jnp.int64(32)) & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    return [lo, hi]


def hash_device_values(arr, seed: np.uint32, force_float: bool = False):
    """uint32 hash of a numeric device array's values."""
    words = _words_u32(arr, force_float)
    h = jnp.full(words[0].shape, jnp.uint32(seed))
    for w in words:
        h = _mix_combine(h, w)
    return fmix32(h)


# Dictionary-hash memo: one blake2b pass per (dictionary identity, seed), not
# per query — weakref-keyed so entries die with their dictionaries (the same
# id-reuse-safe pattern as the engine's device memo caches).
_dict_hash_cache: dict = {}
import threading as _threading

_dict_hash_lock = _threading.RLock()  # concurrent queries share the memo


def host_hash_dictionary(dictionary: np.ndarray, seed: int):
    """Stable uint32 hash per unique string, as a DEVICE array (host blake2b
    once per dictionary entry; hash + upload both memoized per dictionary
    object + seed, so repeat queries are transfer-free)."""
    import weakref

    key = (id(dictionary), int(seed))
    with _dict_hash_lock:
        ent = _dict_hash_cache.get(key)
        if ent is not None and ent[0]() is dictionary:
            return ent[1]
    out = np.empty(len(dictionary), dtype=np.uint32)
    seed_bytes = int(seed).to_bytes(4, "little")
    for i, s in enumerate(dictionary):
        d = hashlib.blake2b(str(s).encode("utf-8"), digest_size=4, salt=seed_bytes).digest()
        out[i] = np.frombuffer(d, dtype=np.uint32)[0]
    if _hash_quantize_enabled():
        # Pow2-pad the table: dictionary sizes are data-dependent, and the
        # table is an operand SHAPE of every fused string-hash program — an
        # unpadded table would re-trace those programs once per distinct
        # cardinality. Gathers only ever index real codes, so padding is
        # invisible to the hash values.
        n_pad = _pow2_len(len(out))
        if n_pad != len(out):
            _devobs.record_pad("hash_dict", len(out) * 4, (n_pad - len(out)) * 4)
            out = np.concatenate([out, np.zeros(n_pad - len(out), np.uint32)])
    dev = jnp.asarray(out)

    def _evict(wr, key=key):
        with _dict_hash_lock:
            ent_now = _dict_hash_cache.get(key)
            if ent_now is not None and ent_now[0] is wr:
                _dict_hash_cache.pop(key, None)

    try:
        with _dict_hash_lock:
            _dict_hash_cache[key] = (weakref.ref(dictionary, _evict), dev)
    except TypeError:
        pass  # non-weakref-able dictionary container: skip memoization
    return dev


def column_hash_u32(column: Column, device_data, seed: np.uint32):
    """uint32 hash of one column's values (device array in, device array out).

    ``device_data`` is the column's device representation (codes for strings)."""
    if column.is_string:
        from ..engine.encoded_device import widen_for_gather

        device_data = widen_for_gather(device_data)
        return host_hash_dictionary(column.dictionary, int(seed))[device_data]
    return hash_device_values(device_data, seed)


def _lane_trace(seed, dh_slot, cols):
    """Trace-time combine over prepared per-column inputs: `cols[i]` is
    ("num", arr) or ("str", codes, dh_table_per_seed...); `dh_slot` picks the
    dict-hash table matching `seed` for string columns (tables sit at
    c[2], c[3], ... in seed order)."""
    h = None
    for c in cols:
        if c[0] == "str":
            from ..engine.encoded_device import widen_for_gather

            codes = widen_for_gather(c[1])
            hc = c[2 + dh_slot][codes]
        else:
            hc = hash_device_values(c[1], seed, force_float=(c[0] == "numf"))
        h = hc if h is None else fmix32(_mix_combine(h, hc))
    return h


def _unflatten(kinds, flat, per_str: int):
    cols, i = [], 0
    for kind in kinds:
        if kind == "str":
            cols.append(("str", *flat[i : i + per_str]))
            i += per_str
        else:
            cols.append((kind, flat[i]))  # "num" | "numf" (forced-float canon)
            i += 1
    return cols


@_observed_jit(label="hashing.key64", static_argnums=(0,))
def _key64_fused(kinds, *flat):
    """Both hash lanes + the 64-bit pack in ONE compiled program. Each eager
    jnp op is a dispatch — ~40 per key64 — so fusing saves dispatch
    overhead on every backend."""
    cols = _unflatten(kinds, flat, 3)
    h1 = _lane_trace(_SEED1, 0, cols)
    h2 = _lane_trace(_SEED2, 1, cols)
    return (h1.astype(jnp.int64) << jnp.int64(32)) | h2.astype(jnp.int64)


@_observed_jit(label="hashing.combined_hash", static_argnums=(0, 1))
def _combined_fused(kinds, seed, *flat):
    cols = _unflatten(kinds, flat, 2)
    return _lane_trace(seed, 0, cols)


@_observed_jit(label="hashing.bucket_id", static_argnums=(0, 1))
def _bucket_id_fused(kinds, num_buckets, *flat):
    cols = _unflatten(kinds, flat, 2)
    h1 = _lane_trace(_SEED1, 0, cols)
    return (h1 % jnp.uint32(num_buckets)).astype(jnp.int32)


def _flat_inputs(columns, device_arrays, seeds, force_float=None):
    """(kinds, flat) for the fused kernels: string columns contribute their
    codes plus one host-hashed dictionary table per seed. `force_float[i]`
    canonicalizes numeric column i through float64 (the cross-kind join
    space — see `_words_u32`).

    Code arrays may arrive NARROW (int8/int16 — engine/encoded_device.py
    stages them that way when the dictionary fits): the string lane is a
    `dh_table[codes]` gather, so any integer code width produces identical
    hashes, and the width folds into the jit cache key as a bounded
    {int8, int16, int32} class set — never a per-cardinality shape."""
    kinds, flat = [], []
    for i, (col, arr) in enumerate(zip(columns, device_arrays)):
        if col.is_string:
            kinds.append("str")
            flat.append(arr)
            for s in seeds:
                flat.append(host_hash_dictionary(col.dictionary, int(s)))
        else:
            kinds.append("numf" if force_float is not None and force_float[i] else "num")
            flat.append(arr)
    return tuple(kinds), flat


def _quantized_row_inputs(device_arrays):
    """(device_arrays possibly pow2-padded, real row count or None). None =
    already on the grid / quantization off — call the fused program as-is."""
    if not _hash_quantize_enabled() or not device_arrays:
        return device_arrays, None
    n = int(jnp.asarray(device_arrays[0]).shape[0])
    if n == 0 or _pow2_len(n) == n:
        return device_arrays, None
    padded = [_pad_pow2(a) for a in device_arrays]
    # Padding-tax ledger: real rows vs the pow2 tail, summed over operands.
    itemsizes = [int(jnp.asarray(a).dtype.itemsize) for a in device_arrays]
    _devobs.record_pad(
        "hash_quantize",
        sum(n * sz for sz in itemsizes),
        sum((_pow2_len(n) - n) * sz for sz in itemsizes),
    )
    return padded, n


def combined_hash_u32(columns, device_arrays, seed: np.uint32):
    """Combine multiple key columns into one uint32 hash (one fused program,
    row dimension pow2-quantized — see `ENV_HASH_QUANTIZE`)."""
    device_arrays, n = _quantized_row_inputs(device_arrays)
    kinds, flat = _flat_inputs(columns, device_arrays, (seed,))
    out = _combined_fused(kinds, seed, *flat)
    return out if n is None else out[:n]


def key64(columns, device_arrays, force_float=None):
    """Signed 64-bit join/sort key from two independent 32-bit hash lanes.

    Equal key tuples always map to equal key64 (value-based hashing); unequal tuples
    collide with probability ~2^-64 and are removed by the join's exact-equality
    verification pass. `force_float[i]` hashes numeric column i in the
    cross-kind float64 space (joint decision of both join sides)."""
    device_arrays, n = _quantized_row_inputs(device_arrays)
    kinds, flat = _flat_inputs(columns, device_arrays, (_SEED1, _SEED2), force_float)
    out = _key64_fused(kinds, *flat)
    return out if n is None else out[:n]


def bucket_id(columns, device_arrays, num_buckets: int):
    """Bucket assignment: h1 % num_buckets (the repartition hash)."""
    device_arrays, n = _quantized_row_inputs(device_arrays)
    kinds, flat = _flat_inputs(columns, device_arrays, (_SEED1,))
    out = _bucket_id_fused(kinds, int(num_buckets), *flat)
    return out if n is None else out[:n]
