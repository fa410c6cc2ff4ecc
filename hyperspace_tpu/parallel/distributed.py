"""Distributed index build + join over a jax.sharding.Mesh.

This is the TPU-native replacement for Spark's cluster-wide shuffle machinery
(SURVEY §2.11): the build's `repartition(numBuckets, cols)` becomes an XLA
`all_to_all` over the mesh's ICI, and the co-bucketed join needs NO communication at
all because both sides' bucket blocks are co-located by construction.

Build exchange (two-pass, static shapes — the standard way around ragged all-to-all):
1. Count pass (shard_map): each device computes its per-destination counts of
   VALID rows (the validity lane masks the caller's pad rows out).
2. Host sync: capacity = global max count (one scalar per mesh; amortized, and
   stable across repeated builds of similar data).
3. Exchange pass (shard_map): valid rows sorted by destination, scattered into a
   padded [n_dev, cap] send matrix per column (pad rows take no slot),
   `lax.all_to_all` over the bucket axis, then a local (bucket, keys...) sort of
   the received rows.

Device d ends up owning buckets [d*B/n, (d+1)*B/n) fully sorted — exactly the layout
the bucketed writer persists and the co-bucketed join consumes.

Compile contract (docs/distributed.md): every device program here is declared
through `observed_jit` with a `parallel.*` label, and every shape it traces is
pow2-quantized — callers pad row counts to `mesh.quantized_rows` and the
exchange capacity is floored at the mesh row quantum — so each program
compiles EXACTLY ONCE per process per workload class, verified by the compile
observatory (`tests/test_mesh_compile.py`; the benchmark's `window_compiles`
reading counts persistent-cache misses inside its measured window). The ordering
contract of the receive-side sort is the engine's canonical build order:
stable (bucket, sort keys...) with ties broken by ORIGINAL global row id —
identical to `ops.partition.host_sort_perm`/`_sort_perm`, which is what makes
mesh-built index files byte-identical to single-device ones.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..telemetry import metrics as _metrics
from ..telemetry import stage_ledger as _stage_ledger
from ..telemetry.compile_log import observed_jit as _observed_jit
from ..telemetry.profiling import StageTimings
from .mesh import BUCKET_AXIS, quantize_cap

#: Packed wire lanes ride uint32 words (`engine/packed_codes.py` layout).
_WORD_BITS = 32

#: All-to-all traffic accounting (ticked once per exchange on the host):
#: payload = real row bytes moved, moved = the padded send-matrix bytes the
#: interconnect actually carries. The gap between them is the static-shape
#: padding tax: the `build:exchange` span carries both, and the benchmark's
#: `exchange_padding` reading is 100 × (moved − payload) / moved over its
#: window's counter deltas. `pad_rows` counts the rows the caller padded the
#: input with (validity 0); the count pass skips them and the exchange gives
#: them no send slot.
_EXCHANGE_ROWS = _metrics.counter("parallel.exchange.rows")
_EXCHANGE_PAD_ROWS = _metrics.counter("parallel.exchange.pad_rows")
_EXCHANGE_BYTES_PAYLOAD = _metrics.counter("parallel.exchange.bytes_payload")
_EXCHANGE_BYTES_MOVED = _metrics.counter("parallel.exchange.bytes_moved")
_EXCHANGES = _metrics.counter("parallel.exchange.count")


def _valid_counts(dest, valid, n_dev: int):
    """[1, n_dev] counts of this device's VALID rows per destination."""
    one_hot = jax.nn.one_hot(dest, n_dev, dtype=jnp.int32)
    return jnp.sum(one_hot * valid.astype(jnp.int32)[:, None], axis=0, keepdims=True)


def _send_slots(dest, valid, n_dev: int):
    """(order, destination, slot) of each row in its send matrix [n_dev, cap].
    A pad row (valid 0) takes destination n_dev, past the last: the stable
    argsort puts it last, and its out-of-range row index makes the scatter
    (`mode="drop"`) skip it, so pads never cross the wire. Valid rows keep
    their original (= global) order within each destination."""
    dest = jnp.where(valid != 0, dest, n_dev)
    order = jnp.argsort(dest)  # stable: ties keep original (= global) order
    dest_s = dest[order]
    starts = jnp.searchsorted(dest_s, jnp.arange(n_dev + 1))
    slot = jnp.arange(dest.shape[0]) - starts[dest_s]
    return order, dest_s, slot


def _record_exchange(
    n_valid: int, n_dev: int, cap: int, lanes, packed_spec=None
) -> Tuple[int, int]:
    """Host-side traffic accounting for one exchange call (cheap: arithmetic
    over lane dtypes, no device sync). `packed_spec` (aligned with `lanes`)
    marks bit-packed wire lanes: a packed lane's interconnect cost is its
    uint32 WORD matrix — `cap // lanes_per_word` words per destination — and
    its payload is the true bits-on-the-wire of the valid rows. Returns
    (payload bytes, moved bytes)."""
    _EXCHANGES.inc()
    _EXCHANGE_ROWS.inc(int(n_valid))
    _EXCHANGE_PAD_ROWS.inc(int(lanes[0].shape[0]) - int(n_valid))
    payload = 0
    moved = 0
    for i, lane in enumerate(lanes):
        bits = packed_spec[i][0] if packed_spec is not None else 0
        if bits:
            payload += -(-int(n_valid) * bits // 8)  # ceil(n_valid*bits/8)
            moved += n_dev * n_dev * (cap * bits // _WORD_BITS) * 4
        else:
            item = int(jnp.asarray(lane).dtype.itemsize)
            payload += int(n_valid) * item
            moved += n_dev * n_dev * cap * item
    _EXCHANGE_BYTES_PAYLOAD.inc(payload)
    _EXCHANGE_BYTES_MOVED.inc(moved)
    # The mesh exchange was the ORIGINAL payload-vs-moved honesty split; it
    # now also feeds the generalized padding ledger so `pad_ratio` covers
    # every pow2 staging site with one definition (padding = moved − payload).
    from ..telemetry import device_observatory as _devobs

    _devobs.record_pad("mesh_exchange", payload, moved - payload)
    return payload, moved


def _exchange_cap(
    stages: Optional[StageTimings], mesh: Mesh, bucket, valid, num_buckets: int
) -> int:
    """Pass 1 and the per-destination capacity, the largest count of valid
    rows quantized: the index build's ``exchange_counts`` stage, whose
    ``shard_rows`` is the real rows on the fullest sending shard."""
    with StageTimings.timed_or_not(stages, "exchange_counts") as sp:
        counts = exchange_counts(mesh, bucket, num_buckets, valid)
        max_count = int(counts.max()) if counts.size else 0
        # Quantize to the mesh row quantum's power-of-two grid so repeated
        # builds of growing data reuse ONE compiled exchange instead of
        # recompiling per exact capacity (the compile-boundedness contract).
        cap = quantize_cap(max_count)
        if sp.recording:
            shard_rows = int(counts.sum(axis=1).max()) if counts.size else 0
            sp.add_attrs(cap=cap, max_count=max_count, shard_rows=shard_rows)
    return cap


# Program factories are lru_cached so repeated exchanges (every distributed build
# and every exchanged join in a process) hit jax's compiled-computation cache
# instead of re-tracing a fresh shard_map closure per call.


@lru_cache(maxsize=128)
def _counts_program(mesh: Mesh, num_buckets: int):
    n_dev = mesh.devices.size

    def count_fn(bucket_local, valid_local):
        dest = bucket_local.astype(jnp.int32) * n_dev // num_buckets
        return _valid_counts(dest, valid_local, n_dev)

    return _observed_jit(
        jax.shard_map(
            count_fn,
            mesh=mesh,
            in_specs=(P(BUCKET_AXIS), P(BUCKET_AXIS)),
            out_specs=P(BUCKET_AXIS),
        ),
        label="parallel.exchange_counts",
    )


def exchange_counts(mesh: Mesh, bucket, num_buckets: int, valid) -> np.ndarray:
    """Pass 1: [n_dev, n_dev] matrix of valid rows device i sends to device j."""
    return np.asarray(_counts_program(mesh, num_buckets)(bucket, valid))


def _pack_wire(send, bits: int):
    """Pack a scattered [n_dev, cap] send matrix (already biased into its
    unsigned field range) into [n_dev, cap/lanes_per_word] uint32 words —
    the shared big-endian layout primitive (`engine/packed_codes.py`)."""
    from ..engine.packed_codes import pack_rows_traced

    return pack_rows_traced(send, bits)


def _unpack_wire(words, bits: int, dtype):
    """Inverse of `_pack_wire`: [n_dev, words] uint32 → [n_dev, cap] biased
    field values in the lane's original dtype."""
    from ..engine.packed_codes import unpack_rows_traced

    return unpack_rows_traced(words, bits).astype(dtype)


@lru_cache(maxsize=128)
def _exchange_program(
    mesh: Mesh,
    num_buckets: int,
    cap: int,
    sort_from_payload: tuple,
    packed_spec: tuple = (),
):
    """Pass 2: the all_to_all exchange and the local in-bucket sort. Input
    lanes arrive narrow, and sort keys may be REFERENCED from payload lanes
    (`sort_from_payload` indexes) instead of shipped twice — the k64 of the
    exchanged join travels once.

    `packed_spec` (static, folded into the program cache key like `cap`) is a
    per-lane (bits, bias) tuple aligned with (bucket, valid, *payload, *keys);
    a (0, 0) entry ships the lane as-is. A packed lane is biased by `bias`
    (so the null code -1 lands on the reserved field value 0), bit-packed
    AFTER the destination scatter, crosses the all_to_all as uint32 words,
    and unpacks back to the identical [n_dev, cap] matrix on the receive
    side — pad slots scatter as 0, pack as the bias value, and unpack back
    to 0, so every downstream operand is value-identical to an unpacked
    exchange and the receive-side sort permutation cannot move.

    The local sort puts invalid rows last, then orders by (bucket, sort
    keys...); the final iota operand breaks ties by receive position =
    (sender id, sender-local order) = ORIGINAL GLOBAL ROW ORDER — the
    canonical stable build order every other build path produces."""
    n_dev = mesh.devices.size
    for bits, _bias in packed_spec:
        if bits:
            assert _WORD_BITS % bits == 0 and cap % (_WORD_BITS // bits) == 0, (
                bits,
                cap,
            )

    def fn(bucket_local, valid_local, payload_local, keys_local):
        dest = bucket_local.astype(jnp.int32) * n_dev // num_buckets
        order, dest_s, slot = _send_slots(dest, valid_local, n_dev)

        def _wire(send):
            return jax.lax.all_to_all(
                send, BUCKET_AXIS, split_axis=0, concat_axis=0, tiled=False
            )

        def _spec(i):
            return packed_spec[i] if i < len(packed_spec) else (0, 0)

        def scatter(col, spec=(0, 0)):
            bits, bias = spec
            send = jnp.zeros((n_dev, cap), dtype=col.dtype)
            send = send.at[dest_s, slot].set(col[order], mode="drop")
            if not bits:
                return _wire(send)
            biased = (send.astype(jnp.int32) + bias) if bias else send
            recv = _unpack_wire(_wire(_pack_wire(biased, bits)), bits, jnp.int32)
            if bias:
                recv = recv - bias
            return recv.astype(col.dtype)

        n_pay = len(payload_local)
        valid_recv = scatter(valid_local, _spec(1))
        bucket_recv = scatter(bucket_local, _spec(0))
        payload_recv = [scatter(c, _spec(2 + i)) for i, c in enumerate(payload_local)]
        keys_recv = [scatter(c, _spec(2 + n_pay + i)) for i, c in enumerate(keys_local)]

        # Receive-side widening is free (post-wire); narrowing kept every
        # value, so the sort operands order the rows as the full-width
        # values would.
        flat_valid = valid_recv.reshape(-1).astype(jnp.int32)
        bucket = bucket_recv.reshape(-1).astype(jnp.int32)
        sort_lanes = [payload_recv[i].reshape(-1) for i in sort_from_payload]
        sort_lanes += [k.reshape(-1) for k in keys_recv]
        sort_operands = (
            1 - flat_valid,
            bucket,
            *sort_lanes,
            jnp.arange(flat_valid.shape[0], dtype=jnp.int32),
        )
        res = jax.lax.sort(sort_operands, num_keys=2 + len(sort_lanes))
        perm = res[-1]
        out_bucket = bucket[perm][None]
        out_valid = flat_valid[perm][None]
        out_payload = [c.reshape(-1)[perm][None] for c in payload_recv]
        return out_bucket, out_valid, out_payload

    return _observed_jit(
        jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(P(BUCKET_AXIS), P(BUCKET_AXIS), P(BUCKET_AXIS), P(BUCKET_AXIS)),
            out_specs=(P(BUCKET_AXIS), P(BUCKET_AXIS), P(BUCKET_AXIS)),
        ),
        label="parallel.exchange",
    )


def distributed_bucketize(
    mesh: Mesh,
    bucket,
    payload: Sequence[jnp.ndarray],
    sort_keys: Sequence[jnp.ndarray],
    num_buckets: int,
    in_valid,
    n_valid: int,
    sort_from_payload: Sequence[int] = (),
    packed_spec: Sequence[Tuple[int, int]] = (),
    stages: Optional[StageTimings] = None,
):
    """Full two-pass distributed bucketize. Rows arrive sharded over the
    mesh: `bucket` is the pre-computed (h1 % num_buckets) lane in its
    smallest width, `in_valid` the int8 validity lane (0 marks the caller's
    pad rows, which take no send slot, so the capacity need only hold the
    `n_valid` real rows), and `sort_from_payload` names payload lanes that
    double as sort keys (so they are not shipped twice). `packed_spec`
    (optional, aligned (bucket, valid, *payload, *sort_keys)) bit-packs the
    marked lanes across the all_to_all (`HYPERSPACE_PACKED_CODES`).

    Returns (bucket_ids, valid, payload) blocks sharded over the mesh, one
    bucket range per device: int32 bucket ids, int32 validity and the
    payload lanes in their input dtypes, valid rows sorted by (bucket,
    sort keys...) and grouped before padding. `stages` (the index build's)
    times the two passes as its ``exchange_counts`` and ``exchange``
    stages."""
    # The whole call is the ``exchange`` stage for attribution: the pad
    # ledger tick in _record_exchange and the exchange program's device time
    # bill the mesh lane, not whichever stage submitted the bucketize.
    with _stage_ledger.stage_scope("exchange"):
        cap = _exchange_cap(stages, mesh, bucket, in_valid, num_buckets)
        n_dev = mesh.devices.size
        spec = tuple(tuple(s) for s in packed_spec)
        traffic = _record_exchange(
            n_valid,
            n_dev,
            cap,
            [bucket, in_valid, *payload, *sort_keys],
            packed_spec=spec if spec else None,
        )
        program = _exchange_program(mesh, num_buckets, cap, tuple(sort_from_payload), spec)
        # The ``exchange`` stage ends when the outputs are ready on every device.
        with StageTimings.timed_or_not(stages, "exchange") as sp:
            out = jax.block_until_ready(
                program(bucket, in_valid, list(payload), list(sort_keys))
            )
            if sp.recording:
                sp.add_attrs(
                    devices=n_dev, cap=cap, bytes_payload=traffic[0], bytes_moved=traffic[1]
                )
        return out


# ---------------------------------------------------------------------------
# Distributed co-bucketed join: zero-communication by construction
# ---------------------------------------------------------------------------


@lru_cache(maxsize=64)
def _join_counts_program(mesh: Mesh):
    def fn(ls, rs, ll, rl):
        lo = jax.vmap(lambda r, l: jnp.searchsorted(r, l, side="left"))(rs, ls)
        hi = jax.vmap(lambda r, l: jnp.searchsorted(r, l, side="right"))(rs, ls)
        rl_b = rl[:, None]
        lo = jnp.minimum(lo, rl_b)
        hi = jnp.minimum(hi, rl_b)
        valid = jnp.arange(ls.shape[1])[None, :] < ll[:, None]
        return jnp.sum(jnp.where(valid, hi - lo, 0), axis=1)

    return _observed_jit(
        jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(P(BUCKET_AXIS), P(BUCKET_AXIS), P(BUCKET_AXIS), P(BUCKET_AXIS)),
            out_specs=P(BUCKET_AXIS),
        ),
        label="parallel.join_counts",
    )


def distributed_bucketed_join_counts(
    mesh: Mesh, l_sorted_keys, r_sorted_keys, l_len, r_len
):
    """Per-bucket match counts for co-located padded bucket matrices [B, cap] sharded
    over the mesh's bucket axis. Runs entirely device-local (the proof that the
    co-bucketed layout needs no collectives: the jitted HLO contains none)."""
    return _join_counts_program(mesh)(l_sorted_keys, r_sorted_keys, l_len, r_len)
