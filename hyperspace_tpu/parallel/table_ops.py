"""Table-level distributed operations: the bridge between host `Table`s and the
mesh kernels in `distributed.py`.

These are the entry points the REAL build and query paths call (not just tests):

- `distributed_bucketize_perm` — the index build's shuffle. The TPU-native
  analogue of the reference's cluster-wide `repartition(numBuckets, cols)` +
  bucketed write (`CreateActionBase.scala:119-140`): rows leave the host as
  row-sharded blocks, ride a two-pass `lax.all_to_all` to their bucket's device,
  and come back grouped by bucket and sorted within bucket, as a permutation.
  Same order as the single-device `ops.partition.bucketize_table` (identical
  hash AND identical stable tie order → byte-identical index files);
  `distributed_bucketize_table` applies it to the table.
- `distributed_exchange_table` — the general join's ShuffleExchange. Both sides
  exchanged with the same key hash are co-partitioned, so the merge join after it
  needs no further communication.
- `distributed_bucketed_join_pairs` — the co-bucketed sort-merge join probe,
  sharded over the mesh's bucket axis with ZERO collectives (the whole point of
  the covering-index layout, reference `JoinIndexRule.scala:137-162`). Same
  contract as `ops.bucket_join.bucketed_merge_join_pairs`.

Compile contract: every shape that reaches a device program here is quantized
on the `mesh.quantize_cap`/`mesh.quantized_rows` pow2 grid — the hash inputs
are padded BEFORE hashing (so `hashing.combined_hash`/`hashing.key64` trace
one shape per workload class, not one per table size — the exact failure mode
that hung the r05 TPU bench for 2400 s inside `ops/hashing.bucket_id`), and
the exchange/probe capacities are floored at the mesh row quantum. Each
`parallel.*` program compiles exactly once per process per class, asserted by
`tests/test_mesh_compile.py`. A mesh build reports each step as a
`build:<stage>` span (`hash`, `h2d`, `exchange_counts`, `exchange`,
`gather`); the benchmark's `exchange_ms` and `mesh_sort_roofline` read the
exchange's two, and `exchange_padding` the `parallel.exchange.*` counters.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine.encoded_device import narrow_codes, record_mesh_wire_stage
from ..engine.table import Table
from ..ops.hashing import _SEED1, combined_hash_u32, key64
from ..telemetry.compile_log import observed_jit as _observed_jit
from ..telemetry.profiling import StageTimings
from .distributed import distributed_bucketize
from .mesh import BUCKET_AXIS, quantize_cap, quantized_rows, row_sharding

_PAD = np.iinfo(np.int64).max


def _bucket_lane_dtype(num_buckets: int):
    """Smallest signed width carrying bucket ids [0, num_buckets)."""
    if num_buckets <= 127:
        return np.int8
    if num_buckets <= 32767:
        return np.int16
    return np.int32


def _coded_rowid_dtype(n_pad_total: int):
    """int32 row ids whenever the padded global row count fits (it always
    does at realistic per-host scales; the int64 fallback keeps the contract
    total)."""
    return np.int32 if n_pad_total <= np.iinfo(np.int32).max else np.int64


def _packed_wire_spec(
    num_buckets: int, bucket_np, n_pad_total: int, rowid_p, key_pairs=()
):
    """Per-lane (bits, bias) mesh-wire spec, ordered (bucket, valid,
    rowid, *extra payload/keys) — empty when the packed layer is off. A lane
    packs only when its wire class genuinely beats its narrow itemsize (an
    int8 bucket lane needing 6 bits stays int8; an int32 row-id lane under
    65537 padded rows drops to 16 bits). `key_pairs` is (column, staged lane)
    per sort key; string keys within a sub-byte class pack biased by 1 so the
    null code -1 lands on the reserved field value 0."""
    from ..engine.packed_codes import (
        bits_for_cardinality,
        packed_codes_enabled,
        wire_bits_for_range,
    )

    if not packed_codes_enabled():
        return ()

    def lane(n_values, arr, bias=0):
        bits = wire_bits_for_range(n_values)
        if bits is None or bits >= 8 * int(arr.dtype.itemsize):
            return (0, 0)
        return (bits, bias)

    spec = [
        lane(num_buckets, bucket_np),
        (1, 0),  # validity: int8 {0, 1} -> 1 bit
        lane(n_pad_total, rowid_p),
    ]
    for col, staged in key_pairs:
        bits = None
        if getattr(col, "is_string", False) and col.dictionary is not None:
            if staged.dtype in (np.int8, np.int16):  # actually narrowed
                bits = bits_for_cardinality(len(col.dictionary))
        spec.append((bits, 1) if bits else (0, 0))
    return tuple(spec)


def _pad_rows(arr: np.ndarray, pad: int, fill=0) -> np.ndarray:
    if pad == 0:
        return arr
    return np.concatenate([arr, np.full(pad, fill, dtype=arr.dtype)])


def _even_shards(arr: np.ndarray, n_dev: int, n_pad_total: int) -> np.ndarray:
    """One exchange wire lane, row-sharded as [n_dev, S] (S = n_pad_total /
    n_dev) and flattened: shard d holds real rows [d*q, min((d+1)*q, n)),
    q = ceil(n / n_dev), then zeros (pad rows, valid 0) to its end. Spreading
    the real rows evenly keeps every shard's sends, and with them the
    exchange capacity, at the real rows' share; keeping them contiguous and
    in shard order keeps (sender, sender-local position) the original global
    row order, the receive sort's tie-break."""
    n = len(arr)
    shard = n_pad_total // n_dev
    q = max(1, -(-n // n_dev))
    full = n // q  # shards filled to q real rows
    out = np.empty((n_dev, shard), dtype=arr.dtype)
    out[:full, :q] = arr[: full * q].reshape(full, q)
    out[:full, q:] = 0
    if full < n_dev:
        rest = n - full * q
        out[full, :rest] = arr[full * q :]
        out[full, rest:] = 0
        out[full + 1 :] = 0
    return out.reshape(-1)


def _wire_rows(n: int, n_dev: int, n_pad_total: int, valid_dtype, rowid_dtype):
    """The (validity, row id) wire lanes of `n` real rows in the
    `_even_shards` layout: row ids carry each row's original index, so the
    permutation the exchange returns is in source-row terms."""
    valid = _even_shards(np.ones(n, valid_dtype), n_dev, n_pad_total)
    rowid = _even_shards(np.arange(n, dtype=rowid_dtype), n_dev, n_pad_total)
    return valid, rowid


def _key_lane(col) -> np.ndarray:
    """A key column's values as the exchange ships them: a string column
    rides its dictionary codes, narrowed to the dictionary's width. The hash
    gathers dh_table[codes] and narrowing keeps every code value, so the
    hashes and the receive-side (bucket, keys..., row) order are those of
    the full-width codes."""
    if col.is_string and col.dictionary is not None:
        return narrow_codes(col)
    return col.data


def _padded_hash_inputs(cols, pad: int):
    """Device inputs for the fused hash programs, padded to the quantized row
    count BEFORE hashing: the hash is elementwise, so padding changes nothing
    for the real rows, and the program traces ONE shape per pow2 class
    instead of one per exact table size. String columns ride their dictionary
    codes (pad code 0 = a valid in-range index; the pad rows' hashes are
    sliced off before the wire lanes are laid out)."""
    return [jnp.asarray(_pad_rows(_key_lane(c), pad)) for c in cols]


def _exchange_keys(
    mesh: Mesh,
    cols,
    n: int,
    num_buckets: int,
    key64_lane: bool = False,
    stages: Optional[StageTimings] = None,
):
    """Hash `n` rows' key columns, stage the exchange's wire lanes on the mesh
    and run the exchange (`distributed_bucketize`). Returns its (bucket ids,
    validity, payload) blocks; payload is (row id,), or (row id, k64) with
    `key64_lane`.

    Every lane is in the even layout (`_even_shards`) and as narrow as its
    values allow: the (h1 % num_buckets) bucket lane in the smallest width
    `num_buckets` fits, int8 validity, int32 row ids when the padded row
    count fits, string keys as dictionary codes in the dictionary's width.
    The build ships the key columns as sort lanes; the join (`key64_lane`)
    ships one 64-bit key lane as payload that doubles as the sort key, so it
    crosses the interconnect once. `stages` (the index build's) times the
    hashing as ``hash`` and the lanes built and put on the mesh as ``h2d``."""
    n_dev = mesh.devices.size
    n_pad_total = quantized_rows(n, n_dev)
    pad = n_pad_total - n
    with StageTimings.timed_or_not(stages, "hash", rows=n, pad_rows=pad):
        arrs_p = _padded_hash_inputs(cols, pad)
        h1_np = np.asarray(combined_hash_u32(cols, arrs_p, _SEED1))

    def even(a):
        return _even_shards(a, n_dev, n_pad_total)

    with StageTimings.timed_or_not(stages, "h2d") as sp:
        bucket = even(
            (h1_np[:n] % np.uint32(num_buckets)).astype(_bucket_lane_dtype(num_buckets))
        )
        valid, rowid = _wire_rows(
            n, n_dev, n_pad_total, np.int8, _coded_rowid_dtype(n_pad_total)
        )
        if key64_lane:
            payload = [rowid, even(np.asarray(key64(cols, arrs_p))[:n])]
            keys = []
            key_pairs = [(None, payload[1])]
            flat_keys = [np.int64, np.int64]  # k64 as payload, then as sort key
            sort_from_payload = (1,)
        else:
            payload = [rowid]
            keys = []
            for c in cols:
                a = _key_lane(c)
                keys.append(even(a.astype(np.int32) if a.dtype == np.bool_ else a))
            key_pairs = list(zip(cols, keys))
            flat_keys = [
                np.int32 if c.data.dtype == np.bool_ else c.data.dtype for c in cols
            ]
            sort_from_payload = ()
        host = [bucket, valid, *payload, *keys]
        packed_spec = _packed_wire_spec(
            num_buckets, bucket, n_pad_total, rowid, key_pairs
        )
        record_mesh_wire_stage(
            n_pad_total,
            [np.uint32, np.int32, np.int64, *flat_keys],
            host,
            packed_spec=packed_spec or None,
        )
        sh = row_sharding(mesh)
        lanes = jax.block_until_ready([jax.device_put(jnp.asarray(a), sh) for a in host])
        if sp.recording:
            sp.set_attr("bytes", sum(a.nbytes for a in host))

    n_pay = len(payload)
    return distributed_bucketize(
        mesh,
        lanes[0],
        lanes[2 : 2 + n_pay],
        lanes[2 + n_pay :],
        num_buckets,
        lanes[1],
        n,
        sort_from_payload=sort_from_payload,
        packed_spec=packed_spec,
        stages=stages,
    )


def _gather_valid_perm(
    bucket, valid, rowid, n: int, num_buckets: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Host-gather an exchange result into (permutation, bucket starts).

    Device d's block holds its bucket range with valid rows first, sorted by
    (bucket, keys...); blocks are in device order, so the concatenation is globally
    grouped by bucket."""
    valid_h = np.asarray(valid).reshape(-1).astype(bool)
    perm = np.asarray(rowid).reshape(-1)[valid_h]
    bucket_v = np.asarray(bucket).reshape(-1)[valid_h]
    assert len(perm) == n, f"exchange dropped rows: {len(perm)} != {n}"
    return perm, np.searchsorted(bucket_v, np.arange(num_buckets + 1))


def distributed_bucketize_perm(
    mesh: Mesh,
    table: Table,
    bucket_columns: Sequence[str],
    num_buckets: int,
    stages: Optional[StageTimings] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Mesh-wide hash-partition + in-bucket sort of `table`'s rows: the
    (permutation, bucket starts) of the canonical build order, the
    permutation in source-row terms.

    The exchange moves (bucket id, row id, sort keys) over ICI; only the
    permutation comes back to the host. Bucket assignment is identical to the
    single-device path (h1 % num_buckets over the same column hash) AND the
    within-bucket order is the same canonical stable (bucket, keys...,
    original row) order, so rows written in this order give BYTE-IDENTICAL
    index files — `HYPERSPACE_DISTRIBUTED=0` is an exact fallback, pinned by
    the on/off oracles in tests/test_mesh_compile.py. The index build writes
    through this order without reordering the table first
    (`index/build_pipeline.write_sorted`).

    `stages` (the index build's) times each step as a stage: ``hash`` and
    ``h2d`` (`_exchange_keys`), ``exchange_counts`` and ``exchange``
    (`distributed.py`), and ``gather`` (the exchange's output copied back into
    the permutation and bucket starts)."""
    n = table.num_rows
    cols = [table.column(c) for c in bucket_columns]
    bucket, out_valid, (rowid_out,) = _exchange_keys(
        mesh, cols, n, num_buckets, stages=stages
    )
    with StageTimings.timed_or_not(stages, "gather") as sp:
        perm, starts = _gather_valid_perm(bucket, out_valid, rowid_out, n, num_buckets)
        if sp.recording:
            sp.set_attr("bytes", bucket.nbytes + out_valid.nbytes + rowid_out.nbytes)
    return perm, starts


def distributed_bucketize_table(
    mesh: Mesh,
    table: Table,
    bucket_columns: Sequence[str],
    num_buckets: int,
    stages: Optional[StageTimings] = None,
) -> Tuple[Table, np.ndarray]:
    """`distributed_bucketize_perm` with the table reordered on the host; a
    drop-in for `ops.partition.bucketize_table`."""
    perm, starts = distributed_bucketize_perm(
        mesh, table, bucket_columns, num_buckets, stages
    )
    return table.take(perm), starts


def distributed_exchange_table(
    mesh: Mesh,
    table: Table,
    key_columns: Sequence[str],
    partitions_per_device: int = 8,
) -> Tuple[Table, np.ndarray, "DistBlocks"]:
    """Real hash exchange of a table over the mesh — what `ShuffleExchangeExec`
    executes in distributed mode. Returns (reordered table, partition starts,
    device-resident key blocks). Two tables exchanged on compatible keys with the
    same mesh are co-partitioned: partition p of both sides lands on the same
    device, so the downstream merge join runs with no further communication — and
    the exchanged keys STAY on device between exchange and probe (the r2 review
    flagged the old host round-trip of the full key column here).

    Hidden assumption made explicit: the probe consumes each device's exchange
    output block directly, so the block must hold that device's partitions with
    valid rows first, sorted by (partition, key64) — exactly what
    `distributed_bucketize`'s receive-side sort produces."""
    n_dev = mesh.devices.size
    num_partitions = n_dev * partitions_per_device
    n = table.num_rows
    cols = [table.column(c) for c in key_columns]
    bucket, out_valid, (rowid_out, k64_out) = _exchange_keys(
        mesh, cols, n, num_partitions, key64_lane=True
    )
    perm, starts = _gather_valid_perm(bucket, out_valid, rowid_out, n, num_partitions)

    # Device-resident key blocks for the co-partitioned probe: invalid slots
    # masked to the probe's pad value (sort-last), real keys clipped below it.
    masked = jnp.where(
        out_valid.astype(bool), jnp.minimum(k64_out, _PAD - 1), _PAD
    )
    buckets_local = num_partitions // n_dev
    lens = np.diff(starts)
    cap = quantize_cap(int(lens.max())) if lens.size and lens.max(initial=0) else 1
    _, lstarts = _local_starts(starts, n_dev, buckets_local)
    blocks = DistBlocks(
        masked,
        jax.device_put(jnp.asarray(lstarts), NamedSharding(mesh, P(BUCKET_AXIS))),
        starts,
        buckets_local,
        cap,
    )
    return table.take(perm), starts, blocks


# ---------------------------------------------------------------------------
# Sharded co-bucketed join probe (zero collectives)
# ---------------------------------------------------------------------------


@lru_cache(maxsize=64)
def _probe_program(mesh: Mesh, buckets_local: int, cap_l: int, cap_r: int):
    """Compiled sharded pad+sort+probe: each device scatters its bucket block into
    padded [B_local, cap] matrices, argsorts within bucket, and range-probes —
    entirely device-local (the jitted HLO contains no collectives)."""

    def fn(lk, lst, rk, rst):
        lk, lst, rk, rst = lk[0], lst[0], rk[0], rst[0]

        def pad_sort(keys, starts, cap):
            m = keys.shape[0]
            pos = jnp.arange(m)
            b_of = jnp.clip(jnp.searchsorted(starts, pos, side="right") - 1, 0, buckets_local - 1)
            slot = pos - starts[b_of]
            padded = jnp.full((buckets_local, cap), _PAD, dtype=jnp.int64)
            # Host-pad rows past the block's real size get slots beyond their
            # bucket's length (harmless: PAD sorts last, lengths mask them) or
            # beyond cap (dropped).
            padded = padded.at[b_of, slot].set(keys, mode="drop")
            order = jnp.argsort(padded, axis=1)
            sorted_keys = jnp.take_along_axis(padded, order, axis=1)
            lengths = starts[1:] - starts[:-1]
            return sorted_keys, order, lengths

        ls, l_order, l_len = pad_sort(lk, lst, cap_l)
        rs, r_order, r_len = pad_sort(rk, rst, cap_r)
        lo = jax.vmap(lambda r, l: jnp.searchsorted(r, l, side="left"))(rs, ls)
        hi = jax.vmap(lambda r, l: jnp.searchsorted(r, l, side="right"))(rs, ls)
        r_len_b = r_len[:, None]
        lo = jnp.minimum(lo, r_len_b)
        hi = jnp.minimum(hi, r_len_b)
        valid_left = jnp.arange(cap_l)[None, :] < l_len[:, None]
        counts = jnp.where(valid_left, hi - lo, 0)
        return lo, counts, l_order, r_order

    return _observed_jit(
        jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(P(BUCKET_AXIS), P(BUCKET_AXIS), P(BUCKET_AXIS), P(BUCKET_AXIS)),
            out_specs=(P(BUCKET_AXIS), P(BUCKET_AXIS), P(BUCKET_AXIS), P(BUCKET_AXIS)),
        ),
        label="parallel.probe",
    )


def _local_starts(
    starts_np: np.ndarray, n_dev: int, buckets_local: int
) -> Tuple[np.ndarray, np.ndarray]:
    """(device bounds [n_dev+1], per-device local bucket offsets [n_dev, B_local+1])
    — the single source of the 'device d owns its contiguous bucket range' layout
    contract shared by the host block builder and the exchange output."""
    bounds = starts_np[0 :: buckets_local][: n_dev + 1]
    local = np.zeros((n_dev, buckets_local + 1), dtype=np.int64)
    for d in range(n_dev):
        local[d] = (
            starts_np[d * buckets_local : (d + 1) * buckets_local + 1] - bounds[d]
        )
    return bounds, local


def _block_layout(
    keys_np: np.ndarray, starts_np: np.ndarray, n_dev: int, buckets_local: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Lay out per-row keys (bucket order) as [n_dev, max_block] device blocks plus
    per-device local bucket offsets [n_dev, B_local+1]; device d's block is its
    contiguous bucket range — host→device transfer is one sharded device_put."""
    bounds, local_starts = _local_starts(starts_np, n_dev, buckets_local)
    max_block = quantize_cap(int(np.diff(bounds).max()) if n_dev else 1)
    blocks = np.full((n_dev, max_block), _PAD, dtype=np.int64)
    for d in range(n_dev):
        lo, hi = int(bounds[d]), int(bounds[d + 1])
        blocks[d, : hi - lo] = keys_np[lo:hi]
    return blocks, local_starts


class DistBlocks:
    """Device-resident sharded block layout of one join side: `blocks`
    [n_dev, max_block] (device, sharded over the bucket axis), `lstarts`
    [n_dev, B_local+1] (device, sharded), plus the host metadata the expansion
    needs. Built ONCE per (table, mesh) — the steady-state sharded join re-probes
    these without any host round-trip of the key columns."""

    __slots__ = ("blocks", "lstarts", "starts_np", "buckets_local", "cap")

    def __init__(self, blocks, lstarts, starts_np, buckets_local, cap):
        self.blocks = blocks
        self.lstarts = lstarts
        self.starts_np = starts_np
        self.buckets_local = buckets_local
        self.cap = cap

    @property
    def nbytes(self) -> int:
        total = 0
        for a in (self.blocks, self.lstarts, self.starts_np):
            total += int(getattr(a, "nbytes", 0) or 0)
        return total


#: Steady-state instrumentation: how many block layouts were BUILT (host→device
#: upload) vs how many probes ran. A cached steady state probes >> builds.
DIST_JOIN_STATS = {"block_builds": 0, "probes": 0}


def pad_starts_to_mesh(starts_np: np.ndarray, n_dev: int) -> np.ndarray:
    """Append empty virtual buckets so the bucket count divides the mesh (the
    default 200-bucket index rides a 16-device mesh: 200 → 208 empty-padded)."""
    B = len(starts_np) - 1
    pad_b = (-B) % n_dev
    if not pad_b:
        return starts_np
    return np.concatenate(
        [starts_np, np.full(pad_b, starts_np[-1], dtype=starts_np.dtype)]
    )


def build_dist_blocks(mesh: Mesh, keys, starts_np: np.ndarray) -> Optional[DistBlocks]:
    """Lay one side's keys out as sharded device blocks (one-time host work; the
    result is cached by the caller per table identity)."""
    n_dev = mesh.devices.size
    starts_np = pad_starts_to_mesh(starts_np, n_dev)
    B = len(starts_np) - 1
    if B == 0:
        return None
    buckets_local = B // n_dev
    lens = np.diff(starts_np)
    if lens.max(initial=0) == 0:
        return None
    cap = quantize_cap(int(lens.max()))
    keys_np = np.minimum(np.asarray(keys), _PAD - 1)
    blocks, lstarts = _block_layout(keys_np, starts_np, n_dev, buckets_local)
    sh = NamedSharding(mesh, P(BUCKET_AXIS))
    DIST_JOIN_STATS["block_builds"] += 1
    return DistBlocks(
        jax.device_put(jnp.asarray(blocks), sh),
        jax.device_put(jnp.asarray(lstarts), sh),
        starts_np,
        buckets_local,
        cap,
    )


def probe_dist_blocks(
    mesh: Mesh, left: DistBlocks, right: DistBlocks
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Sharded zero-collective probe of two pre-built block layouts → global
    (left_row, right_row) pairs. The per-query device→host traffic is the probe
    OUTPUT (lo/counts/orders — bounded by bucket capacity), never the keys.

    Probes the SMALLER side into the larger (search count scales with the
    probing side's capacity), swapping the output pair order back."""
    if left.buckets_local != right.buckets_local:
        return None
    if left.cap > right.cap:
        out = probe_dist_blocks(mesh, right, left)
        if out is None:
            return None
        ri, li = out
        return li, ri
    DIST_JOIN_STATS["probes"] += 1
    lo, counts, l_order, r_order = _probe_program(
        mesh, left.buckets_local, left.cap, right.cap
    )(left.blocks, left.lstarts, right.blocks, right.lstarts)
    counts_h = np.asarray(counts)
    total = int(counts_h.sum())
    if total == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)

    from ..ops.bucket_join import _expand_np

    return _expand_np(
        np.asarray(lo),
        counts_h,
        left.starts_np,
        right.starts_np,
        np.asarray(l_order),
        np.asarray(r_order),
    )


def distributed_bucketed_join_pairs(
    mesh: Mesh,
    l_keys,
    l_starts_np: np.ndarray,
    r_keys,
    r_starts_np: np.ndarray,
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Mesh-sharded equivalent of `bucketed_merge_join_pairs`: all bucket pairs
    probed concurrently, each on the device owning that bucket range, with no data
    exchange. Bucket counts that don't divide the mesh are padded with virtual
    EMPTY buckets (zero length → zero probe work), so the default 200-bucket index
    still takes this path on any mesh size (200 % 16 != 0 included). Returns None
    only when the two sides' bucket counts disagree (caller falls back to the
    single-device kernel).

    Uncached convenience entry (block layouts rebuilt per call); the engine's
    steady-state path caches `build_dist_blocks` per table identity instead."""
    if len(l_starts_np) - 1 != len(r_starts_np) - 1:
        return None
    l_blocks = build_dist_blocks(mesh, l_keys, l_starts_np)
    r_blocks = build_dist_blocks(mesh, r_keys, r_starts_np)
    if l_blocks is None or r_blocks is None:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    return probe_dist_blocks(mesh, l_blocks, r_blocks)
