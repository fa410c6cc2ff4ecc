"""Distributed build/join tests on the virtual 8-device CPU mesh.

The reference has no in-repo distribution engine (Spark's shuffle does it all,
SURVEY §2.11); these tests validate the TPU-native replacement: all_to_all bucketed
exchange preserves the global multiset and lands each bucket on its owning device,
and the co-bucketed join step runs with zero collectives.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.parallel import (
    BUCKET_AXIS,
    distributed_bucketed_join_counts,
    distributed_bucketize,
    make_mesh,
)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


def _bucket_lanes(keys, num_buckets):
    """The exchange's (bucket, validity) lanes for unpadded int64 keys: the
    (h1 % num_buckets) bucket lane in its narrow dtype, all-valid int8."""
    from hyperspace_tpu.engine.table import Column
    from hyperspace_tpu.ops.hashing import _SEED1, column_hash_u32
    from hyperspace_tpu.parallel.table_ops import _bucket_lane_dtype

    h1 = np.asarray(column_hash_u32(Column.from_values(keys), jnp.asarray(keys), _SEED1))
    bucket = (h1 % np.uint32(num_buckets)).astype(_bucket_lane_dtype(num_buckets))
    return jnp.asarray(bucket), jnp.ones(len(keys), dtype=jnp.int8)


class TestDistributedBucketize:
    def test_exchange_preserves_rows_and_bucket_ownership(self, mesh):
        n_dev = 8
        num_buckets = 32
        n = 4096
        rng = np.random.RandomState(3)
        keys = rng.randint(0, 10_000, size=n).astype(np.int64)
        payload = np.arange(n, dtype=np.int64)

        lead, in_valid = _bucket_lanes(keys, num_buckets)
        bucket, valid, (pay_out, keys_out) = distributed_bucketize(
            mesh, lead, [jnp.asarray(payload), jnp.asarray(keys)], [jnp.asarray(keys)],
            num_buckets, in_valid, n,
        )
        # Outputs are [n_dev, n_dev*cap]: one padded block row per device.
        bucket = np.asarray(bucket)
        valid = np.asarray(valid).astype(bool)
        pay_out = np.asarray(pay_out)
        keys_out = np.asarray(keys_out)
        assert bucket.shape[0] == n_dev

        # All rows survive exactly once.
        assert valid.sum() == n
        assert sorted(pay_out[valid].tolist()) == sorted(payload.tolist())

        # Each device block holds only its own bucket range, valid rows first,
        # sorted by (bucket, key).
        for d in range(n_dev):
            dvalid = valid[d]
            vb = bucket[d][dvalid]
            if len(vb) == 0:
                continue
            assert (vb * n_dev // num_buckets == d).all()
            nv = int(dvalid.sum())
            assert dvalid[:nv].all()  # valid rows are contiguous at the front
            vk = keys_out[d][dvalid]
            order = np.lexsort((vk, vb))
            assert (order == np.arange(len(vb))).all()

    def test_matches_single_device_bucketing(self, mesh):
        """Same hash function ⇒ distributed bucket assignment agrees with the
        single-device build path."""
        from hyperspace_tpu.engine.table import Column, Table
        from hyperspace_tpu.ops.partition import bucketize_table

        n = 512
        num_buckets = 16
        keys = np.random.RandomState(0).randint(0, 100, n).astype(np.int64)
        t = Table({"k": Column.from_values(keys)})
        sorted_t, starts = bucketize_table(t, ["k"], num_buckets)
        single_sizes = np.diff(starts)

        lead, in_valid = _bucket_lanes(keys, num_buckets)
        bucket, valid, _ = distributed_bucketize(
            mesh, lead, [jnp.asarray(keys)], [jnp.asarray(keys)], num_buckets, in_valid, n
        )
        bucket = np.asarray(bucket)[np.asarray(valid).astype(bool)]
        dist_sizes = np.bincount(bucket, minlength=num_buckets)
        assert (dist_sizes == single_sizes).all()


class TestDistributedJoin:
    def test_join_counts_with_no_collectives(self, mesh):
        B, cap = 32, 64
        rng = np.random.RandomState(1)
        lk = np.sort(rng.randint(0, 50, size=(B, cap)), axis=1).astype(np.int64)
        rk = np.sort(rng.randint(0, 50, size=(B, cap)), axis=1).astype(np.int64)
        l_len = np.full(B, cap, dtype=np.int64)
        r_len = np.full(B, cap, dtype=np.int64)

        counts = np.asarray(
            distributed_bucketed_join_counts(
                mesh, jnp.asarray(lk), jnp.asarray(rk), jnp.asarray(l_len), jnp.asarray(r_len)
            )
        )
        # Oracle: per-bucket pair counts.
        expect = np.array(
            [
                sum(int((rk[b] == v).sum()) for v in lk[b])
                for b in range(B)
            ]
        )
        assert (counts == expect).all()

        # The compiled HLO must contain no cross-device communication.
        lowered = jax.jit(
            lambda a, b, c, d: distributed_bucketed_join_counts(mesh, a, b, c, d)
        ).lower(jnp.asarray(lk), jnp.asarray(rk), jnp.asarray(l_len), jnp.asarray(r_len))
        hlo = lowered.compile().as_text()
        for coll in ("all-to-all", "all-reduce", "collective-permute", "all-gather"):
            assert coll not in hlo, f"unexpected collective {coll} in bucketed join HLO"

    def test_build_exchange_does_use_all_to_all(self, mesh):
        """Sanity check on the inverse: the build exchange genuinely communicates."""
        from hyperspace_tpu.parallel.distributed import _exchange_program

        n = 256
        keys = np.random.RandomState(2).randint(0, 2**31, n).astype(np.int64)
        lead, in_valid = _bucket_lanes(keys, 16)
        pay = jnp.arange(n, dtype=jnp.int64)
        lowered = jax.jit(_exchange_program(mesh, 16, 64, ())).lower(
            lead, in_valid, [pay], [pay]
        )
        hlo = lowered.compile().as_text()
        assert "all-to-all" in hlo


class TestPadRowsTakeNoSlot:
    """The caller's pad rows (validity 0) neither count toward the exchange
    capacity nor cross the wire, and the real rows are spread evenly over the
    shards (`table_ops._even_shards`)."""

    N_DEV, NUM_BUCKETS, N = 8, 32, 600  # 75 real rows a shard, 53 pads each

    @pytest.mark.parametrize("packed", ["0", "1"], ids=["packed_off", "packed"])
    def test_pads_neither_size_the_cap_nor_arrive(self, mesh, monkeypatch, packed):
        from hyperspace_tpu.engine.table import Column, Table
        from hyperspace_tpu.ops.hashing import _SEED1, column_hash_u32
        from hyperspace_tpu.ops.partition import bucketize_table
        from hyperspace_tpu.parallel import table_ops
        from hyperspace_tpu.parallel.mesh import ENV_ROW_QUANTUM, quantize_cap, quantized_rows
        from hyperspace_tpu.telemetry import tracing
        from hyperspace_tpu.telemetry.profiling import StageTimings

        # A small quantum lets the capacity tell 600 real rows from their pads
        # (32: the packed 1-bit validity lane needs whole words per destination).
        monkeypatch.setenv(ENV_ROW_QUANTUM, "32")
        monkeypatch.setenv("HYPERSPACE_PACKED_CODES", packed)
        n_dev, nb, n = self.N_DEV, self.NUM_BUCKETS, self.N
        keys = np.random.RandomState(5).randint(1, 10_000, n).astype(np.int64)
        t = Table({"k": Column.from_values(keys), "rid": Column.from_values(np.arange(n))})

        # Mark every pad row's id -1 on its way in, so an arriving pad shows.
        real, seen = table_ops.distributed_bucketize, {}

        def spy(mesh_, lead, payload, keys_, nb_, in_valid, n_valid, **kw):
            payload = [jnp.where(in_valid != 0, payload[0], -1)]
            seen["spec"] = kw.get("packed_spec")
            seen["out"] = real(mesh_, lead, payload, keys_, nb_, in_valid, n_valid, **kw)
            return seen["out"]

        monkeypatch.setattr(table_ops, "distributed_bucketize", spy)
        with tracing.capture() as cap, tracing.query_span("build:create_index"):
            with StageTimings("build", "mesh") as st:
                out, starts = table_ops.distributed_bucketize_table(
                    mesh, t, ["k"], nb, stages=st
                )
                st.summary()
        (counts_sp,) = cap.trace.find("build:exchange_counts")
        assert bool(seen["spec"]) == (packed == "1")

        # The count matrix from the same hashes, in numpy: shard d sends
        # real rows [d*q, (d+1)*q); every pad (key 0) hashes to one bucket.
        def dest_of(k):
            h1 = np.asarray(column_hash_u32(Column.from_values(k), jnp.asarray(k), _SEED1))
            return (h1 % nb).astype(np.int64) * n_dev // nb

        dest, pad_dest = dest_of(keys), int(dest_of(np.zeros(1, np.int64))[0])
        q = -(-n // n_dev)
        shard = quantized_rows(n, n_dev) // n_dev
        valid_counts = np.stack(
            [np.bincount(dest[d * q:(d + 1) * q], minlength=n_dev) for d in range(n_dev)]
        )
        with_pads = valid_counts.copy()
        with_pads[:, pad_dest] += shard - valid_counts.sum(axis=1)
        assert (shard - q) * n_dev > n // n_dev  # more pads than real rows a destination
        assert counts_sp.attrs["max_count"] == valid_counts.max()
        assert counts_sp.attrs["shard_rows"] == q
        assert counts_sp.attrs["cap"] == quantize_cap(valid_counts.max())
        assert counts_sp.attrs["cap"] < quantize_cap(with_pads.max())

        # Every valid row arrives exactly once; empty slots read 0 and no
        # pad (id -1) arrives.
        _, valid, (rowid,) = seen["out"]
        valid = np.asarray(valid).reshape(-1).astype(bool)
        rowid = np.asarray(rowid).reshape(-1)
        assert valid.size == n_dev * n_dev * counts_sp.attrs["cap"]
        assert sorted(rowid[valid].tolist()) == list(range(n))
        assert (rowid[~valid] == 0).all()

        # And the output is the single-device build's order.
        single, single_starts = bucketize_table(t, ["k"], nb)
        assert (out.column("rid").data == single.column("rid").data).all()
        assert (np.asarray(starts) == np.asarray(single_starts)).all()


class TestEvenShards:
    @pytest.mark.parametrize("n", [0, 3, 7, 8, 600, 1001, 4096])
    def test_real_rows_spread_evenly_in_order(self, n):
        from hyperspace_tpu.parallel.mesh import quantized_rows
        from hyperspace_tpu.parallel.table_ops import _even_shards, _wire_rows

        n_dev = 8
        n_pad_total = quantized_rows(n, n_dev)
        valid, rowid = _wire_rows(n, n_dev, n_pad_total, np.int8, np.int32)
        lane = _even_shards(np.arange(1, n + 1), n_dev, n_pad_total)
        assert valid.shape == rowid.shape == lane.shape == (n_pad_total,)
        valid = valid.reshape(n_dev, -1).astype(bool)
        per_shard = valid.sum(axis=1)
        assert per_shard.sum() == n
        assert per_shard.max() <= max(1, -(-n // n_dev))
        # Real rows lead each shard, in shard order: the concatenation is the
        # original row order, and every pad slot reads 0.
        for d in range(n_dev):
            assert valid[d, : per_shard[d]].all() and not valid[d, per_shard[d]:].any()
        assert (rowid.reshape(n_dev, -1)[valid] == np.arange(n)).all()
        assert (lane.reshape(n_dev, -1)[valid] == np.arange(1, n + 1)).all()
        assert (lane.reshape(n_dev, -1)[~valid] == 0).all()
