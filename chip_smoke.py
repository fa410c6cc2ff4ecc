"""Bring-up smoke run: the main path once, on the chip, through the public API.

    python chip_smoke.py              # one chip: TPC-H SF1 cardinalities
    python chip_smoke.py --chips 4    # the mesh path on four chips only

One chip: generate the bench's TPC-H-shaped lineitem/orders/part tables from
a fixed seed (6,000,000 / 1,500,000 / 200,000 rows, 64 buckets), build four
covering indexes with `create_index`, then answer a Q3-shaped join-aggregate,
a Q14-shaped filter-join-aggregate and point lookups on `orderkey` submitted
through `QueryServer.run`, each with indexing on. Every answer must equal the
index-off answer (`disable_hyperspace`), and the join rewrites must have
fired. A last phase checks each Pallas kernel against numpy at the widest
shapes its dispatch gate admits.

`--chips 4`: build the lineitem and orders indexes and run the indexed Q3
join (lineitem ⋈ orders rows) on the 4-device mesh, and again at
`HYPERSPACE_DISTRIBUTED=0` in the same process; index files and rows must be
identical, and the sharded state must sit on every device.

Earlier stdout lines carry a JSON record of phase wall times, compiles, cache
traffic, peak device memory and the dispatched programs — smoke timings, not
benchmark metrics. The last line is `{"ok": true, "device": {...}}`, printed
only when every phase passed on a TPU. Everything runs in this one process.
"""

import argparse
import hashlib
import glob
import json
import math
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

SF1 = {"n_lineitem": 6_000_000, "n_orders": 1_500_000, "n_part": 200_000}
NUM_BUCKETS = 64
N_LOOKUPS = 20
SEED = 42
REWRITE_MARK = "bucketed, no exchange"


def _same_rows(got, want, what: str) -> None:
    """Sorted-row equality; float cells (sums whose association order differs
    between plans) compare to 1e-9 relative."""
    got, want = sorted(got), sorted(want)
    if len(got) != len(want):
        raise AssertionError(f"{what}: {len(got)} rows indexed vs {len(want)} index-off")
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            if isinstance(a, float) or isinstance(b, float):
                if not math.isclose(a, b, rel_tol=1e-9, abs_tol=1e-9):
                    raise AssertionError(f"{what}: {g} != {w}")
            elif a != b:
                raise AssertionError(f"{what}: {g} != {w}")


def _check(ok, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _session(warehouse: str, num_buckets: int):
    from hyperspace_tpu import IndexConstants
    from hyperspace_tpu.engine import HyperspaceSession

    s = HyperspaceSession(warehouse=warehouse)
    s.conf.set(IndexConstants.INDEX_SYSTEM_PATH, os.path.join(warehouse, "indexes"))
    s.conf.set(IndexConstants.INDEX_NUM_BUCKETS, num_buckets)
    return s


def _queries(s, src: str):
    from hyperspace_tpu.engine import col

    def read(t):
        return s.read.parquet(os.path.join(src, t))

    def q3():
        return (
            read("lineitem")
            .join(read("orders"), col("orderkey") == col("o_orderkey"))
            .with_column("revenue", col("price") * (1 - col("discount")))
            .group_by("o_custkey")
            .agg(revenue=("revenue", "sum"), n=("qty", "count"))
        )

    def q3_join():
        return (
            read("lineitem")
            .join(read("orders"), col("orderkey") == col("o_orderkey"))
            .select("orderkey", "qty", "o_custkey")
        )

    def q14():
        return (
            read("lineitem")
            .filter((col("shipdate") >= 1000) & (col("shipdate") < 1030))
            .join(read("part"), col("partkey") == col("p_partkey"))
            .with_column("revenue", col("price") * (1 - col("discount")))
            .group_by("p_type")
            .agg(revenue=("revenue", "sum"), n=("qty", "count"))
        )

    def lookup(k: int):
        return (
            read("lineitem")
            .filter(col("orderkey") == k)
            .select("orderkey", "qty", "price", "discount")
        )

    return q3, q3_join, q14, lookup


def _timed(rec: dict, name: str, fn):
    """Run one phase: its wall seconds, and the compiles it paid and the
    Pallas programs it dispatched."""
    from hyperspace_tpu.telemetry import compile_log

    before = compile_log.program_summary()
    t0 = time.monotonic()
    out = fn()
    rec["phase_s"][name] = time.monotonic() - t0
    after = compile_log.program_summary()
    zero = {"compiles": 0, "compile_s": 0.0, "traces": 0}
    delta = {k: {f: v[f] - before.get(k, zero)[f] for f in zero} for k, v in after.items()}
    rec.setdefault("phase_compile", {})[name] = {
        "compiles": sum(d["compiles"] for d in delta.values()),
        "compile_s": sum(d["compile_s"] for d in delta.values()),
        "pallas": sorted(k for k, d in delta.items() if d["traces"] and k.startswith("pallas.")),
    }
    return out


def _compile_stats() -> dict:
    from hyperspace_tpu.telemetry import compile_log, metrics

    snap = metrics.snapshot()
    return {
        "compiles": snap["counters"].get("xla.compiles.count", 0),
        "compile_s": sum(p["compile_s"] for p in compile_log.program_summary().values()),
        "cache": compile_log.compile_cache_summary(),
    }


def _programs() -> dict:
    """Programs dispatched in this process: every label that traced at least
    once, with its compile count and seconds (a persistent-cache hit traces
    without a backend compile)."""
    from hyperspace_tpu.telemetry import compile_log

    return {k: v for k, v in compile_log.program_summary().items() if v["traces"]}


# Pallas kernel-check widths. On the chip, the widest each dispatch gate
# admits: the sorts' cap limit (pallas_sort._MAX_CAP) with 8-row and 1-row
# bucket groups, the probe and the packed probe at the auto gate's ops budget
# (B * cap_l * cap_r = 2^28 over 64 buckets; for the packed probe one side a
# single 4-bit word, the other 524288 codes). Off the chip (interpret mode),
# small widths.
GATE_WIDTHS = {
    "sort": [(8, 32768), (1, 32768)],
    "probe": [(64, 4096, 1024), (64, 131072, 32)],
    "probe_packed": [(64, 524288, 8), (64, 8, 524288)],
}
SMALL_WIDTHS = {
    "sort": [(8, 1024)],
    "probe": [(8, 256, 512)],
    "probe_packed": [(8, 256, 512)],
}


def _kernel_checks(widths: dict) -> dict:
    """Each Pallas kernel at each of `widths`, against numpy."""
    import numpy as np

    from hyperspace_tpu.engine.packed_codes import pack_rows_traced
    from hyperspace_tpu.ops.pallas_probe import probe_packed_pallas, probe_pallas
    from hyperspace_tpu.ops.pallas_sort import (
        sort_codes_packed,
        sort_comp_padded,
        sort_padded_with_order,
    )

    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(SEED)
    interpret = jax.default_backend() != "tpu"
    bits = 4
    top = (1 << bits) - 1  # the pad code; real biased codes sit below it
    done = []

    def check_ranges(lo, cnt, L, R, what):
        lo, cnt = np.asarray(lo), np.asarray(cnt)
        for b in range(L.shape[0]):
            want_lo = np.searchsorted(R[b], L[b], "left")
            want_hi = np.searchsorted(R[b], L[b], "right")
            _check((lo[b] == want_lo).all(), f"{what} lo")
            _check((cnt[b] == want_hi - want_lo).all(), f"{what} counts")

    for B, cap in widths["sort"]:
        keys = rng.randint(-(2**62), 2**62, (B, cap)).astype(np.int64)
        sk, order = sort_padded_with_order(jnp.asarray(keys))
        sk, order = np.asarray(sk), np.asarray(order)
        _check((sk == np.sort(keys, axis=1)).all(), f"pallas.sort keys {B}x{cap}")
        _check((np.take_along_axis(keys, order, 1) == sk).all(), f"pallas.sort order {B}x{cap}")
        done.append(f"pallas.sort {B}x{cap}")

        comp = np.stack([rng.permutation(cap) for _ in range(B)]).astype(np.int32)
        got = np.asarray(sort_comp_padded(jnp.asarray(comp), interpret))
        _check((got == np.sort(comp, axis=1)).all(), f"pallas.sort_comp {B}x{cap}")
        done.append(f"pallas.sort_comp {B}x{cap}")

        codes = rng.randint(0, top, (B, cap)).astype(np.int32)
        sc, order = sort_codes_packed(pack_rows_traced(jnp.asarray(codes), bits), bits)
        want = np.argsort(codes, axis=1, kind="stable")
        _check((np.asarray(order) == want).all(), f"pallas.sort_codes order {B}x{cap}")
        _check((np.asarray(sc) == np.take_along_axis(codes, want, 1)).all(), f"pallas.sort_codes {B}x{cap}")
        done.append(f"pallas.sort_codes {B}x{cap}")

    for B, cap_l, cap_r in widths["probe"]:
        L = np.sort(rng.randint(0, 3 * cap_r, (B, cap_l)), axis=1).astype(np.int64)
        R = np.sort(rng.randint(0, 3 * cap_r, (B, cap_r)), axis=1).astype(np.int64)
        lo, cnt = probe_pallas(jnp.asarray(L), jnp.asarray(R), np.full(B, cap_l), np.full(B, cap_r))
        check_ranges(lo, cnt, L, R, f"pallas.probe {B}x{cap_l}x{cap_r}")
        done.append(f"pallas.probe {B}x{cap_l}x{cap_r}")

    for B, cap_l, cap_r in widths["probe_packed"]:
        L = np.sort(rng.randint(0, top, (B, cap_l)), axis=1).astype(np.int32)
        R = np.sort(rng.randint(0, top, (B, cap_r)), axis=1).astype(np.int32)
        lw, rw = pack_rows_traced(jnp.asarray(L), bits), pack_rows_traced(jnp.asarray(R), bits)
        lo, cnt = probe_packed_pallas(lw, rw, bits, np.full(B, cap_l), np.full(B, cap_r))
        check_ranges(lo, cnt, L, R, f"pallas.probe_packed {B}x{cap_l}x{cap_r}")
        done.append(f"pallas.probe_packed {B}x{cap_l}x{cap_r}")
    return {"checked": done}


def run_smoke(
    base: str,
    n_lineitem: int,
    n_orders: int,
    n_part: int,
    num_buckets: int = NUM_BUCKETS,
    n_lookups: int = N_LOOKUPS,
    kernel_widths: dict = GATE_WIDTHS,
) -> dict:
    """The one-chip main path at the given sizes, under `base`. Returns the
    record; raises on any failed check."""
    import numpy as np

    from bench import write_tpch_tables
    from hyperspace_tpu import IndexConfig
    from hyperspace_tpu.hyperspace import Hyperspace, disable_hyperspace, enable_hyperspace
    from hyperspace_tpu.serve.scheduler import QueryServer
    import jax

    rec = {
        "kind": "smoke timings, not benchmark metrics",
        "rows": {"lineitem": n_lineitem, "orders": n_orders, "part": n_part},
        "num_buckets": num_buckets,
        "phase_s": {},
    }
    src = os.path.join(base, "tpch")
    _timed(rec, "datagen", lambda: write_tpch_tables(src, n_lineitem, n_orders, n_part, SEED))

    s = _session(os.path.join(base, "lake"), num_buckets)
    hs = Hyperspace(s)
    q3, q3_join, q14, lookup = _queries(s, src)
    indexes = [
        ("lineitem", IndexConfig("liIdx", ["orderkey"], ["qty", "price", "discount"])),
        ("orders", IndexConfig("ordIdx", ["o_orderkey"], ["o_custkey"])),
        ("lineitem", IndexConfig("liPartIdx", ["partkey"], ["qty", "price", "discount", "shipdate"])),
        ("part", IndexConfig("partIdx", ["p_partkey"], ["p_type"])),
    ]
    for table, cfg in indexes:
        _timed(
            rec,
            f"build_{cfg.index_name}",
            lambda: hs.create_index(s.read.parquet(os.path.join(src, table)), cfg),
        )

    for name, q in (("q3", q3), ("q14", q14)):
        enable_hyperspace(s)
        plan = q().explain_string()
        if REWRITE_MARK not in plan:
            raise AssertionError(f"{name}: join rewrite did not fire:\n{plan}")
        got = _timed(rec, f"{name}_indexed", lambda: q().collect().rows())
        _timed(rec, f"{name}_indexed_warm", lambda: q().collect())
        disable_hyperspace(s)
        want = _timed(rec, f"{name}_index_off", lambda: q().collect().rows())
        _same_rows(got, want, name)
        rec[f"{name}_groups"] = len(got)

    keys = np.random.RandomState(SEED).randint(0, n_orders, n_lookups).tolist()
    enable_hyperspace(s)
    plan = lookup(keys[0]).explain_string()
    if "liIdx" not in plan:
        raise AssertionError(f"point lookup did not use the index:\n{plan}")
    with QueryServer() as server:
        got = _timed(
            rec,
            "lookups_indexed",
            lambda: [server.run(lambda k=k: lookup(k).collect().rows(), lane="interactive") for k in keys],
        )
    disable_hyperspace(s)
    for k, rows in zip(keys, got):
        _same_rows(rows, lookup(k).collect().rows(), f"lookup {k}")
    rec["lookups"] = len(keys)
    rec["lookup_rows"] = sum(len(r) for r in got)

    rec["main_path_programs"] = _programs()
    rec["main_path_pallas"] = sorted(k for k in rec["main_path_programs"] if k.startswith("pallas."))
    rec["kernels"] = _timed(rec, "kernel_checks", lambda: _kernel_checks(kernel_widths))
    rec["compile"] = _compile_stats()
    mem = jax.devices()[0].memory_stats() or {}
    rec["peak_bytes_in_use"] = mem.get("peak_bytes_in_use")
    return rec


def _part_digests(index_root: str) -> set:
    out = set()
    for p in glob.glob(os.path.join(index_root, "**", "part-*"), recursive=True):
        with open(p, "rb") as f:
            out.add(hashlib.sha256(f.read()).hexdigest())
    return out


def _mesh_bytes_per_device() -> dict:
    """Bytes of the live arrays sharded over more than one device, per device:
    the device-resident key blocks of the sharded build and probe."""
    import jax

    per: dict = {}
    for a in jax.live_arrays():
        if len(a.sharding.device_set) < 2:
            continue
        for shard in a.addressable_shards:
            key = str(shard.device.id)
            per[key] = per.get(key, 0) + shard.data.nbytes
    return dict(sorted(per.items()))


def run_mesh(base: str, n_lineitem: int, n_orders: int, n_part: int, num_buckets: int = NUM_BUCKETS) -> dict:
    """The mesh path against the single-device path, in this process: the
    lineitem and orders indexes, then the indexed Q3 join, both ways."""
    from bench import write_tpch_tables
    from hyperspace_tpu import IndexConfig
    from hyperspace_tpu.hyperspace import Hyperspace, enable_hyperspace

    import jax

    rec = {
        "kind": "smoke timings, not benchmark metrics",
        "rows": {"lineitem": n_lineitem, "orders": n_orders},
        "num_buckets": num_buckets,
        "devices": len(jax.devices()),
        "phase_s": {},
    }
    src = os.path.join(base, "tpch")
    _timed(rec, "datagen", lambda: write_tpch_tables(src, n_lineitem, n_orders, n_part, SEED))
    results = {}
    for mode in ("1", "0"):
        os.environ["HYPERSPACE_DISTRIBUTED"] = mode
        try:
            lake = os.path.join(base, f"lake_dist{mode}")
            s = _session(lake, num_buckets)
            hs = Hyperspace(s)
            _q3, q3_join, _q14, _lookup = _queries(s, src)
            for table, cfg in (
                ("lineitem", IndexConfig("liIdx", ["orderkey"], ["qty", "price", "discount"])),
                ("orders", IndexConfig("ordIdx", ["o_orderkey"], ["o_custkey"])),
            ):
                _timed(
                    rec,
                    f"build_{cfg.index_name}_dist{mode}",
                    lambda: hs.create_index(s.read.parquet(os.path.join(src, table)), cfg),
                )
            enable_hyperspace(s)
            if REWRITE_MARK not in q3_join().explain_string():
                raise AssertionError(f"dist{mode}: join rewrite did not fire")
            join_rows = _timed(rec, f"q3_join_dist{mode}", lambda: q3_join().collect().rows())
            if mode == "1":
                rec["mesh_bytes_per_device"] = _mesh_bytes_per_device()
            results[mode] = (_part_digests(os.path.join(lake, "indexes")), join_rows)
        finally:
            os.environ.pop("HYPERSPACE_DISTRIBUTED", None)
    (d1, j1), (d0, j0) = results["1"], results["0"]
    if not d1 or d1 != d0:
        raise AssertionError(f"index part-* sha256 sets differ: {len(d1)} mesh vs {len(d0)} single")
    if sorted(j1) != sorted(j0):
        raise AssertionError("q3 join rows differ between the mesh and single-device paths")
    rec["index_files"] = len(d1)
    rec["q3_join_rows"] = len(j1)
    on_devices = [d for d, b in rec["mesh_bytes_per_device"].items() if b > 0]
    if len(on_devices) < rec["devices"]:
        raise AssertionError(f"sharded state on devices {on_devices} only")
    rec["compile"] = _compile_stats()
    rec["programs"] = _programs()
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    from hyperspace_tpu.util.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {devices[0].platform!r})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: {len(devices)} chips visible, {args.chips} asked", file=sys.stderr)
        return 1
    enable_compile_cache(os.path.join(HERE, ".jax_cache"))
    base = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.chips == 4:
            rec = run_mesh(base, SF1["n_lineitem"], SF1["n_orders"], SF1["n_part"])
        else:
            rec = run_smoke(base, **SF1)
    finally:
        shutil.rmtree(base, ignore_errors=True)
    print(json.dumps({"smoke_record": rec}, default=str), flush=True)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
