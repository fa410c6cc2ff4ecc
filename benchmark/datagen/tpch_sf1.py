"""TPC-H SF 1 tables from a seed, by dbgen's value rules (spec v3.0.1, 4.2.3).

Only the columns the configuration's queries read are made (`reduced` in
`configs/tpch_sf1.json`); dates are int64 day numbers, decimals float64.
"""

import numpy as np

SYLLABLE_1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
SYLLABLE_2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
SYLLABLE_3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
P_TYPES = np.array([f"{a} {b} {c}" for a in SYLLABLE_1 for b in SYLLABLE_2 for c in SYLLABLE_3])


def day(iso: str) -> int:
    return int(np.datetime64(iso, "D").astype(np.int64))


def orderkeys(n: int) -> np.ndarray:
    """dbgen's sparse keys: the first 8 of every 32 (4.2.3, O_ORDERKEY)."""
    i = np.arange(n, dtype=np.int64)
    return (i // 8) * 32 + i % 8 + 1


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE (4.2.3): (90000 + ((key/10) mod 20001) + 100 (key mod 1000)) / 100."""
    return (90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)) / 100.0


# Every seed gets the same multiset of lines per order (so the same table
# sizes, and the same compiled shapes), in its own order.
LINES_SEED = 4231


def generate(cfg: dict, seed: int) -> dict:
    """{table: {column: ndarray}} for `cfg` (the configuration's JSON)."""
    rng = np.random.Generator(np.random.PCG64(seed))
    n_ord, n_part = cfg["orders"], cfg["part"]
    start = day(cfg["start_date"])
    last_orderdate = day(cfg["end_date"]) - cfg["orderdate_end_offset_days"]

    o_orderkey = orderkeys(n_ord)
    o_orderdate = rng.integers(start, last_orderdate + 1, n_ord, dtype=np.int64)
    fixed = np.random.Generator(np.random.PCG64(LINES_SEED))
    lines = rng.permutation(fixed.integers(cfg["lines_per_order_min"], cfg["lines_per_order_max"] + 1, n_ord))
    n_li = int(lines.sum())
    l_orderkey = np.repeat(o_orderkey, lines)
    l_shipdate = np.repeat(o_orderdate, lines) + rng.integers(
        cfg["shipdate_offset_days_min"], cfg["shipdate_offset_days_max"] + 1, n_li, dtype=np.int64
    )
    l_partkey = rng.integers(1, n_part + 1, n_li, dtype=np.int64)
    l_quantity = rng.integers(1, 51, n_li).astype(np.float64)
    return {
        "lineitem": {
            "l_orderkey": l_orderkey,
            "l_partkey": l_partkey,
            "l_quantity": l_quantity,
            "l_extendedprice": l_quantity * retail_price(l_partkey),
            "l_discount": rng.integers(0, 11, n_li) / 100.0,
            "l_tax": rng.integers(0, 9, n_li) / 100.0,
            "l_shipdate": l_shipdate,
        },
        "orders": {
            "o_orderkey": o_orderkey,
            "o_orderdate": o_orderdate,
            "o_shippriority": np.zeros(n_ord, dtype=np.int64),
        },
        "part": {
            "p_partkey": np.arange(1, n_part + 1, dtype=np.int64),
            "p_type": P_TYPES[rng.integers(0, len(P_TYPES), n_part)],
        },
    }
