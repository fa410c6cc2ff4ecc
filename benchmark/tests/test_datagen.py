import hashlib

import numpy as np

import harness
from conftest import small_cell


def _digest(data: dict) -> str:
    h = hashlib.sha256()
    for table in sorted(data):
        for col in sorted(data[table]):
            a = np.ascontiguousarray(data[table][col])
            h.update(f"{table}.{col}.{a.dtype}.{a.shape}".encode())
            h.update(a.tobytes())
    return h.hexdigest()


def test_same_seed_same_tables(tmp_path):
    cell = small_cell("tpch_sf1.build")
    gen = harness.config_module("datagen", "tpch_sf1")
    seed = 2**31 + 4242
    a, b, c = gen.generate(cell["config"], seed), gen.generate(cell["config"], seed), gen.generate(cell["config"], seed + 1)
    assert _digest(a) == _digest(b)
    assert _digest(a) != _digest(c)
    for d, name in ((a, "x"), (b, "y")):
        harness.write_tables(d, str(tmp_path / name), cell["config"]["files"])
    for f in sorted((tmp_path / "x").rglob("*.parquet")):
        g = tmp_path / "y" / f.relative_to(tmp_path / "x")
        assert f.read_bytes() == g.read_bytes()


def test_tpch_shapes():
    cell = small_cell("tpch_sf1.build")
    cfg = dict(cell["config"], orders=60000, part=20000)
    data = harness.config_module("datagen", "tpch_sf1").generate(cfg, 7)
    li, o, p = data["lineitem"], data["orders"], data["part"]
    _, lines = np.unique(li["l_orderkey"], return_counts=True)
    assert lines.min() == 1 and lines.max() == 7 and len(lines) == cfg["orders"]
    assert len(np.unique(p["p_type"])) == 150
    assert o["o_orderkey"][:9].tolist() == [1, 2, 3, 4, 5, 6, 7, 8, 33]  # dbgen's sparse keys
    odate = o["o_orderdate"][np.searchsorted(o["o_orderkey"], li["l_orderkey"])]
    gap = li["l_shipdate"] - odate
    assert gap.min() == 1 and gap.max() == 121
    assert (o["o_shippriority"] == 0).all()
