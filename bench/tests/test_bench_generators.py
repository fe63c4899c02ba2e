"""Seeded generators: sizes, key domains, and the same work for every seed."""

import numpy as np
import pytest

from benchlib import spec

BENCH = spec.load_benchmark()


def _family(config_name):
    cfg = spec.config(BENCH, config_name)
    return cfg, spec.family(cfg)


def test_graph_config_keeps_com_dblp_edges_per_vertex():
    cfg, _ = _family("graph-tri-dblp")
    src = cfg["source_sizes"]
    assert cfg["edges"] / cfg["vertices"] == pytest.approx(src["edges"] / src["vertices"],
                                                            rel=1e-3)
    assert cfg["edges"] == 2**16


def test_graph_generator_size_and_relabelling():
    cfg, fam = _family("graph-tri-dblp")
    small = dict(cfg, vertices=600, edges=2000)
    a, b = fam.make_dataset(small, 1), fam.make_dataset(small, 2**31 + 5)
    for d in (a, b):
        e = d.edges
        assert e.shape == (2000, 2)
        assert np.all(e[:, 0] < e[:, 1]) and e.max() < 600
        assert np.unique(e, axis=0).shape[0] == 2000
    # another seed relabels the same graph: same degree sequence, same triangles
    deg = lambda e: np.sort(np.bincount(e.ravel(), minlength=600))
    assert np.array_equal(deg(a.edges), deg(b.edges))
    assert not np.array_equal(a.edges, b.edges)
    ta = fam.reference_triangles(a.edges, 600)
    tb = fam.reference_triangles(b.edges, 600)
    assert ta.shape == tb.shape and ta.shape[0] > 0
    # the same seed gives the same graph
    assert np.array_equal(fam.make_dataset(small, 1).edges, a.edges)


def test_ssb_table_sizes_and_key_domains():
    cfg, fam = _family("ssb-q4-sf1")
    t = fam.Tables(cfg, 7)
    assert cfg["lineorder_rows"] == 6_000_000
    assert t.days.size == 2557                      # 1992-01-01 .. 1998-12-31
    per_day = cfg["lineorder_rows"] / t.days.size
    mar = t.month(1998, 3)
    assert mar.shape == (round(per_day * 31), len(fam.FACT))
    col = {c: mar[:, j] for j, c in enumerate(fam.FACT)}
    assert set(np.unique(col["orderdate"] // 100)) == {199803}
    for c, n in (("custkey", cfg["customer_rows"]), ("suppkey", cfg["supplier_rows"]),
                 ("partkey", cfg["part_rows"])):
        assert col[c].min() >= 1 and col[c].max() <= n
    # (orderkey, linenumber) is the row's key; orders have 1 to 7 lines, and
    # the lines of an order share its orderdate and custkey
    key = mar[:, :2]
    assert np.unique(key, axis=0).shape[0] == mar.shape[0]
    assert col["linenumber"].min() == 1 and col["linenumber"].max() == 7
    lines = np.bincount(np.unique(col["orderkey"], return_inverse=True)[1])
    assert 3.8 < lines.mean() < 4.2
    for c in ("orderdate", "custkey"):
        per_order = np.unique(np.stack([col["orderkey"], col[c]], axis=1), axis=0)
        assert per_order.shape[0] == np.unique(col["orderkey"]).size
    # the measures as SSB defines them: revenue = extendedprice less discount
    assert col["revenue"].min() > 0 and col["revenue"].max() < 2**31
    assert np.all(col["supplycost"] < col["revenue"])
    assert np.unique(t.month(1998, 4)[:, 0]).min() > col["orderkey"].max()
    year = t.lineorder("1997")
    assert abs(year.shape[0] - per_day * 365) <= 12
    cust = t.customer(1)
    assert set(np.unique(cust[:, 1] // 5)) == {1}   # AMERICA's nations only
    assert 0.15 < cust.shape[0] / cfg["customer_rows"] < 0.25
    part = t.part([1, 2])
    assert set(np.unique(part[:, 1] // 5 + 1)) == {1, 2}
    assert np.unique(t.supplier(1)[:, 0]).size == t.supplier(1).shape[0]


def test_ssb_seed_relabels_keys_but_keeps_the_work():
    cfg, fam = _family("ssb-q4-sf1")
    small = dict(cfg, lineorder_rows=50_000)
    a, b = fam.make_dataset(small, 3), fam.make_dataset(small, 2**32 + 9)
    q = {"name": "q4.1", "materialize": True}
    ra = a.reference(q, {"partition": "1998-05"})
    rb = b.reference(q, {"partition": "1998-05"})
    assert ra.shape == rb.shape and ra.shape[0] > 0
    assert not np.array_equal(ra, rb)
    # skew survives the relabelling: the hottest customer is as hot in both
    cust = fam.FACT.index("custkey")
    top = lambda d: np.bincount(d.tables.month(1998, 5)[:, cust]).max()
    assert top(a) == top(b)


def test_zipf_keys_follow_the_skew():
    _, fam = _family("ssb-q4-sf1")
    keys = fam.zipf_keys(np.random.default_rng(0), 100, 200_000, 0.8)
    counts = np.bincount(keys, minlength=101)[1:]
    w = np.arange(1, 101, dtype=float) ** -0.8
    np.testing.assert_allclose(counts / counts.sum(), w / w.sum(), atol=0.004)
