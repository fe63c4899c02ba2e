"""Each plain reference against brute force at a tiny size."""

import itertools

import numpy as np

from benchlib import spec

BENCH = spec.load_benchmark()


def test_triangle_reference_matches_brute_force():
    cfg = spec.config(BENCH, "graph-tri-dblp")
    fam = spec.family(cfg)
    rng = np.random.default_rng(4)
    for n, m in ((12, 30), (40, 200), (60, 25)):
        edges = fam.normalize_edges(rng.integers(0, n, size=(m, 2)))
        adj = {tuple(e) for e in edges.tolist()}
        want = [t for t in itertools.combinations(range(n), 3)
                if {(t[0], t[1]), (t[0], t[2]), (t[1], t[2])} <= adj]
        got = fam.reference_triangles(edges, n)
        assert [tuple(r) for r in got.tolist()] == want


def test_triangle_reference_of_an_empty_graph():
    fam = spec.family(spec.config(BENCH, "graph-tri-dblp"))
    assert fam.reference_triangles(np.zeros((0, 2), np.int64), 5).shape == (0, 3)


def test_star_reference_matches_brute_force():
    fam = spec.family(spec.config(BENCH, "ssb-q4-sf1"))
    rng = np.random.default_rng(9)
    n = 80
    fact = np.stack([rng.integers(1, 30, n), rng.integers(1, 8, n),       # order, line
                     rng.integers(1, 6, n), rng.integers(1, 9, n),        # date, customer
                     rng.integers(1, 5, n), rng.integers(1, 7, n),        # supplier, part
                     rng.integers(1, 100, n), rng.integers(1, 50, n)], axis=1)
    fact = np.concatenate([fact, fact[:10]])            # duplicates: set semantics
    cust = np.array([[1, 3], [2, 4], [5, 3], [8, 9]])
    supp = np.array([[1, 0], [3, 2]])
    part = np.array([[2, 11], [4, 12], [6, 13]])
    date = np.array([[1, 1997], [2, 1997], [4, 1998]])
    got = fam.reference_star(fact, cust, supp, part, date)
    want = set()
    for f in set(map(tuple, fact.tolist())):
        f = dict(zip(fam.FACT, f))
        for cc, cn in cust.tolist():
            for ss, sn in supp.tolist():
                for pp, pc in part.tolist():
                    for dd, y in date.tolist():
                        if (f["custkey"], f["suppkey"], f["partkey"], f["orderdate"]) \
                                == (cc, ss, pp, dd):
                            row = dict(f, c_nation=cn, s_nation=sn, p_category=pc, d_year=y)
                            want.add(tuple(row[k] for k in fam.COLUMNS))
    assert got.shape[1] == len(fam.COLUMNS)
    assert [tuple(r) for r in got.tolist()] == sorted(want)
    assert len(want) > 0
