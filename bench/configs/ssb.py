"""Star Schema Benchmark deployments: Q4.x star joins through ``JoinSession``.

Tables, all int-coded, at the sizes the configuration file gives:

  lineorder(orderkey, linenumber, orderdate, custkey, suppkey, partkey,
      revenue, supplycost), partitioned by month, with ``lineorder_rows``
      spread evenly over the days of the date table. As in SSB's generator, an
      order has 1 to 7 lines and one orderdate and custkey; each line has its
      own suppkey and partkey; each foreign key is Zipf(``fk_skew``) over its
      dimension's keys. revenue = quantity * price * (100 - discount) // 100
      and supplycost = 6 * price // 10, with quantity 1-50, discount 0-10 and
      the part's price in cents as TPC-H's dbgen makes it. lineorder's other
      nine columns are read by no Q4.x query, so the client projects them away
      (a column store reads only the columns a query names);
  customer(custkey, c_nation), supplier(suppkey, s_nation): nation uniform over
      ``nations``, region = nation // (nations / regions);
  part(partkey, p_category): category uniform over mfgrs x categories_per_mfgr,
      mfgr = category // categories_per_mfgr + 1;
  date(orderdate, d_year): one row per day, orderdate coded yyyymmdd.

The engine has no selections, so the client does what a warehouse front end
would: it prunes lineorder to the partitions a query touches and filters each
dimension by the query's predicates, then submits the 5-relation star
lineorder ⋈ customer ⋈ supplier ⋈ part ⋈ date (the general, Yannakakis route).

Tables come from ``base_seed``; the run's ``--seed`` relabels the customer,
supplier and part keys by random permutations (in the dimension and in
lineorder alike) and shuffles rows. So every seed gets the same join up to
isomorphism, the same work, and different keys, orders and hashes.

Guarantee (from the configuration file): the answer is the exact set of joined
rows, or with ``materialize: false`` their exact number. The plain reference
below computes it with numpy alone.
"""

from __future__ import annotations

import calendar
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

#: lineorder's columns as the client submits them.
FACT = ("orderkey", "linenumber", "orderdate", "custkey", "suppkey", "partkey",
        "revenue", "supplycost")

#: output columns of every Q4.x star, in the order the engine emits them
#: (sorted attribute names).
COLUMNS = tuple(sorted(FACT + ("c_nation", "s_nation", "p_category", "d_year")))

#: the most lines an order has (SSB: 1 to 7, uniform).
MAX_LINES = 7

#: orderkeys of month m (counted from the first year's January) lie in
#: m * ORDERS_PER_MONTH + 1 .., so that every orderkey is unique.
ORDERS_PER_MONTH = 1_000_000


def zipf_keys(rng: np.random.Generator, n_keys: int, size: int, skew: float) -> np.ndarray:
    """``size`` keys in 1..n_keys, key k drawn with weight k^-skew."""
    w = np.arange(1, n_keys + 1, dtype=np.float64) ** (-skew)
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    return np.searchsorted(cdf, rng.random(size), side="right").astype(np.int64) + 1


def days_of(year: int, month: int) -> np.ndarray:
    n = calendar.monthrange(year, month)[1]
    return year * 10000 + month * 100 + np.arange(1, n + 1, dtype=np.int64)


def part_price(partkey: np.ndarray) -> np.ndarray:
    """A part's retail price in cents, by TPC-H dbgen's formula."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def month_names(partition: str) -> List[Tuple[int, int]]:
    """``"1998-03"`` -> [(1998, 3)]; ``"1997"`` -> the twelve months of 1997."""
    if "-" in partition:
        y, m = partition.split("-")
        return [(int(y), int(m))]
    return [(int(partition), m) for m in range(1, 13)]


class Tables:
    """The deployment's tables for one run's seed, partitions made on demand."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg = cfg
        base = np.random.default_rng([int(cfg["base_seed"]), 0])
        self.n_cust = int(cfg["customer_rows"])
        self.n_supp = int(cfg["supplier_rows"])
        self.n_part = int(cfg["part_rows"])
        per_region = int(cfg["nations"]) // int(cfg["regions"])
        self.per_region = per_region
        self.c_nation = base.integers(0, int(cfg["nations"]), self.n_cust + 1)
        self.s_nation = base.integers(0, int(cfg["nations"]), self.n_supp + 1)
        n_cat = int(cfg["mfgrs"]) * int(cfg["categories_per_mfgr"])
        self.p_category = base.integers(0, n_cat, self.n_part + 1)
        first, last = int(cfg["first_year"]), int(cfg["last_year"])
        self.days = np.concatenate([days_of(y, m) for y in range(first, last + 1)
                                    for m in range(1, 13)])
        self.rows_per_day = int(cfg["lineorder_rows"]) / self.days.size
        rng = np.random.default_rng(seed)
        # key relabelling of this seed: base key k is called perm[k]
        self.cperm = np.concatenate([[0], rng.permutation(self.n_cust) + 1])
        self.sperm = np.concatenate([[0], rng.permutation(self.n_supp) + 1])
        self.pperm = np.concatenate([[0], rng.permutation(self.n_part) + 1])
        self.shuffle_seed = int(rng.integers(0, 2**62))
        self._months: Dict[Tuple[int, int], np.ndarray] = {}

    def month(self, year: int, month: int) -> np.ndarray:
        """lineorder's partition (year, month), keys relabelled; (n, 8) int64
        over :data:`FACT`."""
        key = (year, month)
        if key not in self._months:
            rng = np.random.default_rng([int(self.cfg["base_seed"]), year, month])
            days = days_of(year, month)
            n = int(round(self.rows_per_day * days.size))
            skew = float(self.cfg["fk_skew"])
            lines = rng.integers(1, MAX_LINES + 1, n)       # more orders than needed
            n_orders = int(np.searchsorted(np.cumsum(lines), n)) + 1
            order = np.repeat(np.arange(n_orders), lines[:n_orders])[:n]
            first = np.concatenate([[0], np.cumsum(lines[:n_orders])[:-1]])
            ordinal = (year - int(self.cfg["first_year"])) * 12 + month - 1
            part = zipf_keys(rng, self.n_part, n, skew)
            price = part_price(part)
            quantity = rng.integers(1, 51, n)
            discount = rng.integers(0, 11, n)
            rows = np.stack([
                ordinal * ORDERS_PER_MONTH + order + 1,
                np.arange(n) - first[order] + 1,
                days[rng.integers(0, days.size, n_orders)][order],
                self.cperm[zipf_keys(rng, self.n_cust, n_orders, skew)][order],
                self.sperm[zipf_keys(rng, self.n_supp, n, skew)],
                self.pperm[part],
                quantity * price * (100 - discount) // 100,
                6 * price // 10,
            ], axis=1)
            shuffle = np.random.default_rng([self.shuffle_seed, year, month]).permutation(n)
            self._months[key] = rows[shuffle]
        return self._months[key]

    def lineorder(self, partition: str) -> np.ndarray:
        return np.concatenate([self.month(y, m) for y, m in month_names(partition)])

    def customer(self, region: int) -> np.ndarray:
        keys = np.nonzero(self.c_nation[1:] // self.per_region == region)[0] + 1
        return np.stack([self.cperm[keys], self.c_nation[keys]], axis=1)

    def supplier(self, region: int) -> np.ndarray:
        keys = np.nonzero(self.s_nation[1:] // self.per_region == region)[0] + 1
        return np.stack([self.sperm[keys], self.s_nation[keys]], axis=1)

    def part(self, mfgrs: List[int]) -> np.ndarray:
        per = int(self.cfg["categories_per_mfgr"])
        mf = self.p_category[1:] // per + 1
        keys = np.nonzero(np.isin(mf, mfgrs))[0] + 1
        return np.stack([self.pperm[keys], self.p_category[keys]], axis=1)

    def date(self, partition: str) -> np.ndarray:
        days = np.concatenate([days_of(y, m) for y, m in month_names(partition)])
        return np.stack([days, days // 10000], axis=1)


def _in(values: np.ndarray, keys: np.ndarray) -> np.ndarray:
    keys = np.sort(keys)
    pos = np.clip(np.searchsorted(keys, values), 0, max(keys.size - 1, 0))
    return (keys.size > 0) & (keys[pos] == values)


def _lookup(values: np.ndarray, table: np.ndarray) -> np.ndarray:
    order = np.argsort(table[:, 0])
    keys, attr = table[order, 0], table[order, 1]
    return attr[np.searchsorted(keys, values)]


def reference_star(fact, customer, supplier, part, date) -> np.ndarray:
    """The star's exact answer, rows over :data:`COLUMNS`, sorted; set semantics.
    ``fact`` has the columns of :data:`FACT`."""
    f = np.unique(np.asarray(fact, np.int64).reshape(-1, len(FACT)), axis=0)
    col = {c: f[:, j] for j, c in enumerate(FACT)}
    keep = (_in(col["orderdate"], date[:, 0]) & _in(col["custkey"], customer[:, 0])
            & _in(col["suppkey"], supplier[:, 0]) & _in(col["partkey"], part[:, 0]))
    cols = {c: v[keep] for c, v in col.items()}
    cols.update({
        "c_nation": _lookup(cols["custkey"], customer),
        "s_nation": _lookup(cols["suppkey"], supplier),
        "p_category": _lookup(cols["partkey"], part),
        "d_year": _lookup(cols["orderdate"], date),
    })
    rows = np.stack([cols[c] for c in COLUMNS], axis=1).reshape(-1, len(COLUMNS))
    return rows[np.lexsort(rows.T[::-1])] if rows.size else rows


def sorted_rows(rows) -> np.ndarray:
    rows = np.asarray(rows, np.int64).reshape(-1, len(COLUMNS))
    return rows[np.lexsort(rows.T[::-1])] if rows.size else rows


@dataclass
class StarRequest:
    """One Q4.x star over one partition."""

    key: tuple
    query: object                  # repro JoinQuery
    materialize: bool

    def submit(self, session):
        return session.submit(self.query, materialize=self.materialize)

    def submit_async(self, session):
        return session.submit_async(self.query, materialize=self.materialize)

    def answer(self, result):
        if not self.materialize:
            return int(result.count)
        return sorted_rows(result.rows)


class Dataset:
    def __init__(self, cfg: dict, seed: int):
        self.cfg = cfg
        self.tables = Tables(cfg, seed)
        self._requests: Dict[tuple, StarRequest] = {}
        self._reference: Dict[tuple, np.ndarray] = {}

    def describe(self) -> str:
        c = self.cfg
        return (f"ssb lineorder_rows={c['lineorder_rows']} customer={c['customer_rows']} "
                f"supplier={c['supplier_rows']} part={c['part_rows']} days={self.tables.days.size}")

    def _spec(self, query: dict, params: dict):
        q = self.cfg["queries"][query["name"]]
        partition = params.get("partition", q.get("partition"))
        if partition is None:
            raise ValueError(f"query {query['name']} needs a partition")
        return q, str(partition), bool(query.get("materialize", True))

    def _inputs(self, q: dict, partition: str):
        t = self.tables
        return (t.lineorder(partition), t.customer(int(q["c_region"])),
                t.supplier(int(q["s_region"])), t.part(list(q["p_mfgr"])),
                t.date(partition))

    def request(self, query: dict, params: dict) -> StarRequest:
        from repro.core.query import JoinQuery, Relation

        q, partition, mat = self._spec(query, params)
        key = (query["name"], partition, mat)
        if key not in self._requests:
            fact, cust, supp, part, date = self._inputs(q, partition)
            rels = [
                Relation.make(FACT, fact),
                Relation.make(("custkey", "c_nation"), cust),
                Relation.make(("suppkey", "s_nation"), supp),
                Relation.make(("partkey", "p_category"), part),
                Relation.make(("orderdate", "d_year"), date),
            ]
            self._requests[key] = StarRequest(key, JoinQuery.make(rels), mat)
        return self._requests[key]

    def reference(self, query: dict, params: dict):
        q, partition, mat = self._spec(query, params)
        key = (query["name"], partition)
        if key not in self._reference:
            self._reference[key] = reference_star(*self._inputs(q, partition))
        rows = self._reference[key]
        return rows if mat else int(rows.shape[0])


def make_dataset(cfg: dict, seed: int) -> Dataset:
    return Dataset(cfg, seed)
