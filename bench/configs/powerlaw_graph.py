"""Power-law graph deployments: subgraph enumeration through ``JoinSession.submit_pattern``.

The configuration file gives the graph's size (``vertices``, ``edges``), its
degree skew and ``base_seed``. The graph is a Zipf graph drawn from
``base_seed`` (a copy of the generator in ``repro.graph.graphs``, so that the
yardstick cannot move with the program); the run's ``--seed`` relabels its
vertices by a random permutation. So every seed gets the same graph up to
isomorphism, the same work, and different vertex ids, orders and hashes.

Guarantee (from the configuration file): every occurrence of the pattern in the
simple undirected graph is reported exactly once. The plain reference below
enumerates triangles with numpy alone.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np


def normalize_edges(edges: np.ndarray) -> np.ndarray:
    """u < v per row, self-loops and duplicates dropped, rows sorted."""
    e = np.asarray(edges, np.int64).reshape(-1, 2)
    e = e[e[:, 0] != e[:, 1]]
    e = np.stack([e.min(axis=1), e.max(axis=1)], axis=1)
    return np.unique(e, axis=0)


def zipf_graph_edges(rng: np.random.Generator, n_vertices: int, n_edges: int,
                     skew: float) -> np.ndarray:
    """``n_edges`` distinct edges whose endpoints are drawn ∝ rank^-skew."""
    ranks = np.arange(1, n_vertices + 1, dtype=np.float64)
    probs = ranks ** (-max(0.0, skew))
    probs /= probs.sum()
    collected = np.zeros((0, 2), np.int64)
    for _ in range(64):
        need = n_edges - collected.shape[0]
        if need <= 0:
            break
        u = rng.choice(n_vertices, size=2 * need, p=probs)
        v = rng.choice(n_vertices, size=2 * need, p=probs)
        collected = normalize_edges(np.concatenate([collected, np.stack([u, v], axis=1)]))
    if collected.shape[0] > n_edges:
        keep = rng.permutation(collected.shape[0])[:n_edges]
        collected = collected[np.sort(keep)]
    return collected


def relabel(edges: np.ndarray, n_vertices: int, rng: np.random.Generator) -> np.ndarray:
    perm = rng.permutation(n_vertices)
    return normalize_edges(perm[edges])


def reference_triangles(edges: np.ndarray, n_vertices: int) -> np.ndarray:
    """Every triangle once, as rows (a, b, c) with a < b < c, sorted.

    Orients each edge from the lower to the higher (degree, id) rank, walks the
    oriented 2-paths u -> v -> w, and keeps those whose edge (u, w) exists."""
    e = normalize_edges(edges)
    if e.shape[0] == 0:
        return np.zeros((0, 3), np.int64)
    deg = np.bincount(e.ravel(), minlength=n_vertices)
    rank = np.empty(n_vertices, np.int64)
    rank[np.lexsort((np.arange(n_vertices), deg))] = np.arange(n_vertices)
    fwd = rank[e[:, 0]] < rank[e[:, 1]]
    src = np.where(fwd, e[:, 0], e[:, 1])
    dst = np.where(fwd, e[:, 1], e[:, 0])
    order = np.argsort(src, kind="stable")
    src, dst = src[order], dst[order]
    indptr = np.zeros(n_vertices + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n_vertices), out=indptr[1:])
    # 2-paths u -> v -> w: for each oriented edge (u, v), every out-neighbour w of v
    fan = indptr[dst + 1] - indptr[dst]
    u = np.repeat(src, fan)
    v = np.repeat(dst, fan)
    offs = np.arange(int(fan.sum())) - np.repeat(np.cumsum(fan) - fan, fan)
    w = dst[indptr[v] + offs]
    keys = src * n_vertices + dst                      # sorted: src, then dst
    keys.sort()
    want = u * n_vertices + w
    pos = np.searchsorted(keys, want)
    hit = (pos < keys.size) & (keys[np.minimum(pos, keys.size - 1)] == want)
    tri = np.sort(np.stack([u[hit], v[hit], w[hit]], axis=1), axis=1)
    return np.unique(tri, axis=0)


@dataclass
class GraphRequest:
    """One enumeration request for ``query["pattern"]`` over the run's graph."""

    pattern_name: str
    graph: object                    # repro.graph.Graph
    pattern: object                  # repro.graph.Pattern

    @property
    def key(self) -> tuple:
        return (self.pattern_name,)

    def submit(self, session):
        return session.submit_pattern(self.pattern, self.graph)

    def submit_async(self, session):
        raise NotImplementedError("submit_pattern has no asynchronous form")

    @staticmethod
    def answer(result) -> np.ndarray:
        return np.asarray(result.occurrences, np.int64)


class Dataset:
    def __init__(self, cfg: dict, seed: int):
        n, m = int(cfg["vertices"]), int(cfg["edges"])
        base = zipf_graph_edges(np.random.default_rng(int(cfg["base_seed"])), n, m,
                                float(cfg["skew"]))
        self.n_vertices = n
        self.edges = relabel(base, n, np.random.default_rng(seed))
        self._graph = None
        self._reference: Optional[np.ndarray] = None

    def describe(self) -> str:
        return f"graph vertices={self.n_vertices} edges={self.edges.shape[0]}"

    def request(self, query: dict, params: dict) -> GraphRequest:
        from repro.graph import Graph
        from repro.graph import patterns

        if params:
            raise ValueError(f"graph requests take no parameters, got {params}")
        name = query["pattern"]
        if name != "triangle":
            raise ValueError(f"no plain reference for pattern {name!r}")
        if self._graph is None:
            self._graph = Graph.from_edges(self.edges, self.n_vertices)
        return GraphRequest(name, self._graph, getattr(patterns, name)())

    def reference(self, query: dict, params: dict) -> np.ndarray:
        if query["pattern"] != "triangle":
            raise ValueError(f"no plain reference for pattern {query['pattern']!r}")
        if self._reference is None:
            self._reference = reference_triangles(self.edges, self.n_vertices)
        return self._reference


def make_dataset(cfg: dict, seed: int) -> Dataset:
    return Dataset(cfg, seed)
