"""The chip smoke script off the chip: it must refuse a non-TPU platform, and its
phases and oracle must hold on the CPU at a tiny size (the rehearsal that keeps
it runnable without spending chip time)."""

from __future__ import annotations

import importlib.util
import itertools
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_fails_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_oracle_triangles_matches_brute_force(smoke):
    rng = np.random.default_rng(3)
    n = 40
    edges = rng.integers(0, n, (300, 2))   # duplicates, both orders, self-loops
    adj = np.zeros((n, n), bool)
    for u, v in edges:
        if u != v:
            adj[u, v] = adj[v, u] = True
    want = sum(adj[a, b] and adj[b, c] and adj[a, c]
               for a, b, c in itertools.combinations(range(n), 3))
    assert smoke.oracle_triangles(edges, n) == want


def test_triangle_phase_on_cpu(smoke, monkeypatch):
    """Phase a end to end at 2^12 edges on one CPU device (the jnp reference
    kernels run there, so the on-chip kernel count is stubbed)."""
    import jax
    from jax.sharding import Mesh

    monkeypatch.setattr(smoke, "kernel_executables", lambda cache: 1)
    mesh = Mesh(np.array(jax.devices()[:1]), ("join",))
    smoke.phase_triangles(mesh, 2**10, 2**12)
