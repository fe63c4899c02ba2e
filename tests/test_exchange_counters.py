"""The exchange's counters on four virtual CPU devices: ``a2a_bytes`` of a run
is the sum, over its launches, of the bytes its executables hand to
``all_to_all`` on every device, counted here independently from each
dispatch's jaxpr; the ``executor.launch`` and ``executor.round`` spans carry
the bytes and the rows per device; a one-device mesh runs no ``all_to_all``
and counts 0 (JAX lowers an ``all_to_all`` over one device to nothing).
Four devices need the XLA flag before JAX starts, so the runs
happen in a child process."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

CHILD = r"""
import glob, json, sys, tempfile
import numpy as np
import jax
from jax.sharding import Mesh
from jax.profiler import ProfileData
from repro.graph import triangle, zipf_graph
from repro.mpc import DataplaneExecutor, ExecutableCache, JoinSession


def jaxprs(params):
    for v in params.values():
        for x in (v if isinstance(v, (list, tuple)) else [v]):
            if hasattr(x, "eqns"):
                yield x
            elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr


def a2a_operand_bytes(jaxpr):
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "all_to_all":
            total += sum(int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
                         for v in eqn.invars)
        for sub in jaxprs(eqn.params):
            total += a2a_operand_bytes(sub)
    return total


class Recording(DataplaneExecutor):
    # each dispatch is launched once: the bytes its jaxpr sends on every
    # device; over an axis of one device an all_to_all lowers to nothing
    expected = 0

    def _run_buckets(self, round_name, items, dispatch, **kwargs):
        def recording(bucket):
            fn, args, post = dispatch(bucket)
            if self.mesh.size > 1:
                closed = jax.make_jaxpr(fn)(*args)
                Recording.expected += a2a_operand_bytes(closed.jaxpr) * self.mesh.size
            return fn, args, post

        return super()._run_buckets(round_name, items, recording, **kwargs)


def spans(path):
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, dict(ev.stats)) for ev in line.events
                        if ev.name in ("executor.launch", "executor.round")]
    return out


graph = zipf_graph(np.random.default_rng(1), 300, 1200)
out = {}
for name, n_dev, batch in (("batched", 4, True), ("unbatched", 4, False), ("one", 1, True)):
    Recording.expected = 0
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("join",))
    ex = Recording(mesh=mesh, batch_stages=batch, compiled_cache=ExecutableCache())
    log_dir = tempfile.mkdtemp()
    with JoinSession(p=16, executor=ex) as session, jax.profiler.trace(log_dir):
        res = session.submit_pattern(triangle(), graph)
    (path,) = glob.glob(log_dir + "/plugins/profile/*/*.xplane.pb")
    got = spans(path)
    eng = res.engine
    out[name] = {
        "count": res.count, "a2a_bytes": eng.a2a_bytes, "expected": Recording.expected,
        "launch_a2a": sum(int(a["a2a_bytes"]) for n, a in got if n == "executor.launch"),
        "device_rows": eng.device_rows,
        "round_rows": {a["round"]: [int(x) for x in str(a["device_rows"]).split()]
                       for n, a in got if n == "executor.round" and "device_rows" in a},
    }
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", CHILD], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", ["batched", "unbatched"])
def test_a2a_bytes_are_the_send_buffers_of_every_launch(runs, name):
    r = runs[name]
    assert r["a2a_bytes"] == r["expected"] > 0
    assert r["launch_a2a"] == r["a2a_bytes"]


def test_no_all_to_all_on_one_device_counts_nothing(runs):
    one = runs["one"]
    assert one["count"] == runs["batched"]["count"]
    assert one["a2a_bytes"] == one["expected"] == one["launch_a2a"] == 0


@pytest.mark.parametrize("name", ["batched", "unbatched", "one"])
def test_round_spans_carry_the_rows_per_device(runs, name):
    r = runs[name]
    assert r["device_rows"] and r["round_rows"] == r["device_rows"]
    n_dev = 1 if name == "one" else 4
    assert all(len(rows) == n_dev and min(rows) > 0 for rows in r["device_rows"].values())
