"""CPU rehearsal of the four-chip triangle cell, on four virtual CPU devices.

``bench/configs/graph-tri-dblp-x4.json`` is not yet a cell of BENCHMARK.json:
it has not run on four chips. These tests add the entries below to a copy of
BENCHMARK.json, as the change that proves the cell on the chips will add them,
and drive the cell through the harness at a tiny size in a child process that
starts JAX with four host devices. A sound run is correct, with rows on every
device after the route round; an altered answer is not.
"""

import json
import os
import subprocess
import sys

from benchlib import spec

CONFIG = {"name": "graph-tri-dblp-x4", "source": "https://snap.stanford.edu/data/com-DBLP.html",
          "file": "bench/configs/graph-tri-dblp-x4.json", "reduced": ["edges", "vertices"],
          "why": "com-DBLP's edges per vertex and power-law degrees, cut to 2^18 edges: "
                 "a quarter of them per chip"}
CELL = {"name": "tri-dblp-x4.enum", "config": "graph-tri-dblp-x4", "traffic": "enum",
        "chips": 4, "why": "tri-dblp.enum's client on a 2^18-edge graph over four chips: the "
                           "route rounds' all_to_all and how skew spreads rows over devices"}

CHILD = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax
from benchlib import harness, spec
from cellcases import alter_answers

bench = spec.load_benchmark()
bench["configs"].append(json.loads(sys.argv[3]))
bench["workloads"].append(json.loads(sys.argv[4]))
if sys.argv[5] == "alter":
    class Patch:
        setattr = staticmethod(setattr)
    alter_answers(Patch())
sinks = []
capture = harness.capture_submits
harness.capture_submits = lambda session: sinks.append(capture(session)) or sinks[-1]
devices = jax.devices()
assert len(devices) == 4, devices
out = harness.run_cell(bench, "tri-dblp-x4.enum", 2**31 + 11, 2.0, False, devices[:4],
                       overrides={"config": {"vertices": 600, "edges": 2000}},
                       log=lambda m: None)
cfg = spec.config(bench, "graph-tri-dblp-x4")
out["config_edges"] = cfg["edges"]
out["device_rows"] = sinks[0][-1].result.device_rows
print(json.dumps(out))
"""


def _child(mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", CHILD, str(spec.BENCH_DIR), str(spec.ROOT / "src"),
         json.dumps(CONFIG), json.dumps(CELL), mode],
        cwd=spec.BENCH_DIR / "tests", env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_config_keeps_com_dblp_edges_per_vertex_at_four_times_the_edges():
    bench = spec.load_benchmark()
    one = spec.config(bench, "graph-tri-dblp")
    bench["configs"].append(CONFIG)
    four = spec.config(bench, "graph-tri-dblp-x4")
    assert four["edges"] == 4 * one["edges"]
    assert abs(four["edges"] / four["vertices"] - one["edges"] / one["vertices"]) < 1e-3
    assert {k: v for k, v in four.items() if k not in ("edges", "vertices", "name")} \
        .keys() == {k for k in one if k not in ("edges", "vertices", "name")}


def test_sound_run_on_four_devices_is_correct():
    out = _child("sound")
    assert out["correct"] is True, out["checks"]
    assert out["device"]["count"] == 4 and out["failed"] == 0
    assert out["config_edges"] == 2**18
    assert out["device_rows"]
    for rnd, rows in out["device_rows"].items():
        assert len(rows) == 4 and all(r > 0 for r in rows), (rnd, rows)


def test_altered_answer_on_four_devices_is_not_correct():
    out = _child("alter")
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0
