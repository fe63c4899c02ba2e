#!/usr/bin/env python3
"""The control of each cell's correctness check: it has to come out not correct.

The control is the plain reference put in the program's place with the
configuration's guarantee broken: each answer loses its last ``max(1, n // 1000)``
rows (a count loses as many), as a bounded output buffer without the overflow
retry would lose them. It runs through the harness exactly as a benchmark run
does (same data, schedule, window and comparison) on several seeds in one
process, and prints each run's checks. The benchmark's own runs never run it.

A closed loop's call to the control returns after ``ANSWER_S`` seconds, so that
its window holds about as many answers as a run of the program does, each of
them compared, and not the million an instant answer would give.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 --seconds 5

Like ``bench/run.py`` it refuses a host without a TPU; the CPU tests drive the
same :class:`ControlSession` through the harness at a small size.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from concurrent.futures import Future
from pathlib import Path
from types import SimpleNamespace

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

#: seconds a closed loop's call to the control takes.
ANSWER_S = 0.5


def lossy(answer):
    """The reference's answer without its last max(1, n // 1000) rows."""
    if isinstance(answer, (int, np.integer)):
        return int(answer) - max(1, int(answer) // 1000)
    n = answer.shape[0]
    return answer[: n - max(1, n // 1000)]


class ControlSession:
    """Answers each request of a cell from the reference, lossily."""

    def __init__(self, cell, devices=None):
        self.cell = cell
        self.stats = SimpleNamespace()

    def _params(self, obj) -> dict:
        for key, req in self.cell._requests.items():
            if getattr(req, "graph", None) is obj or getattr(req, "query", None) is obj:
                return dict(key)
        raise KeyError("the control got a request the cell never built")

    def submit_pattern(self, pattern, graph):
        time.sleep(ANSWER_S)
        return SimpleNamespace(occurrences=lossy(self.cell.reference(self._params(graph))))

    def submit(self, query, materialize=True, **kwargs):
        time.sleep(ANSWER_S)
        return self._answer(query)

    def _answer(self, query):
        want = lossy(self.cell.reference(self._params(query)))
        if isinstance(want, int):
            return SimpleNamespace(rows=None, count=want)
        return SimpleNamespace(rows=want, count=want.shape[0])

    def submit_async(self, query, materialize=True, **kwargs):
        fut: Future = Future()
        fut.set_result(self._answer(query))
        return fut

    def close(self):
        pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH_DIR), str(ROOT / "src")]
    from benchlib import harness, spec

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"control: JAX found no TPU (platform {devices[0].platform!r})", file=sys.stderr)
        return 1
    bench = spec.load_benchmark(ROOT)
    chips = int(spec.workload(bench, args.workload)["chips"])
    for seed in args.seeds:
        out = harness.run_cell(bench, args.workload, seed, args.seconds, False,
                               devices[:chips], session_factory=ControlSession,
                               measure=False)
        print(json.dumps({"workload": args.workload, "seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"], "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
