#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell's configuration, traffic mix and
metrics are named in BENCHMARK.json. The run makes its data from ``--seed``,
warms up, measures for ``--seconds`` seconds, checks every answer against the
configuration's plain reference, and prints one JSON object as the last line of
standard output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown`` of the trace, and last the
``checks`` that decided ``correct``, each number beside its limit.

It exits non-zero and prints no result when JAX finds no TPU or fewer chips
than the cell asks for, or when the program under test is not in the checkout.
JAX's persistent compilation cache is kept at ``.jax_cache/`` in the checkout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH_DIR))
    from benchlib import harness, spec

    bench = spec.load_benchmark(ROOT)
    entry = spec.workload(bench, args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program under test (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    # the compile cache lives in the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r})", file=sys.stderr)
        return 1
    chips = int(entry["chips"])
    if len(devices) < chips:
        print(f"bench: the cell asks for {chips} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.mpc.executors import enable_compile_cache

    enable_compile_cache()
    out = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                           bool(args.trace), devices[:chips])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
