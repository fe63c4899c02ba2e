"""The benchmark's CPU tests: they import ``benchlib`` from ``bench/`` and the
program from ``src/``, and start no accelerator backend at import."""

import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
for p in (BENCH_DIR, BENCH_DIR.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import pytest  # noqa: E402

#: each cell at a size a CPU test run can hold.
TINY = {
    "tri-dblp.enum": {"config": {"vertices": 300, "edges": 1000}},
    "ssb-q4.year-count": {"config": {"lineorder_rows": 60_000}},
    "ssb-q4.month-open": {"config": {"lineorder_rows": 60_000},
                          "traffic": {"rate_per_s": 3.0}},
}
SECONDS = 2.0


@pytest.fixture
def run_tiny():
    """Run a cell through the harness on the CPU at its tiny size; no chip check."""
    import jax

    from benchlib import harness, spec

    def run(cell, seed, **kwargs):
        kwargs.setdefault("log", lambda m: None)
        return harness.run_cell(spec.load_benchmark(), cell, seed, SECONDS, False,
                                jax.devices()[:1], overrides=TINY[cell], **kwargs)

    return run
