"""Checks shared by the per-cell CPU rehearsals (one test file per cell, so that
the test workers split them)."""

import numpy as np

from benchlib import spec


def check_sound_run(out, cell):
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    want = {m["name"] for m in spec.cell_metrics(spec.load_benchmark(), cell)["end_to_end"]}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert all(c["limit"] == 0 for c in out["checks"].values())


def alter_answers(monkeypatch):
    """Break the timed path where the answer is produced: the executor's first
    result row (or its count) comes back altered."""
    from repro.mpc.executors import DataplaneExecutor

    inner = DataplaneExecutor.run_many

    def run_many(self, programs, *args, **kwargs):
        results, batch = inner(self, programs, *args, **kwargs)
        r = results[0]
        if r.rows is not None and r.rows.shape[0]:
            rows = np.array(r.rows, copy=True)
            rows[0, -1] += 1
            r.rows = rows
        r.count += 1
        return results, batch

    monkeypatch.setattr(DataplaneExecutor, "run_many", run_many)


def check_caught(out):
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0
    assert out["checks"]["rows_off_max"]["value"] > 0
