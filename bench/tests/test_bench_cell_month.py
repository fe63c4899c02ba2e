"""CPU rehearsal of cell ssb-q4.month-open at a tiny size, through the harness: a sound run
is correct, and the control and an altered answer are not."""

from cellcases import alter_answers, check_caught, check_sound_run

CELL = "ssb-q4.month-open"


def test_sound_run_is_correct(run_tiny):
    check_sound_run(run_tiny(CELL, 2**31 + 3), CELL)


def test_altered_answer_is_not_correct(run_tiny, monkeypatch):
    alter_answers(monkeypatch)
    check_caught(run_tiny(CELL, 17))


def test_control_is_not_correct(run_tiny):
    from control import ControlSession

    check_caught(run_tiny(CELL, 5, session_factory=ControlSession, measure=False))


def test_unanswered_request_is_not_correct(run_tiny, monkeypatch):
    """A request whose answer never comes counts against correct, after the wait."""
    from concurrent.futures import Future

    from benchlib import harness
    from repro.mpc.service import JoinSession

    inner = JoinSession.submit_async
    window_calls = []          # the warm-up is answered; the window's every second call not

    def submit_async(self, *args, **kwargs):
        if window_calls:
            window_calls.append(1)
            if len(window_calls) % 2:
                return Future()
        return inner(self, *args, **kwargs)

    window = harness.open_loop

    def faulty_window(*args, **kwargs):
        monkeypatch.setattr(harness, "LATE_S", 0.5)
        window_calls.append(1)
        return window(*args, **kwargs)

    monkeypatch.setattr(JoinSession, "submit_async", submit_async)
    monkeypatch.setattr(harness, "open_loop", faulty_window)
    out = run_tiny(CELL, 23)
    assert out["correct"] is False
    assert out["checks"]["unanswered"]["value"] > 0 and out["failed"] > 0


def test_one_wrong_answer_of_a_repeated_month_is_not_correct(run_tiny, monkeypatch):
    """Every answer is compared: of many requests for one month, all alike in
    shape, the second of the window comes back with one value altered."""
    import jax
    import numpy as np

    from benchlib import harness
    from conftest import TINY
    from repro.mpc.executors import DataplaneExecutor

    inner = DataplaneExecutor.run_many
    emits = []                 # the window's materialized results, counted

    def run_many(self, programs, *args, **kwargs):
        results, batch = inner(self, programs, *args, **kwargs)
        r = results[0]
        if emits and r.rows is not None and r.rows.shape[0]:
            emits.append(1)
            if len(emits) == 3:
                rows = np.array(r.rows, copy=True)
                rows[-1, -1] += 1
                r.rows = rows
        return results, batch

    window = harness.open_loop

    def faulty_window(*args, **kwargs):
        emits.append(1)
        return window(*args, **kwargs)

    monkeypatch.setattr(DataplaneExecutor, "run_many", run_many)
    monkeypatch.setattr(harness, "open_loop", faulty_window)
    one_month = {"values": ["1998-12"], "zipf": 1.0}
    overrides = {"config": TINY[CELL]["config"],
                 "traffic": dict(TINY[CELL]["traffic"], pick={"partition": one_month})}
    out = harness.run_cell(harness.specmod.load_benchmark(), CELL, 29, 3.0, False,
                           jax.devices()[:1], overrides=overrides,
                           log=lambda m: None)
    assert len(emits) > 3 and out["attempted"] >= 4
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] == 1
    assert out["checks"]["rows_off_max"]["value"] == 2
