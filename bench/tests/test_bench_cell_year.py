"""CPU rehearsal of cell ssb-q4.year-count at a tiny size, through the harness: a sound run
is correct, and the control and an altered answer are not."""

from cellcases import alter_answers, check_caught, check_sound_run

CELL = "ssb-q4.year-count"


def test_sound_run_is_correct(run_tiny):
    check_sound_run(run_tiny(CELL, 2**31 + 3), CELL)


def test_altered_answer_is_not_correct(run_tiny, monkeypatch):
    alter_answers(monkeypatch)
    check_caught(run_tiny(CELL, 17))


def test_control_is_not_correct(run_tiny):
    from control import ControlSession

    check_caught(run_tiny(CELL, 5, session_factory=ControlSession, measure=False))
