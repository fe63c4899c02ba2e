"""``benchlib.spans``: device idle split by the innermost host span, busy by op
round, on planes made in the shape ``jax.profiler.ProfileData`` reads, with
program spans on two host threads."""

import json
from dataclasses import dataclass, field
from typing import List

import pytest

from benchlib import spans

MS = 1e6


@dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: list = field(default_factory=list)


@dataclass
class Line:
    name: str
    events: List[Ev]


@dataclass
class Plane:
    name: str
    lines: List[Line]


def ev(name, start_ms, end_ms, **stats):
    return Ev(name, start_ms * MS, (end_ms - start_ms) * MS, list(stats.items()))


def recorded():
    """A 100 ms window. The device runs 0-20 and 50-60 ms. The client thread
    submits inline at 2-40 ms (the rounds' host work ends at 30 ms, the run at
    38 ms) and then waits for arrivals; a drainer thread serves a batch of two
    requests at 55-90 ms, its run at 60-85 ms."""
    client = Line("python", [
        ev("bench.window", 0, 100), ev("bench.submit", 0, 45), ev("bench.idle", 45, 100),
        ev("service.submit", 2, 40, requests=1), ev("executor.run", 5, 38, requests=1),
        ev("executor.op", 5, 30, round="output"), ev("executor.round", 10, 30, round="output"),
        ev("PjitFunction(step)", 21, 50),                   # not a span of ours: ignored
    ])
    drainer = Line("python", [
        ev("service.batch", 55, 90, requests="2 3"), ev("executor.run", 60, 85),
    ])
    device = Plane("/device:TPU:0", [
        Line("XLA Modules", [ev("jit_step", 0, 60)]),
        Line("XLA Ops", [ev("fusion.1", 0, 15), ev("sort.2", 10, 20), ev("fusion.3", 50, 60)]),
    ])
    return [Plane("/host:CPU", [client, drainer]), device]


def test_idle_goes_to_the_innermost_span_program_first():
    r = spans.report(recorded())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.030)
    assert r["idle_s"] == pytest.approx(0.070)
    want = {"executor.round": 10, "executor.run": 8 + 25, "service.submit": 2,
            "service.batch": 5, "bench.submit": 5, "bench.idle": 5 + 10}
    assert r["idle_by_span"] == {k: pytest.approx(v / 1e3) for k, v in want.items()}
    # the drainer's run holds the 60-85 ms idle though the client thread sits in
    # bench.idle then: a program span before a caller span, across threads
    assert r["idle_in_program_s"] == pytest.approx(0.050)
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])


def test_busy_by_round_and_requests():
    r = spans.report(recorded())
    assert r["busy_by_round"] == {"output": pytest.approx(0.010)}
    assert r["busy_outside_rounds_s"] == pytest.approx(0.020)
    assert r["requests"] == 3


def test_idle_outside_every_span_is_none():
    held = [spans.Span("executor.op", 10, 20, 0)]
    got = spans.attribute([(0, 5), (8, 30)], held)
    assert got == {"none": 5 + 2 + 10, "executor.op": 10}


def test_without_a_window_the_device_ops_bound_it():
    planes = recorded()
    client = planes[0].lines[0]
    client.events = [e for e in client.events if e.name != "bench.window"]
    r = spans.report(planes)
    assert r["window_s"] == pytest.approx(0.060)
    assert r["idle_s"] == pytest.approx(0.030)


@pytest.mark.parametrize("lo, hi, want", [(2, 6, 2), (0, 10, 6), (3, 5, 0), (6, 7, 1)])
def test_overlap_with_busy_intervals(lo, hi, want):
    assert spans.overlap([(0, 3), (5, 8)], lo, hi) == want


def test_main_prints_one_json_object(monkeypatch, capsys):
    import jax.profiler

    class Fake:
        planes = recorded()

    monkeypatch.setattr(jax.profiler.ProfileData, "from_file", lambda path: Fake)
    assert spans.main(["run.xplane.pb", "--top", "2"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert list(out["idle_by_span"]) == ["executor.run", "bench.idle"]
