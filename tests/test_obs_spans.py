"""Host spans inside JoinSession, read back from a CPU profiler trace.

One session answers a sync submit, a ``submit_async`` and a ``submit_pattern``
under ``jax.profiler.trace``; the ``.xplane.pb`` is read with ``ProfileData``.
The trace must hold the span catalogue of docs/design/09-service.md
("Tracing") with its nesting and request ids, the timers on the results must
be those spans' durations (one system, not two), the transfer counters must
count what was shipped, and the answers must not change under the profiler.
"""

import glob
from collections import defaultdict
from dataclasses import dataclass

import numpy as np
import pytest

from repro.core.query import random_query
from repro.graph import triangle, zipf_graph
from repro.mpc import DataplaneExecutor, ExecutableCache, JoinSession

#: µs: a timer on a result against its span's duration in the trace
CLOCK_SLACK_US = 50.0

CATALOGUE = (
    "graph.enumerate", "graph.compile_pattern", "graph.postprocess",
    "service.submit", "service.batch", "service.resolve",
    "planner.stats", "planner.compile", "planner.verify",
    "executor.run", "executor.op", "executor.round", "executor.host_prep",
    "executor.compile", "executor.launch", "executor.sync", "executor.assemble",
)
PHASES = ("host_prep", "compile", "launch", "sync")


@dataclass
class Span:
    name: str
    start: float        # ns
    end: float
    thread: int
    args: dict

    @property
    def us(self) -> float:
        return (self.end - self.start) / 1e3

    @property
    def requests(self) -> list:
        return [int(x) for x in str(self.args.get("requests", "")).split()]

    def holds(self, other: "Span") -> bool:
        return (self.thread == other.thread and self.start <= other.start
                and other.end <= self.end and self is not other)


def query():
    return random_query(np.random.default_rng(2), "clique", 3, tuples_per_rel=200,
                        dom_size=30, skew=2.0)


def graph():
    return zipf_graph(np.random.default_rng(1), 120, 400)


class Staged(DataplaneExecutor):
    """Records the numpy operands every dispatch hands to its launch."""

    staged_bytes = 0

    def _run_buckets(self, round_name, items, dispatch, **kwargs):
        def recording(bucket):
            fn, args, post = dispatch(bucket)
            Staged.staged_bytes += sum(a.nbytes for a in args if isinstance(a, np.ndarray))
            return fn, args, post

        return super()._run_buckets(round_name, items, recording, **kwargs)


def answers(session, q, g):
    """One sync submit, one async submit and one pattern submit; the
    SessionResult of each session.submit is captured as the bench does."""
    captured = []
    inner = session.submit

    def submit(*a, **k):
        res = inner(*a, **k)
        captured.append(res)
        return res

    session.submit = submit
    sync = session.submit(q)
    asynchronous = session.submit_async(q).result(timeout=600)
    pattern = session.submit_pattern(triangle(), g)
    return sync, asynchronous, pattern, captured[-1]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    import jax
    from jax.profiler import ProfileData

    q, g = query(), graph()
    with JoinSession(p=4) as plain:
        want = answers(plain, q, g)[:3]
    # a fresh executable cache, so the traced run compiles (executor.compile)
    # and plans (planner.compile) inside the trace
    Staged.staged_bytes = 0
    session = JoinSession(p=4, executor=Staged(compiled_cache=ExecutableCache()))
    log_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(log_dir)):
        got = answers(session, q, g)
    session.close()
    (path,) = glob.glob(str(log_dir / "plugins/profile/*/*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.split(".")[0] in ("graph", "service", "planner", "executor"):
                    spans.append(Span(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                      thread, dict(ev.stats)))
    return want, got, spans


def named(spans, name, request=None):
    return [s for s in spans if s.name == name
            and (request is None or request in s.requests)]


def inside(outer, spans, name):
    return [s for s in spans if s.name == name and outer.holds(s)]


def test_every_catalogue_span_is_in_the_trace(traced):
    _, _, spans = traced
    found = {s.name for s in spans}
    assert set(CATALOGUE) <= found, set(CATALOGUE) - found


def test_spans_nest_by_layer(traced):
    _, _, spans = traced

    def parent(span, names):
        return any(p.holds(span) for p in spans if p.name in names)

    service = ("service.submit", "service.batch")
    for s in spans:
        layer = s.name.split(".")[0]
        if layer == "planner":
            assert parent(s, service), s
        elif s.name == "executor.run":
            assert parent(s, service), s
        elif s.name in ("executor.op", "executor.assemble"):
            assert parent(s, ("executor.run",)), s
        elif s.name == "executor.round":
            assert parent(s, ("executor.op",)), s
        elif s.name in ("executor." + p for p in PHASES):
            assert parent(s, ("executor.round",)), s
        elif s.name in ("graph.compile_pattern", "graph.postprocess"):
            assert parent(s, ("graph.enumerate",)), s
        elif s.name == "service.resolve":
            assert parent(s, service), s
    (enum,) = named(spans, "graph.enumerate")
    assert len(inside(enum, spans, "service.submit")) == 1


def test_request_ids_follow_the_submits(traced):
    _, (sync, asynchronous, _, pattern_sub), spans = traced
    ids = [sync.request_id, asynchronous.request_id, pattern_sub.request_id]
    assert len(set(ids)) == 3 and min(ids) > 0
    submits = named(spans, "service.submit")
    assert sorted(r for s in submits for r in s.requests) == sorted(
        [sync.request_id, pattern_sub.request_id])
    (batch,) = named(spans, "service.batch")
    assert batch.requests == [asynchronous.request_id]
    assert int(str(batch.args["queued_us"]).split()[0]) >= 0
    assert batch.thread != submits[0].thread            # the drainer's own thread
    for top in submits + [batch]:
        below = [s for s in spans if top.holds(s)]
        assert below and all(s.requests == top.requests for s in below)


def test_result_timers_are_the_spans(traced):
    _, (sync, asynchronous, pattern, pattern_sub), spans = traced
    for res in (sync, asynchronous, pattern_sub):
        rid = res.request_id
        for field, name in (("stats_us", "planner.stats"), ("compile_us", "planner.compile"),
                            ("verify_us", "planner.verify")):
            got = sum(s.us for s in named(spans, name, rid))
            assert getattr(res, field) == pytest.approx(got, abs=CLOCK_SLACK_US), field
        (run,) = named(spans, "executor.run", rid)
        eng = res.result
        rounds = defaultdict(float)
        for s in inside(run, spans, "executor.round"):
            rounds[s.args["round"]] += s.us
        assert set(rounds) == set(eng.round_us)
        for k, us in rounds.items():
            assert eng.round_us[k] == pytest.approx(us, abs=CLOCK_SLACK_US), k
        for phase in PHASES:
            got = sum(s.us for s in inside(run, spans, "executor." + phase))
            assert eng.phase_us[phase] == pytest.approx(got, abs=CLOCK_SLACK_US), phase
        ops = inside(run, spans, "executor.op")
        own = sum(op.us - sum(r.us for r in inside(op, spans, "executor.round"))
                  for op in ops)
        (assemble,) = inside(run, spans, "executor.assemble")
        assert eng.lowering_us == pytest.approx(own + assemble.us, abs=CLOCK_SLACK_US)
    (enum,) = named(spans, "graph.enumerate")
    host = sum(s.us for n in ("graph.compile_pattern", "graph.postprocess")
               for s in inside(enum, spans, n))
    assert pattern.host_us == pytest.approx(host, abs=CLOCK_SLACK_US)
    assert pattern.host_us > 0


def test_transfer_counters_count_what_was_shipped(traced):
    _, (sync, asynchronous, _, pattern_sub), spans = traced
    engines = [r.result for r in (sync, asynchronous, pattern_sub)]
    assert sum(e.h2d_bytes for e in engines) == Staged.staged_bytes > 0
    for res in (sync, asynchronous, pattern_sub):
        (run,) = named(spans, "executor.run", res.request_id)
        launches = inside(run, spans, "executor.launch")
        syncs = inside(run, spans, "executor.sync")
        assert res.result.h2d_bytes == sum(int(s.args["h2d_bytes"]) for s in launches)
        assert res.result.d2h_bytes == sum(int(s.args["d2h_bytes"]) for s in syncs) > 0
        assert res.result.host_syncs >= res.result.dispatches > 0


def test_answers_do_not_change_under_the_profiler(traced):
    want, got, _ = traced
    for w, g in zip(want[:2], got[:2]):
        assert w.count == g.count
        assert w.rows.dtype == g.rows.dtype and np.array_equal(w.rows, g.rows)
    assert np.array_equal(want[2].occurrences, got[2].occurrences)
    assert want[2].occurrences.dtype == got[2].occurrences.dtype
