"""One run of one cell: set-up, the measured window, metrics, the check, the last line.

The harness holds what every cell shares: timing with due times, the profiler
window, the trace reduction, the peaks table and the result line. What belongs
to one configuration, mix or metric is found by name (see :mod:`benchlib.spec`).

Set-up makes the data from ``--seed``, builds one ``JoinSession``, times the
first submit of the cell's query (``cold_query_ms``), and submits every request
shape the window will use twice more, so that the plan cache, the learned
capacities and the executables are all warm. Then the window runs the mix:

  * closed loop: one client calls the entry and waits for its answer, again and
    again, until ``--seconds`` have passed; the window closes with the last answer;
  * open loop: requests are sent at their due times whatever happened before;
    latency runs from the due time to the answer; the window closes when the
    last request is answered (or a minute after the last is due).

After the window every answer is compared with the configuration's plain
reference (see :func:`check_answers`).
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from . import spec as specmod
from . import trace as tracemod
from .traffic import Schedule, make_schedule

#: how long after the window an answer may still arrive and count as late, not missing.
LATE_S = 60.0


def process_age_s() -> float:
    """Seconds since this process started, from /proc (the set-up clock)."""
    try:
        fields = Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()
        start_ticks = int(fields[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED_AT


_IMPORTED_AT = time.perf_counter()


@dataclass
class Sent:
    """One request of a run: when it was due, sent and answered, and what came back."""

    params: dict
    request: object
    due: Optional[float] = None            # perf_counter seconds; None in a closed loop
    sent: float = 0.0
    done: Optional[float] = None
    result: object = None                  # what the entry returned
    error: Optional[str] = None
    sessions: list = field(default_factory=list)   # the SessionResults behind it

    @property
    def answered(self) -> bool:
        return self.done is not None and self.error is None

    @property
    def latency_ms(self) -> float:
        start = self.due if self.due is not None else self.sent
        return (self.done - start) * 1e3


@dataclass
class RunRecord:
    """What the metric readers read (``bench/metrics/<name>.py``: ``read(run)``)."""

    cell: str
    window_s: float
    setup_s: float
    window: List[Sent]
    cold: Sent
    trace: Optional[tracemod.TraceSummary] = None
    peaks: Optional[dict] = None

    @property
    def answered(self) -> List[Sent]:
        return [s for s in self.window if s.answered]

    def session_results(self) -> list:
        return [r for s in self.answered for r in s.sessions]

    def batches(self) -> List[dict]:
        """The executor runs of the window, once each (coalesced requests share
        one run, and each of their results carries its counters)."""
        seen, out = set(), []
        for r in self.session_results():
            eng = r.result
            key = (tuple(sorted(eng.phase_us.items())), tuple(sorted(eng.round_us.items())))
            if key not in seen:
                seen.add(key)
                out.append({"phase_us": dict(eng.phase_us), "round_us": dict(eng.round_us)})
        return out


class Cell:
    """A workload of BENCHMARK.json with its configuration, mix and data."""

    def __init__(self, bench: dict, name: str, seed: int, seconds: float,
                 overrides: Optional[dict] = None):
        entry = specmod.workload(bench, name)
        self.cfg = specmod.config(bench, entry["config"])
        self.mix = specmod.traffic(entry["traffic"])
        if overrides:
            self.cfg.update(overrides.get("config", {}))
            self.mix.update(overrides.get("traffic", {}))
        self.family = specmod.family(self.cfg)
        self.seconds = seconds
        self.schedule: Schedule = make_schedule(self.mix, seed, seconds)
        self.dataset = self.family.make_dataset(self.cfg, seed)
        self._requests: Dict[tuple, object] = {}

    def request(self, params: dict):
        key = tuple(sorted(params.items()))
        if key not in self._requests:
            self._requests[key] = self.dataset.request(self.schedule.query, params)
        return self._requests[key]

    def reference(self, params: dict):
        return self.dataset.reference(self.schedule.query, params)


def make_session(cell: "Cell", devices):
    """The system under test: one JoinSession over a mesh of ``devices``, with
    the configuration's ``max_coalesce`` where it names one."""
    from jax.sharding import Mesh

    from repro.mpc.executors import DataplaneExecutor
    from repro.mpc.service import JoinSession

    cfg = cell.cfg
    mesh = Mesh(np.array(devices), ("join",))
    kwargs = {"max_coalesce": int(cfg["max_coalesce"])} if "max_coalesce" in cfg else {}
    return JoinSession(p=int(cfg["p"]), executor=DataplaneExecutor(mesh=mesh), **kwargs)


def capture_submits(session) -> list:
    """Record the SessionResult of every ``session.submit`` (``submit_pattern``
    goes through it) in the returned list."""
    sink: list = []
    inner = session.submit

    def submit(*args, **kwargs):
        res = inner(*args, **kwargs)
        sink.append(res)
        return res

    session.submit = submit
    return sink


def _call(sent: Sent, session, sink: list, asynchronous: bool, traced: bool) -> None:
    """Send one request and wait for its answer."""
    n0 = len(sink)
    sent.sent = time.perf_counter()
    try:
        if asynchronous:
            with tracemod.annotate(traced, "bench.submit_async"):
                fut = sent.request.submit_async(session)
            with tracemod.annotate(traced, "bench.wait"):
                sent.result = fut.result(timeout=LATE_S * 10)
            sent.sessions = [sent.result]
        else:
            with tracemod.annotate(traced, "bench.submit"):
                sent.result = sent.request.submit(session)
            sent.sessions = sink[n0:]
        sent.done = time.perf_counter()
    except Exception as e:  # a failed request is reported and counted, not fatal
        sent.error = f"{type(e).__name__}: {e}"


def warm_up(cell: Cell, session, sink: list) -> (Sent, List[Sent]):
    """Time the first submit of the cell's query, then warm every shape the
    window uses: each distinct request twice more, through the window's entry."""
    asynchronous = cell.schedule.loop == "open"
    first = cell.schedule.params[0]
    cold = Sent(params=first, request=cell.request(first))
    _call(cold, session, sink, asynchronous, False)
    if cold.error:
        raise RuntimeError(f"the cold query failed: {cold.error}")
    warm = []
    for params in cell.schedule.distinct:
        for _ in range(2):
            s = Sent(params=params, request=cell.request(params))
            _call(s, session, sink, asynchronous, False)
            if s.error:
                raise RuntimeError(f"a warm-up query failed: {s.error}")
            warm.append(s)
    return cold, warm


def closed_loop(cell: Cell, session, sink: list, traced: bool) -> (List[Sent], float, float):
    out: List[Sent] = []
    params = cell.schedule.params
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < cell.seconds:
        s = Sent(params=params[i % len(params)], request=cell.request(params[i % len(params)]))
        _call(s, session, sink, False, traced)
        out.append(s)
        i += 1
    t1 = max([s.done for s in out if s.done is not None], default=time.perf_counter())
    return out, t0, t1


def open_loop(cell: Cell, session, traced: bool) -> (List[Sent], float, float):
    out: List[Sent] = []
    futures = []
    t0 = time.perf_counter()
    for due_rel, params in zip(cell.schedule.due_s, cell.schedule.params):
        s = Sent(params=params, request=cell.request(params), due=t0 + due_rel)
        wait = s.due - time.perf_counter()
        if wait > 0:
            with tracemod.annotate(traced, "bench.idle"):
                time.sleep(wait)
        s.sent = time.perf_counter()
        try:
            with tracemod.annotate(traced, "bench.submit_async"):
                fut = s.request.submit_async(session)
        except Exception as e:
            s.error = f"{type(e).__name__}: {e}"
            out.append(s)
            continue

        def on_done(f, s=s):
            s.done = time.perf_counter()

        fut.add_done_callback(on_done)
        futures.append((s, fut))
        out.append(s)
    close = t0 + cell.seconds
    with tracemod.annotate(traced, "bench.wait"):
        for s, fut in futures:
            try:
                s.result = fut.result(timeout=max(0.0, close + LATE_S - time.perf_counter()))
                s.sessions = [s.result]
            except Exception as e:
                s.error = f"{type(e).__name__}: {e}"
                s.done = None
    t1 = max([s.done for s in out if s.done is not None] + [close])
    return out, t0, t1


def rows_off(got, want) -> int:
    """How far an answer is from the reference: |count difference| for counts,
    the size of the multiset symmetric difference for rows."""
    if isinstance(want, (int, np.integer)):
        return abs(int(got) - int(want))
    g, w = np.asarray(got, np.int64), np.asarray(want, np.int64)
    if g.shape == w.shape and np.array_equal(g, w):
        return 0
    if g.ndim != 2 or w.ndim != 2 or g.shape[1] != w.shape[1] or not (g.size and w.size):
        return len(g) + len(w)
    _, inv = np.unique(np.concatenate([g, w]), axis=0, return_inverse=True)
    inv = inv.reshape(-1)
    k = int(inv.max()) + 1
    return int(np.abs(np.bincount(inv[: len(g)], minlength=k)
                      - np.bincount(inv[len(g):], minlength=k)).sum())


def check_answers(cell: Cell, window: List[Sent]) -> Dict[str, Dict[str, float]]:
    """Compare every answer of the window with the plain reference.

    Each number has the limit 0: the configurations promise exact answers."""
    unanswered = sum(1 for s in window if not s.answered)
    wrong, worst = 0, 0
    for s in window:
        if not s.answered:
            continue
        off = rows_off(s.request.answer(s.result), cell.reference(s.params))
        wrong += off > 0
        worst = max(worst, off)
    return {
        "unanswered": {"value": unanswered, "limit": 0},
        "wrong_answers": {"value": wrong, "limit": 0},
        "rows_off_max": {"value": worst, "limit": 0},
    }


def load_peaks(kind: str, bench_dir: Path = specmod.BENCH_DIR) -> dict:
    table = json.loads((bench_dir / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(bench: dict, name: str, seed: int, seconds: float, trace: bool, devices,
             overrides: Optional[dict] = None,
             session_factory: Optional[Callable] = None,
             measure: bool = True,
             log: Callable[[str], None] = lambda m: print(m, file=sys.stderr, flush=True)
             ) -> dict:
    """Run one cell on ``devices`` and return the result line's object."""
    cell = Cell(bench, name, seed, seconds, overrides)
    log(f"cell {name} seed={seed} seconds={seconds} trace={int(trace)} "
        f"{cell.dataset.describe()} loop={cell.schedule.loop}")
    session = (session_factory or make_session)(cell, devices)
    sink = capture_submits(session)
    trace_dir = None
    try:
        cold, warm = warm_up(cell, session, sink)
        log(f"cold_query_ms={cold.latency_ms} warm_ms={[round(w.latency_ms, 3) for w in warm]}")
        with contextlib.ExitStack() as stack:
            if trace:
                trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-"))
                profiler = stack.enter_context(tracemod.Profiler(trace_dir))
            setup_s = process_age_s()
            with tracemod.annotate(trace, tracemod.WINDOW_SPAN):
                if cell.schedule.loop == "closed":
                    window, t0, t1 = closed_loop(cell, session, sink, trace)
                else:
                    window, t0, t1 = open_loop(cell, session, trace)
        mem = memory_peak_bytes(devices)
        summary = tracemod.read_xplane(profiler.xplane()) if trace else None
        record = RunRecord(cell=name, window_s=t1 - t0, setup_s=setup_s, window=window,
                           cold=cold, trace=summary)
    finally:
        session.close()
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    late = [s.sent - s.due for s in window if s.due is not None]
    if late:
        log(f"generator lateness ms: max={max(late) * 1e3} mean={np.mean(late) * 1e3}")
    log(f"window_s={record.window_s} requests={len(window)} answered={len(record.answered)}")

    kind = devices[0].device_kind
    platform = devices[0].platform
    record.peaks = load_peaks(kind) if platform == "tpu" else None
    metrics_of = specmod.cell_metrics(bench, name)["per_layer" if trace else "end_to_end"]
    metrics = {}
    for m in metrics_of if measure else ():
        value = specmod.metric_reader(m["name"]).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = check_answers(cell, window)
    correct = bool(window) and all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": platform, "kind": kind, "count": len(devices)}
    if mem is not None:
        device["memory_peak_bytes"] = mem
    out = {
        "correct": correct,
        "attempted": len(window),
        "failed": len(window) - len(record.answered),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.device_ops(10)],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps[:10]],
        }
    out["checks"] = checks
    for s in window:
        if s.error:
            log(f"request failed: {s.error}")
            break
    for k, c in checks.items():
        log(f"check {k} = {c['value']} (limit {c['limit']})")
    return out
