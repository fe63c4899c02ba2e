"""Where a traced run's device idle went, by the program span that held the host.

    python3 bench/benchlib/spans.py <run.xplane.pb> [--top 20]

Reads one ``.xplane.pb`` (a ``--trace 1`` window, or ``jax.profiler.trace``
around a ``JoinSession``: docs/design/09-service.md, "Tracing") and prints
one JSON object:

  * ``idle_by_span``: the first device's idle time split by the innermost host
    span that covers each part of it. Program spans (``graph.``, ``service.``,
    ``planner.``, ``executor.``) come first; idle that no program span covers
    goes to the innermost benchmark span (``bench.``), else to ``none``.
    Across threads the shortest covering span counts as the innermost;
  * ``busy_by_round``: device busy time inside the ``executor.round`` spans,
    by op round. A round ends in a blocking readback, so the device operations
    that run inside its span are its own;
  * ``requests``: how many requests the service spans carried, to divide by.

The window is the ``bench.window`` span when the trace has one, else the
extent of the device's operations.
"""

from __future__ import annotations

import argparse
import bisect
import json
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):                        # run as a script
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from benchlib.trace import DEVICE_PLANE, OPS_LINE, WINDOW_SPAN, gaps, union  # noqa: E402

PROGRAM = ("graph.", "service.", "planner.", "executor.")
CALLER = ("bench.",)


@dataclass
class Span:
    name: str
    start: float            # ns, the profiler's clock
    end: float
    thread: int
    args: Dict[str, object] = field(default_factory=dict)

    @property
    def program(self) -> bool:
        return self.name.startswith(PROGRAM)


def attribute(intervals: List[Tuple[float, float]], spans: List[Span]) -> Dict[str, float]:
    """Split the sorted, disjoint ``intervals`` by the innermost span covering
    each part: program spans before benchmark spans, then the shortest. Parts
    that no span covers go to ``"none"``. Returns ns by span name."""
    events = sorted([(s.start, 1, i) for i, s in enumerate(spans)]
                    + [(s.end, 0, i) for i, s in enumerate(spans)])
    rank = [(0 if s.program else 1, s.end - s.start) for s in spans]
    out: Dict[str, float] = defaultdict(float)
    active: set = set()
    k = 0

    def advance(upto: float) -> None:
        nonlocal k
        while k < len(events) and events[k][0] <= upto:
            _, starts, i = events[k]
            (active.add if starts else active.discard)(i)
            k += 1

    for lo, hi in intervals:
        advance(lo)
        t = lo
        while t < hi:
            end = min(events[k][0], hi) if k < len(events) else hi
            inner = min(active, key=rank.__getitem__, default=None)
            out["none" if inner is None else spans[inner].name] += end - t
            t = end
            advance(t)
    return dict(out)


def overlap(busy: List[Tuple[float, float]], lo: float, hi: float) -> float:
    """Length of [lo, hi) that ``busy`` (sorted, disjoint) covers."""
    i = max(0, bisect.bisect_right(busy, (lo, float("inf"))) - 1)
    got = 0.0
    while i < len(busy) and busy[i][0] < hi:
        got += max(0.0, min(hi, busy[i][1]) - max(lo, busy[i][0]))
        i += 1
    return got


def read_planes(planes) -> Tuple[List[Span], Dict[int, List[Tuple[float, float]]]]:
    """The program's and the benchmark's host spans (one ``thread`` per host
    line), and each device's operation intervals."""
    spans: List[Span] = []
    devices: Dict[int, List[Tuple[float, float]]] = defaultdict(list)
    thread = 0
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices[int(m.group(1))].extend(
                    (ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events)
            elif plane.name.startswith("/host:"):
                spans.extend(Span(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                  thread, dict(ev.stats))
                             for ev in line.events if ev.name.startswith(PROGRAM + CALLER))
            thread += 1
    return spans, dict(devices)


def report(planes, top: int = 20) -> dict:
    spans, devices = read_planes(planes)
    busy = union(devices[min(devices)]) if devices else []
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0].start, windows[0].end
    elif busy:
        lo, hi = busy[0][0], busy[-1][1]
    else:
        lo = hi = 0.0
    busy = [(max(s, lo), min(e, hi)) for s, e in busy if e > lo and s < hi]
    named = [s for s in spans if s.name != WINDOW_SPAN and s.end > lo and s.start < hi]
    idle = attribute(gaps(busy, lo, hi), named)
    rounds: Dict[str, float] = defaultdict(float)
    for s in named:
        if s.name == "executor.round":
            rounds[str(s.args.get("round"))] += overlap(busy, s.start, s.end)
    ids = {r for s in named if s.name in ("service.submit", "service.batch")
           for r in str(s.args.get("requests", "")).split()}
    busy_s = sum(e - s for s, e in busy) / 1e9

    def ranked(d):
        return dict(sorted(((k, v / 1e9) for k, v in d.items()), key=lambda kv: -kv[1])[:top])

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_s,
        "idle_s": (hi - lo) / 1e9 - busy_s,
        "idle_in_program_s": sum(v for k, v in idle.items() if k.startswith(PROGRAM)) / 1e9,
        "idle_by_span": ranked(idle),
        "busy_by_round": ranked(rounds),
        "busy_outside_rounds_s": busy_s - sum(rounds.values()) / 1e9,
        "requests": len(ids),
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--top", type=int, default=20)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData

    print(json.dumps(report(ProfileData.from_file(args.xplane).planes, args.top), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
