"""The profiler window and the reduction from a device trace to numbers.

A ``--trace 1`` run records one profiler trace over its whole measured window.
The benchmark's own code marks host spans with ``jax.profiler.TraceAnnotation``
(:func:`annotate`): ``bench.window`` around the window, and a span around each
call into the service and each wait for a result. The reduction reads the
``.xplane.pb`` file with ``jax.profiler.ProfileData`` and gives, on the clock
of the ``bench.window`` span:

  * the union of the intervals in which an operation ran on each device, and
    the idle share 1 - busy / window;
  * device time by operation (averaged over the devices used), each named by
    its HLO text without layouts, operand names and attributes;
  * the longest idle gaps, each named by the host event that covers most of it.
"""

from __future__ import annotations

import contextlib
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
#: a device plane of the trace, one per chip.
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: the line of a device plane that holds one event per operation executed.
OPS_LINE = "XLA Ops"
#: operation names that are collectives (all_to_all, all_reduce, ...).
COLLECTIVE = re.compile(r"all-to-all|all-reduce|all-gather|reduce-scatter|collective-permute"
                        r"|all_to_all|all_reduce|all_gather|psum", re.IGNORECASE)


def annotate(enabled: bool, name: str):
    """A host span in the profiler's trace, or nothing when tracing is off."""
    if not enabled:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class Profiler:
    """Starts the JAX profiler into ``log_dir`` and stops it; Python tracing off."""

    def __init__(self, log_dir: Path):
        self.log_dir = Path(log_dir)

    def __enter__(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        return False

    def xplane(self) -> Path:
        found = sorted(self.log_dir.glob("plugins/profile/*/*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.log_dir}")
        return found[-1]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping [start, end) intervals; sorted, disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The parts of [lo, hi) that ``busy`` (disjoint, sorted) leaves uncovered."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


@dataclass
class OpEvent:
    name: str               # the op's HLO text, as the trace names it
    start_ns: float
    dur_ns: float
    device: int


_BRACES = re.compile(r"\{[^{}]*\}")
_ATTRS = re.compile(r", [a-z_]+=.*$")
_OPERAND = re.compile(r" %[\w.-]+")


def short_name(hlo: str) -> str:
    """``%x = (s32[8]{0:T(1024)}) custom-call(s32[8]{0} %a), kind=...`` ->
    ``%x = (s32[8]) custom-call(s32[8])``."""
    s = _BRACES.sub("", _BRACES.sub("", hlo))
    s = _ATTRS.sub("", s)
    head, eq, tail = s.partition(" = ")
    return head + eq + _OPERAND.sub("", tail)


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                        # union of device op intervals, mean over devices
    n_devices: int
    op_s: Dict[str, float]               # device time by short op name, mean over devices
    collective_s: float                  # device time of collectives, mean over devices
    idle_gaps: List[Tuple[str, float]]   # longest idle gaps, named by host activity
    ops: List[OpEvent] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def device_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]

    def op_time_s(self, pattern: str) -> float:
        """Device time (mean over devices) of ops whose short name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(v for k, v in self.op_s.items() if rx.search(k))


def _host_events(planes) -> List[Tuple[float, float, str]]:
    out = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns > 0:
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    return out


def _label(host: List[Tuple[float, float, str]], s: float, e: float) -> str:
    """The host event that covers most of [s, e); among equals, the shortest."""
    best, best_key = "no host span", (0.0, 0.0)
    for hs, he, name in host:
        if name == WINDOW_SPAN:
            continue
        cover = min(he, e) - max(hs, s)
        if cover <= 0:
            continue
        key = (cover, -(he - hs))
        if key > best_key:
            best, best_key = name, key
    return best


def reduce_trace(planes, top_gaps: int = 10) -> TraceSummary:
    """Reduce the planes of one trace (``ProfileData.planes``) to a summary."""
    planes = list(planes)
    host = _host_events(planes)
    windows = [(s, e) for s, e, n in host if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found {len(windows)}")
    w0, w1 = windows[0]
    per_device: Dict[int, List[OpEvent]] = {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        dev = int(m.group(1))
        evs = per_device.setdefault(dev, [])
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
                if e <= s:
                    continue
                evs.append(OpEvent(ev.name, s, e - s, dev))
    if not per_device:
        raise ValueError("the trace has no TPU device plane")
    n = len(per_device)
    op_s: Dict[str, float] = {}
    busy = 0.0
    first = min(per_device)
    first_busy: List[Tuple[float, float]] = []
    for dev, evs in per_device.items():
        u = union((ev.start_ns, ev.start_ns + ev.dur_ns) for ev in evs)
        busy += sum(e - s for s, e in u)
        if dev == first:
            first_busy = u
        for ev in evs:
            name = short_name(ev.name)
            op_s[name] = op_s.get(name, 0.0) + ev.dur_ns
    op_s = {k: v / n / 1e9 for k, v in op_s.items()}
    idle = sorted(gaps(first_busy, w0, w1), key=lambda g: -(g[1] - g[0]))[:top_gaps]
    return TraceSummary(
        window_s=(w1 - w0) / 1e9,
        busy_s=busy / n / 1e9,
        n_devices=n,
        op_s=op_s,
        collective_s=sum(v for k, v in op_s.items() if COLLECTIVE.search(k)),
        idle_gaps=[(_label(host, s, e), (e - s) / 1e9) for s, e in idle],
        ops=[ev for evs in per_device.values() for ev in evs],
    )


def read_xplane(path: Path) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_trace(ProfileData.from_file(str(path)).planes)
