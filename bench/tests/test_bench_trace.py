"""The trace reduction: busy union, idle share, op and collective times, idle
gaps named by host activity. Checked on a small trace built in the shape that
``jax.profiler.ProfileData`` reads from a TPU's ``.xplane.pb`` (planes
``/host:CPU`` and ``/device:TPU:<n>``, device ops on the line ``XLA Ops``)."""

from dataclasses import dataclass, field
from typing import List

import pytest

from benchlib import trace


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


MS = 1e6


def recorded():
    """A 100 ms window; device 0 busy 0-20, 15-30 (overlap), 50-60 ms; device 1
    busy 0-40 ms. The host submits at 0-35 ms and waits at 35-100 ms."""
    host = Plane("/host:CPU", [
        Line("python", [Ev("bench.window", 0, 100 * MS), Ev("bench.submit", 0, 35 * MS),
                        Ev("bench.wait", 35 * MS, 65 * MS)]),
        Line("tf_pjrt", [Ev("PjitFunction(local_join)", 31 * MS, 3 * MS)]),
    ])
    dev0 = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_step", 0, 60 * MS)]),
        Line("XLA Ops", [Ev("merge_join_counts", 0, 20 * MS, [("bytes_accessed", 4096)]),
                         Ev("all-to-all.3", 15 * MS, 15 * MS),
                         Ev("sort.1", 50 * MS, 10 * MS),
                         Ev("sort.1", 120 * MS, 10 * MS)]),   # after the window: cut
    ])
    dev1 = Plane("/device:TPU:1", [Line("XLA Ops", [Ev("merge_join_counts", 0, 40 * MS)])])
    other = Plane("/device:TPU:0 SparseCore 0", [Line("XLA Ops", [Ev("x", 0, 99 * MS)])])
    return [host, dev0, dev1, other]


def test_busy_idle_and_op_times():
    s = trace.reduce_trace(recorded())
    assert s.n_devices == 2
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx((0.040 + 0.040) / 2)     # union per device, mean
    assert s.idle_share == pytest.approx(0.6)
    assert s.op_s["merge_join_counts"] == pytest.approx((0.020 + 0.040) / 2)
    assert s.op_s["sort.1"] == pytest.approx(0.010 / 2)
    assert s.collective_s == pytest.approx(0.015 / 2)
    assert s.op_time_s("merge_join") == pytest.approx(0.030)
    assert s.device_ops(1) == [("merge_join_counts", pytest.approx(0.030))]
    kept = [e for e in s.ops if e.name == "merge_join_counts"]
    assert sorted(e.device for e in kept) == [0, 1]
    assert len(s.ops) == 4                                    # the op after the window is cut


def test_idle_gaps_are_named_by_what_the_host_did():
    s = trace.reduce_trace(recorded())
    # device 0 idles 30-50 ms and 60-100 ms, while the host waits
    assert [(n, pytest.approx(t)) for n, t in s.idle_gaps] == [
        ("bench.wait", 0.040), ("bench.wait", 0.020)]


def test_interval_helpers():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.gaps([(2, 3), (5, 6)], 0, 10) == [(0, 2), (3, 5), (6, 10)]


def test_a_trace_without_window_or_device_is_refused():
    planes = recorded()
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_trace([p for p in planes if p.name != "/host:CPU"])
    with pytest.raises(ValueError, match="TPU"):
        trace.reduce_trace([planes[0]])


def test_short_names_drop_layouts_operands_and_attributes():
    hlo = ('%vmap_jit_merge_join_counts__.1 = (s32[4194304]{0:T(1024)S(1)}, s32[4194304]{0:T(1024)S(1)}) '
           'custom-call(s32[4194304]{0:T(1024)S(1)} %fusion, s32[32768]{0:T(1024)S(1)} %bitcast.93), '
           'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}')
    assert trace.short_name(hlo) == ('%vmap_jit_merge_join_counts__.1 = (s32[4194304], s32[4194304]) '
                                     'custom-call(s32[4194304], s32[32768])')
    assert trace.short_name("%sort.1 = s32[8]{0} sort(s32[8]{0} %p), dimensions={0}") == \
        "%sort.1 = s32[8] sort(s32[8])"


def test_reduction_of_a_recorded_chip_trace(tmp_path):
    """A trace recorded on one TPU v5e: 12 triangle queries on a 1000-edge graph
    (``tri-dblp.enum`` cut to a tiny size), 0.216 s window."""
    import gzip
    import json
    from pathlib import Path
    from types import SimpleNamespace

    from benchlib import spec

    data = Path(__file__).parent / "data" / "tiny_tri.xplane.pb.gz"
    path = tmp_path / "tiny.xplane.pb"
    path.write_bytes(gzip.decompress(data.read_bytes()))
    s = trace.read_xplane(path)
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(0.216240711)
    assert s.busy_s == pytest.approx(0.011007396)
    assert 0.0 < s.busy_s < s.window_s and s.collective_s == 0.0
    names = [e.name for e in s.ops]
    assert sum("merge_join_counts" in n for n in names) == 24
    assert sum("merge_join_pairs" in n for n in names) == 24
    assert all(name == "bench.submit" for name, _ in s.idle_gaps)
    peaks = json.loads((spec.BENCH_DIR / "peaks.json").read_text())["devices"]["TPU v5 lite"]
    run = SimpleNamespace(trace=s, peaks=peaks)
    share = spec.metric_reader("kernel.probe_device_share").read(run)
    roof = spec.metric_reader("kernel.probe_roofline").read(run)
    assert 0.0 < share < 100.0
    assert 0.0 < roof < 100.0
