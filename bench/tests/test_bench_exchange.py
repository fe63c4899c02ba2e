"""The four-chip cell's entries and the readers of what exists only across
chips: collective time and its share of the interconnect's roofline, read from
a made-up four-plane trace; the all_to_all bytes and the spread of routed rows
over devices, read from made-up executor runs."""

from types import SimpleNamespace

import pytest
from test_bench_cell_tri_x4 import CELL, CONFIG

from benchlib import collectives, spec, trace
from benchlib.harness import RunRecord, Sent
from test_bench_trace import Ev, Line, Plane

MS = 1e6
PEAKS = {"ici_bits_per_s": 1600e9, "hbm_bytes_per_s": 819e9}
X4 = "tri-dblp-x4.enum"
NEW = ("device.collective_ms", "device.collective_roofline", "executor.a2a_mb",
       "rounds.route_imbalance")

#: one all-to-all of 2·4·64·3 int32 per chip (6,144 bytes), plus its counts (2·4 int32)
SYNC = ("%all-to-all.3 = (s32[2,4,64,3]{3,2,1,0:T(8,128)}, s32[2,4,1]{2,1,0}) "
        "all-to-all(s32[2,4,64,3]{3,2,1,0:T(8,128)} %fusion.1, s32[2,4,1]{2,1,0} %b.2), "
        "channel_id=1, replica_groups={{0,1,2,3}}, dimensions={1}")
START = ("%all-to-all-start.1 = ((s32[4,256,2]{2,1,0}), s32[4,256,2]{2,1,0}) "
         "all-to-all-start(s32[4,256,2]{2,1,0:T(8,128)} %copy.4), channel_id=2, "
         "replica_groups=[1,4]<=[4], dimensions={0}")
DONE = ("%all-to-all-done.1 = s32[4,256,2]{2,1,0} "
        "all-to-all-done(((s32[4,256,2]{2,1,0}), s32[4,256,2]{2,1,0}) %all-to-all-start.1)")
SYNC_BYTES = 2 * 4 * 64 * 3 * 4 + 2 * 4 * 4
START_BYTES = 4 * 256 * 2 * 4


def four_planes():
    """A 100 ms window on four chips. Each chip runs a sync all-to-all for 1 ms
    (at 10 ms), a start at 30 ms whose done ends at 32 ms, and a 20 ms kernel."""
    host = Plane("/host:CPU", [Line("python", [Ev("bench.window", 0, 100 * MS),
                                               Ev("bench.submit", 0, 100 * MS)])])
    planes = [host]
    for d in range(4):
        planes.append(Plane(f"/device:TPU:{d}", [Line("XLA Ops", [
            Ev(SYNC, 10 * MS, 1 * MS),
            Ev(START, 30 * MS, 0.1 * MS),
            Ev("%fusion.9 = s32[8]{0} fusion(s32[8]{0} %p)", 31 * MS, 0.5 * MS),
            Ev(DONE, 31.9 * MS, 0.1 * MS),
            Ev("%merge_join_counts = s32[8]{0} custom-call(s32[8]{0} %a)", 50 * MS, 20 * MS),
        ])]))
    return planes


def record(window, summary=None, peaks=PEAKS):
    return RunRecord(cell=X4, window_s=1.0, setup_s=1.0, window=window,
                     cold=Sent(params={}, request=None), trace=summary, peaks=peaks)


def answered(engines=()):
    return Sent(params={}, request=None, sent=0.0, done=1.0,
                sessions=[SimpleNamespace(result=e) for e in engines])


def engine(phase, **counters):
    return SimpleNamespace(phase_us={"host_prep": phase}, round_us={"output": phase},
                           **counters)


def read(name, run):
    return spec.metric_reader(name).read(run)


def test_the_cell_and_its_configuration_are_the_rehearsed_entries():
    bench = spec.load_benchmark()
    four = bench["configs"][-1]
    # The deployment names its own source: the same data source and cuts as the
    # one-chip configuration would make it no configuration of its own.
    assert {k: v for k, v in four.items() if k != "source"} == \
        {k: v for k, v in CONFIG.items() if k != "source"}
    one = next(c for c in bench["configs"] if c["name"] == "graph-tri-dblp")
    assert four["reduced"] == one["reduced"] and four["source"] != one["source"]
    assert "com-DBLP" in four["source"] and "MPC" in four["source"]
    assert bench["workloads"][-1] == CELL
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert entries[name]["workloads"] == [X4]
        assert entries[name]["moves"] == "latency_p50_ms"
    got = {m["name"] for m in spec.cell_metrics(bench, X4)["per_layer"]}
    assert set(NEW) <= got
    for cell in ("tri-dblp.enum", "ssb-q4.year-count", "ssb-q4.month-open"):
        assert not set(NEW) & {m["name"] for m in spec.cell_metrics(bench, cell)["per_layer"]}


@pytest.mark.parametrize("hlo, kind, nbytes, group", [
    (SYNC, "sync", SYNC_BYTES, 4),
    (START, "start", START_BYTES, 7),      # groups not spelled out: the default
    (DONE, "done", None, None),
    # the route round's all-to-all as a four-chip TPU v5e trace names it
    ("%all_to_all.7 = s32[4,4,65536,3]{2,0,3,1:T(4,128)S(1)} all-to-all("
     "s32[4,4,65536,3]{2,0,3,1:T(4,128)S(1)} %copy.55), channel_id=1, "
     "replica_groups={{0,1,2,3}}, dimensions={1}", "sync", 4 * 4 * 65536 * 3 * 4, 4),
    ("%all-to-all-start = (s32[2]{0}) async-start(s32[2,3]{1,0} %x), "
     "calls=%all-to-all.async_computation", "start", 24, 7),
    ("%fusion.2 = s32[8]{0} fusion(s32[8]{0} %all-to-all.3)", None, None, None),
    ("%copy-start = (s32[8]{0}) async-start(s32[8]{0} %x), calls=%copy", None, None, None),
])
def test_all_to_all_events_are_told_apart_and_sized(hlo, kind, nbytes, group):
    assert collectives.all_to_all_kind(hlo) == kind
    if nbytes is not None:
        assert collectives.operand_bytes(hlo) == nbytes
        assert collectives.group_size(hlo, 7) == group
        assert collectives.leaving_bytes(hlo, 7) == pytest.approx(nbytes * (group - 1) / group)


def test_collective_time_and_roofline_on_four_chips():
    s = trace.reduce_trace(four_planes())
    assert s.n_devices == 4
    # the sync op, the start and the done each count their own time
    assert s.collective_s == pytest.approx((1 + 0.1 + 0.1) * 1e-3)
    run = record([answered(), answered()], s)
    assert read("device.collective_ms", run) == pytest.approx(1.2 / 2)
    spans = collectives.all_to_all_intervals(s.ops)
    assert sorted(round(t * 1e3, 6) for _, t in spans) == [1.0] * 4 + [2.0] * 4
    # the start's groups are not spelled out: the trace's four chips
    least = 4 * (SYNC_BYTES + START_BYTES) * 3 / 4 / (1600e9 / 8)
    want = 100 * least / (4 * (1.0 + 2.0) * 1e-3)
    assert read("device.collective_roofline", run) == pytest.approx(want)
    assert 0 < want <= 100


def test_route_imbalance_is_the_median_of_each_runs_worst_round():
    runs = [
        engine(1.0, device_rows={"step1": [10, 10, 10, 10], "step3-route": [40, 20, 20, 20]}),
        engine(2.0, device_rows={"step3-route": [30, 30, 30, 30]}),
        engine(3.0, device_rows={"step3-route": [10, 10, 10, 50], "hc-route": [0, 0, 0, 0]}),
    ]
    run = record([answered([e]) for e in runs])
    # worst rounds: 40/25 = 1.6, 1.0, 50/20 = 2.5
    assert read("rounds.route_imbalance", run) == pytest.approx(1.6)


def test_a2a_mb_counts_each_run_once():
    shared = engine(1.0, a2a_bytes=3_000_000)
    twin = SimpleNamespace(**vars(shared))
    alone = engine(5.0, a2a_bytes=1_000_000)
    run = record([answered([shared]), answered([twin]), answered([alone])])
    assert read("executor.a2a_mb", run) == pytest.approx(4.0 / 3)


def untraced():
    return record([answered([engine(1.0)])])


def without_peaks():
    return record([answered([engine(1.0)])], trace.reduce_trace(four_planes()), None)


def without_all_to_all():
    planes = four_planes()
    for plane in planes[1:]:
        plane.lines[0].events = [e for e in plane.lines[0].events
                                 if collectives.all_to_all_kind(e.name) is None]
    return record([answered([engine(1.0)])], trace.reduce_trace(planes))


@pytest.mark.parametrize("name, make", [
    *[(name, lambda: record([])) for name in NEW],            # nothing answered
    ("device.collective_ms", untraced),
    ("device.collective_roofline", untraced),
    ("device.collective_roofline", without_peaks),
    ("device.collective_roofline", without_all_to_all),
    ("executor.a2a_mb", untraced),                             # runs without the counter
    ("rounds.route_imbalance", untraced),
])
def test_new_readers_read_nothing_where_there_is_nothing(name, make):
    assert read(name, make()) is None
