"""The readers of the program's own spans and counters (``graph.host_ms``,
``executor.lowering_host_ms``, ``executor.transfer_mb``), on made-up runs; and
the trace reduction of the recorded chip trace, pinned field by field, so that
spans the program adds to a trace leave every existing reduction as it was."""

import gzip
import hashlib
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchlib import spec, trace
from benchlib.harness import RunRecord, Sent


def engine(phase=1.0, lowering_us=None, h2d=None, d2h=None):
    """An executor run's result; ``None`` fields are absent, as before the
    program reported them."""
    eng = SimpleNamespace(phase_us={"host_prep": phase}, round_us={"output": 2 * phase})
    if lowering_us is not None:
        eng.lowering_us = lowering_us
    if h2d is not None:
        eng.h2d_bytes, eng.d2h_bytes = h2d, d2h
    return eng


def record(sents):
    return RunRecord(cell="x", window_s=1.0, setup_s=1.0, window=sents,
                     cold=Sent(params={}, request=None))


def answered(result=None, engines=()):
    return Sent(params={}, request=None, sent=0.0, done=1.0, result=result,
                sessions=[SimpleNamespace(result=e) for e in engines])


def read(name, run):
    return spec.metric_reader(name).read(run)


@pytest.mark.parametrize("host_us, want", [((1500.0, 2500.0), 2.0), ((0.0,), 0.0)])
def test_graph_host_ms_is_the_mean_per_answered_query(host_us, want):
    run = record([answered(SimpleNamespace(host_us=us)) for us in host_us])
    assert read("graph.host_ms", run) == pytest.approx(want)


@pytest.mark.parametrize("window", [
    [],                                                          # nothing answered
    [answered(SimpleNamespace(count=3))],                        # results without the field
    [Sent(params={}, request=None, sent=0.0, error="failed")],   # a failed request only
])
def test_graph_host_ms_reads_nothing_where_there_is_nothing(window):
    assert read("graph.host_ms", record(window)) is None


def test_executor_readers_count_a_coalesced_run_once():
    shared = engine(phase=1.0, lowering_us=3000.0, h2d=4_000_000, d2h=2_000_000)
    alone = engine(phase=5.0, lowering_us=1000.0, h2d=1_000_000, d2h=1_000_000)
    # two requests answered by one coalesced run (each result carries its
    # counters), a third by a run of its own
    twin = SimpleNamespace(**vars(shared))
    run = record([answered(engines=[shared]), answered(engines=[twin]),
                  answered(engines=[alone])])
    assert read("executor.lowering_host_ms", run) == pytest.approx((3.0 + 1.0) / 3)
    assert read("executor.transfer_mb", run) == pytest.approx((6.0 + 2.0) / 3)


@pytest.mark.parametrize("name", ["executor.lowering_host_ms", "executor.transfer_mb"])
@pytest.mark.parametrize("window", [
    [],
    [answered(engines=[engine()])],                  # runs without the counters
    [answered(engines=[])],                          # answers with no executor run
])
def test_executor_readers_read_nothing_where_there_is_nothing(name, window):
    assert read(name, record(window)) is None


def test_new_metrics_are_entries_of_the_benchmark():
    bench = spec.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert entries["graph.host_ms"]["workloads"] == ["tri-dblp.enum"]
    for name in ("graph.host_ms", "executor.lowering_host_ms", "executor.transfer_mb"):
        assert entries[name]["moves"] == "latency_p50_ms"
    for cell in ("tri-dblp.enum", "ssb-q4.year-count", "ssb-q4.month-open"):
        got = {m["name"] for m in spec.cell_metrics(bench, cell)["per_layer"]}
        assert {"executor.lowering_host_ms", "executor.transfer_mb"} <= got
        assert ("graph.host_ms" in got) == (cell == "tri-dblp.enum")


def digest(x) -> str:
    return hashlib.sha256(json.dumps(x, sort_keys=True).encode()).hexdigest()


def test_the_recorded_chip_trace_reduces_as_before(tmp_path):
    """Every field of the reduction of ``tiny_tri.xplane.pb.gz`` (12 triangle
    queries on one TPU v5e), as the reduction gave it before the program
    carried spans of its own."""
    path = tmp_path / "tiny.xplane.pb"
    path.write_bytes(gzip.decompress(
        (Path(__file__).parent / "data" / "tiny_tri.xplane.pb.gz").read_bytes()))
    s = trace.read_xplane(path)
    assert (s.window_s, s.busy_s, s.n_devices, s.collective_s) == (
        0.216240711, 0.011007396, 1, 0)
    assert s.idle_gaps == [("bench.submit", t) for t in (
        0.010535421, 0.010299884, 0.010283196, 0.010272057, 0.010087785,
        0.010069978, 0.010059294, 0.010036697, 0.009909725, 0.009690942)]
    assert len(s.op_s) == 189
    assert digest(s.op_s) == "31166ea6e69e0afaf97667c9521a74b1703f922ca0f96b632bfb293201aa4891"
    assert len(s.ops) == 2388
    assert digest([[e.name, e.start_ns, e.dur_ns, e.device] for e in s.ops]) == \
        "85cecf9fcdc803ac26610283dfa6c0498bbc9dee58be4d86d4fb522918ad5369"
