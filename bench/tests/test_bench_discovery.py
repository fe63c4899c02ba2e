"""BENCHMARK.json's shape, and discovery of configurations, mixes and metrics by name."""

import json
import re
import shutil

import pytest

from benchlib import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_entries_have_the_contract_keys_and_names():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and len(c["source"]) <= 200
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert len(names) == len(set(names))


def test_every_cell_reports_setup_another_metric_and_a_layer():
    e2e_names = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e_names
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", ())) <= cells
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e_names
        for cell in m.get("workloads", cells):
            moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    for cell in cells:
        got = spec.cell_metrics(BENCH, cell)
        e2e = {m["name"] for m in got["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2 and got["per_layer"], cell


def test_every_part_of_every_cell_is_found_by_name():
    for w in BENCH["workloads"]:
        cfg = spec.config(BENCH, w["config"])
        assert hasattr(spec.family(cfg), "make_dataset")
        mix = spec.traffic(w["traffic"])
        assert mix["loop"] in ("open", "closed")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(spec.metric_reader(m["name"]).read)


def test_a_new_mix_and_metric_are_new_files(tmp_path):
    bench_dir = tmp_path / "bench"
    shutil.copytree(spec.BENCH_DIR / "traffic", bench_dir / "traffic")
    (bench_dir / "metrics").mkdir()
    (bench_dir / "traffic" / "burst.json").write_text(
        json.dumps({"loop": "open", "rate_per_s": 9, "query": {"name": "q4.1"}}))
    (bench_dir / "metrics" / "x.new_ms.py").write_text("def read(run):\n    return 1.5\n")
    assert spec.traffic("burst", bench_dir=bench_dir)["rate_per_s"] == 9
    assert spec.metric_reader("x.new_ms", bench_dir=bench_dir).read(None) == 1.5
    with pytest.raises(spec.SpecError):
        spec.traffic("absent", bench_dir=bench_dir)
    with pytest.raises(spec.SpecError):
        spec.checked_name("../etc")


def test_metrics_of_a_cell_follow_their_workloads_key():
    bench = {
        "end_to_end": [{"name": "a", "unit": "s"}, {"name": "b", "unit": "s", "workloads": ["y"]}],
        "per_layer": [{"name": "l1", "moves": "a"}, {"name": "l2", "moves": "b"},
                      {"name": "l3", "moves": "a", "workloads": ["y"]}],
    }
    x = spec.cell_metrics(bench, "x")
    assert [m["name"] for m in x["end_to_end"]] == ["a"]
    assert [m["name"] for m in x["per_layer"]] == ["l1"]
    y = spec.cell_metrics(bench, "y")
    assert [m["name"] for m in y["per_layer"]] == ["l1", "l2", "l3"]
