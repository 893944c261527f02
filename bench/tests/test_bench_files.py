"""Every file the benchmark finds by name is there and well formed, and a
cell can be added from files and entries alone."""
import json
import re
import shutil
from pathlib import Path

import pytest

import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH_JSON = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH_JSON["workloads"]]


def test_top_level_keys_and_command():
    assert set(BENCH_JSON) == {"command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"}
    assert BENCH_JSON["command"][:2] == ["python3", "bench/run.py"]
    for p in BENCH_JSON["paths"]:
        assert (ROOT / p).is_dir()
    assert 1 <= BENCH_JSON["run_seconds"] <= 51


def test_names_units_and_entry_keys():
    seen = set()
    for c in BENCH_JSON["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCH_JSON["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        for k in ("name", "config", "traffic"):
            assert NAME.match(w[k]), w[k]
    for m in BENCH_JSON["end_to_end"] + BENCH_JSON["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in seen
        seen.add(m["name"])
    for m in BENCH_JSON["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH_JSON["end_to_end"])
    for m in BENCH_JSON["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_config_has_a_cell_and_its_files():
    used = {w["config"] for w in BENCH_JSON["workloads"]}
    assert used == {c["name"] for c in BENCH_JSON["configs"]}
    for c in BENCH_JSON["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["source"] == c["source"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_and_metrics(cell):
    c = harness.load_cell(cell, root=ROOT)
    assert c.traffic["kind"] == "train"
    assert (ROOT / "bench" / "kinds" / f"{c.traffic['kind']}.py").is_file()
    assert c.workload["limits"]
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e, (m["name"], cell)
        assert callable(harness.load_reader(m["name"]))


def test_a_cell_is_added_by_files_and_entries_alone(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH_JSON))
    conf = dict(json.loads((ROOT / bench["configs"][0]["file"]).read_text()))
    conf["model"] = dict(conf["model"], num_layers=6)
    (root / "bench" / "configs" / "throwaway.json").write_text(
        json.dumps(conf))
    (root / "bench" / "traffic" / "throwaway-mix.json").write_text(
        json.dumps({"kind": "train"}))
    (root / "bench" / "workloads" / "throwaway.cell.json").write_text(
        json.dumps({"limits": {"loss_gap": 1.0}}))
    (root / "bench" / "metrics" / "throwaway_share.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["configs"].append({"name": "throwaway", "source": "x",
                             "file": "bench/configs/throwaway.json",
                             "reduced": ["num_layers"], "why": "test"})
    bench["workloads"].append({"name": "throwaway.cell",
                               "config": "throwaway",
                               "traffic": "throwaway-mix", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0].setdefault("workloads", []).append(
        "throwaway.cell")
    bench["per_layer"].append({"name": "throwaway_share", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device",
                               "moves": bench["end_to_end"][0]["name"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    c = harness.load_cell("throwaway.cell", root=root)
    assert c.model["num_layers"] == 6 and c.traffic["kind"] == "train"
    names = [m["name"] for m in c.per_layer]
    assert names == ["throwaway_share"]
    assert harness.load_reader("throwaway_share", root / "bench")(None) == 42
    # the new metric has no workloads list: every cell that reports what it
    # moves reports it too
    for cell in CELLS:
        old = harness.load_cell(cell, root=root)
        if any(m["name"] == bench["end_to_end"][0]["name"]
               for m in old.end_to_end):
            assert "throwaway_share" in [m["name"] for m in old.per_layer]
