"""Cells, configurations, traffic mixes and metrics are found by name,
from files: a new workload file is a new cell with no code edit."""
import json
import shutil

from chipbench import cells

ROOT = cells.ROOT


def test_every_benchmark_cell_is_found():
    bench = cells.load_benchmark()
    for name in [w["name"] for w in bench["workloads"]]:
        cell = cells.find(bench, name)
        assert cell.sizing["slots"] > 0 and cell.config["name"] == \
            cell.entry["config"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(cells.reader(m["name"]))


def test_new_workload_file_is_a_new_cell(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for d in ("configs", "workloads", "traffic"):
        shutil.copytree(ROOT / "chipbench" / d, tmp_path / "chipbench" / d)
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "olmoe-1b-7b.chat", "config":
                               "olmoe-1b-7b", "traffic": "chat", "chips": 1,
                               "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "chipbench" / "traffic" / "chat.json").write_text(json.dumps(
        {"arrival": "poisson", "rate_rps": 2.0, "block": 10,
         "prompt_classes": {"256": 0.5, "2048": 0.5},
         "output_classes": {"64": 0.5, "512": 0.5}}))
    (tmp_path / "chipbench" / "workloads" / "olmoe-1b-7b.chat.json").write_text(
        json.dumps({"slots": 4, "positions": 2560,
                    "check": {"tokens": 64, "min_tokens": 8, "mean_gap": 1}}))
    cell = cells.find(bench, "olmoe-1b-7b.chat", root=tmp_path)
    assert cell.mix["rate_rps"] == 2.0 and cell.sizing["slots"] == 4
    assert [m["name"] for m in cell.end_to_end] == ["itl_p95_ms", "setup_s"]
    assert cell.per_layer == []


def test_benchmark_file_keeps_its_shape():
    import re
    bench = cells.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    cfg_names = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file() and name.match(c["name"])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and w["config"] in cfg_names
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        reported = [m for m in bench["end_to_end"]
                    if "workloads" not in m or w["name"] in m["workloads"]]
        assert "setup_s" in [m["name"] for m in reported]
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert "workloads" not in moved or w in moved["workloads"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert name.match(m["name"]) and m["better"] in ("lower", "higher")
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
    n = len(bench["workloads"])
    assert n <= 24
    # a full check of 24 cells fits its time
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
