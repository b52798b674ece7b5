"""The harness finds a new configuration, traffic mix and per-layer
metric by file name, with no edit to any file that was there."""

import filecmp
import json
import os
import shutil

from bench import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)


def test_new_files_are_found_without_editing_existing_ones(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    before = {p: (root / p).read_bytes() for p in ("BENCHMARK.json",)}

    cfg = json.loads((root / "bench/configs/ddm8-dit-b2.json").read_text())
    cfg["name"] = "ddm8-dit-b2-copy"
    (root / "bench/configs/ddm8-dit-b2-copy.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/closed-batch4.json").write_text(json.dumps(
        {"kind": "closed", "clients": 1, "batch": 4, "prompts": "unique",
         "warmup_calls": 2, "trace_s": 6.0, "check_calls": 1}))
    (root / "bench/metrics/calls.batch.py").write_text(
        "def read(run):\n    return float(run.got['calls'])\n")

    bench = json.loads(before["BENCHMARK.json"])
    bench["configs"].append({"name": "ddm8-dit-b2-copy", "source": "x",
                             "file": "bench/configs/ddm8-dit-b2-copy.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "b2-batch4",
                               "config": "ddm8-dit-b2-copy",
                               "traffic": "closed-batch4", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "calls.batch", "unit": "calls",
                               "better": "higher", "source": "host_clock",
                               "layer": "serving loop",
                               "moves": "img_per_s",
                               "workloads": ["b2-batch4"]})
    bench["end_to_end"][1]["workloads"].append("b2-batch4")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    assert "ddm8-dit-b2-copy" in harness.configs(str(root))
    assert "closed-batch4" in harness.traffics(str(root))
    assert "calls.batch" in harness.metric_readers(str(root))
    cell = harness.cell(str(root), "b2-batch4")
    assert cell["config"]["name"] == "ddm8-dit-b2-copy"
    assert cell["traffic"]["batch"] == 4
    assert [m["name"] for m in cell["per_layer"]] == ["calls.batch"]
    assert {m["name"] for m in cell["end_to_end"]} == {"setup_s",
                                                       "img_per_s"}
    read = harness.load_reader(cell["per_layer"][0]["path"])
    assert read(type("Run", (), {"got": {"calls": 3}})()) == 3.0

    # nothing that was there changed
    cmp = filecmp.dircmp(BENCH, root / "bench",
                         ignore=["__pycache__"])
    assert not cmp.diff_files
    for sub in ("configs", "traffic", "metrics"):
        assert not filecmp.dircmp(os.path.join(BENCH, sub),
                                  root / "bench" / sub).diff_files


def test_every_named_file_exists():
    bench = harness.benchmark(REPO)
    configs = harness.configs(REPO)
    mixes = harness.traffics(REPO)
    readers = harness.metric_readers(REPO)
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(REPO, c["file"]))
        assert c["name"] in configs
    for w in bench["workloads"]:
        assert w["traffic"] in mixes
        cell = harness.cell(REPO, w["name"])
        assert cell["per_layer"], w["name"]
    for m in bench["per_layer"]:
        assert m["name"] in readers
