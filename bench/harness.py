"""Finds what ``BENCHMARK.json`` names, by file name, under ``bench/``.

* a configuration ``<name>`` is ``bench/configs/<name>.json``;
* a traffic mix ``<name>`` is ``bench/traffic/<name>.json``;
* a per-layer metric ``<name>`` is read by ``bench/metrics/<name>.py``,
  whose ``read(run)`` returns the number, or ``None`` where the run holds
  nothing to read (the metric is then left out of the result line).

So a later change adds a cell, a mix or a metric by adding files and
entries, and edits nothing that is here.  A configuration larger than one
chip states ``expert_shards`` N (absent: 1), the number of chips its
experts are placed on, K/N to a chip; the ``chips`` of its cells must
equal N, and N must divide the number of experts.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os

from bench import traffic as traffic_mod

BENCH = os.path.dirname(os.path.abspath(__file__))


def _names(root: str, sub: str, ext: str) -> dict[str, str]:
    out = {}
    for path in sorted(glob.glob(os.path.join(root, "bench", sub, "*" + ext))):
        out[os.path.basename(path)[: -len(ext)]] = path
    return out


def configs(root: str) -> dict[str, str]:
    return _names(root, "configs", ".json")


def traffics(root: str) -> dict[str, str]:
    return _names(root, "traffic", ".json")


def metric_readers(root: str) -> dict[str, str]:
    return {k: v for k, v in _names(root, "metrics", ".py").items()
            if not k.startswith("_")}


def benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_reader(path: str):
    """The ``read`` function of a metric file."""
    name = "bench_metric_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell(root: str, workload: str) -> dict:
    """Everything one run of ``workload`` needs: its entry, configuration,
    traffic mix, and the metrics (end-to-end and per-layer) it reports."""
    bench = benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    shards = config.get("expert_shards", 1)
    if not isinstance(shards, int) or shards < 1 \
            or len(config["experts"]) % shards:
        raise ValueError(f"{conf['name']}: expert_shards {shards!r} does not "
                         f"divide its {len(config['experts'])} experts")
    if entry["chips"] != shards:
        raise ValueError(f"{workload}: chips {entry['chips']} but "
                         f"{conf['name']} places its experts on {shards}")
    mix = traffic_mod.load(traffics(root)[entry["traffic"]])

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    readers = metric_readers(root)
    per_layer = []
    for metric in bench["per_layer"]:
        if mine(metric):
            if metric["name"] not in readers:
                raise KeyError(f"no reader bench/metrics/{metric['name']}.py")
            per_layer.append(dict(metric, path=readers[metric["name"]]))
    return {
        "workload": entry,
        "config": config,
        "traffic": mix,
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": per_layer,
    }
