"""Finds everything a cell needs by the names BENCHMARK.json gives: the
configuration's file, the traffic mix's file, and one file per per-layer
metric.  There is no registry: a later PR adds files and entries."""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _read(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark(path: str = "BENCHMARK.json") -> Dict[str, Any]:
    return _read(os.path.join(ROOT, path))


def load_traffic(name: str) -> Dict[str, Any]:
    t = _read(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))
    t["name"] = name
    return t


def load_cell(workload: str, traffic_override: str = "",
              benchmark: str = "BENCHMARK.json") -> Dict[str, Any]:
    """The cell `workload`: its entry, configuration, traffic mix and the
    metrics it reports.  `traffic_override` runs the cell's configuration
    under another traffic file (the capacity probe: see
    traffic/chat-capacity.json)."""
    bench = load_benchmark(benchmark)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have: {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _read(os.path.join(ROOT, entry["file"]))
    config["name"] = entry["name"]

    def reported(m: Dict[str, Any]) -> bool:
        return "workloads" not in m or workload in m["workloads"]

    layer_metrics: List[Dict[str, Any]] = []
    for m in bench["per_layer"]:
        if reported(m):
            spec = _read(os.path.join(BENCH_DIR, "layer_metrics",
                                      f"{m['name']}.json"))
            spec.update(name=m["name"], unit=m["unit"])
            layer_metrics.append(spec)
    return {
        "cell": cell, "config": config,
        "traffic": load_traffic(traffic_override or cell["traffic"]),
        "end_to_end": [m for m in bench["end_to_end"] if reported(m)],
        "layer_metrics": layer_metrics,
        "run_seconds": bench["run_seconds"],
    }
