"""Finds everything a cell needs by name: from BENCHMARK.json the
configuration's file, the traffic mix's file and one file per per-layer
metric; from the configuration's `"kind"` the model kind
(kinds/<kind>.py); from the traffic file's `"kind"` the traffic kind
(traffic_kinds/<kind>.py); from a metric's `cost_fn` the function that
counts a kernel's operations and bytes.  There is no registry and no
branch on a name: a later PR adds files and entries (PERF.md section 4 has
the recipe)."""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Callable, Dict, List

from benchmarks.lib import peaks

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _read(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark(path: str = "BENCHMARK.json") -> Dict[str, Any]:
    return _read(os.path.join(ROOT, path))


def _found(directory: str) -> List[str]:
    try:
        names = os.listdir(os.path.join(BENCH_DIR, directory))
    except OSError:
        return []
    return sorted(n[:-3] for n in names
                  if n.endswith(".py") and not n.startswith("_"))


_LOADED: Dict[str, Any] = {}        # path -> module


def _load_by_name(directory: str, name: str, what: str):
    """The module `<directory>/<name>.py`, loaded by path (a kind's name
    may hold a hyphen) once per process."""
    path = os.path.join(BENCH_DIR, directory, f"{name}.py")
    if not re.fullmatch(r"[\w.-]+", str(name)) or not os.path.isfile(path):
        raise ValueError(f"no {what} {name!r}: benchmarks/{directory}/ "
                         f"holds {_found(directory)}")
    if path not in _LOADED:
        module_spec = importlib.util.spec_from_file_location(
            f"benchmarks.{directory}." + re.sub(r"\W", "_", name), path)
        module = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(module)
        _LOADED[path] = module
    return _LOADED[path]


def model_kind(name: str):
    """What the harness knows about one architecture: check,
    transformer_kwargs, param_counts, train_flops_per_token,
    kv_bytes_per_token, parity + TOLERANCES + CHECKS, COST_FNS."""
    return _load_by_name("kinds", name, "model kind")


def traffic_kind(name: str):
    """How one kind of traffic is offered: CELL (the runner in lib/) and,
    for a serving kind, clients() and drive()."""
    return _load_by_name("traffic_kinds", name, "traffic kind")


def cost_fns(kind) -> Dict[str, Callable]:
    """Cost functions by name: the cell's kind first, then the shared
    table."""
    return {**peaks.COST_FNS, **getattr(kind, "COST_FNS", {})}


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
    traffic = load_traffic(traffic_override or cell["traffic"])
    # Every name is resolved here, in the driver, before a worker starts.
    kind = model_kind(config.get("kind"))
    kind.check(config)
    traffic_kind(traffic.get("kind"))
    known = cost_fns(kind)

    def reported(m: Dict[str, Any]) -> bool:
        return "workloads" not in m or workload in m["workloads"]

    layer_metrics: List[Dict[str, Any]] = []
    for m in bench["per_layer"]:
        if reported(m):
            spec = _read(os.path.join(BENCH_DIR, "layer_metrics",
                                      f"{m['name']}.json"))
            spec.update(name=m["name"], unit=m["unit"])
            for k in spec.get("kernels", ()):
                if k["cost_fn"] not in known:
                    raise ValueError(
                        f"metric {m['name']!r}: no cost function "
                        f"{k['cost_fn']!r} in kind {config['kind']!r} or "
                        f"lib/peaks.py (found: {sorted(known)})")
            layer_metrics.append(spec)
    return {
        "cell": cell, "config": config, "traffic": traffic,
        "cost_fns": known,
        "end_to_end": [m for m in bench["end_to_end"] if reported(m)],
        "layer_metrics": layer_metrics,
        "run_seconds": bench["run_seconds"],
    }
