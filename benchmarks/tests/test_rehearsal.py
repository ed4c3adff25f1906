"""The harness end to end at a toy size on the CPU: interpret-mode
kernels, the runtime's own workers, 4 virtual devices for the fsdp cell.
Never a device number: the line says `rehearsal` and platform `cpu`.
About three minutes; run by the builder, not by tier-1."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.lib import spec

TMP = os.path.join(spec.BENCH_DIR, "tests", ".tmp")
DATA = os.path.join(spec.BENCH_DIR, "tests", "data")


def toy_twins():
    """The toys under tests/data/ by what each stands for: a traffic mix's
    twin names the mix (`twin_of`), a configuration's twin is the toy of
    its model kind.  A cell added with a twin is found; one without keeps
    its own files and is not rehearsed here."""
    traffic, configs = {}, {}
    for name in sorted(os.listdir(DATA)):
        if not name.endswith(".json"):
            continue
        with open(os.path.join(DATA, name)) as f:
            toy = json.load(f)
        if "twin_of" in toy:
            traffic[toy["twin_of"]] = "../tests/data/" + name[:-5]
        elif "kind" in toy:
            configs[toy["kind"]] = "benchmarks/tests/data/" + name
    return traffic, configs


@pytest.fixture(scope="module")
def rehearsal_benchmark():
    """BENCHMARK.json with every configuration swapped for the toy of its
    kind and every traffic mix for its toy twin: same cells, same
    metrics."""
    bench = spec.load_benchmark()
    traffic, configs = toy_twins()
    for c in bench["configs"]:
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            c["file"] = configs.get(json.load(f)["kind"], c["file"])
    for w in bench["workloads"]:
        w["traffic"] = traffic.get(w["traffic"], w["traffic"])
    os.makedirs(TMP, exist_ok=True)
    path = os.path.join(TMP, "BENCHMARK.rehearsal.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return os.path.relpath(path, spec.ROOT)


def _run(benchmark, workload, trace, extra=()):
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", workload,
         "--seed", "3000000001", "--seconds", "6", "--trace", str(trace),
         "--rehearsal", "--benchmark", benchmark, *extra],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,trace,metric", [
    ("train-4k-1chip", 0, "train_tokens_per_s_per_chip"),
    ("train-4k-fsdp4", 1, "train_step_ms_p50"),
    ("serve-batch-saturated", 0, "decode_tokens_per_s"),
    ("serve-chat-steady", 0, "tpot_p95_ms"),
    ("serve-chat-steady", 1, "chat_stream_gap_p95_ms"),
    ("serve-prefix-sessions", 0, "decode_tokens_per_s"),
    ("serve-prefix-sessions", 1, "sessions_prefix_hit_share")])
def test_cell_rehearses_on_cpu(rehearsal_benchmark, workload, trace, metric):
    line = _run(rehearsal_benchmark, workload, trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    # a rehearsal looks at no chip: nothing waited, nothing retried
    assert (line["device"]["chip_wait_s"], line["device"]["release_wait_s"],
            line["device"]["busy_retries"]) == (0.0, 0.0, 0)
    assert line["metrics"][metric]["value"] > 0
    if not trace:
        assert line["metrics"]["setup_s"]["value"] > 0
    if workload == "train-4k-fsdp4":
        assert line["device"]["count"] == 4
        assert line["checks"]["state_devices"] == 4
    assert line["checks"].get("compiles_in_window", 0) == 0
    assert line["checks"]["compared"]["flash_err"][0] < \
        line["checks"]["compared"]["flash_err"][1]
    if workload == "serve-prefix-sessions":
        # the engine's own counter reached the line, with no harness code
        assert line["counters"]["prefix_cache.hit_tokens"] > 0
        assert line["counters"]["prompt_tokens"] > \
            line["counters"]["prefix_cache.hit_tokens"]
        assert line["extra"]["engine_ttft_hit_n"] > 0
        assert line["extra"]["conversations_finished"] > 0


def test_added_kind_files_run_with_no_other_file_changed(tmp_path):
    """What a `model_config` PR does: a copy of benchmarks/ gains one model
    kind, one traffic kind, a configuration, a traffic mix, two per-layer
    metrics and its entries; every file that was there is byte for byte
    the repo's, and the rehearsal runs the new cell."""
    tree = tmp_path / "benchmarks"
    shutil.copytree(spec.BENCH_DIR, tree, ignore=shutil.ignore_patterns(
        "__pycache__", "*.pyc", ".tmp", "*.pb"))
    before = {p: p.read_bytes() for p in tree.rglob("*") if p.is_file()}
    with open(os.path.join(DATA, "tiny-l2.json")) as f:
        config = dict(json.load(f), kind="toy-gqa")
    with open(os.path.join(DATA, "tiny-batch.json")) as f:
        mix = dict(json.load(f), kind="toy_loop")
    shutil.copy(os.path.join(DATA, "toy-gqa.py"), tree / "kinds")
    shutil.copy(os.path.join(DATA, "toy_loop.py"), tree / "traffic_kinds")
    (tree / "configs" / "toy.json").write_text(json.dumps(config))
    (tree / "traffic" / "toy.json").write_text(json.dumps(mix))
    layer = {"layer": "Engine host loop", "moves": "decode_tokens_per_s",
             "cells": ["toy-cell"]}
    (tree / "layer_metrics" / "toy_queries.json").write_text(json.dumps(dict(
        layer, source="counter", reduction="value",
        key="prefix_cache.queries")))
    (tree / "layer_metrics" / "toy_roofline.json").write_text(json.dumps(dict(
        layer, source="trace", reduction="roofline_share", kernels=[
            {"op_pattern": "^paged_attention", "cost_fn": "toy_cost"}])))
    bench = spec.load_benchmark()
    metric = {"better": "higher", "source": "program_counter",
              "layer": "Engine host loop", "moves": "decode_tokens_per_s",
              "workloads": ["toy-cell"]}
    bench.update(
        configs=[{"name": "toy", "file": "benchmarks/configs/toy.json"}],
        workloads=[{"name": "toy-cell", "config": "toy", "traffic": "toy",
                    "chips": 1}],
        end_to_end=[dict(m, workloads=["toy-cell"])
                    for m in bench["end_to_end"]
                    if m["name"] in ("decode_tokens_per_s", "setup_s")],
        per_layer=[dict(metric, name="toy_queries", unit="requests"),
                   dict(metric, name="toy_roofline", unit="%")])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = subprocess.run(
        [sys.executable, str(tree / "run.py"), "--workload", "toy-cell",
         "--seed", "2147483659", "--seconds", "4", "--trace", "1",
         "--rehearsal"],
        cwd=str(tmp_path), capture_output=True, text=True, timeout=900,
        env=dict(os.environ, PYTHONPATH=spec.ROOT))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"]["compared"]["toy_err"] == [0.25, 0.5]
    assert line["extra"]["toy_loop"] == line["attempted"] > 0
    assert line["metrics"]["toy_queries"]["value"] >= line["attempted"]
    assert "toy_roofline" not in line["metrics"]    # no device trace here
    assert all(p.read_bytes() == b for p, b in before.items())


def test_without_a_tpu_there_is_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "train-4k-1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
