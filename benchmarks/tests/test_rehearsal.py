"""The harness end to end at a toy size on the CPU: interpret-mode
kernels, the runtime's own workers, 4 virtual devices for the fsdp cell.
Never a device number: the line says `rehearsal` and platform `cpu`.
About three minutes; run by the builder, not by tier-1."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.lib import spec

TMP = os.path.join(spec.BENCH_DIR, "tests", ".tmp")
TRAFFIC = {"pretrain-4k": "../tests/data/tiny-pretrain",
           "batch-saturated": "../tests/data/tiny-batch",
           "chat-steady": "../tests/data/tiny-chat"}


@pytest.fixture(scope="module")
def rehearsal_benchmark():
    """BENCHMARK.json with every configuration swapped for the toy and
    every traffic mix for its toy twin: same cells, same metrics."""
    bench = spec.load_benchmark()
    for c in bench["configs"]:
        c["file"] = "benchmarks/tests/data/tiny-l2.json"
    for w in bench["workloads"]:
        w["traffic"] = TRAFFIC[w["traffic"]]
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
    ("serve-chat-steady", 1, "chat_stream_gap_p95_ms")])
def test_cell_rehearses_on_cpu(rehearsal_benchmark, workload, trace, metric):
    line = _run(rehearsal_benchmark, workload, trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"][metric]["value"] > 0
    if not trace:
        assert line["metrics"]["setup_s"]["value"] > 0
    if workload == "train-4k-fsdp4":
        assert line["device"]["count"] == 4
        assert line["checks"]["state_devices"] == 4
    assert line["checks"].get("compiles_in_window", 0) == 0


def test_without_a_tpu_there_is_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "train-4k-1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
