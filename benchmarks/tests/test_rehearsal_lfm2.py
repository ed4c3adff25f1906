"""The cell `serve-lfm2-agent-sessions` end to end at a toy size on the CPU
(kind `lfm2-moe`, traffic `agent-sessions`): the reference path of every
kernel, the runtime's own workers, the toy twin the program's tests use
(tests/data/lfm2_tiny.json).  Never a device number.  About three minutes;
run by the builder, not by tier-1."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.lib import spec

TMP = os.path.join(spec.BENCH_DIR, "tests", ".tmp")
CELL = "serve-lfm2-agent-sessions"


@pytest.fixture(scope="module")
def rehearsal_benchmark():
    bench = spec.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    for c in bench["configs"]:
        if c["name"] == cell["config"]:
            c["file"] = "tests/data/lfm2_tiny.json"
    cell["traffic"] = "../tests/data/tiny-agent-sessions"
    os.makedirs(TMP, exist_ok=True)
    path = os.path.join(TMP, "BENCHMARK.rehearsal-lfm2.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return os.path.relpath(path, spec.ROOT)


@pytest.mark.parametrize("trace", [0, 1])
def test_lfm2_agent_sessions_rehearses_on_cpu(rehearsal_benchmark, trace):
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL,
         "--seed", "3000000001", "--seconds", "6", "--trace", str(trace),
         "--rehearsal", "--benchmark", rehearsal_benchmark],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    compared = line["checks"]["compared"]
    assert sorted(compared) == sorted(
        spec.model_kind("lfm2-moe").CHECKS["serve"])
    # bf16 at the toy's width of 256 errs more than the limits set at 2048
    # allow (tests/test_lfm2.py TOY_BF16); what a hit restores is exact
    assert compared["logits_after_hit_err"][0] < \
        compared["logits_after_hit_err"][1]
    c = line["counters"]
    assert c["prefill.chunk_tokens"] > c["prefill.chunks"] > 0
    assert c["moe.routed_rows"] >= 2 * c["moe.layer_steps"] > 0
    assert c["prefix_cache.hit_tokens"] > 0
    if trace:       # the counter metrics read; the trace ones need a chip
        m = line["metrics"]
        assert m["lfm2_expert_load_max_over_mean"]["value"] >= 1.0
        assert 0 < m["lfm2_prefix_hit_share"]["value"] < 100
        assert "lfm2_expert_decode_roofline" not in m
    else:
        assert line["metrics"]["decode_tokens_per_s"]["value"] > 0
        assert line["metrics"]["setup_s"]["value"] > 0
