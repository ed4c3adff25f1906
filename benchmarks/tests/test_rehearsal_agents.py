"""The cell `serve-agent-sessions` end to end at a toy size on the CPU
(kind `afmoe`, traffic `agent-sessions`): the reference path of every
kernel, the runtime's own workers.  Never a device number.  A test file of
its own, because tests/test_rehearsal.py maps traffic names through a fixed
dictionary (PERF.md section 7).  About three minutes; run by the builder,
not by tier-1."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.lib import spec

TMP = os.path.join(spec.BENCH_DIR, "tests", ".tmp")
CELL = "serve-agent-sessions"


@pytest.fixture(scope="module")
def rehearsal_benchmark():
    """BENCHMARK.json with the cell's configuration and traffic swapped for
    their toy twins: the same cell, the same metrics."""
    bench = spec.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    for c in bench["configs"]:
        if c["name"] == cell["config"]:
            c["file"] = "benchmarks/tests/data/tiny-afmoe.json"
    cell["traffic"] = "../tests/data/tiny-agent-sessions"
    os.makedirs(TMP, exist_ok=True)
    path = os.path.join(TMP, "BENCHMARK.rehearsal-agents.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return os.path.relpath(path, spec.ROOT)


@pytest.mark.parametrize("trace", [0, 1])
def test_agent_sessions_rehearses_on_cpu(rehearsal_benchmark, trace):
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL,
         "--seed", "3000000001", "--seconds", "6", "--trace", str(trace),
         "--rehearsal", "--benchmark", rehearsal_benchmark],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    compared = line["checks"]["compared"]
    assert sorted(compared) == sorted(
        spec.model_kind("afmoe").CHECKS["serve"])
    assert all(value < limit for value, limit in compared.values())
    c = line["counters"]
    # the 560-token system prompt took two chunks of 512, once (priming)
    assert c["prefill.multi_chunk_requests"] >= 1
    assert c["prefill.chunk_tokens"] > c["prefill.chunks"] > 0
    assert c["moe.routed_rows"] >= 2 * c["moe.layer_steps"] > 0
    assert c["kv.sliding_positions_held"] > c["kv.sliding_positions_dead"] > 0
    assert c["prefix_cache.hit_tokens"] > 0
    if trace:       # the counter metrics read; the trace ones need a chip
        m = line["metrics"]
        assert m["agents_expert_load_max_over_mean"]["value"] >= 1.0
        assert 0 < m["agents_window_dead_share"]["value"] < 100
        assert m["agents_tokens_per_engine_step"]["value"] > 0
        assert "agents_expert_decode_roofline" not in m
    else:
        assert line["metrics"]["decode_tokens_per_s"]["value"] > 0
        assert line["metrics"]["setup_s"]["value"] > 0
