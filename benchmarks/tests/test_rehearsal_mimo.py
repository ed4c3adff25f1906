"""The cell `serve-mimo-agent-sessions` end to end at a toy size on the CPU
(kind `sink-window-moe`, traffic `agent-sessions`): the reference path of
every kernel, the runtime's own workers, the toy twin the program's tests use
(tests/data/mimo_v2_tiny.json).  Never a device number.  About three
minutes; run by the builder, not by tier-1."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.lib import spec

TMP = os.path.join(spec.BENCH_DIR, "tests", ".tmp")
CELL = "serve-mimo-agent-sessions"


@pytest.fixture(scope="module")
def rehearsal_benchmark():
    bench = spec.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    for c in bench["configs"]:
        if c["name"] == cell["config"]:
            c["file"] = "tests/data/mimo_v2_tiny.json"
    cell["traffic"] = "../tests/data/tiny-agent-sessions"
    os.makedirs(TMP, exist_ok=True)
    path = os.path.join(TMP, "BENCHMARK.rehearsal-mimo.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return os.path.relpath(path, spec.ROOT)


@pytest.mark.parametrize("trace", [0, 1])
def test_mimo_agent_sessions_rehearses_on_cpu(rehearsal_benchmark, trace):
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
        spec.model_kind("sink-window-moe").CHECKS["serve"])
    # bf16 at the toy's width of 64 errs more than the limits set at 4096
    # allow (tests/test_mimo_v2.py); what a checkpoint restores, and what
    # float32 makes of the program's own inputs, is exact
    assert compared["logits_after_hit_err"][0] == 0.0
    assert compared["route_own_input_mismatch_share"][0] == 0.0
    assert compared["route_own_input_weight_err"][0] < 1e-6
    c = line["counters"]
    assert c["prefill.chunk_tokens"] > c["prefill.chunks"] > 0
    assert c["prefix_cache.hit_tokens"] > 0
    # the serve key reached the engine; hits were restored from checkpoints
    # of the rings, which the radix cache owns; the expert layers counted
    # their share
    assert line["checks"].get("engine_warmup_s", 0) > 0
    assert c["state.restores"] > 0 and c["state.snapshots"] > 0
    assert c["moe.picked_rows"] == c["moe.routed_rows"] + c["moe.absent_rows"]
    assert c["moe.padded_rows"] > c["moe.routed_rows"] > 0
    if trace:       # the counter metrics read; the trace ones need a chip
        m = line["metrics"]
        assert 0 < m["mimo_state_usable_share"]["value"] <= 100
        assert 0 < m["mimo_state_full_restore_share"]["value"] <= 100
        assert 0 < m["mimo_expert_tile_fill_share"]["value"] < 100
        assert m["mimo_tokens_per_engine_step"]["value"] > 0
        assert m["mimo_evict_host_ms_per_dispatch"]["value"] >= 0
        assert m["mimo_state_host_ms_per_dispatch"]["value"] > 0
        assert "mimo_ring_step_roofline" not in m
    else:
        assert line["metrics"]["decode_tokens_per_s"]["value"] > 0
        assert line["metrics"]["setup_s"]["value"] > 0
