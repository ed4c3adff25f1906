"""The cell `serve-axk1-agent-sessions` end to end at a toy size on the CPU
(kind `mla-moe`, traffic `agent-sessions`): the reference path of every
kernel, the runtime's own workers, the toy twin the program's tests use
(tests/data/axk1_tiny.json).  Never a device number.  A few minutes; run by
the builder, not by tier-1."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.lib import spec

TMP = os.path.join(spec.BENCH_DIR, "tests", ".tmp")
CELL = "serve-axk1-agent-sessions"


@pytest.fixture(scope="module")
def rehearsal_benchmark():
    bench = spec.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    for c in bench["configs"]:
        if c["name"] == cell["config"]:
            c["file"] = "tests/data/axk1_tiny.json"
    cell["traffic"] = "../tests/data/tiny-agent-sessions"
    os.makedirs(TMP, exist_ok=True)
    path = os.path.join(TMP, "BENCHMARK.rehearsal-axk1.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return os.path.relpath(path, spec.ROOT)


@pytest.mark.parametrize("trace", [0, 1])
def test_axk1_agent_sessions_rehearses_on_cpu(rehearsal_benchmark, trace):
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
        spec.model_kind("mla-moe").CHECKS["serve"])
    # bf16 at the toy's width of 64 errs more than the limits set at 7168
    # allow (tests/test_axk1.py TOY_BF16); a hit is exact
    assert compared["logits_after_hit_err"][0] < \
        compared["logits_after_hit_err"][1]
    c = line["counters"]
    assert c["prefill.chunk_tokens"] > c["prefill.chunks"] > 0
    # 4 of the router's 16 experts are held: most picks lie elsewhere
    assert c["moe.absent_rows"] > c["moe.routed_rows"] > 0
    assert c["moe.picked_rows"] == c["moe.routed_rows"] + c["moe.absent_rows"]
    assert c["prefix_cache.hit_tokens"] > 0
    if trace:       # the counter metrics read; the trace ones need a chip
        m = line["metrics"]
        assert m["axk1_expert_load_max_over_mean"]["value"] >= 1.0
        assert 0 < m["axk1_prefix_hit_share"]["value"] < 100
        assert 5 < m["axk1_expert_local_share"]["value"] < 50
        assert "axk1_expert_decode_roofline" not in m
        assert "axk1_latent_decode_roofline" not in m
    else:
        assert line["metrics"]["decode_tokens_per_s"]["value"] > 0
        assert line["metrics"]["setup_s"]["value"] > 0
