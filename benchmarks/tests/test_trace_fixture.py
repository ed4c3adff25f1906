"""The trace reduction on a small trace recorded on a v5e
(_chip/tiny_trace.py, PR 23): three calls, 20 ms apart, of one program —
a 4-iteration fori_loop of 1024^3 bf16 matmuls, then the paged kernel."""

import os

import pytest

from benchmarks.lib import reductions, spec, trace_reduce

FIXTURE = os.path.join(spec.BENCH_DIR, "tests", "data", "tiny_v5e.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    planes = trace_reduce.read_xplane(FIXTURE)
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    return trace_reduce.summarize(planes)


def test_programs_and_ops_by_stable_name(summary):
    assert [n for n, _ in summary["module_ms"]] == ["jit_tiny_program"] * 3
    assert all(ms == pytest.approx(0.2393, abs=2e-4)
               for _, ms in summary["module_ms"])
    assert summary["op_counts"]["paged_attention.1"] == 3
    assert summary["op_counts"]["convolution_multiply_fusion.2"] == 12
    # the while spans its body's matmuls: left out, or they count twice
    assert not any(n.startswith("while") for n in summary["op_seconds"])
    assert summary["op_seconds"]["paged_attention.1"] == \
        pytest.approx(552.5e-6, rel=1e-3)
    assert summary["op_seconds"]["convolution_multiply_fusion.2"] == \
        pytest.approx(138.8e-6, rel=1e-3)


def test_busy_idle_and_gaps(summary):
    assert summary["busy_s"] == pytest.approx(716.9e-6, rel=1e-3)
    assert summary["window_s"] == pytest.approx(44.05e-3, rel=1e-3)
    # two sleeps of 20 ms between three calls
    assert [round(g, 3) for g in summary["gaps_s"][:2]] == [0.022, 0.022]
    assert summary["gaps_s"][2] < 1e-6
    assert summary["collective_exposed_s"] == 0.0
    b = trace_reduce.breakdown(summary)
    assert b["device_ops"][0][0] == "paged_attention.1"
    assert b["idle_gaps"][0][0] == "unattributed"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_menu_on_the_recorded_trace(summary):
    obs = {"counters": {}, "series": {}, "trace": summary,
           "device_kind": "TPU v5 lite",
           "config": {"num_attention_heads": 32, "num_key_value_heads": 8,
                      "head_dim": 128},
           "shapes": {"slots": 8, "live_context": 8 * (8 * 16 - 3)}}
    rd = lambda **k: reductions.read_metric(dict(name="m", **k), obs)
    assert rd(reduction="idle_share") == pytest.approx(98.37, abs=0.01)
    assert rd(reduction="share_of_busy", op_pattern="^paged_attention") == \
        pytest.approx(77.07, abs=0.01)
    assert rd(reduction="p50", module_pattern="tiny_program") == \
        pytest.approx(0.2393, abs=2e-4)
    # 1000 positions x 8 KV heads x 128 x 2 (k, v) x 2 B (+ q, o) = 4.2 MB per call:
    # 5.2 us at 819 GB/s against the 184 us the kernel took
    roof = rd(reduction="roofline_share", kernels=[
        {"op_pattern": "^paged_attention", "cost_fn": "paged_decode"}])
    assert roof == pytest.approx(2.80, abs=0.01)


def test_host_only_trace_gives_nothing(tmp_path):
    assert trace_reduce.summarize([]) == {}
