import json
import os

import pytest

from benchmarks.lib import peaks, reductions, spec, trace_reduce

model = spec.model_kind("dense-llama")


def test_unknown_device_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peak"):
        peaks.peaks_for("TPU v9 imaginary")


def _cfg(name):
    return json.load(open(os.path.join(spec.BENCH_DIR, "configs",
                                       f"{name}.json")))


def test_parameter_counts_and_flops_per_token():
    l4, l16 = _cfg("mistral-7b-l4"), _cfg("mistral-7b-l16")
    assert model.param_counts(l4)["total"] == 1_134_596_096
    assert model.param_counts(l16)["total"] == 3_751_940_096
    # 6 x (all but the input table) + 12 L s d
    assert model.train_flops_per_token(l4, 4096) == pytest.approx(
        6 * (1_134_596_096 - 131_072_000) + 12 * 4 * 4096 * 4096)
    assert model.kv_bytes_per_token(l16) == 64 * 1024


def test_kernel_costs_from_shapes():
    c = _cfg("mistral-7b-l4")
    f, b = peaks.flash_fwd(c, {"batch": 4, "seq": 4096})
    assert f == 4 * 4 * 32 * 4096 * 4096 * 128 / 2
    t, bound = peaks.roofline_seconds(f, b, "TPU v5 lite")
    assert bound == "compute" and t == pytest.approx(f / 197e12)
    f2, _ = peaks.flash_bwd_dkdv(c, {"batch": 4, "seq": 4096})
    f3, _ = peaks.flash_bwd_dq(c, {"batch": 4, "seq": 4096})
    assert f2 == 2 * f and f3 == 1.5 * f
    f, b = peaks.paged_decode(c, {"live_context": 10_000, "slots": 32})
    assert b == 2 * (2 * 10_000 * 8 * 128 + 2 * 32 * 32 * 128)
    assert peaks.roofline_seconds(f, b, "TPU v5 lite")[1] == "memory"


def test_interval_arithmetic():
    u = trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)] and trace_reduce.total(u) == 6
    assert trace_reduce.subtract([(0, 10)], [(2, 3), (5, 12)]) == \
        [(0, 2), (3, 5)]
    assert trace_reduce.subtract([(0, 2), (4, 6)], [(1, 5)]) == \
        [(0, 1), (5, 6)]
    assert trace_reduce.subtract([(0, 2)], []) == [(0, 2)]


def test_summarize_synthetic_plane():
    ops = [("while.1", 0, 100), ("fusion.1", 0, 40), ("all-gather.2", 30, 60),
           ("paged_attention.3", 70, 100), ("fusion.1", 150, 200)]
    s = trace_reduce.summarize([{"name": "/device:TPU:0", "ops": ops,
                                 "modules": [("jit_step(1)", 0, 200)]}])
    assert s["busy_s"] == pytest.approx(140e-9)        # while left out
    assert s["window_s"] == pytest.approx(200e-9)
    assert s["collective_exposed_s"] == pytest.approx(20e-9)
    assert s["op_seconds"]["fusion.1"] == pytest.approx(90e-9)
    assert s["op_counts"]["paged_attention.3"] == 1
    assert s["gaps_s"][0] == pytest.approx(50e-9)
    obs = {"counters": {"a": 6.0, "b": 3.0}, "series": {"x": [1, 2, 3, 4]},
           "trace": s, "config": {}, "shapes": {}, "device_kind": "TPU v5 lite"}
    rd = lambda **k: reductions.read_metric(dict(name="m", **k), obs)
    assert rd(reduction="idle_share") == pytest.approx(30.0)
    assert rd(reduction="share_of_busy", op_pattern="^paged") == \
        pytest.approx(100 * 30 / 140)
    assert rd(reduction="share_of_window") == pytest.approx(10.0)
    assert rd(reduction="ratio", numerator="a", denominator="b") == 2.0
    assert rd(reduction="ratio", numerator="a", denominator="nope") is None
    assert rd(reduction="p50", series="x") == 2.5
    assert rd(reduction="p50", series="absent") is None
    assert rd(reduction="p95", module_pattern="jit_step") == \
        pytest.approx(200e-6)
    empty = dict(obs, trace={})
    assert reductions.read_metric(
        {"name": "m", "reduction": "idle_share"}, empty) is None
    with pytest.raises(ValueError):
        rd(reduction="mode")


def test_every_metric_and_cell_of_the_benchmark_resolves():
    bench = spec.load_benchmark()
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert spec.traffic_kind(cell["traffic"]["kind"]).CELL in (
            "train_cell", "serve_cell")
        spec.model_kind(cell["config"]["kind"]).check(cell["config"])
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert cell["layer_metrics"]
        for m in cell["layer_metrics"]:
            assert m["moves"] in names and m["moves"] in e2e
            assert w["name"] in m["cells"]
