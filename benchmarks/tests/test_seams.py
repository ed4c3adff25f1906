"""The four look-ups by name (lib/spec.py): a model kind, a traffic kind
and a cost function are files a later PR ADDS, an unknown name fails in the
driver before any worker starts and says what was found, and the engine's
counters reach a per-layer metric with no harness code.  Fast: nothing here
starts the runtime (tests/test_rehearsal.py runs an added kind end to end).
"""

import json
import os
import random
import re
import shutil
import subprocess
import sys
import time
from collections import Counter

import pytest

from benchmarks.lib import peaks, reductions, reference, spec, worker_util

SEEDS = [0, 1, 7, 2 ** 31 + 11, 3_000_000_001]
REPO = spec.ROOT          # the fixture below points spec elsewhere
DATA = os.path.join(spec.BENCH_DIR, "tests", "data")


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """A copy of benchmarks/ + BENCHMARK.json that the look-ups read from:
    what a later PR's checkout looks like before it adds its files."""
    bench_dir = tmp_path / "benchmarks"
    shutil.copytree(spec.BENCH_DIR, bench_dir, ignore=shutil.ignore_patterns(
        "tests", "__pycache__", "*.pyc"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    monkeypatch.setattr(spec, "BENCH_DIR", str(bench_dir))
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    return bench_dir


def _edit(path, **changes):
    with open(path) as f:
        data = json.load(f)
    data.update(changes)
    with open(path, "w") as f:
        json.dump(data, f)


def test_unknown_model_kind_names_the_kinds_found(tree):
    _edit(tree / "configs" / "mistral-7b-l16.json", kind="sparse-olmoe")
    held = sorted(p.stem for p in (tree / "kinds").glob("*.py"))
    assert "dense-llama" in held
    with pytest.raises(ValueError, match=r"no model kind 'sparse-olmoe'.*"
                       r"kinds/ holds " + re.escape(str(held))):
        spec.load_cell("serve-batch-saturated")
    spec.load_cell("train-4k-1chip")        # the other configuration loads


def test_unknown_traffic_kind_names_the_kinds_found(tree):
    _edit(tree / "traffic" / "chat-steady.json", kind="bursty")
    with pytest.raises(ValueError, match=r"no traffic kind 'bursty'.*"
                       r"\['closed_loop', 'open_loop', 'sessions', "
                       r"'train_job'\]"):
        spec.load_cell("serve-chat-steady")
    with pytest.raises(ValueError, match="no traffic kind None"):
        spec.traffic_kind(None)
    with pytest.raises(ValueError, match="no model kind '../lib/spec'"):
        spec.model_kind("../lib/spec")


def test_unknown_cost_function_names_the_functions_found(tree):
    _edit(tree / "layer_metrics" / "batch_paged_roofline.json", kernels=[
        {"op_pattern": "^paged_attention", "cost_fn": "expert_matmul"}])
    with pytest.raises(ValueError, match=r"batch_paged_roofline.*no cost "
                       r"function 'expert_matmul'.*'paged_decode'"):
        spec.load_cell("serve-batch-saturated")
    spec.load_cell("serve-chat-steady")     # a cell that does not read it


def test_an_unknown_name_fails_before_any_worker_starts(tree):
    _edit(tree / "traffic" / "batch-saturated.json", kind="bursty")
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, str(tree / "run.py"), "--workload",
         "serve-batch-saturated", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--rehearsal"],
        cwd=str(tree.parent), capture_output=True, text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no traffic kind 'bursty'" in proc.stderr
    assert "ray_tpu.init" not in proc.stderr and time.time() - t0 < 60


def test_a_kind_file_added_to_the_tree_is_found(tree):
    shutil.copy(os.path.join(DATA, "toy-gqa.py"), tree / "kinds")
    shutil.copy(os.path.join(DATA, "toy_loop.py"), tree / "traffic_kinds")
    kind = spec.model_kind("toy-gqa")
    assert kind.TOLERANCES["toy_err"] == 0.5
    assert spec.traffic_kind("toy_loop").CELL == "serve_cell"
    fns = spec.cost_fns(kind)
    assert fns["toy_cost"]({}, {}) == (2.0, 4.0)
    assert fns["paged_decode"]({}, {}) == (1.0, 1.0)     # the kind's first
    assert fns["flash_fwd"] is peaks.flash_fwd           # then the shared
    assert spec.cost_fns(spec.model_kind("dense-llama"))["paged_decode"] \
        is peaks.paged_decode
    _edit(tree / "configs" / "mistral-7b-l16.json", kind="toy-gqa")
    _edit(tree / "traffic" / "batch-saturated.json", kind="toy_loop")
    _edit(tree / "layer_metrics" / "batch_paged_roofline.json", kernels=[
        {"op_pattern": "^paged_attention", "cost_fn": "toy_cost"}])
    cell = spec.load_cell("serve-batch-saturated")
    assert cell["config"]["kind"] == "toy-gqa"
    # roofline_share takes the merged table: 7 calls x 4 B / 819 GB/s
    tr = {"busy_s": 1.0, "window_s": 1.0,
          "op_seconds": {"paged_attention.6": 1e-9},
          "op_counts": {"paged_attention.6": 7}}
    obs = {"counters": {}, "series": {}, "trace": tr, "config": {},
           "shapes": {}, "device_kind": "TPU v5 lite", "cost_fns": fns}
    m = next(m for m in cell["layer_metrics"]
             if m["name"] == "batch_paged_roofline")
    assert reductions.read_metric(m, obs) == pytest.approx(
        100 * 7 * 4.0 / 819e9 / 1e-9)


def test_counters_are_every_numeric_leaf_under_its_dotted_path():
    stats = {"steps": 7, "warmed": True, "engine_error": None,
             "backend": "tpu", "chips": [0], "warmup_s": 6.5,
             "blocks": {"used": 3, "free": 5},
             "prefix_cache": {"enabled": True, "hits": 2, "hit_tokens": 64}}
    flat = worker_util.numeric_leaves(stats)
    assert flat == {"steps": 7.0, "warmup_s": 6.5, "blocks.used": 3.0,
                    "blocks.free": 5.0, "prefix_cache.hits": 2.0,
                    "prefix_cache.hit_tokens": 64.0}
    later = dict(flat, steps=19.0, **{"prefix_cache.hit_tokens": 640.0,
                                      "new_counter": 1.0})
    d = worker_util.deltas(flat, later)
    assert d["steps"] == 12.0 and d["prefix_cache.hit_tokens"] == 576.0
    assert "new_counter" not in d
    # the benchmark's own names never shadow the program's
    both = worker_util.beside(d, {"requests": 4.0, "steps": 1.0})
    assert both["requests"] == 4.0 and both["steps"] == 1.0
    assert both["engine.steps"] == 12.0 and "engine.requests" not in both
    # ... and a data file reads one with `ratio`, no harness code
    with open(os.path.join(spec.BENCH_DIR, "layer_metrics",
                           "sessions_prefix_hit_share.json")) as f:
        m = dict(json.load(f), name="sessions_prefix_hit_share")
    obs = {"counters": dict(d, prompt_tokens=1152.0), "series": {},
           "trace": {}}
    assert reductions.read_metric(m, obs) == pytest.approx(50.0)
    assert reductions.read_metric(m, dict(obs, counters={})) is None


def test_every_required_check_is_judged_by_the_kinds_own_limit():
    kind = spec.model_kind("dense-llama")
    serve = kind.CHECKS["serve"]
    assert set(kind.TOLERANCES) == set(serve) == {"flash_err", "paged_err"}
    assert kind.CHECKS["train"] == ("flash_err",)
    ok = {"flash_err": 0.007, "paged_err": 0.004, "flash_is_kernel": True}
    faults, compared = reference.judge(kind.TOLERANCES, serve, ok)
    assert faults == [] and compared == {
        "flash_err": [0.007, kind.TOLERANCES["flash_err"]],
        "paged_err": [0.004, kind.TOLERANCES["paged_err"]]}
    for bad in (kind.TOLERANCES["paged_err"], float("nan"),
                kind.NOT_COMPARED):
        faults, _ = reference.judge(kind.TOLERANCES, serve,
                                    dict(ok, paged_err=bad))
        assert len(faults) == 1 and faults[0].startswith("paged_err")


@pytest.mark.parametrize("required, checks, said", [
    (("flash_err", "paged_err"), {"flash_err": 0.007}, "paged_err is missing"),
    (("flash_err", "paged_err"), {}, "flash_err is missing"),
    (("flash_err", "other_err"), {"flash_err": 0.007, "other_err": 0.0},
     "other_err has no limit"),
    ((), {"flash_err": 0.007}, "names no check"),
])
def test_a_kind_that_compares_nothing_or_too_little_is_not_correct(
        required, checks, said):
    """A parity() that returns {} or leaves a required entry out is a
    fault, never a silent pass."""
    kind = spec.model_kind("dense-llama")
    faults, _ = reference.judge(kind.TOLERANCES, required, checks)
    assert faults and said in faults[0]


# -- the `sessions` plan: test_traffic.py's invariants ---------------------
@pytest.fixture(scope="module")
def sessions():
    cfg = spec.load_cell("serve-prefix-sessions")
    return spec.traffic_kind("sessions"), cfg["traffic"], \
        cfg["config"]["serve"]


def test_sessions_same_conversations_in_every_block_on_every_seed(sessions):
    kind, tr, sv = sessions
    k = tr["multiset_size"]
    want = Counter(kind.block_of_conversations(tr, sv["prompt_pad"]))
    plans = [kind.sessions_plan(tr, sv, 0, random.Random(s)) for s in SEEDS]
    for plan in plans:
        assert len(plan) == k * 64
        for b in range(0, len(plan), k):        # however far a run gets
            assert Counter(plan[b:b + k]) == want
    assert plans[0] != plans[1] and plans[0][:k] != plans[0][k:2 * k]
    # the file's parameters: equally popular tenants, 3-5 turns, lengths
    # that are the stated distributions' own quantiles
    assert Counter(t for t, _, _ in want.elements()) == \
        {t: k // 4 for t in range(4)}
    assert {len(m) for _, m, _ in want} == {3, 4, 5}
    turns = sum(len(m) for _, m, _ in want.elements())
    from benchmarks.lib import traffic
    assert sorted(x for _, m, _ in want.elements() for x in m) == \
        traffic.quantile_lengths(tr["message_tokens"], turns)
    assert sorted(x for _, _, r in want.elements() for x in r) == \
        traffic.quantile_lengths(tr["reply_tokens"], turns)
    assert [t["system_tokens"] for t in tr["tenants"]] == \
        [256, 320, 384, 448]


def test_sessions_every_prompt_fits_the_engine(sessions):
    kind, tr, sv = sessions
    for s in SEEDS[:2]:
        for tenant, msgs, reps in kind.sessions_plan(
                tr, sv, sv["num_slots"], random.Random(s)):
            assert len(msgs) == len(reps)
            prompt = tr["tenants"][tenant]["system_tokens"]
            for m, r in zip(msgs, reps):
                prompt += m
                assert prompt <= sv["prompt_pad"]
                assert prompt + r <= sv["max_len"]
                prompt += r
    with pytest.raises(ValueError, match="do not fit prompt_pad"):
        kind.block_of_conversations(tr, 480)


def test_sessions_first_wave_is_staggered(sessions):
    """The first wave's first replies lose whole dispatches, so that about
    as many slots come free after each of the first dispatches as in a loop
    that has run for long; nothing else in the plan changes."""
    kind, tr, sv = sessions
    chunk = sv["decode_chunk"]
    plain = kind.sessions_plan(tr, sv, 0, random.Random(5))
    cut = kind.sessions_plan(tr, sv, 32, random.Random(5))
    assert plain[32:] == cut[32:]
    left = Counter()
    for (_, m0, r0), (_, m1, r1) in zip(plain[:32], cut[:32]):
        assert m0 == m1 and r0[1:] == r1[1:]
        assert 2 <= r1[0] <= r0[0] and (r0[0] - r1[0]) % chunk == 0
        left[max(1, -(-(r1[0] - 1) // chunk))] += 1
    # dispatches left to the first replies: no more than two thirds of the
    # slots come free together (traffic.stagger's cut by tokens freed 25)
    assert max(left.values()) <= 21 and len(left) >= 2
    assert kind.clients(tr, sv) == 2 * sv["num_slots"]
