"""The two invariants PR 22's refusal turned on: for any two seeds, the
same arrival count and the same multiset of (prompt, output) lengths;
only the order and the times differ."""

import random
from collections import Counter

import pytest

from benchmarks.lib import spec, traffic

SEEDS = [0, 1, 7, 2 ** 31 + 11, 3_000_000_001]


def _mix(name):
    return spec.load_traffic(name)


@pytest.mark.parametrize("seconds", [10.0, 50.0])
def test_open_loop_same_count_and_multiset_on_every_seed(seconds):
    tr = _mix("chat-steady")
    plans = [traffic.open_loop_plan(tr, seconds, random.Random(s))
             for s in SEEDS]
    n = traffic.arrival_count(tr, seconds)
    assert n == round(tr["rate_per_s"] * seconds)
    for plan in plans:
        assert len(plan) == n
        assert all(0.0 <= t <= seconds for t, _, _ in plan)
        assert [t for t, _, _ in plan] == sorted(t for t, _, _ in plan)
    prompts = [Counter(p for _, p, _ in plan) for plan in plans]
    outputs = [Counter(o for _, _, o in plan) for plan in plans]
    assert all(c == prompts[0] for c in prompts)
    assert all(c == outputs[0] for c in outputs)
    # `pair_key`: the same PAIRS too, so the same cached positions held live
    pairs = [Counter((p, o) for _, p, o in plan) for plan in plans]
    assert all(c == pairs[0] for c in pairs)
    by_seed = dict(tr)
    del by_seed["pair_key"]
    loose = [Counter((p, o) for _, p, o in traffic.open_loop_plan(
        by_seed, seconds, random.Random(s))) for s in SEEDS[:2]]
    assert loose[0] != loose[1]
    # ... and the seed does change the order and the times
    assert plans[0] != plans[1]
    assert [t for t, _, _ in plans[0]] != [t for t, _, _ in plans[1]]
    assert [p for _, p, _ in plans[0]] != [p for _, p, _ in plans[1]]


@pytest.mark.parametrize("seconds", [10.0, 50.0])
def test_paced_arrivals_are_the_same_gaps_on_every_seed(seconds):
    """What the driver's refusal of PR 23's first benchmark turned on: the
    seed may order the arrivals' gaps, not choose them."""
    tr = _mix("chat-steady")
    assert tr["arrivals"]["process"] == "paced"
    n = traffic.arrival_count(tr, seconds)
    want = traffic.arrival_gaps(tr["arrivals"], n, seconds)
    assert abs(sum(want) - seconds) < 1e-9
    j, mean = tr["arrivals"]["jitter"], seconds / n
    assert (1 - j) * mean <= want[0] and want[-1] <= (1 + j) * mean
    orders = []
    for s in SEEDS:
        times = [t for t, _, _ in traffic.open_loop_plan(
            tr, seconds, random.Random(s))]
        gaps = [b - a for a, b in zip(times, times[1:])]
        first = seconds - (times[-1] - times[0]) # the gap the window cuts
        assert sorted(round(g, 9) for g in gaps + [first]) == \
            [round(g, 9) for g in want]
        orders.append(gaps)
    assert orders[0] != orders[1]


def test_uniform_arrivals_are_the_default():
    tr = dict(_mix("chat-steady"))
    del tr["arrivals"]
    a = traffic.arrival_times(tr, 110, 50.0, random.Random(3))
    b = traffic.arrival_times(dict(tr, arrivals={"process": "uniform"}),
                              110, 50.0, random.Random(3))
    assert a == b == sorted(a) and 0.0 <= a[0] and a[-1] <= 50.0
    with pytest.raises(ValueError):
        traffic.arrival_times(dict(tr, arrivals={"process": "bursty"}),
                              110, 50.0, random.Random(3))


@pytest.mark.parametrize("name", ["batch-saturated", "chat-capacity"])
def test_closed_loop_same_multiset_in_every_block_on_every_seed(name):
    tr = _mix(name)
    k = tr["multiset_size"]
    plans = [traffic.closed_loop_plan(tr, 0, random.Random(s))
             for s in SEEDS]
    assert all(len(p) == k * traffic.PLAN_BLOCKS for p in plans)
    want_p = Counter(traffic.quantile_lengths(tr["prompt_tokens"], k))
    want_o = Counter(traffic.quantile_lengths(tr["output_tokens"], k))
    for plan in plans:
        for b in range(0, len(plan), k):        # however far a run gets
            block = plan[b:b + k]
            assert Counter(p for p, _ in block) == want_p
            assert Counter(o for _, o in block) == want_o
    assert plans[0] != plans[1]
    assert plans[0][:k] != plans[0][k:2 * k]


def test_stagger_cuts_only_the_first_wave():
    outs = [200] * 100
    cut = traffic.stagger(outs, 32)
    assert cut[32:] == outs[32:]
    assert cut[31] == 200 and cut[0] == 7
    assert cut[:32] == sorted(cut[:32])


@pytest.mark.parametrize("name,key,lo,hi", [
    ("chat-steady", "prompt_tokens", 16, 512),
    ("chat-steady", "output_tokens", 16, 256),
    ("batch-saturated", "prompt_tokens", 64, 512),
    ("batch-saturated", "output_tokens", 128, 256)])
def test_lengths_follow_the_stated_distribution(name, key, lo, hi):
    xs = traffic.quantile_lengths(_mix(name)[key], 400)
    assert min(xs) >= lo and max(xs) <= hi and xs == sorted(xs)
    dist = _mix(name)[key]
    mid = xs[len(xs) // 2]
    if dist["dist"] == "log_normal":
        assert abs(mid - dist["median"]) <= 2
    else:
        assert abs(mid - (lo * hi) ** 0.5) <= 3


def test_train_rows_depend_on_seed_and_row_only():
    a = traffic.train_tokens(3_000_000_001, 8, 4, 64, 32000)
    b = traffic.train_tokens(3_000_000_001, 10, 2, 64, 32000)
    assert a.shape == (4, 65) and a.dtype.name == "int32"
    assert (a[2:] == b).all() and (a[0] != a[1]).any()
    assert (a != traffic.train_tokens(5, 8, 4, 64, 32000)).any()
    assert a.min() >= 0 and a.max() < 32000
