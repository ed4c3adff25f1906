"""The one general traffic generator.  A traffic mix is a data file
(traffic/<name>.json) whose `"kind"` names the file that offers it
(traffic_kinds/<kind>.py, found by lib/spec.py):

  train_job    sequences of `seq_len` tokens, a fresh batch every step
  closed_loop  `clients` callers (or `clients_per_slot` x the engine's
               slots), each sending its next request when its reply ends
  open_loop    `rate_per_s` independent arrivals over the window
  sessions     a closed loop of conversations behind tenants' system
               prompts (its plan is in its own file)

Here are the pieces the kinds share: lengths, pairs, arrivals, tokens, the
closed loop's blocks and stagger.

Two invariants make runs of one cell comparable (tests/test_traffic.py):
for ANY seed the request lengths are the same multiset and, in an open
loop, the arrival count is the same.  The seed chooses only which arrival
gets which (prompt, output) pair, where in the window each arrival falls,
and the token values.

Lengths are the mid-point quantiles of the stated distribution, so the
multiset is the distribution's own shape with no sampling noise.  Open-loop
arrivals are N = round(rate x seconds) points, so the offered load is
exact, placed as the file's `arrivals` says:

  {"process": "uniform"}   (the default) each point uniform in the window:
      a Poisson process conditioned on its count.  The seed decides how
      many arrivals bunch up, so it changes the work.
  {"process": "paced", "jitter": j}   the N gaps between arrivals are a
      fixed multiset, evenly spread over (1 - j, 1 + j) / rate, and the
      seed only orders them: every seed offers the same gaps.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import Any, Dict, List, Sequence, Tuple

def quantile_lengths(dist: Dict[str, Any], n: int) -> List[int]:
    """`n` integer lengths: the (i + 0.5) / n quantiles of `dist`, clipped
    to [min, max].  `dist`: {"dist": "log_uniform", "min", "max"} or
    {"dist": "log_normal", "median", "sigma", "min", "max"} or
    {"dist": "constant", "value"}."""
    kind = dist["dist"]
    if kind == "constant":
        return [int(dist["value"])] * n
    lo, hi = int(dist["min"]), int(dist["max"])
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if kind == "log_uniform":
            x = math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
        elif kind == "log_normal":
            x = dist["median"] * math.exp(
                dist["sigma"] * NormalDist().inv_cdf(u))
        else:
            raise ValueError(f"unknown distribution {kind!r}")
        out.append(min(max(int(round(x)), lo), hi))
    return out


def length_pairs(traffic: Dict[str, Any], n: int,
                 rng: random.Random) -> List[Tuple[int, int]]:
    """`n` (prompt, output) pairs.  Both marginals are fixed multisets.
    Without `pair_key` the seed pairs them up (independent lengths) and
    orders them.  With it the pairing is the file's own, drawn from that
    key, and the seed only orders the pairs: every seed offers the same
    multiset of PAIRS, so the cached positions a run holds live (prompt x
    the time its output takes) are the same too."""
    prompts = quantile_lengths(traffic["prompt_tokens"], n)
    outputs = quantile_lengths(traffic["output_tokens"], n)
    if "pair_key" in traffic:
        random.Random(int(traffic["pair_key"])).shuffle(outputs)
        pairs = list(zip(prompts, outputs))
        rng.shuffle(pairs)
        return pairs
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    return list(zip(prompts, outputs))


def arrival_count(traffic: Dict[str, Any], seconds: float) -> int:
    return int(round(traffic["rate_per_s"] * seconds))


def arrival_gaps(arrivals: Dict[str, Any], n: int,
                 seconds: float) -> List[float]:
    """The paced process's `n` gaps, ascending: they add up to `seconds`
    and are the same for every seed."""
    j = float(arrivals["jitter"])
    if not 0.0 <= j < 1.0:
        raise ValueError(f"jitter {j} is outside [0, 1)")
    return [(1.0 - j + 2.0 * j * (i + 0.5) / n) * seconds / n
            for i in range(n)]


def arrival_times(traffic: Dict[str, Any], n: int, seconds: float,
                  rng: random.Random) -> List[float]:
    arrivals = traffic.get("arrivals") or {"process": "uniform"}
    if arrivals["process"] == "uniform":
        return sorted(rng.uniform(0.0, seconds) for _ in range(n))
    if arrivals["process"] != "paced":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    gaps = arrival_gaps(arrivals, n, seconds)
    rng.shuffle(gaps)
    # The window opens somewhere inside the first gap, so the last
    # arrival falls inside it too.
    t, times = -rng.random() * gaps[0], []
    for g in gaps:
        t += g
        times.append(t)
    return times


def prompt_tokens(length: int, vocab: int, rng: random.Random) -> List[int]:
    """Unshared random prompt: no two requests have a block in common
    (beyond chance), so the prefix cache is bypassed."""
    return [rng.randrange(vocab) for _ in range(length)]


def stagger(outputs: Sequence[int], first_wave: int) -> List[int]:
    """Closed loop only: the first `first_wave` requests are admitted
    together, so left alone they would also end together and the window
    would open on a lock-step engine.  Request i of the first wave keeps
    (i + 1) / first_wave of its output length — the residual lives of a
    loop that has been running for a long time.  (The multiset of FULL
    lengths is unchanged; only these first requests are cut.)"""
    out = list(outputs)
    for i in range(min(first_wave, len(out))):
        out[i] = max(2, math.ceil(out[i] * (i + 1) / first_wave))
    return out


PLAN_BLOCKS = 64


def closed_loop_plan(traffic: Dict[str, Any], first_wave: int,
                     rng: random.Random) -> List[Tuple[int, int]]:
    """The (prompt, output) sequence the clients draw from, in order.  A
    run consumes as many requests as the system is fast, so the plan is
    made of blocks of `multiset_size` pairs, each block the SAME multiset
    in an order of its own: every run, on every seed, works through the
    same mixture however far it gets (a single shuffled list of 512 gave
    each seed a different first 170: spread 4 %, my chip run, PR 23)."""
    pairs: List[Tuple[int, int]] = []
    for _ in range(PLAN_BLOCKS):
        pairs += length_pairs(traffic, int(traffic["multiset_size"]), rng)
    cut = stagger([o for _, o in pairs], first_wave)
    return [(p, o) for (p, _), o in zip(pairs, cut)]


def open_loop_plan(traffic: Dict[str, Any], seconds: float,
                   rng: random.Random
                   ) -> List[Tuple[float, int, int]]:
    """[(due_s, prompt, output)] sorted by due time."""
    n = arrival_count(traffic, seconds)
    pairs = length_pairs(traffic, n, rng)
    times = arrival_times(traffic, n, seconds, rng)
    return [(t, p, o) for t, (p, o) in zip(times, pairs)]


def train_tokens(seed: int, first_row: int, rows: int, seq_len: int,
                 vocab: int):
    """Rows [first_row, first_row + rows) of the job's data: seq_len + 1
    uniform random token ids each, a function of (seed, row) alone so any
    worker can make any block."""
    import numpy as np
    out = np.empty((rows, seq_len + 1), np.int32)
    for r in range(rows):
        g = np.random.default_rng([seed % (2 ** 63), first_row + r])
        out[r] = g.integers(0, vocab, seq_len + 1, dtype=np.int32)
    return out
