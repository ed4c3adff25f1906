"""Traffic kind `sessions`: a closed loop of CONVERSATIONS.  Each of the
`tenants` has one system prompt; a conversation starts with its tenant's
system prompt and runs `turns` turns: turn k + 1's prompt is turn k's
prompt + the reply's own tokens + a new user message.  A caller sends its
next turn when the reply returns (no think time) and starts a new
conversation when one ends.  What is shared (`"sharing"` in the file): the
system prompt across a tenant's conversations, the history within one.

Two invariants, as for the other kinds (tests/test_seams.py): for ANY seed
every block of the plan is the same multiset of conversations (tenant,
message lengths, reply lengths), and every prompt fits `prompt_pad`, every
prompt + reply `max_len`.  The seed orders each block and makes the tokens.

Set-up ends when every system prompt is in the engine's prefix cache (one
priming request per tenant, answered) and the first wave is admitted.
"""

from __future__ import annotations

import math
import threading
import time
from typing import Any, Dict, List, Sequence, Tuple

from benchmarks.lib import reductions, spec, traffic
from benchmarks.lib.serve_cell import DRAIN_DEADLINE_S, Record, send_fn

closed_loop = spec.traffic_kind("closed_loop")   # its callers and window
CELL, clients = closed_loop.CELL, closed_loop.clients

# (tenant index, message lengths, reply lengths), one entry per turn
Conversation = Tuple[int, Tuple[int, ...], Tuple[int, ...]]

PRIME_MESSAGE, PRIME_REPLY = 8, 2   # the request that caches a system prompt


def block_of_conversations(tr: Dict[str, Any], prompt_pad: int
                           ) -> List[Conversation]:
    """The `multiset_size` conversations every block of the plan holds, from
    the file alone (no seed).  Tenants are equally popular.  Message and
    reply lengths are the mid-point quantiles of the stated distributions,
    one per turn of the block, dealt so that every conversation's last
    prompt fits `prompt_pad`: a last turn's reply enters no prompt, so the
    longest replies go there; every other length goes, longest first, to
    the conversation with the most room left per open turn."""
    tenants = tr["tenants"]
    n = int(tr["multiset_size"])
    if n % len(tenants):
        raise ValueError("multiset_size is not a multiple of the tenants")
    owner = [i % len(tenants) for i in range(n)]
    turns = [int(tenants[t]["turns"]) for t in owner]
    room = [prompt_pad - int(tenants[t]["system_tokens"]) for t in owner]
    total = sum(turns)
    messages = sorted(traffic.quantile_lengths(tr["message_tokens"], total),
                      reverse=True)
    replies = sorted(traffic.quantile_lengths(tr["reply_tokens"], total),
                     reverse=True)
    got_m: List[List[int]] = [[] for _ in range(n)]
    got_r: List[List[int]] = [[] for _ in range(n)]
    last, replies = replies[:n], replies[n:]

    def open_slots(c: int) -> int:
        return (turns[c] - len(got_m[c])) + (turns[c] - 1 - len(got_r[c]))

    for length, got, per in ([(x, got_r, -1) for x in replies]
                             + [(x, got_m, 0) for x in messages]):
        free = [c for c in range(n) if len(got[c]) < turns[c] + per]
        c = max(free, key=lambda c: (room[c] / open_slots(c), -c))
        got[c].append(length)
        room[c] -= length
    if min(room) < 0:
        raise ValueError(f"the file's lengths do not fit prompt_pad "
                         f"{prompt_pad}: room left {sorted(room)[:4]}")
    return [(owner[c], tuple(reversed(got_m[c])),
             tuple(reversed(got_r[c])) + (last[c],)) for c in range(n)]


def stagger_by_dispatch(first_replies: Sequence[int], first_wave: int,
                        chunk: int) -> List[int]:
    """The first wave is admitted in ONE dispatch, so left alone its replies
    would also end together.  Request i of it has already run
    floor(u_i x h) of the h dispatches its first reply takes (the admitting
    dispatch yields 1 + `chunk` tokens, each later one `chunk`), with
    u_i = (i + 0.5) / first_wave: the residual lives of a loop that has run
    for long, in the engine's own unit, the serve block's `decode_chunk`.
    (traffic.stagger's cut by tokens left 25 of 32 slots free after one
    dispatch; the admissions then swung 25 / 7 / 25 / 12 ... for 15
    dispatches, down to 2-4 where the narrow prefill is taken, and the
    rate read by how many of those a window caught: PERF.md section 2.)"""
    out = list(first_replies)
    for i in range(min(first_wave, len(out))):
        h = max(1, math.ceil((out[i] - 1) / chunk))
        out[i] -= chunk * int((i + 0.5) / first_wave * h)
    return out


def sessions_plan(tr: Dict[str, Any], sv: Dict[str, Any], first_wave: int,
                  rng) -> List[Conversation]:
    """The conversations the callers draw from, in order: PLAN_BLOCKS
    blocks, each the same multiset in an order of its own (a run gets as
    far as the system is fast).  The first wave's first replies are cut
    (stagger_by_dispatch), so that the conversations admitted together do
    not run in lock-step."""
    block = block_of_conversations(tr, sv["prompt_pad"])
    for tenant, msgs, reps in block:
        longest = tr["tenants"][tenant]["system_tokens"] + sum(msgs) \
            + sum(reps[:-1])
        if longest > sv["prompt_pad"] or longest + reps[-1] > sv["max_len"]:
            raise ValueError(f"a conversation of tenant {tenant} reaches "
                             f"{longest} + {reps[-1]} tokens")
    plan: List[Conversation] = []
    for _ in range(traffic.PLAN_BLOCKS):
        order = list(block)
        rng.shuffle(order)
        plan += order
    cut = stagger_by_dispatch([reps[0] for _, _, reps in plan], first_wave,
                              sv["decode_chunk"])
    return [(t, msgs, (first,) + reps[1:])
            for (t, msgs, reps), first in zip(plan, cut)]


def drive(handle, tr, sv, vocab, rng, seconds, on_window):
    send = send_fn(tr)
    n_clients = clients(tr, sv)
    first_wave = min(n_clients, sv["num_slots"])
    plan = sessions_plan(tr, sv, first_wave, rng)
    systems = [traffic.prompt_tokens(int(t["system_tokens"]), vocab, rng)
               for t in tr["tenants"]]
    messages = [[traffic.prompt_tokens(m, vocab, rng) for m in msgs]
                for _, msgs, _ in plan]
    records: List[Record] = []
    started = finished = 0
    lock = threading.Lock()
    stop = threading.Event()

    def one(prompt: List[int], max_new: int) -> Record:
        with lock:
            rec = Record(len(records), len(prompt), max_new, due=time.time())
            records.append(rec)
        send(handle, rec, prompt, vocab)
        return rec

    # Every system prompt into the prefix cache, before any conversation.
    primers = [threading.Thread(target=one, args=(
        s + traffic.prompt_tokens(PRIME_MESSAGE, vocab, rng), PRIME_REPLY))
        for s in systems]
    for t in primers:
        t.start()
    for t in primers:
        t.join(timeout=DRAIN_DEADLINE_S)
    n_primed = len(records)

    def client() -> None:
        nonlocal started, finished
        while not stop.is_set():
            with lock:
                i = started
                started += 1
            tenant, _, reps = plan[i % len(plan)]
            history = list(systems[tenant])
            for message, max_new in zip(messages[i % len(plan)], reps):
                rec = one(history + message, max_new)
                if not rec.ok or stop.is_set():
                    break
                history += message + rec.tokens
            else:
                with lock:
                    finished += 1

    t0 = closed_loop.callers_window(
        client, n_clients, records, lock, n_primed, first_wave, tr["reply"],
        seconds, on_window, stop)

    # TTFT of a hit against a miss, by the engine's own decomposition:
    # reported, not judged (PERF.md section 7, item 1).
    def ttft_ms(hit: bool) -> List[float]:
        return [(r.breakdown["route_s"] + r.breakdown["queue_s"]
                 + r.breakdown["prefill_s"]) * 1e3 for r in records
                if r.ok and r.breakdown is not None
                and bool(r.breakdown.get("cache_hit")) == hit]
    hit, miss = ttft_ms(True), ttft_ms(False)
    # Requests admitted per dispatch, in time order (admissions less than
    # 50 ms apart are one dispatch's): more than the engine's narrow width
    # in one dispatch take its full-width prefill.
    admitted_at = sorted(r.sent + r.breakdown["route_s"]
                         + r.breakdown["queue_s"] for r in records
                         if r.ok and r.breakdown is not None)
    per_dispatch: List[int] = []
    for a, b in zip([float("-inf")] + admitted_at, admitted_at):
        if b - a > 0.05:
            per_dispatch.append(0)
        per_dispatch[-1] += 1
    return t0, records, {
        "admitted_per_dispatch": per_dispatch,
        "clients": n_clients, "primed": n_primed,
        "conversations_started": started,
        "conversations_finished": finished,
        "engine_ttft_hit_p50_ms": reductions.percentile(hit, 0.5),
        "engine_ttft_hit_n": len(hit),
        "engine_ttft_miss_p50_ms": reductions.percentile(miss, 0.5),
        "engine_ttft_miss_n": len(miss)}
