"""Traffic kind `open_loop`: N = round(`rate_per_s` x seconds) independent
arrivals over the window (lib/traffic.py's open_loop_plan), each timed from
when it was DUE; at most `max_in_flight` at once."""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict

from benchmarks.lib import traffic
from benchmarks.lib.serve_cell import DRAIN_DEADLINE_S, Record, send_fn

CELL = "serve_cell"      # benchmarks/lib/serve_cell.py runs the cell


def clients(tr: Dict[str, Any], sv: Dict[str, Any]) -> int:
    """Requests in flight at most: the thread pool."""
    return int(tr["max_in_flight"])


def drive(handle, tr, sv, vocab, rng, seconds, on_window):
    send = send_fn(tr)
    plan = traffic.open_loop_plan(tr, seconds, rng)
    prompts = [traffic.prompt_tokens(p, vocab, rng) for _, p, _ in plan]
    records = [Record(i, p, o, due) for i, (due, p, o) in enumerate(plan)]
    pool = ThreadPoolExecutor(max_workers=clients(tr, sv),
                              thread_name_prefix="bench-client")
    t0 = time.time() + 0.05
    on_window(t0)
    futures = []
    for rec, prompt in zip(records, prompts):
        rec.due += t0                       # timed from when it was DUE
        delay = rec.due - time.time()
        if delay > 0:
            time.sleep(delay)
        futures.append(pool.submit(send, handle, rec, prompt, vocab))
    remaining = t0 + seconds - time.time()
    if remaining > 0:
        time.sleep(remaining)
    deadline = time.time() + DRAIN_DEADLINE_S
    for f in futures:
        try:
            f.result(timeout=max(deadline - time.time(), 0.1))
        except Exception:
            pass
    pool.shutdown(wait=False, cancel_futures=True)
    return t0, records, {"offered": len(records),
                         "rate_per_s": tr["rate_per_s"]}
