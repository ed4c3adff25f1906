"""Traffic kind `closed_loop`: `clients` callers (or `clients_per_slot` x
the engine's slots), each sending its next request when its reply ends.
The plan is lib/traffic.py's closed_loop_plan: blocks of `multiset_size`
(prompt, output) pairs, every block the same multiset."""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List

from benchmarks.lib import traffic
from benchmarks.lib.serve_cell import DRAIN_DEADLINE_S, Record, send_fn

CELL = "serve_cell"      # benchmarks/lib/serve_cell.py runs the cell


def clients(tr: Dict[str, Any], sv: Dict[str, Any]) -> int:
    """Requests in flight at most: the callers."""
    return int(tr.get("clients") or tr["clients_per_slot"] * sv["num_slots"])


def drive(handle, tr, sv, vocab, rng, seconds, on_window):
    send = send_fn(tr)
    n_clients = clients(tr, sv)
    first_wave = min(n_clients, sv["num_slots"])
    plan = traffic.closed_loop_plan(tr, first_wave, rng)
    prompts = [traffic.prompt_tokens(p, vocab, rng) for p, _ in plan]
    records: List[Record] = []
    lock = threading.Lock()
    stop = threading.Event()

    def client() -> None:
        while not stop.is_set():
            with lock:
                i = len(records)
                p, o = plan[i % len(plan)]
                rec = Record(i, p, o, due=time.time())
                records.append(rec)
            send(handle, rec, prompts[i % len(plan)], vocab)

    t0 = callers_window(client, n_clients, records, lock, 0, first_wave,
                        tr["reply"], seconds, on_window, stop)
    # Every request sent is followed to its end and checked.
    return t0, records, {"clients": n_clients}


def callers_window(client, n_clients, records, lock, skip, first_wave,
                   reply, seconds, on_window, stop) -> float:
    """Start the callers, open the window, hold it, stop them and wait for
    each to end; returns the window's start.  The window opens when the
    whole first wave (the `first_wave` records after the first `skip`) has
    been admitted (the ramp is set-up): streamed, when each has its first
    token; unary, when the first (shortest, see traffic.stagger) reply is
    back."""
    threads = [threading.Thread(target=client, daemon=True,
                                name=f"bench-client-{i}")
               for i in range(n_clients)]
    for t in threads:
        t.start()
    need = first_wave if reply == "stream" else 1
    while True:
        with lock:
            wave = records[skip:skip + first_wave]
        if len(wave) == first_wave and sum(
                1 for r in wave if r.stamps or r.error) >= need:
            break
        time.sleep(0.005)
    t0 = time.time()
    on_window(t0)
    time.sleep(seconds)
    stop.set()
    for t in threads:
        t.join(timeout=DRAIN_DEADLINE_S)
    return t0
