"""A serving cell: one traffic file of a serving kind against
serve.run(serve.deployment(BenchLLM)) — the program's LLMDeployment with
the few methods the benchmark needs inside the process that holds the
chip.  The load comes from this (jax-free) driver process: one thread per
request in flight, each reading its token stream and stamping every token
with the host clock.

How the requests are offered is the traffic kind's (traffic_kinds/<kind>.py:
`clients`, `drive`), what the architecture needs is the model kind's
(kinds/<kind>.py); both are found by name (lib/spec.py).  The request
record, the two ways to send one, the window, the drain and every
end-to-end formula are here, and are the only ones."""

from __future__ import annotations

import random
import threading
import time
from typing import Any, Dict, List, Optional

from benchmarks.lib import (reductions, reference, spec, traffic,
                            worker_util)
from ray_tpu.serve.llm import LLMDeployment

TRACE_SECONDS = 4.0       # traced part of the window: its last seconds
WARM_REQUESTS = 4         # streamed through the whole path before the clock
DRAIN_DEADLINE_S = 150.0  # after the window; unfinished = failed
FAILED_TPOT_MS = DRAIN_DEADLINE_S * 1e3   # a failed request misses any limit


class BenchLLM(LLMDeployment):
    """LLMDeployment + weights from the seed in one jitted call, the
    kernels' parity checks, a profiler window, and the engine's own TTFT
    decomposition for streamed requests."""

    def __init__(self, config: Dict[str, Any], seed: int, rehearsal: bool,
                 trace_dir: str, keep_trace: bool) -> None:
        import jax
        from ray_tpu.models import transformer

        self._kind = spec.model_kind(config["kind"])
        self._compiles = worker_util.CompileCounter()
        self._device = worker_util.device_info(require_tpu=not rehearsal)
        sv = config["serve"]
        cfg_kwargs = worker_util.with_dtypes(self._kind.transformer_kwargs(
            config, max_seq=sv["max_len"], param_dtype=sv["param_dtype"]))
        cfg = transformer.TransformerConfig(**cfg_kwargs)
        # One program makes every weight on the device, in the type it is
        # served in (the program's eager init cost 95 s cold: PERF.md).
        params = jax.block_until_ready(jax.jit(
            lambda key: transformer.init_params(cfg, key))(
                jax.random.PRNGKey(seed % (2 ** 31))))
        self._n_params = transformer.num_params(params)
        self._seed = seed
        self._trace = worker_util.TraceWindow(trace_dir)
        self._keep_trace = keep_trace
        self._breakdowns: List[Dict[str, Any]] = []
        self._bd_lock = threading.Lock()
        super().__init__(
            cfg_kwargs, params=params, seed=seed,
            num_slots=sv["num_slots"], max_len=sv["max_len"],
            prompt_pad=sv["prompt_pad"], decode_chunk=sv["decode_chunk"],
            pipeline_depth=sv["pipeline_depth"],
            kv_block_size=sv["kv_block_size"],
            kv_num_blocks=sv["kv_num_blocks"])

    def generate_stream(self, prompt, max_new: int = 32):
        """The program's generate_stream (submit + stream), keeping what
        `generate()` returns as `ttft_breakdown` — the streamed path drops
        it (PERF.md, Open questions)."""
        route_t0 = time.time()
        req = self.batcher.submit(prompt, max_new, streaming=True,
                                  model_id=self._request_model_id())
        yield from req.stream()
        with self._bd_lock:
            self._breakdowns.append({
                "route_s": max(req._t0 - route_t0, 0.0),
                "queue_s": req.queue_s, "prefill_s": req.prefill_s,
                "finish_reason": req.finish_reason,
                "tokens": len(req.tokens)})

    def take_breakdowns(self) -> List[Dict[str, Any]]:
        with self._bd_lock:
            out, self._breakdowns = self._breakdowns, []
        return out

    def bench_info(self) -> Dict[str, Any]:
        """`engine`: every number the program's stats() holds (for a paged
        engine that includes kv_stats()), under its dotted path."""
        st = self.stats()
        return {"device": self._device, "params": self._n_params,
                "memory_peak_bytes": worker_util.memory_peak_bytes(),
                "memory_stats": worker_util.memory_stats_fullest(),
                "compiles": self._compiles.count, "steps": st["steps"],
                "warmed": st["warmed"], "engine_error": st["engine_error"],
                "warmup_s": st["warmup_s"],
                "engine": worker_util.numeric_leaves(st)}

    def kernel_parity(self) -> Dict[str, Any]:
        """Only while the engine is idle: a dispatch donates the pool."""
        return self._kind.parity("serve", self.batcher.cfg, self._seed,
                                 caches=self.batcher.caches)

    def trace_start(self) -> float:
        self._trace.start()
        return time.time()

    def trace_stop(self) -> float:
        self._trace.stop()
        return time.time()

    def trace_summary(self) -> Dict[str, Any]:
        """Reading the trace holds this process for seconds: only after
        the traffic has drained."""
        return self._trace.summary(keep=self._keep_trace)


class Record:
    """One request, as the client saw it."""
    __slots__ = ("index", "prompt_len", "max_new", "due", "sent", "stamps",
                 "tokens", "bad_token", "error", "breakdown")

    def __init__(self, index: int, prompt_len: int, max_new: int,
                 due: float) -> None:
        self.index, self.prompt_len, self.max_new = index, prompt_len, max_new
        self.due = due
        self.sent = 0.0
        self.stamps: List[float] = []
        self.tokens: List[int] = []
        self.bad_token = False
        self.error: Optional[str] = None
        self.breakdown: Optional[Dict[str, Any]] = None

    @property
    def ok(self) -> bool:
        return (self.error is None and not self.bad_token
                and len(self.stamps) == self.max_new)


def unary_one(handle, rec: Record, prompt: List[int], vocab: int) -> None:
    """A caller that waits for its whole reply (`"reply": "unary"`): all
    its tokens are delivered at once, when `generate` returns."""
    import ray_tpu
    rec.sent = time.time()
    try:
        out = ray_tpu.get(handle.generate.remote(prompt, max_new=rec.max_new),
                          timeout=DRAIN_DEADLINE_S)
        rec.stamps = [time.time()] * len(out["tokens"])
        rec.tokens = list(out["tokens"])
        rec.bad_token = not all(isinstance(t, int) and 0 <= t < vocab
                                for t in out["tokens"])
        rec.breakdown = dict(out["ttft_breakdown"],
                             finish_reason=out["finish_reason"])
    except Exception as e:
        rec.error = f"{type(e).__name__}: {e}"


def stream_one(handle, rec: Record, prompt: List[int], vocab: int) -> None:
    import ray_tpu
    rec.sent = time.time()
    try:
        gen = handle.generate_stream.options(stream=True).remote(
            prompt, rec.max_new)
        for ref in gen:
            tok = ray_tpu.get(ref, timeout=DRAIN_DEADLINE_S)
            rec.stamps.append(time.time())
            rec.tokens.append(tok)
            if not (isinstance(tok, int) and 0 <= tok < vocab):
                rec.bad_token = True
    except Exception as e:            # counted as a failed request
        rec.error = f"{type(e).__name__}: {e}"


def _wait_warm(handle, deadline_s: float) -> Dict[str, Any]:
    import ray_tpu
    t0 = time.time()
    while True:
        info = ray_tpu.get(handle.bench_info.remote(), timeout=deadline_s)
        if info["engine_error"] is not None:
            raise RuntimeError(f"engine failed: {info['engine_error']}")
        if info["warmed"]:
            return info
        if time.time() - t0 > deadline_s:
            raise RuntimeError("engine warm-up exceeded its deadline")
        time.sleep(0.25)


def send_fn(tr: Dict[str, Any]):
    return {"unary": unary_one, "stream": stream_one}[tr["reply"]]


def _tokens_in_window(r: "Record", t0: float, t1: float) -> float:
    """Output tokens of `r` that fall inside [t0, t1].  Streamed: by each
    token's own stamp.  Unary: the reply's tokens spread evenly from its
    first token (sent + the reply's own ttft_breakdown) to its arrival —
    decode hands a slot 8 tokens per dispatch at a steady pace, so this is
    where they were made.  Counting a reply whole at its arrival instead
    charges the window for ~3000 tokens in flight at its end (PERF.md §2)."""
    if not r.stamps:
        return 0.0
    if r.breakdown is None:
        return float(sum(1 for s in r.stamps if t0 <= s <= t1))
    bd = r.breakdown
    a = r.sent + bd["route_s"] + bd["queue_s"] + bd["prefill_s"]
    b = r.stamps[-1]
    if b <= a:
        return float(len(r.stamps)) if t0 <= b <= t1 else 0.0
    return len(r.stamps) * max(0.0, min(b, t1) - max(a, t0)) / (b - a)


def _live_context(records, ta: float, tb: float, samples: int = 64) -> float:
    """Time-average over [ta, tb] of the cached positions the live requests
    hold: prompt + tokens so far.  Streamed: by the client's token clock.
    Unary: from admission (sent + the engine's own queue_s) to the reply,
    tokens taken as arriving evenly."""
    if tb <= ta:
        return 0.0
    spans = []
    for r in records:
        if not r.stamps:
            continue
        if r.breakdown is not None:
            start = r.sent + r.breakdown["queue_s"] + r.breakdown["prefill_s"]
            spans.append((start, r.stamps[-1], r, None))
        else:
            spans.append((r.stamps[0], r.stamps[-1], r, r.stamps))
    total = 0.0
    for k in range(samples):
        t = ta + (tb - ta) * (k + 0.5) / samples
        for start, end, r, stamps in spans:
            if not start <= t <= end:
                continue
            if stamps is not None:
                got = sum(1 for s in stamps if s <= t)
            else:
                got = r.max_new * (t - start) / max(end - start, 1e-9)
            total += r.prompt_len + got
    return total / samples


def run(cell: Dict[str, Any], args, trace_dir: str, scratch: str = ""
        ) -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu import serve

    cfg, tr = cell["config"], cell["traffic"]
    sv, vocab = cfg["serve"], cfg["vocab_size"]
    seconds = float(args.seconds)
    rng = random.Random(args.seed)
    kind = spec.model_kind(cfg["kind"])
    drive = spec.traffic_kind(tr["kind"])
    in_flight = drive.clients(tr, sv)
    options = ({"num_cpus": 1} if args.rehearsal else {"num_tpus": 1})
    llm = serve.deployment(BenchLLM, name="bench_llm", num_replicas=1,
                           max_concurrent_queries=in_flight + 8,
                           # writing a trace out holds the replica longer
                           # than the default 30 s probe deadline
                           health_check_timeout_s=600.0,
                           ray_actor_options=options)
    handle = serve.run(llm.bind(cfg, args.seed, args.rehearsal, trace_dir,
                                args.keep_trace))
    info = _wait_warm(handle, 1000.0)

    # Through the whole path once (router, stream plane), which also
    # leaves live blocks in the pool for the parity check.
    warm = [Record(-1 - i, 32, 12, 0.0) for i in range(WARM_REQUESTS)]
    warm_threads = [threading.Thread(
        target=stream_one,
        args=(handle, r, traffic.prompt_tokens(r.prompt_len, vocab, rng),
              vocab)) for r in warm]
    for t in warm_threads:
        t.start()
    for t in warm_threads:
        t.join()
    if not all(r.ok for r in warm):
        raise RuntimeError(f"warm-up request failed: "
                           f"{[r.error for r in warm]}")
    checks = ray_tpu.get(handle.kernel_parity.remote(), timeout=600)
    ray_tpu.get(handle.take_breakdowns.remote(), timeout=60)
    before = ray_tpu.get(handle.bench_info.remote(), timeout=60)

    trace_out: Dict[str, Any] = {}

    def trace_window(t0: float) -> None:
        # The end of the window, so that writing the trace out (which
        # stalls the replica) falls into the drain, not the window.
        time.sleep(max(t0 + seconds - TRACE_SECONDS - time.time(), 0.0))
        ta = ray_tpu.get(handle.trace_start.remote(), timeout=120)
        time.sleep(max(t0 + seconds - time.time(), 0.0))
        tb = ray_tpu.get(handle.trace_stop.remote(), timeout=300)
        trace_out.update(started_unix=ta, stopped_unix=tb)

    tracer: List[threading.Thread] = []

    def on_window(t0: float) -> None:
        if args.trace:
            tracer.append(threading.Thread(target=trace_window, args=(t0,),
                                           daemon=True))
            tracer[-1].start()

    t0, records, extra = drive.drive(handle, tr, sv, vocab, rng, seconds,
                                     on_window)
    for t in tracer:
        t.join(timeout=400)
    if args.trace:
        trace_out.update(ray_tpu.get(handle.trace_summary.remote(),
                                     timeout=600))
    after = ray_tpu.get(handle.bench_info.remote(), timeout=60)
    breakdowns = ray_tpu.get(handle.take_breakdowns.remote(), timeout=60)
    breakdowns += [r.breakdown for r in records if r.breakdown]
    serve.shutdown()

    t1 = t0 + seconds
    in_window = sum(_tokens_in_window(r, t0, t1) for r in records)
    # Open loop: every request is due inside the window by construction.
    # Closed loop: every request SENT is checked, and followed to its end.
    failed = [r for r in records if not r.ok]
    done = [r for r in records if r.ok]
    tpot = [(r.stamps[-1] - r.stamps[0]) / (r.max_new - 1) * 1e3
            for r in done if r.max_new > 1]
    tpot += [FAILED_TPOT_MS] * len(failed)
    ttft = [(r.stamps[0] - r.due) * 1e3 for r in done]
    gaps = [(b - a) * 1e3 for r in done
            for a, b in zip(r.stamps, r.stamps[1:])]
    lateness = [(r.sent - r.due) * 1e3 for r in records]
    steps = after["steps"] - before["steps"]
    delivered = sum(len(r.stamps) for r in records)

    faults, compared = reference.judge(kind.TOLERANCES, kind.CHECKS["serve"],
                                       checks)
    if after["compiles"] != before["compiles"]:
        faults.append(f"{after['compiles'] - before['compiles']} "
                      f"compilations inside the window")
    if after["params"] != kind.param_counts(cfg)["total"]:
        faults.append(f"{after['params']} parameters, the file's sizes "
                      f"give {kind.param_counts(cfg)['total']}")
    wrong = [b for b in breakdowns if b["finish_reason"] != "length"]
    if wrong:
        faults.append(f"{len(wrong)} requests did not finish by length: "
                      f"{wrong[0]}")
    if any(r.bad_token for r in records):
        faults.append("a token outside the vocabulary")
    if failed:
        f = failed[0]
        faults.append(f"{len(failed)} failed requests, e.g. #{f.index}: "
                      f"{len(f.stamps)}/{f.max_new} tokens, {f.error}")

    ta = trace_out.get("started_unix", 0.0)
    tb = trace_out.get("stopped_unix", 0.0)
    rep = {
        "device": after["device"],
        "memory_peak_bytes": after["memory_peak_bytes"],
        "window_start_unix": t0,
        # Everything the engine counts, `after - before` (the ramp, the
        # window and the drain: what every request sent caused), beside
        # what the client alone knows.  A layer_metrics/<name>.json reads
        # any of them by name.
        "counters": worker_util.beside(
            worker_util.deltas(before["engine"], after["engine"]),
            {"output_tokens": float(delivered),
             "engine_steps": float(steps), "requests": float(len(records)),
             "prompt_tokens": float(sum(r.prompt_len for r in records))}),
        "series": {
            "tpot_ms": tpot, "ttft_ms": ttft, "stream_gap_ms": gaps,
            "queue_ms": [b["queue_s"] * 1e3 for b in breakdowns],
            "route_ms": [b["route_s"] * 1e3 for b in breakdowns],
            "lateness_ms": lateness,
        },
        "trace": {k: v for k, v in trace_out.items()
                  if not k.endswith("_unix")},
        "shapes": {"slots": sv["num_slots"],
                   "live_context": _live_context(records, ta, tb)},
        "checks": dict(checks, engine_warmup_s=info["warmup_s"],
                       compared=compared),
        "extra": dict(
            extra, generator_lateness_p95_ms=reductions.percentile(
                lateness, 0.95),
            ttft_p50_ms=reductions.percentile(ttft, 0.5),
            ttft_p95_ms=reductions.percentile(ttft, 0.95),
            tpot_p50_ms=reductions.percentile(tpot, 0.5),
            # what sits at and beyond the 95th percentile: [ms, tokens]
            tpot_top=sorted(
                ([(r.stamps[-1] - r.stamps[0]) / (r.max_new - 1) * 1e3,
                  r.max_new] for r in done if r.max_new > 1),
                reverse=True)[:8],
            memory_stats=after["memory_stats"],
            tokens_in_window=in_window, requests=len(records),
            replies_in_window_tokens=sum(
                len(r.stamps) for r in done if t0 <= r.stamps[-1] <= t1),
            completed_in_window=sum(1 for r in done
                                    if t0 <= r.stamps[-1] <= t1)),
    }
    end_to_end = {
        "decode_tokens_per_s": in_window / seconds,
        "tpot_p95_ms": reductions.percentile(tpot, 0.95),
    }
    return {"report": rep, "faults": faults, "attempted": len(records),
            "failed": len(failed), "end_to_end": end_to_end}
