"""Serve benchmark: decode throughput + TTFT for continuous batching.

Analog of BASELINE.json config #5 ("Llama Ray Serve continuous
batching") scaled to the attached single chip: a GPT-2-small-class
model served through the ContinuousBatcher engine, closed-loop clients
firing short prompts.  Writes SERVE_BENCH_<round>.json (SERVE_ROUND
env, default r05) and prints one JSON line; a run that fails exits
non-zero with its traceback.  The engines run in THIS process, which
therefore holds the chip.  The reference publishes no serving numbers
(BASELINE.md "published": {}).  None of the numbers earlier rounds
recorded with this script were taken on current code (PERF.md).

The default config is chunk 16 / depth 4; env knobs sweep:

  SERVE_SLOTS / SERVE_CHUNK / SERVE_DEPTH / SERVE_MAX_NEW — one run
  SERVE_SWEEP=1 — try several (chunk, depth) points with a short run
                  each, then measure the best at full length
  SERVE_MODEL=llama-1b — the ~1B-param serving config
"""

from __future__ import annotations

import json
import os
import threading
import time


def _build(cfg_name: str):
    import jax
    from ray_tpu.models import transformer
    if cfg_name == "llama-8b-int8":
        # The BASELINE north star: 8B-shape Llama serving on ONE 16 GB
        # chip.  bf16 weights alone are ~15 GB (no room for KV); the
        # weight-only int8 path (models/quantize.py) is ~7.5 GB + KV.
        # Weights are random int8 built directly on device — identical
        # compute/memory profile to a converted real checkpoint.
        from ray_tpu.models import quantize
        cfg = transformer.TransformerConfig(
            vocab_size=128_256, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, d_ff=14_336, max_seq=1024,
            dtype=jax.numpy.bfloat16, remat=False)
        params = quantize.init_quantized_params(cfg, jax.random.PRNGKey(0))
        return cfg, params, "llama-8b-class int8 (~8B)"
    if cfg_name == "llama-1b":
        cfg = transformer.TransformerConfig(
            vocab_size=32_000, d_model=2048, n_layers=16, n_heads=16,
            n_kv_heads=8, d_ff=5504, max_seq=1024,
            dtype=jax.numpy.bfloat16, remat=False)
        label = "llama-1b-class (~1.1B)"
    else:
        cfg = transformer.TransformerConfig(
            vocab_size=50_304, d_model=768, n_layers=12, n_heads=12,
            d_ff=3072, max_seq=1024, arch="gpt2",
            dtype=jax.numpy.bfloat16, remat=False)
        label = "gpt2-small (124M)"
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params, label


def _run_once(cfg, params, *, num_slots, decode_chunk, pipeline_depth,
              max_new, n_requests, max_len=256, prompt_pad=64):
    import numpy as np
    from ray_tpu.serve.llm import ContinuousBatcher

    bat = ContinuousBatcher(params, cfg, num_slots=num_slots,
                            max_len=max_len, prompt_pad=prompt_pad,
                            decode_chunk=decode_chunk,
                            pipeline_depth=pipeline_depth)
    try:
        return _measure(bat, cfg, num_slots=num_slots,
                        decode_chunk=decode_chunk,
                        pipeline_depth=pipeline_depth,
                        max_new=max_new, n_requests=n_requests)
    finally:
        bat.stop()


def _measure(bat, cfg, *, num_slots, decode_chunk, pipeline_depth,
             max_new, n_requests):
    """Two phases against one engine config.

    Throughput: open-loop saturation — ALL requests submitted up front
    (the engine admits as slots free), one waiter thread.  The previous
    closed-loop one-thread-per-slot harness put num_slots Python
    threads on this 1-vCPU host; at 48 slots the GIL thrash measured
    the harness, not the engine.  TTFT under saturation is queueing
    delay, so it is measured separately.

    Latency: 4 closed-loop clients (light load, slots mostly free) —
    the TTFT a user sees when the service is not saturated.
    """
    import numpy as np
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, size=(16,)).tolist()
               for _ in range(n_requests)]
    bat.generate(prompts[0], max_new=4)       # compile warmup

    t0 = time.time()
    reqs = [bat.submit(p, max_new=max_new) for p in prompts]
    for r in reqs:
        if not r.done.wait(600):
            raise TimeoutError("saturated run stalled")
        if r.error is not None:
            raise r.error
    wall = time.time() - t0
    total_tokens = sum(len(r.tokens) for r in reqs)

    lat_results = []
    lock = threading.Lock()
    # 96 samples: enough that the reported p95 is a real percentile
    # (index 91), not the max of a handful of requests.
    lat_work = list(prompts[:96])

    def client():
        while True:
            with lock:
                if not lat_work:
                    return
                p = lat_work.pop()
            out = bat.generate(p, max_new=max_new, timeout=600)
            with lock:
                lat_results.append(out)

    threads = [threading.Thread(target=client) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    # Streaming check: time-to-first-token through the stream path.
    st0 = time.time()
    first_tok_s = None
    streamed = []
    for tok in bat.generate_stream(prompts[0], max_new=8):
        if first_tok_s is None:
            first_tok_s = time.time() - st0
        streamed.append(tok)

    from ray_tpu.util.state import _percentile as pct

    ttfts = sorted(r["ttft_s"] for r in lat_results)
    queues = sorted(r.get("queue_s", 0.0) for r in lat_results)
    prefills = sorted(r.get("prefill_s", 0.0) for r in lat_results)
    return {
        "num_slots": num_slots,
        "decode_chunk": decode_chunk,
        "pipeline_depth": pipeline_depth,
        "requests": len(reqs),
        "max_new_tokens": max_new,
        "req_per_s": round(len(reqs) / wall, 2),
        "decode_tokens_per_s": round(total_tokens / wall, 1),
        "ttft_p50_ms": round(pct(ttfts, 0.50) * 1e3, 1),
        "ttft_p95_ms": round(pct(ttfts, 0.95) * 1e3, 1),
        # Where the TTFT milliseconds go (engine-side decomposition:
        # queue = submit -> slot admission, prefill = admission ->
        # first token; route is the proxy/router hop, not traversed by
        # this direct-engine harness) — so a regression in a future
        # round is attributable to a stage, not just a total.
        "ttft_breakdown": {
            "queue_p50_ms": round(pct(queues, 0.50) * 1e3, 1),
            "queue_p95_ms": round(pct(queues, 0.95) * 1e3, 1),
            "prefill_p50_ms": round(pct(prefills, 0.50) * 1e3, 1),
            "prefill_p95_ms": round(pct(prefills, 0.95) * 1e3, 1),
            "route": "n/a (direct engine, no proxy hop)",
        },
        "ttft_load": "4 closed-loop clients (unsaturated), 96 samples",
        "stream_first_token_ms": round((first_tok_s or 0) * 1e3, 1),
        "stream_tokens": len(streamed),
        "wall_s": round(wall, 2),
    }


def _pct(sorted_vals, q):
    from ray_tpu.util.state import _percentile
    return _percentile(sorted_vals, q)


def _shared_prefix_workload(cfg, n_requests, n_lat, *, sys_len,
                            tail_len, block_size, seed=0):
    """The millions-of-users shape (ROADMAP open item 1): 80% of
    requests are one of 4 long system prompts + a tiny unique tail,
    20% are fully unique.  sys_len is block-aligned so the whole
    system prompt is prefix-shareable.  Returns (throughput_prompts,
    latency_prompts) drawn from the SAME system prompts, so the
    latency phase runs against a warm cache."""
    import numpy as np
    rng = np.random.RandomState(seed)
    sys_len = (sys_len // block_size) * block_size
    sys_prompts = [rng.randint(0, cfg.vocab_size,
                               size=(sys_len,)).tolist()
                   for _ in range(4)]

    def draw():
        if rng.random() < 0.8:
            return sys_prompts[rng.randint(4)] + rng.randint(
                0, cfg.vocab_size, size=(tail_len,)).tolist()
        return rng.randint(0, cfg.vocab_size,
                           size=(sys_len + tail_len,)).tolist()

    return ([draw() for _ in range(n_requests)],
            [draw() for _ in range(n_lat)])


def _ttft_split(results):
    hits = sorted(r["ttft_s"] for r in results if r["cache_hit"])
    misses = sorted(r["ttft_s"] for r in results if not r["cache_hit"])
    cell = lambda xs: {  # noqa: E731
        "n": len(xs),
        "p50_ms": round(_pct(xs, 0.50) * 1e3, 1) if xs else None,
        "p95_ms": round(_pct(xs, 0.95) * 1e3, 1) if xs else None}
    return {"hit": cell(hits), "miss": cell(misses)}


def _measure_shared_prefix(bat, prompts, lat_prompts, max_new,
                           n_clients):
    """Two phases over the shared-prefix workload.

    Throughput: open-loop saturation — all requests submitted up front
    (the >= 48-concurrent-clients shape without 48 Python threads on a
    1-vCPU host).  TTFT under saturation is queue-position, so it is
    NOT reported from this phase.

    Latency: n_clients closed-loop clients against the now-warm prefix
    cache — the TTFT a user actually sees, split by cache_hit (this is
    where a hit's suffix-only narrow prefill shows up).  On CPU one
    client keeps the serial host from charging concurrent decode
    compute to TTFT; on TPU extra decode width is near-free, so 4."""
    bat.generate(prompts[0][:8], max_new=2)   # compile warmup
    t0 = time.time()
    reqs = [bat.submit(p, max_new=max_new) for p in prompts]
    for r in reqs:
        if not r.done.wait(600):
            raise TimeoutError("shared_prefix run stalled")
        if r.error is not None:
            raise r.error
    wall = time.time() - t0
    total_tokens = sum(len(r.tokens) for r in reqs)

    lat_results = []
    lock = threading.Lock()
    work = list(lat_prompts)

    def client():
        while True:
            with lock:
                if not work:
                    return
                p = work.pop()
            out = bat.generate(p, max_new=max_new, timeout=600)
            with lock:
                lat_results.append(out)

    threads = [threading.Thread(target=client)
               for _ in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    out = {
        "requests": len(reqs),
        "wall_s": round(wall, 2),
        "decode_tokens_per_s": round(total_tokens / wall, 1),
        "ttft_load": f"{n_clients} closed-loop clients (unsaturated), "
                     f"{len(lat_results)} samples, warm cache",
        "ttft_p50_ms": round(_pct(sorted(
            r["ttft_s"] for r in lat_results), 0.50) * 1e3, 1),
        "ttft_p95_ms": round(_pct(sorted(
            r["ttft_s"] for r in lat_results), 0.95) * 1e3, 1),
        "ttft_by_cache_hit": _ttft_split(lat_results),
        "finish_reasons": {
            fr: sum(1 for r in reqs if r.finish_reason == fr)
            for fr in sorted({r.finish_reason for r in reqs})},
    }
    stats = getattr(bat, "kv_stats", None)
    if stats is not None:
        st = stats()
        pc = st["prefix_cache"]
        out["prefix_cache"] = {
            "hit_ratio": round(pc["hits"] / max(pc["queries"], 1), 3),
            "queries": pc["queries"],
            "hits": pc["hits"],
            "hit_tokens": pc["hit_tokens"],
            "evictions": pc["evictions"],
            "cached_blocks": pc["cached_blocks"],
        }
        out["kv_blocks"] = st["blocks"]
    return out


def _run_shared_prefix(cfg, params, label, dev, on_tpu) -> dict:
    """Paged vs dense at KV-MEMORY PARITY: the dense engine provisions
    max_len positions per slot, so a fixed HBM budget caps its slot
    count; the paged engine spends the SAME budget as a block pool and
    runs more slots because requests only hold blocks for tokens they
    actually have (and 80% of them SHARE their system-prompt blocks).
    The win measured here is the paged-KV thesis: more concurrency and
    earlier admission per byte of KV, not a faster kernel."""
    from ray_tpu.serve.llm import ContinuousBatcher, PagedBatcher

    block = 16
    if on_tpu:
        # max_len must cover prompt (192+8) + max_new (64) = 264 with
        # one cap-margin position to spare, or every request truncates
        # with finish_reason "cache" and the tok/s compare is bogus.
        dense_slots, paged_slots, max_len = 16, 48, 288
        chunk, depth, max_new, n_requests = 16, 3, 64, 256
        prompt_pad, sys_len, tail_len = 224, 192, 8
    else:
        dense_slots, paged_slots, max_len = 4, 8, 128
        chunk, depth, max_new, n_requests = 4, 2, 16, 48
        prompt_pad, sys_len, tail_len = 64, 48, 4
    kv_budget_blocks = dense_slots * (max_len // block)
    n_clients = 4 if on_tpu else 1
    n_lat = 96 if on_tpu else 24
    prompts, lat_prompts = _shared_prefix_workload(
        cfg, n_requests, n_lat, sys_len=sys_len, tail_len=tail_len,
        block_size=block)
    engines = {}
    dense = ContinuousBatcher(params, cfg, num_slots=dense_slots,
                              max_len=max_len, prompt_pad=prompt_pad,
                              decode_chunk=chunk, pipeline_depth=depth)
    try:
        engines["dense"] = {
            "num_slots": dense_slots, "max_len": max_len,
            "kv_positions": dense_slots * max_len,
            **_measure_shared_prefix(dense, prompts, lat_prompts,
                                     max_new, n_clients)}
    finally:
        dense.stop()
    paged = PagedBatcher(params, cfg, num_slots=paged_slots,
                         max_len=max_len, prompt_pad=prompt_pad,
                         decode_chunk=chunk, pipeline_depth=depth,
                         kv_block_size=block,
                         kv_num_blocks=kv_budget_blocks)
    try:
        engines["paged"] = {
            "num_slots": paged_slots, "max_len": max_len,
            "kv_block_size": block, "kv_num_blocks": kv_budget_blocks,
            "kv_positions": kv_budget_blocks * block,
            **_measure_shared_prefix(paged, prompts, lat_prompts,
                                     max_new, n_clients)}
    finally:
        paged.stop()
    d, p = engines["dense"], engines["paged"]
    hit_p50 = p["ttft_by_cache_hit"]["hit"]["p50_ms"]
    return {
        "metric": "serve_shared_prefix",
        "scenario": "shared_prefix (80% of requests share one of 4 "
                    "long system prompts)",
        "model": label,
        "device": str(getattr(dev, "device_kind", dev.platform)),
        "platform": "tpu" if on_tpu else "cpu",
        "kv_budget_note": "both engines hold the same KV positions; "
                          "dense spends them as per-slot max_len "
                          "slabs, paged as a shared block pool",
        "engines": engines,
        "paged_vs_dense": {
            "decode_tps_speedup": round(
                p["decode_tokens_per_s"]
                / max(d["decode_tokens_per_s"], 1e-9), 2),
            "ttft_p50_cache_hit_vs_dense": (
                round(hit_p50 / max(d["ttft_p50_ms"], 1e-9), 3)
                if hit_p50 is not None else None),
        },
    }


# ===========================================================================
# Bursty diurnal replay: autoscaling + admission control + chaos
# ===========================================================================
def _run_bursty() -> dict:
    """Diurnal-replay drill for the overload-robustness layer
    (ROADMAP item 5 acceptance): a low -> burst -> low client pattern
    against an autoscaled, admission-controlled deployment.

    Asserts-by-measurement: TTFT p95 stays inside the configured SLO
    while the replica count tracks load (scale_up AND scale_down
    events in the capture); excess burst traffic is shed with
    structured rejections whose p95 latency is < 10 ms; a seeded
    chaos kill_replica during the downscale phase produces zero
    user-visible errors.  Pure control-plane behavior — runs the same
    on CPU and TPU (platform recorded in the JSON)."""
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private.config import config
    from ray_tpu.serve._admission import RequestRejectedError
    from ray_tpu.serve._controller import CONTROLLER_NAME
    from ray_tpu.util import chaos as chaos_api
    from ray_tpu.util import metrics
    from ray_tpu.util.state import _percentile as pct

    TTFT_SLO_MS = 400.0
    ray_tpu.init(num_cpus=8)

    @serve.deployment(
        num_replicas=1, max_concurrent_queries=16,
        autoscaling_config={"min_replicas": 1, "max_replicas": 4,
                            "target_queue_depth": 2.0,
                            "target_ttft_ms": TTFT_SLO_MS,
                            "downscale_slo_fraction": 0.9,
                            "upscale_delay_s": 0.3,
                            "downscale_delay_s": 2.0,
                            "interval_s": 0.25},
        admission_config={"max_queue_depth": 12,
                          "retry_after_s": 0.2})
    class Diurnal:
        async def __call__(self, x):
            import asyncio
            await asyncio.sleep(0.04)
            return x

    handle = serve.run(Diurnal.bind())

    samples = []                  # (t, running, draining, target)
    stop_sampler = threading.Event()

    def sampler():
        t0 = time.time()
        while not stop_sampler.is_set():
            try:
                st = serve.status()["Diurnal"]
                samples.append((round(time.time() - t0, 2),
                                len(st["replica_states"]),
                                st["draining_replicas"],
                                st["target_replicas"]))
            except Exception:
                pass
            stop_sampler.wait(0.25)

    threading.Thread(target=sampler, daemon=True).start()

    lock = threading.Lock()
    phase_stats: dict = {}

    def run_phase(name: str, seconds: float, clients: int) -> None:
        oks, rejects, errors = [], [], []
        deadline = time.time() + seconds

        def client():
            while time.time() < deadline:
                t0 = time.perf_counter()
                try:
                    ray_tpu.get(handle.remote(1), timeout=30)
                    dt = time.perf_counter() - t0
                    with lock:
                        oks.append(dt)
                except RequestRejectedError as e:
                    dt = time.perf_counter() - t0
                    with lock:
                        rejects.append((dt, e.reason,
                                        e.retry_after_s))
                    time.sleep(min(e.retry_after_s, 0.3))
                except Exception as e:  # noqa: BLE001
                    with lock:
                        errors.append(repr(e))

        threads = [threading.Thread(target=client)
                   for _ in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        ok_sorted = sorted(oks)
        rej_sorted = sorted(r[0] for r in rejects)
        phase_stats[name] = {
            "seconds": seconds, "clients": clients,
            "completed": len(oks), "shed": len(rejects),
            "errors": len(errors), "error_samples": errors[:3],
            "ttft_p50_ms": (round(pct(ok_sorted, 0.5) * 1e3, 1)
                            if oks else None),
            "ttft_p95_ms": (round(pct(ok_sorted, 0.95) * 1e3, 1)
                            if oks else None),
            "reject_p95_ms": (round(pct(rej_sorted, 0.95) * 1e3, 3)
                              if rejects else None),
            "reject_reasons": sorted({r[1] for r in rejects}),
        }

    run_phase("low_warm", 6.0, 2)
    run_phase("burst", 10.0, 16)
    # Downscale phase: arm ONE seeded replica kill so the drill
    # covers chaos-during-scale-down (zero user-visible errors —
    # un-started requests fail over).
    config.set("chaos_seed", 17)
    config.set("chaos_spec", "serve.assign:kind=kill_replica:p=1:n=1")
    chaos_api.refresh()
    chaos_api.reset_trace()
    run_phase("low_cooldown", 14.0, 2)
    chaos_trace = [(s, site, kind)
                   for s, site, kind in chaos_api.trace()]
    config.set("chaos_spec", "")
    config.set("chaos_seed", 0)
    chaos_api.refresh()
    stop_sampler.set()

    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    ostat = ray_tpu.get(controller.overload_status.remote(),
                        timeout=30)["Diurnal"]
    shed_counts: dict = {}
    for s in metrics.scrape():
        if s["name"] == metrics.SERVE_REQUESTS_SHED_METRIC:
            shed_counts[(s["tags"] or {}).get("reason", "?")] = \
                s["value"]
    events = ostat.get("autoscale_events") or []
    actions = [e.get("action") for e in events]
    max_replicas = max((s[1] for s in samples), default=1)
    out = {
        "metric": "serve_bursty_diurnal",
        "scenario": "bursty diurnal replay: low -> burst -> low "
                    "against SLO autoscaling + admission control, "
                    "seeded kill_replica during the downscale",
        "ttft_slo_ms": TTFT_SLO_MS,
        "phases": phase_stats,
        "replica_timeline": samples,
        "max_replicas_seen": max_replicas,
        "scale_up_seen": "scale_up" in actions,
        "scale_down_seen": "scale_down" in actions,
        "autoscale_events": events,
        "shed_total_by_reason": shed_counts,
        "chaos_trace": chaos_trace,
        "chaos_user_visible_errors": sum(
            p["errors"] for p in phase_stats.values()),
        "slo_met": all(
            p["ttft_p95_ms"] is not None
            and p["ttft_p95_ms"] <= TTFT_SLO_MS
            for p in phase_stats.values()),
    }
    serve.shutdown()
    ray_tpu.shutdown()
    return out


def main() -> None:
    model = os.environ.get("SERVE_MODEL", "gpt2s")

    if os.environ.get("SERVE_SCENARIO") == "bursty":
        # Control-plane drill: no model, no device — it runs the same
        # with or without a chip and touches no jax backend.
        out = _run_bursty()
        out["platform"] = "none (control plane only)"
        rnd = os.environ.get("SERVE_ROUND", "r08")
        with open(f"SERVE_BENCH_{rnd}_bursty.json", "w") as f:
            json.dump(out, f, indent=1)
        print(json.dumps(out))
        return

    from ray_tpu._private.accelerators import use_compile_cache
    use_compile_cache(os.environ)       # before jax is imported

    import jax

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    cfg, params, label = _build(model)

    if os.environ.get("SERVE_SCENARIO") == "shared_prefix":
        out = _run_shared_prefix(cfg, params, label, dev, on_tpu)
        rnd = os.environ.get("SERVE_ROUND", "r07")
        # Platform is recorded IN the JSON, so a CPU capture is a
        # legitimate record for this scenario (paged-vs-dense at
        # memory parity is an engine property, not a device one).
        with open(f"SERVE_BENCH_{rnd}.json", "w") as f:
            json.dump(out, f, indent=1)
        print(json.dumps(out))
        return

    slots = int(os.environ.get("SERVE_SLOTS", 16 if on_tpu else 4))
    chunk = int(os.environ.get("SERVE_CHUNK", 16 if on_tpu else 4))
    depth = int(os.environ.get("SERVE_DEPTH", 4 if on_tpu else 2))
    max_new = int(os.environ.get("SERVE_MAX_NEW",
                                 64 if on_tpu else 8))
    n_requests = 256 if on_tpu else 12

    sweep_on = os.environ.get("SERVE_SWEEP", "").lower() \
        not in ("", "0", "false")
    if sweep_on and on_tpu:
        # Short runs over the grid, then the winner at full length.
        # Slots dominate: tokens/dispatch = slots x chunk, and where
        # the per-dispatch host cost is mostly fixed wider decode
        # batches win until device time passes it (not measured on
        # current code).
        best, best_cfg = -1.0, None
        grid = [(16, 16, 3), (32, 16, 3), (48, 8, 3), (48, 16, 3),
                (48, 16, 2)]
        sweep_log = []
        for s, c, d in grid:
            r = _run_once(cfg, params, num_slots=s,
                          decode_chunk=c, pipeline_depth=d,
                          max_new=max_new, n_requests=96)
            sweep_log.append({"slots": s, "chunk": c, "depth": d,
                              "tps": r["decode_tokens_per_s"],
                              "ttft_p50_ms": r["ttft_p50_ms"]})
            # Round target: TTFT p50 <= 50 ms at light load.
            if r["decode_tokens_per_s"] > best \
                    and r["ttft_p50_ms"] <= 50.0:
                best, best_cfg = r["decode_tokens_per_s"], (s, c, d)
        if best_cfg is None:                    # nothing met the TTFT bar
            e = max(sweep_log, key=lambda e: e["tps"])
            best_cfg = (e["slots"], e["chunk"], e["depth"])
        slots, chunk, depth = best_cfg
    else:
        sweep_log = None

    r = _run_once(cfg, params, num_slots=slots, decode_chunk=chunk,
                  pipeline_depth=depth, max_new=max_new,
                  n_requests=n_requests)
    out = {
        "metric": "serve_continuous_batching",
        "model": label,
        "device": str(getattr(dev, "device_kind", dev.platform)),
        **r,
        "vs_r02_decode_tps": round(
            r["decode_tokens_per_s"] / 920.0, 2),
    }
    if sweep_log:
        out["sweep"] = sweep_log
    suffix = "" if model == "gpt2s" else f"_{model.replace('-', '_')}"
    rnd = os.environ.get("SERVE_ROUND", "r05")
    if on_tpu:   # never clobber the hardware record with a CPU smoke run
        with open(f"SERVE_BENCH_{rnd}{suffix}.json", "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
