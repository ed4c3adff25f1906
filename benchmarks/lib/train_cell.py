"""A training cell: one `train_job` traffic file run through
TpuTrainer -> CompiledTrainStep in ONE worker that leases the cell's chips.
`train_loop` runs in that worker; `run` in the jax-free driver.  What the
architecture needs (the program's config, parameters and FLOPs from the
file's sizes, the parity checks and their limits) is the model kind's,
found by the configuration's `"kind"` (lib/spec.py)."""

from __future__ import annotations

import math
import time
from typing import Any, Dict

from benchmarks.lib import reference, spec

WARMUP_STEPS = 2          # compile + one steady step, counted as set-up
TRACE_STEPS = 3           # traced steps: the last ones of the window


def train_loop(config: Dict[str, Any]) -> None:
    import jax

    import ray_tpu.data
    from benchmarks.lib import peaks, traffic, worker_util
    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.train import session
    from ray_tpu.train.train_step import CompiledTrainStep, make_optimizer

    compiles = worker_util.CompileCounter()
    rehearsal = config["rehearsal"]
    device = worker_util.device_info(require_tpu=not rehearsal)
    chips = config["chips"]
    devices = jax.devices()
    if len(devices) != chips:
        raise RuntimeError(f"{len(devices)} devices for a {chips}-chip cell")
    mc, job, seed = config["config"], config["traffic"], config["seed"]
    kind = spec.model_kind(mc["kind"])
    tr = mc["train"]
    seq, batch = job["seq_len"], tr["batch_per_chip"] * chips

    cfg = tfm.TransformerConfig(**worker_util.with_dtypes(
        kind.transformer_kwargs(
            mc, max_seq=seq, param_dtype=tr["param_dtype"], remat=True,
            remat_policy=tr["remat_policy"], xent_chunk=tr["xent_chunk"],
            attn_block_k=tr["attn_block_k"], attn_impl="flash")))
    checks = kind.parity("train", cfg, seed, seq=min(seq, 512))

    mesh = make_mesh(MeshSpec(fsdp=chips), devices=devices)
    step = CompiledTrainStep(cfg, mesh, optimizer=make_optimizer(
        total_steps=10_000, kind=tr["optimizer"]))
    state = jax.block_until_ready(step.init_state(seed=seed % (2 ** 31)))
    n_params = tfm.num_params(state.params)
    wq = state.params["layers"]["wq"]
    state_devices = len({s.device for s in wq.addressable_shards})
    sharded = wq.addressable_shards[0].data.size * chips == wq.size

    # The job's data: rows made from (seed, row) by ray_tpu.data map tasks,
    # fed host -> HBM by iter_device_batches; a fresh batch every step.
    rows = batch * (WARMUP_STEPS + job["max_steps"])
    vocab = cfg.vocab_size

    def make_rows(block):
        ids = block["id"]
        return {"tokens": traffic.train_tokens(
            seed, int(ids[0]), len(ids), seq, vocab)}

    feed = iter(ray_tpu.data.range(rows, block_rows=batch)
                .map_batches(make_rows)
                .iter_device_batches(batch, sharding=step.data_sharding))

    flops_per_token = kind.train_flops_per_token(mc, seq)
    tel = session.get_context().telemetry(
        tokens_per_step=batch * seq, flops_per_token=flops_per_token,
        jit_fns=[step])

    def one_step(state):
        with tel.data_wait():
            tokens = next(feed)["tokens"]
        with tel.device_step():
            state, metrics = step(state, tokens)
            jax.block_until_ready(metrics)
        return state, metrics, tel.end_step()

    for _ in range(WARMUP_STEPS):
        state, metrics, _ = one_step(state)

    trace = (worker_util.TraceWindow(config["trace_dir"])
             if config["trace"] else None)
    trace_summary: Dict[str, Any] = {}
    seconds = config["seconds"]
    losses, recs = [], []
    compiles_before = compiles.count
    tel_before = worker_util.numeric_leaves(tel.snapshot())
    window_start_unix = time.time()
    t0 = time.perf_counter()
    tracing = False
    while True:
        now = time.perf_counter() - t0
        # The last TRACE_STEPS steps of the window, so that writing the
        # trace out and reading it fall after the measured time.
        if trace is not None and not tracing and recs and \
                now >= seconds - (TRACE_STEPS + 0.5) * now / len(recs):
            trace.start()
            tracing = True
        state, metrics, rec = one_step(state)
        losses.append(metrics["loss"])
        recs.append(rec)
        if time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    if tracing:
        trace.stop()
        trace_summary = trace.summary(keep=config["keep_trace"])
    compiles_in_window = compiles.count - compiles_before

    losses = [float(x) for x in losses]
    steps = len(recs)
    step_ms = [r["wall"] * 1e3 for r in recs]
    data_wait = sum(r["phases"].get("data_wait", 0.0) for r in recs)
    tokens_per_s_per_chip = steps * batch * seq / elapsed / chips
    # Everything the trainer's telemetry counts, `after - before` over the
    # window, beside the benchmark's own: a layer_metrics/<name>.json
    # reads any of them by name.
    counters = worker_util.beside(
        worker_util.deltas(tel_before,
                           worker_util.numeric_leaves(tel.snapshot())),
        {"data_wait_s": data_wait, "window_s": elapsed, "steps": steps,
         "params": n_params})
    if not rehearsal:       # a utilization exists only against a real peak
        counters["train_mfu_pct"] = (
            100.0 * tokens_per_s_per_chip * flops_per_token
            / peaks.peaks_for(device["kind"])["bf16_flops_per_s"])
    session.report({
        "device": device,
        "memory_peak_bytes": worker_util.memory_peak_bytes(),
        "window_start_unix": window_start_unix,
        "elapsed_s": elapsed, "steps": steps,
        "tokens_per_s_per_chip": tokens_per_s_per_chip,
        "counters": counters,
        "series": {"train_step_ms": step_ms},
        "trace": trace_summary,
        "shapes": {"batch": tr["batch_per_chip"], "seq": seq},
        "extra": {"step_ms_max": max(step_ms),
                  "step_ms_max_at": step_ms.index(max(step_ms)),
                  "data_wait_s": data_wait, "steps": steps,
                  "memory_stats": worker_util.memory_stats_fullest()},
        "checks": dict(
            checks, first_loss=losses[0], ln_vocab=math.log(vocab),
            losses_finite=all(math.isfinite(x) for x in losses),
            compiles_in_window=compiles_in_window,
            state_devices=state_devices, state_sharded=sharded,
            params=n_params, params_expected=kind.param_counts(mc)["total"]),
    })


def run(cell: Dict[str, Any], args, trace_dir: str, storage: str
        ) -> Dict[str, Any]:
    from ray_tpu.train import RunConfig, ScalingConfig, TpuTrainer

    chips = cell["cell"]["chips"]
    rehearsal = args.rehearsal
    scaling = (ScalingConfig(num_workers=1, use_tpu=False)
               if rehearsal else
               ScalingConfig(num_workers=1, use_tpu=True,
                             chips_per_worker=chips))
    result = TpuTrainer(
        train_loop,
        train_loop_config={
            "config": cell["config"], "traffic": cell["traffic"],
            "seed": args.seed, "seconds": args.seconds, "chips": chips,
            "trace": bool(args.trace), "trace_dir": trace_dir,
            "keep_trace": args.keep_trace, "rehearsal": rehearsal},
        scaling_config=scaling,
        run_config=RunConfig(name="bench", storage_path=storage),
    ).fit()
    if result.error is not None:
        raise RuntimeError(f"train cell failed: {result.error}")
    rep = (result.metrics_dataframe or [None])[-1]
    if not rep or "checks" not in rep:
        raise RuntimeError("the train worker reported nothing")
    c = rep["checks"]
    kind = spec.model_kind(cell["config"]["kind"])
    faults, c["compared"] = reference.judge(
        kind.TOLERANCES, kind.CHECKS["train"], c)
    if abs(c["first_loss"] - c["ln_vocab"]) >= 1.5:
        faults.append(f"first loss {c['first_loss']:.3f} is not near "
                      f"ln(vocab) {c['ln_vocab']:.3f}")
    if not c["losses_finite"]:
        faults.append("a loss was not finite")
    if c["compiles_in_window"]:
        faults.append(f"{c['compiles_in_window']} compilations inside "
                      f"the window")
    if c["state_devices"] != chips or (chips > 1 and not c["state_sharded"]):
        faults.append(f"state on {c['state_devices']} of {chips} devices")
    if c["params"] != c["params_expected"]:
        faults.append(f"{c['params']} parameters, the file's sizes give "
                      f"{c['params_expected']}")
    return {
        "report": rep, "faults": faults,
        "attempted": rep["steps"], "failed": 0,
        "end_to_end": {
            "train_tokens_per_s_per_chip": rep["tokens_per_s_per_chip"]},
    }
