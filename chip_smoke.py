#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on a TPU.

Drives the main path once, through the entry points a user calls, at the
full width of the `llama-1b` preset with random weights from a seed:

  1. `ray_tpu.init()` — the node advertises the chips detection found;
  2. train leg — `TpuTrainer(..., ScalingConfig(use_tpu=True)).fit()`: a
     few `CompiledTrainStep` steps (flash kernel, chunked cross-entropy,
     Adafactor) at seq 2048 in ONE worker that leased the chips;
  3. hand-over — the train worker's process has exited before the serve
     replica's worker is started (a chip belongs to one process);
  4. serve leg — `serve.run(serve.deployment(LLMDeployment, ...))`: the
     paged engine answers concurrent, streamed and prefix-sharing
     requests, one replica per chip.

The driver process never initialises a JAX backend: every device touch
happens in a worker the node service spawned.  Any failed check, phase,
request or worker ends the run with a non-zero exit code and no result
line; so does a machine without a chip, or JAX held to the CPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # fsdp=4 train step, four replicas

The last line of stdout is the result:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import shutil
import signal
import sys
import tempfile
import time

MODEL = "llama-1b"
SEED = 0
SEQ = 2048
TRAIN_STEPS = 6
# Sized to fit with room, not to saturate: XLA's ahead-of-time memory
# analysis plans 14.9 GiB of one v5e's 15.75 for this step at batch 2
# (batch 4 also runs on the chip, against a plan of 16.9 GiB: PERF.md);
# under fsdp=4 each chip holds a quarter of the state.
TRAIN_BATCH = {1: 2, 4: 8}
MAX_NEW = 16
PROMPT_PAD = 64
KV_BLOCK = 16
PREFIX_BLOCKS = 3
REQUESTS_PER_REPLICA = 8
DEADLINE_S = 1100           # the whole run, compilation included


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# train leg (runs in the TPU worker the trainer spawned)
# ---------------------------------------------------------------------------
def train_loop(config):
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import transformer as tfm
    from ray_tpu.ops.attention import attention_reference, flash_attention
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.train import session
    from ray_tpu.train.train_step import CompiledTrainStep, make_optimizer

    assert jax.default_backend() == "tpu", jax.default_backend()
    devices = jax.devices()
    chips = config["chips"]
    assert len(devices) == chips, (devices, chips)
    batch, seq = config["batch"], config["seq"]

    # The one-chip recipe: "names" remat + Adafactor is what lets
    # 1.5 B f32 parameters train in 16 GB; the flash kernel is forced.
    cfg = dataclasses.replace(
        tfm.PRESETS[config["model"]], max_seq=seq, remat=True,
        remat_policy="names", xent_chunk=2048, attn_block_k=1024,
        attn_impl="flash")

    # The kernel against the f32 reference on a small input, at this
    # model's head layout.
    ks = jax.random.split(jax.random.PRNGKey(config["seed"]), 3)
    q = jax.random.normal(ks[0], (1, cfg.n_heads, 256, cfg.head_dim),
                          jnp.bfloat16)
    k, v = (jax.random.normal(kk, (1, cfg.kv_heads, 256, cfg.head_dim),
                              jnp.bfloat16) for kk in ks[1:])
    flash_err = float(jnp.max(jnp.abs(
        flash_attention(q, k, v).astype(jnp.float32)
        - attention_reference(q, k, v).astype(jnp.float32))))
    assert flash_err < 2e-2, f"flash vs reference: max err {flash_err}"

    mesh = make_mesh(MeshSpec(fsdp=chips), devices=devices)
    step = CompiledTrainStep(
        cfg, mesh, optimizer=make_optimizer(total_steps=1000,
                                            kind="adafactor"))
    t0 = time.perf_counter()
    state = jax.block_until_ready(step.init_state(seed=config["seed"]))
    init_s = time.perf_counter() - t0
    wq = state.params["layers"]["wq"]
    shard_devices = {s.device for s in wq.addressable_shards}
    assert len(shard_devices) == chips, shard_devices
    if chips > 1:
        # parallel/mesh.py takes the FIRST devices of a list longer
        # than the spec: a state that landed on one chip would still
        # train, on a quarter of the machine.
        assert wq.addressable_shards[0].data.size * chips == wq.size
        in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
        assert all(b > 2 ** 28 for b in in_use), in_use

    rng = np.random.RandomState(config["seed"])
    for i in range(config["steps"]):
        tokens = rng.randint(0, cfg.vocab_size,
                             size=(batch, seq + 1)).astype(np.int32)
        t0 = time.perf_counter()
        state, metrics = step(state, step.shard_batch(tokens))
        metrics = jax.block_until_ready(metrics)
        wall = time.perf_counter() - t0
        session.report({
            "step": i + 1,
            "loss": float(metrics["loss"]),
            "grad_norm": float(metrics["grad_norm"]),
            "wall_s": wall, "init_s": init_s, "flash_err": flash_err,
            "compiled": step._cache_size(),
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "state_devices": len(shard_devices),
            "peak_bytes": max(d.memory_stats()["peak_bytes_in_use"]
                              for d in devices),
            "vocab": cfg.vocab_size, "pid": os.getpid(),
        })


def run_train_leg(chips: int, storage: str) -> dict:
    from ray_tpu.train import RunConfig, ScalingConfig, TpuTrainer

    t0 = time.time()
    result = TpuTrainer(
        train_loop,
        train_loop_config={"model": MODEL, "seed": SEED, "chips": chips,
                           "batch": TRAIN_BATCH[chips], "seq": SEQ,
                           "steps": TRAIN_STEPS},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     chips_per_worker=chips),
        run_config=RunConfig(name="chip_smoke", storage_path=storage),
    ).fit()
    wall = time.time() - t0
    if result.error is not None:
        raise SmokeFailure(f"train leg failed: {result.error}")
    steps = result.metrics_dataframe or []
    check(len(steps) == TRAIN_STEPS,
          f"train leg reported {len(steps)} of {TRAIN_STEPS} steps")
    first, last = steps[0], steps[-1]
    for s in steps:
        log("train step {step}: loss={loss:.4f} grad_norm={grad_norm:.3f} "
            "wall={wall_s:.2f}s compiled={compiled} "
            "peak={peak_gib:.2f}GiB".format(
                peak_gib=s["peak_bytes"] / 2 ** 30, **s))
        check(math.isfinite(s["loss"]) and math.isfinite(s["grad_norm"]),
              f"non-finite train metrics at step {s['step']}: {s}")
    check(first["platform"] == "tpu", f"train ran on {first['platform']}")
    check(first["device_count"] == chips == first["state_devices"],
          f"train state on {first['state_devices']} of {chips} chips")
    # Random weights over uniform random tokens: the loss starts at the
    # entropy of the vocabulary, give or take the logit variance.
    check(abs(first["loss"] - math.log(first["vocab"])) < 1.5,
          f"step-1 loss {first['loss']:.3f} is not near "
          f"ln(vocab)={math.log(first['vocab']):.3f}")
    recompiles = last["compiled"] - steps[1]["compiled"]
    check(recompiles == 0, f"{recompiles} recompiles after step 2")
    steady = sorted(s["wall_s"] for s in steps[2:])
    log(f"train leg: wall={wall:.1f}s "
        f"init(compile+run)={first['init_s']:.1f}s "
        f"step1(compile+run)={first['wall_s']:.1f}s "
        f"steady_step_p50={steady[len(steady) // 2]:.2f}s "
        f"recompiles_after_step2={recompiles} "
        f"flash_vs_reference_max_err={first['flash_err']:.2e} "
        f"peak={last['peak_bytes'] / 2 ** 30:.2f}GiB")
    return last


# ---------------------------------------------------------------------------
# hand-over
# ---------------------------------------------------------------------------
def _proc_stat(pid: int) -> list:
    """Fields of /proc/<pid>/stat after the command name (state first)."""
    with open(f"/proc/{pid}/stat") as f:
        return f.read().rsplit(")", 1)[1].split()


def _process_gone(pid: int) -> bool:
    try:
        return _proc_stat(pid)[0] == "Z"
    except OSError:
        return True


def _process_started_at(pid: int) -> float:
    """Unix time `pid` started, to the second (boot time's resolution)."""
    with open("/proc/stat") as f:
        boot = next(int(ln.split()[1]) for ln in f
                    if ln.startswith("btime"))
    return boot + int(_proc_stat(pid)[19]) / os.sysconf("SC_CLK_TCK")


def wait_train_worker_gone(pid: int) -> float:
    """The chip is free once its holder's process has exited — not when
    the actor was told to stop."""
    t0 = time.time()
    while not _process_gone(pid):
        check(time.time() - t0 < 60,
              f"train worker pid {pid} still alive 60s after fit()")
        time.sleep(0.05)
    log(f"hand-over: train worker pid {pid} exited "
        f"{time.time() - t0:.2f}s after fit() returned")
    return time.time()


# ---------------------------------------------------------------------------
# serve leg
# ---------------------------------------------------------------------------
def smoke_llm_class():
    from ray_tpu.serve.llm import LLMDeployment

    class SmokeLLM(LLMDeployment):
        def kernel_parity(self) -> float:
            """The paged kernel against the gather reference over this
            replica's LIVE block pool (layer 0, the tables and lengths
            the traffic left behind), at full width.  Also proves that
            "auto" lowers to the Mosaic kernel on this backend."""
            import jax
            import jax.numpy as jnp
            from ray_tpu.ops import paged_attention as pa
            c, cfg = self.batcher.caches, self.batcher.cfg
            B = c.lengths.shape[0]
            q = jax.random.normal(jax.random.PRNGKey(1),
                                  (B, cfg.n_heads, cfg.head_dim), cfg.dtype)
            M = c.block_tables.shape[1] * c.kp.shape[3]
            args = (q, c.kp[0], c.vp[0], c.block_tables,
                    jnp.minimum(c.lengths + 1, M))
            auto = jax.jit(lambda *a: pa.paged_attention(*a, impl="auto"))
            assert "tpu_custom_call" in auto.lower(*args).as_text(), \
                "impl='auto' did not lower to the Pallas kernel"
            ref = pa.paged_attention_reference(*args)
            return float(jnp.max(jnp.abs(
                auto(*args).astype(jnp.float32)
                - ref.astype(jnp.float32))))

    return SmokeLLM


def _call(replica, method: str, *args, **kwargs):
    """One request to ONE replica (the router picks replicas itself)."""
    return replica.handle_request.remote(method, args, kwargs)


def check_answer(out: dict, vocab: int, what: str) -> None:
    toks = out["tokens"]
    check(len(toks) == MAX_NEW, f"{what}: {len(toks)} tokens, "
                                f"wanted {MAX_NEW}: {out}")
    check(all(isinstance(t, int) and 0 <= t < vocab for t in toks),
          f"{what}: token outside the vocabulary: {toks}")
    check(out["finish_reason"] == "length",
          f"{what}: finish_reason {out['finish_reason']!r}")


def run_serve_leg(chips: int, train_gone_at: float) -> dict:
    import dataclasses
    import random

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models.transformer import PRESETS

    cfg_kwargs = dataclasses.asdict(PRESETS[MODEL])
    vocab = cfg_kwargs["vocab_size"]
    t0 = time.time()
    llm = serve.deployment(smoke_llm_class(), name="smoke_llm",
                           num_replicas=chips,
                           ray_actor_options={"num_tpus": 1})
    handle = serve.run(llm.bind(cfg_kwargs=cfg_kwargs, seed=SEED,
                                prompt_pad=PROMPT_PAD,
                                kv_block_size=KV_BLOCK))
    controller = ray_tpu.get_actor(serve.CONTROLLER_NAME)
    replicas = ray_tpu.get(controller.get_replicas.remote("smoke_llm"),
                           timeout=60)["replicas"]
    check(len(replicas) == chips, f"{len(replicas)} replicas for "
                                  f"{chips} chips")

    # Warm-up compiles six shapes on the engine thread; `generate`
    # waits 300 s for its answer, so wait for the engine here instead.
    stats = []
    for r in replicas:
        while True:
            st = ray_tpu.get(_call(r, "stats"), timeout=600)
            check(st["engine_error"] is None,
                  f"replica engine failed: {st['engine_error']}")
            if st["warmed"]:
                break
            check(time.time() - t0 < 600, "replica warm-up exceeded 600s")
            time.sleep(0.5)
        stats.append(st)
    ready_s = time.time() - t0
    for st in stats:
        check(st["backend"] == "tpu", f"replica backend {st['backend']}")
        check(st["device_count"] == 1 and len(st["chips"]) == 1,
              f"replica is not pinned to one chip: {st}")
        started = _process_started_at(st["pid"])
        check(started >= train_gone_at - 1.5,
              f"replica pid {st['pid']} started "
              f"{train_gone_at - started:.2f}s BEFORE the train worker "
              f"had exited")
    check(len({st["pid"] for st in stats}) == chips
          and len({st["chips"][0] for st in stats}) == chips,
          f"replicas share a process or a chip: "
          f"{[(st['pid'], st['chips']) for st in stats]}")
    log(f"serve leg: {chips} replica(s) warm after {ready_s:.1f}s on "
        f"chips {sorted(st['chips'][0] for st in stats)}, "
        f"{stats[0]['device_kind']} (slowest replica: weights "
        f"{max(st['params_s'] for st in stats):.1f}s, warm-up compile+run "
        f"{max(st['warmup_s'] for st in stats):.1f}s)")

    rnd = random.Random(SEED)

    def prompt(n):
        return [rnd.randrange(vocab) for _ in range(n)]

    # Through the handle: several requests in flight at once, and one
    # streamed.
    t0 = time.time()
    n_req = REQUESTS_PER_REPLICA * chips
    refs = [handle.generate.remote(prompt(rnd.randint(4, PROMPT_PAD)),
                                   max_new=MAX_NEW) for _ in range(n_req)]
    stream = handle.generate_stream.options(stream=True).remote(
        prompt(24), MAX_NEW)
    for i, out in enumerate(ray_tpu.get(refs, timeout=600)):
        check_answer(out, vocab, f"request {i}")
    streamed = [ray_tpu.get(ref, timeout=600) for ref in stream]
    check_answer({"tokens": streamed, "finish_reason": "length"}, vocab,
                 "streamed request")
    traffic_s = time.time() - t0

    # Per replica: two prompts sharing PREFIX_BLOCKS full KV blocks —
    # the second must be served from the radix cache — and the kernel
    # against its reference on the pool that traffic left behind.
    t0 = time.time()
    shared = prompt(PREFIX_BLOCKS * KV_BLOCK)
    for i, r in enumerate(replicas):
        cold = ray_tpu.get(_call(r, "generate", shared + prompt(8),
                                 max_new=MAX_NEW), timeout=600)
        warm = ray_tpu.get(_call(r, "generate", shared + prompt(8),
                                 max_new=MAX_NEW), timeout=600)
        check_answer(cold, vocab, f"replica {i} prefix request 1")
        check_answer(warm, vocab, f"replica {i} prefix request 2")
        check(warm["cache_hit"]
              and warm["cached_tokens"] >= PREFIX_BLOCKS * KV_BLOCK,
              f"replica {i}: second prefix-sharing request missed the "
              f"cache: {warm}")
        err = ray_tpu.get(_call(r, "kernel_parity"), timeout=600)
        check(err < 1e-2, f"replica {i}: paged kernel vs reference "
                          f"max err {err}")
        st = ray_tpu.get(_call(r, "stats"), timeout=60)
        check(st["steps"] > 0 and st["engine_error"] is None,
              f"replica {i} engine did not step: {st}")
        check(st["prefix_cache"]["hits"] >= 1, f"replica {i}: {st}")
        log(f"replica {i}: pid={st['pid']} chip={st['chips'][0]} "
            f"steps={st['steps']} prefix_hits={st['prefix_cache']['hits']} "
            f"paged_kernel_vs_reference_max_err={err:.2e} "
            f"peak={st['peak_bytes'] / 2 ** 30:.2f}GiB")
    served = n_req + 1 + 2 * chips
    log(f"serve leg: {served} requests answered with no error "
        f"({n_req} concurrent + 1 streamed in {traffic_s:.1f}s, "
        f"{2 * chips} prefix-sharing + parity in {time.time() - t0:.1f}s)")
    serve.shutdown()
    return stats[0]


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
def tail_worker_logs(session_dir: str, lines: int = 60) -> None:
    for path in sorted(glob.glob(os.path.join(session_dir, "logs",
                                              "worker-*.log"))):
        with open(path, errors="replace") as f:
            tail = f.readlines()[-lines:]
        sys.stderr.write(f"----- {path} (last {len(tail)} lines)\n")
        sys.stderr.writelines(tail)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=sorted(TRAIN_BATCH),
                    default=1)
    chips = ap.parse_args().chips

    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        log(f"JAX_PLATFORMS={platforms} holds JAX off the TPU: nothing "
            f"to prove here")
        return 2

    import ray_tpu                      # the repo, or an ImportError
    from ray_tpu._private.accelerators import detect_num_chips

    found = detect_num_chips()
    if found < chips:
        log(f"{found} TPU chip(s) found (/dev/accel*, /dev/vfio/<n>), "
            f"{chips} needed")
        return 2

    def on_deadline(signum, frame):
        raise SmokeFailure(f"not finished after {DEADLINE_S}s")
    signal.signal(signal.SIGALRM, on_deadline)
    signal.alarm(DEADLINE_S)

    t_start = time.time()
    storage = tempfile.mkdtemp(prefix="chip_smoke_")
    ray_tpu.init()          # no num_tpus: what detection found
    session_dir = ray_tpu._session.session_dir
    ok = False
    try:
        total = ray_tpu.cluster_resources().get("TPU", 0)
        check(total == found, f"node advertises TPU={total}, detection "
                              f"found {found}")
        log(f"node advertises TPU={total:g}; using {chips}")
        train = run_train_leg(chips, storage)
        gone_at = wait_train_worker_gone(train["pid"])
        replica = run_serve_leg(chips, gone_at)
        check(replica["device_kind"] == train["device_kind"],
              "legs ran on different devices")
        jax = sys.modules.get("jax")
        if jax is not None:
            from jax._src import xla_bridge
            check(not xla_bridge.backends_are_initialized(),
                  "the driver process initialised a JAX backend")
        ok = True
    except BaseException as e:
        log(f"FAILED: {type(e).__name__}: {e}")
        import traceback
        traceback.print_exc()
        tail_worker_logs(session_dir)
    finally:
        signal.alarm(0)
        ray_tpu.shutdown()      # stops every worker it started
        shutil.rmtree(storage, ignore_errors=True)
    if not ok:
        return 1
    log(f"platform={train['platform']} device_kind={train['device_kind']} "
        f"device_count={train['device_count']} "
        f"total_wall={time.time() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": train["platform"], "kind": train["device_kind"],
        "count": train["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
