"""ON-DEVICE runtime validation: the TPU-specific hot paths that the
CPU suite can only approximate — the serving engines' pipelined decode
(copy_to_host_async through the real transfer engine, the compiled
paged kernel behind the block tables), the CompiledTrainStep (donation
+ bf16 on real HBM), and the iter_device_batches host->HBM prefetch
pipeline.

    python -m pytest tests_tpu/ -q        # errors without a TPU
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _tiny_cfg(dtype=None):
    """head_dim 64, so the paged kernel takes it; the full forward used
    as the oracle asks for the reference attention by name (on a TPU
    "auto" is the flash kernel, which refuses these short sequences)."""
    from ray_tpu.models.transformer import TransformerConfig
    return TransformerConfig(vocab_size=97, d_model=256, n_heads=4,
                             n_kv_heads=2, n_layers=2, d_ff=128,
                             max_seq=128, attn_impl="reference",
                             dtype=dtype or jnp.float32, remat=False)


_PROMPTS = [[5, 9, 11], [3], [60, 2, 8, 40, 7], [1, 2]]


def _full_forward_tokens(params, cfg, prompt, n):
    from ray_tpu.models import transformer
    seq, want = list(prompt), []
    for _ in range(n):
        logits = transformer.forward(
            params, np.asarray([seq], np.int32), cfg)
        want.append(int(np.argmax(np.asarray(logits[0, -1],
                                             np.float32))))
        seq.append(want[-1])
    return want


@pytest.fixture
def exact_f32_matmuls():
    """One matmul precision for an engine's steps and its oracle: at the
    TPU's default a f32 dot is a single bf16 pass, and two differently
    shaped programs then disagree in the last bits.  Set process-wide —
    the engines trace their steps on their own threads."""
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", None)


def test_engine_decode_matches_full_forward_on_tpu(exact_f32_matmuls):
    """The continuous-batching engine (pipelined dispatches, async
    device->host copies, the compiled paged kernel and its block
    tables) decodes EXACTLY what repeated full forward passes produce —
    on the real chip, where dispatch/copy overlap is real concurrency,
    not interpreter sequencing."""
    from ray_tpu.models import transformer
    from ray_tpu.serve.llm import PagedBatcher

    cfg = _tiny_cfg()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    bat = PagedBatcher(params, cfg, num_slots=4, max_len=64,
                       prompt_pad=16, decode_chunk=4, pipeline_depth=3,
                       kv_block_size=8)
    try:
        reqs = [bat.submit(p, max_new=8) for p in _PROMPTS]
        for r in reqs:
            assert r.done.wait(300), "engine stalled on TPU"
            assert r.error is None, r.error
    finally:
        bat.stop()
    for prompt, req in zip(_PROMPTS, reqs):
        want = _full_forward_tokens(params, cfg, prompt, 8)
        assert req.tokens == want, (prompt, req.tokens, want)


def test_compiled_train_step_on_tpu():
    """CompiledTrainStep on real HBM: loss decreases over steps, state
    donation doesn't corrupt, metrics are finite bf16-safe numbers."""
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.train.train_step import CompiledTrainStep

    cfg = _tiny_cfg(dtype=jnp.bfloat16)
    mesh = make_mesh(MeshSpec(), devices=jax.devices()[:1])
    step = CompiledTrainStep(cfg, mesh)
    state = step.init_state(seed=0)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab_size, (4, 65)).astype(np.int32)
    losses = []
    for _ in range(40):
        state, metrics = step(state, step.shard_batch(tokens))
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)), losses
    # Same batch every step: the model must be memorizing it (the lr
    # schedule warms up, so early deltas are tiny — measured 0.40 over
    # 40 steps in fp32; bf16 on-chip tracks within noise).
    assert losses[-1] < losses[0] - 0.2, losses


def test_iter_device_batches_prefetch_on_tpu():
    """Data's host->HBM pipeline lands jax Arrays ON THE TPU with the
    right shapes/values, with prefetch in flight."""
    import ray_tpu
    from ray_tpu import data as rdata

    # This process holds the chip, so the node must not advertise it:
    # no worker could ever lease it.
    ray_tpu.init(num_cpus=2, num_tpus=0, ignore_reinit_error=True)
    try:
        n = 64
        ds = rdata.from_numpy(
            {"x": np.arange(n * 8, dtype=np.float32).reshape(n, 8),
             "y": np.arange(n, dtype=np.int32)},
            block_rows=16)
        seen = 0
        for batch in ds.iter_device_batches(batch_size=16,
                                            prefetch=2):
            assert isinstance(batch["x"], jax.Array)
            assert batch["x"].devices() == {jax.devices()[0]}
            assert batch["x"].shape == (16, 8)
            row0 = int(np.asarray(batch["y"])[0])
            np.testing.assert_array_equal(
                np.asarray(batch["x"][0]),
                np.arange(row0 * 8, row0 * 8 + 8, dtype=np.float32))
            seen += 1
        assert seen == 4
    finally:
        ray_tpu.shutdown()


def test_engine_streaming_on_tpu():
    """Streaming consumer receives tokens incrementally while the
    pipelined engine keeps dispatching (SSE data-plane path)."""
    from ray_tpu.models import transformer
    from ray_tpu.serve.llm import PagedBatcher

    cfg = _tiny_cfg()
    params = transformer.init_params(cfg, jax.random.PRNGKey(1))
    bat = PagedBatcher(params, cfg, num_slots=2, max_len=64,
                       prompt_pad=16, decode_chunk=4,
                       pipeline_depth=2, kv_block_size=8)
    try:
        toks = list(bat.generate_stream([7, 8, 9], max_new=12))
        assert len(toks) == 12
        out = bat.generate([7, 8, 9], max_new=12)
        assert out["tokens"] == toks     # stream == non-stream
    finally:
        bat.stop()


def test_step_gradients_against_float32_parameter_form_on_tpu():
    """`train-4k-1chip`'s own step (benchmarks/configs/mistral-7b-l4.json,
    batch 4 x 4,097): the gradient as `CompiledTrainStep` takes it (product
    weights differentiated in bf16, widened after) beside the form that
    differentiates with respect to the float32 parameters, each reduced
    INSIDE its program to a leaf's sum of squares and a wrapping uint32 sum
    of its bits.  tests/test_train_step_grads.py holds the two equal bit
    for bit on the CPU; the chip's two programs fuse the work around the
    products differently and agree to the size of their own rounding
    (PERF.md §6, PR 53): same loss, every leaf's norm within 1e-4.  Prints
    the leaves whose bits differ and both programs' seconds."""
    import json
    import os
    import time

    from benchmarks.lib import spec, worker_util
    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.parallel.sharding import use_mesh
    from ray_tpu.train.train_step import CompiledTrainStep, make_optimizer

    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "mistral-7b-l4.json")) as f:
        mc = json.load(f)
    tr, seq = mc["train"], 4096
    cfg = tfm.TransformerConfig(**worker_util.with_dtypes(
        spec.model_kind(mc["kind"]).transformer_kwargs(
            mc, max_seq=seq, param_dtype=tr["param_dtype"], remat=True,
            remat_policy=tr["remat_policy"], xent_chunk=tr["xent_chunk"],
            attn_block_k=tr["attn_block_k"], attn_impl="flash")))
    mesh = make_mesh(MeshSpec(), devices=jax.devices()[:1])
    step = CompiledTrainStep(cfg, mesh, optimizer=make_optimizer(
        total_steps=10_000, kind=tr["optimizer"]))
    params = step.init_state(seed=31).params
    tokens = step.shard_batch(np.random.default_rng(31).integers(
        0, cfg.vocab_size, (tr["batch_per_chip"], seq + 1), dtype=np.int32))

    def reduced(loss, grads):
        return loss, jax.tree.map(lambda g: (
            jnp.sum(jnp.square(g.astype(jnp.float32))),
            jnp.sum(jax.lax.bitcast_convert_type(g.astype(jnp.float32),
                                                 jnp.uint32),
                    dtype=jnp.uint32)), grads)

    def own(params, tokens):
        with use_mesh(mesh):
            metrics, grads = step.metrics_and_grads(params, tokens)
            return reduced(metrics["loss"], grads)

    def float32_form(params, tokens):
        with use_mesh(mesh):
            (loss, _), grads = jax.value_and_grad(
                lambda p: tfm.loss_fn(p, tokens, cfg, mesh),
                has_aux=True)(params)
            return reduced(loss, grads)

    got = {}
    for name, fn in (("own", own), ("float32", float32_form)):
        t0 = time.perf_counter()
        loss, leaves = jax.block_until_ready(jax.jit(fn)(params, tokens))
        got[name] = (float(loss), {
            jax.tree_util.keystr(path): (float(sq), int(bits))
            for path, (sq, bits) in jax.tree_util.tree_leaves_with_path(
                leaves, is_leaf=lambda x: isinstance(x, tuple))})
        print(f"{name}: loss {float(loss).hex()}, compile + run "
              f"{time.perf_counter() - t0:.1f} s")
    (loss, mine), (want_loss, theirs) = got["own"], got["float32"]
    assert mine.keys() == theirs.keys() and len(mine) == 12
    print("leaves whose bits differ:",
          sorted(k for k in mine if mine[k][1] != theirs[k][1]))
    assert abs(loss - want_loss) <= 1e-6 * abs(want_loss), (loss, want_loss)
    off = {k: abs(mine[k][0] - theirs[k][0]) / theirs[k][0] for k in mine}
    print("sum of squares, relative difference:",
          {k: float(f"{v:.2g}") for k, v in off.items()})
    assert all(mine[k][0] > 0 for k in mine)
    assert max(off.values()) <= 2e-4, off


def test_chunked_loss_gradient_against_checkpointed_form_on_tpu():
    """The chunked loss at `train-4k-1chip`'s size (4 x 4,096 tokens of
    4,096, a head of 32,000, blocks of 2,048, bf16 operands): since PR 55
    its gradients are made in the trip that has the logits
    (`fused_cross_entropy`'s `custom_vjp`); beside it the form it replaced,
    the same trip under `jax.checkpoint` differentiated by autodiff, kept
    HERE as the reference.  Same loss, `dx` and the head's gradient: sums
    of squares within 2e-4 (this file's limit) and every entry within 3e-2
    of the largest (the head's gradient is a bf16 carry that the backward
    scan summed over the trips last to first and this one first to last),
    with a cotangent of 1 and of 3.  The benchmark's `correct` does not look at the loss's gradient
    (benchmarks/lib/train_cell.py: `flash_err`, the first loss against
    ln(vocab), finiteness), so this is what holds it on the chip.  Prints
    the readings and both programs' seconds a call."""
    import dataclasses
    import time

    from ray_tpu.models import transformer as tfm

    B, S, D, V, chunk = 4, 4096, 4096, 32000, 2048
    cfg = dataclasses.replace(tfm.PRESETS["llama-1b"], d_model=D,
                              vocab_size=V, xent_chunk=chunk,
                              dtype=jnp.bfloat16)
    kx, kw, kt = jax.random.split(jax.random.PRNGKey(55), 3)
    x = jax.random.normal(kx, (B, S, D), jnp.bfloat16)
    w = 0.02 * jax.random.normal(kw, (D, V), jnp.float32)
    targets = jax.random.randint(kt, (B, S), 0, V, jnp.int32)

    def checkpointed(x, w, targets):
        xb, tb = x.reshape(-1, chunk, D), targets.reshape(-1, chunk)
        wd = w.astype(cfg.dtype)

        def body(carry, inp):
            logits = jnp.einsum("cd,dv->cv", inp[0], wd,
                                preferred_element_type=jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, inp[1][:, None], axis=1)[:, 0]
            return carry + jnp.sum(lse - tgt), None

        total, _ = jax.lax.scan(jax.checkpoint(body),
                                jnp.zeros((), jnp.float32), (xb, tb))
        return total / tb.size

    def own(x, w, targets):
        return tfm.fused_cross_entropy(x, w, targets, cfg)

    def reduced(loss, scale):
        def f(x, w, targets):
            value, (gx, gw) = jax.value_and_grad(
                lambda x, w: scale * loss(x, w, targets), argnums=(0, 1))(x, w)
            return value / scale, gx, gw
        return jax.jit(f)

    for scale in (1.0, 3.0):
        got = {}
        for name, loss in (("own", own), ("checkpointed", checkpointed)):
            fn = reduced(loss, scale)
            jax.block_until_ready(fn(x, w, targets))
            t0 = time.perf_counter()
            for _ in range(5):
                out = jax.block_until_ready(fn(x, w, targets))
            got[name] = out
            print(f"cotangent {scale}: {name}: loss {float(out[0]).hex()}, "
                  f"{(time.perf_counter() - t0) / 5 * 1e3:.2f} ms a call")
        (loss, gx, gw), (want, want_gx, want_gw) = (
            got["own"], got["checkpointed"])
        assert abs(float(loss) - float(want)) <= 1e-6 * abs(float(want))
        for name, g, wg in (("dx", gx, want_gx), ("head", gw, want_gw)):
            g, wg = g.astype(jnp.float32), wg.astype(jnp.float32)
            sq, want_sq = float(jnp.sum(g * g)), float(jnp.sum(wg * wg))
            apart = float(jnp.max(jnp.abs(g - wg)) / jnp.max(jnp.abs(wg)))
            print(f"cotangent {scale}: {name}: sum of squares {sq:.6e} "
                  f"against {want_sq:.6e}, largest difference {apart:.2g} "
                  f"of the largest entry, "
                  f"{int(jnp.sum(g != wg))} of {g.size} entries differ")
            assert want_sq > 0 and abs(sq - want_sq) <= 2e-4 * want_sq
            assert apart <= 3e-2    # bf16: tests/test_xent_sharding.py's
