"""Transformer model + sharded train step tests on the 8-device mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import transformer as tfm
from ray_tpu.parallel.mesh import MeshSpec, make_mesh
from ray_tpu.train.train_step import CompiledTrainStep, make_optimizer


def _tiny(arch="llama", **kw):
    base = tfm.PRESETS["tiny"]
    return tfm.TransformerConfig(**{
        **{f.name: getattr(base, f.name)
           for f in base.__dataclass_fields__.values()},
        "arch": arch, **kw})


@pytest.mark.parametrize("arch", ["llama", "gpt2"])
def test_forward_shapes_and_dtype(arch):
    cfg = _tiny(arch)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                cfg.vocab_size)
    logits = tfm.forward(params, tokens, cfg)
    assert logits.shape == (2, 64, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert np.all(np.isfinite(logits))


@pytest.mark.parametrize("arch", ["llama", "gpt2"])
def test_logical_axes_match_params(arch):
    cfg = _tiny(arch)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    axes = tfm.logical_axes(cfg)
    p_flat, p_tree = jax.tree.flatten(params)
    a_flat, a_tree = jax.tree.flatten(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    assert p_tree == a_tree, "axes tree must mirror params tree"
    for p, a in zip(p_flat, a_flat):
        assert p.ndim == len(a), f"rank mismatch: {p.shape} vs {a}"


def test_causality():
    """Changing a future token must not affect earlier logits."""
    cfg = _tiny("llama", remat=False)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0,
                                cfg.vocab_size)
    logits1 = tfm.forward(params, tokens, cfg)
    tokens2 = tokens.at[0, 20].set((tokens[0, 20] + 1) % cfg.vocab_size)
    logits2 = tfm.forward(params, tokens2, cfg)
    np.testing.assert_allclose(logits1[0, :20], logits2[0, :20],
                               atol=1e-4)
    assert not np.allclose(logits1[0, 20:], logits2[0, 20:])


def test_gqa_model():
    cfg = _tiny("llama", n_kv_heads=2)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    assert params["layers"]["wk"].shape == (cfg.n_layers, cfg.d_model, 2,
                                            cfg.head_dim)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0,
                                cfg.vocab_size)
    assert np.all(np.isfinite(tfm.forward(params, tokens, cfg)))


@pytest.mark.parametrize("mesh_spec", [
    MeshSpec(dp=8),                  # pure DP
    MeshSpec(fsdp=8),                # ZeRO-style
    MeshSpec(dp=2, fsdp=2, tp=2),    # 3D
    MeshSpec(fsdp=2, tp=4),
])
def test_train_step_converges(mesh_spec, cpu_mesh_devices):
    """Loss must drop when overfitting one batch — end-to-end through the
    sharded pjit step (fwd+bwd+adamw) on every mesh layout."""
    cfg = _tiny("llama", remat=False)
    mesh = make_mesh(mesh_spec)
    step = CompiledTrainStep(
        cfg, mesh, optimizer=make_optimizer(learning_rate=1e-2,
                                            warmup_steps=1,
                                            total_steps=100))
    state = step.init_state(seed=0)
    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(8, 65)).astype(np.int32)
    batch = step.shard_batch(tokens)
    first = None
    for _ in range(12):
        state, metrics = step(state, batch)
        if first is None:
            first = float(metrics["loss"])
    last = float(metrics["loss"])
    assert np.isfinite(last)
    assert last < first * 0.8, f"loss did not drop: {first} -> {last}"


def test_train_step_sp_mesh(cpu_mesh_devices):
    """Sequence-parallel training: ring attention inside the jitted step."""
    cfg = _tiny("llama", remat=False, max_seq=256)
    mesh = make_mesh(MeshSpec(dp=2, sp=4))
    step = CompiledTrainStep(
        cfg, mesh, optimizer=make_optimizer(learning_rate=1e-2,
                                            warmup_steps=1,
                                            total_steps=100))
    state = step.init_state(seed=0)
    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(4, 129)).astype(np.int32)
    batch = step.shard_batch(tokens)
    first = None
    for _ in range(10):
        state, metrics = step(state, batch)
        if first is None:
            first = float(metrics["loss"])
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["loss"]) < first


def test_dp_equals_single_device(cpu_mesh_devices):
    """The sharded step must be numerically equivalent to the unsharded
    one (GSPMD correctness check)."""
    cfg = _tiny("llama", remat=False)
    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(8, 33)).astype(np.int32)

    def run(mesh_spec, n_steps=3):
        if mesh_spec is None:
            mesh = make_mesh(MeshSpec(), devices=jax.devices()[:1])
        else:
            mesh = make_mesh(mesh_spec)
        step = CompiledTrainStep(
            cfg, mesh, optimizer=make_optimizer(learning_rate=1e-3,
                                                warmup_steps=1,
                                                total_steps=100),
            donate_state=False)
        state = step.init_state(seed=0)
        batch = step.shard_batch(tokens)
        losses = []
        for _ in range(n_steps):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        return losses

    single = run(None)                # 1x1 mesh on one device
    dp = run(MeshSpec(dp=8))
    tp = run(MeshSpec(fsdp=2, tp=2, dp=2))
    np.testing.assert_allclose(single, dp, rtol=2e-4)
    # The fsdp/tp leg reduces matmul partials in a different order
    # than the single-device program.  Computation is f32 throughout —
    # the tolerance, not the math, absorbs the difference.
    np.testing.assert_allclose(single, tp, rtol=1e-2)
