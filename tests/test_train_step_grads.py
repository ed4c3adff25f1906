"""`CompiledTrainStep` differentiates the loss with respect to the layers'
product weights in the compute dtype and widens their gradients where the
optimizer reads them (models/transformer.py `with_product_weights_cast`).
Held here: that is the step that differentiates with respect to the float32
parameters, bit for bit: gradients, `grad_norm`, the updated parameters and
the optimizer's state."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.parallel.mesh import MeshSpec, make_mesh
from ray_tpu.parallel.sharding import use_mesh
from ray_tpu.train.train_step import (CompiledTrainStep, TrainState,
                                       make_optimizer)

TINY = dataclasses.replace(tfm.PRESETS["tiny"], remat=True,
                           remat_policy="names", xent_chunk=64)
CONFIGS = {
    "llama": TINY,
    "gpt2": dataclasses.replace(TINY, arch="gpt2", rope_theta=0.0),
    "moe": dataclasses.replace(TINY, moe_experts=4, moe_top_k=2),
}


def _step_and_batch(arch, kind, chips, **overrides):
    cfg = dataclasses.replace(CONFIGS[arch], **overrides)
    mesh = make_mesh(MeshSpec(fsdp=chips), devices=jax.devices()[:chips])
    step = CompiledTrainStep(
        cfg, mesh, donate_state=False,
        # no warm-up: the first step already moves the parameters
        optimizer=make_optimizer(warmup_steps=0, total_steps=100, kind=kind))
    tokens = step.shard_batch(np.random.default_rng(7).integers(
        0, cfg.vocab_size, (4, 65), dtype=np.int32))
    return step, tokens


def _parents_form(step):
    """The step as it was before, line for line: the loss differentiated
    with respect to the parameters as they are kept -> (the jitted step,
    its gradient alone)."""
    cfg, mesh = step.cfg, step.mesh

    def grads_of(params, tokens):
        with use_mesh(mesh):
            return jax.value_and_grad(
                lambda p: tfm.loss_fn(p, tokens, cfg, mesh),
                has_aux=True)(params)

    def step_fn(state, tokens):
        with use_mesh(mesh):
            (loss, metrics), grads = grads_of(state.params, tokens)
            updates, new_opt = step.optimizer.update(
                grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
            metrics = dict(metrics)
            metrics["grad_norm"] = optax.global_norm(grads)
            return TrainState(state.step + 1, new_params, new_opt), metrics

    return jax.jit(
        step_fn, in_shardings=(step.state_shardings, step.data_sharding),
        out_shardings=(step.state_shardings, None)), jax.jit(
            lambda params, tokens: grads_of(params, tokens)[1],
            in_shardings=(step.param_shardings, step.data_sharding),
            out_shardings=step.param_shardings)


def _assert_same_bits(got, want, rtol=0.0):
    """Every leaf equal bit for bit, or (`rtol`) its floating leaves that
    near."""
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), b in zip(jax.tree.leaves_with_path(got),
                            jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        if rtol and jnp.issubdtype(a.dtype, jnp.floating):
            # (a bfloat16 moment may round the other way: one of its ulps)
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=max(rtol, float(jnp.finfo(a.dtype).eps)), atol=1e-9,
                err_msg=name)
        else:
            np.testing.assert_array_equal(
                np.atleast_1d(a).view(np.uint8),
                np.atleast_1d(b).view(np.uint8), err_msg=name)


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("kind", ["adafactor", "adamw"])
@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_step_is_the_float32_parameters_step_bit_for_bit(arch, kind, chips):
    step, tokens = _step_and_batch(arch, kind, chips)
    assert step.cfg.param_dtype == jnp.float32
    assert step.cfg.dtype == jnp.bfloat16
    state = step.init_state(seed=3)
    parents_step, parents_grads = _parents_form(step)

    def own_grads(params, tokens):
        with use_mesh(step.mesh):
            return step.metrics_and_grads(params, tokens)[1]

    grads = parents_grads(state.params, tokens)
    _assert_same_bits(jax.jit(
        own_grads, in_shardings=(step.param_shardings, step.data_sharding),
        out_shardings=step.param_shardings)(state.params, tokens), grads)
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree.leaves(grads["layers"]))
    # On one device everything the step returns is the parent's form's bit
    # for bit.  Under the mesh XLA:CPU lays the [L, d, h, k] stacks out
    # otherwise when they are bfloat16 and its fused square-and-sum of
    # `grad_norm` then adds the SAME terms (the gradients above) in another
    # order: `grad_norm` may differ in its last places, and with it what
    # `clip_by_global_norm` scales by (adamw).  Adafactor reads no norm.
    norm_rtol = 0.0 if chips == 1 else 5e-7
    clipped_rtol = 4 * norm_rtol if kind == "adamw" else 0.0
    # two steps: the second starts from the first's parameters and moments
    before = state.params
    for _ in range(2):
        want_state, want = parents_step(state, tokens)
        state, metrics = step(state, tokens)
        assert sorted(metrics) == sorted(want)
        norm, want_norm = metrics.pop("grad_norm"), want.pop("grad_norm")
        _assert_same_bits(norm, want_norm, rtol=norm_rtol)
        _assert_same_bits(metrics, want)
        _assert_same_bits(state, want_state, rtol=clipped_rtol)
        if clipped_rtol:
            state = want_state
    assert not np.array_equal(np.asarray(state.params["layers"]["wq"]),
                              np.asarray(before["layers"]["wq"]))


@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_the_model_says_which_leaves_are_cast(arch):
    """Exactly the leaves `_layer_body` / `_moe_block` cast whole before a
    product come back in the compute dtype; every other leaf IS the
    caller's."""
    cfg = CONFIGS[arch]
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    cast = tfm.with_product_weights_cast(params, cfg)
    assert jax.tree.structure(cast) == jax.tree.structure(params)
    for name, leaf in cast["layers"].items():
        if name in tfm.PRODUCT_WEIGHTS:
            assert leaf.dtype == cfg.dtype and leaf.ndim >= 3, name
        else:
            assert leaf is params["layers"][name], name
    assert all(cast[k] is v for k, v in params.items() if k != "layers")
    # every stacked matrix of a layer is one of them, but the router (read
    # in float32)
    matrices = {k for k, v in params["layers"].items() if v.ndim >= 3}
    assert matrices - {"w_router"} == set(tfm.PRODUCT_WEIGHTS) & matrices
    assert {"wq", "wk", "wv", "wo", "w_up", "w_down"} <= matrices


def _casts(jaxpr) -> int:
    """`convert_element_type` equations of a jaxpr and everything under it."""
    count = 0
    for eqn in jaxpr.eqns:
        count += eqn.primitive.name == "convert_element_type"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count += _casts(sub)
    return count


@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_parameters_in_the_compute_dtype_trace_no_added_cast(arch):
    """With bfloat16 parameters every product weight is passed through: the
    gradient's jaxpr holds as many casts as the parent's form's, and the
    helper returns the very leaves it was given."""
    step, tokens = _step_and_batch(arch, "adafactor", 1,
                                   param_dtype=jnp.bfloat16)
    cfg, mesh = step.cfg, step.mesh
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    cast = tfm.with_product_weights_cast(params, cfg)
    assert all(a is b for a, b in zip(jax.tree.leaves(cast),
                                      jax.tree.leaves(params)))

    def own(params, tokens):
        with use_mesh(mesh):
            return step.metrics_and_grads(params, tokens)[1]

    def parents(params, tokens):
        with use_mesh(mesh):
            return jax.grad(lambda p: tfm.loss_fn(p, tokens, cfg, mesh)[0])(
                params)

    assert _casts(jax.make_jaxpr(own)(params, tokens).jaxpr) == _casts(
        jax.make_jaxpr(parents)(params, tokens).jaxpr)
