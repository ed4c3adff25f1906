"""Sharded training step: the compile-once pjit analog of the reference's
per-step Train loop.

Where the reference's TorchTrainer runs an eager torch loop with NCCL DDP
(train/torch/config.py:115 init_process_group) and stays out of the step
path (SURVEY.md §3.5), the TPU build compiles the ENTIRE step — forward,
backward, optimizer, metrics — into one XLA program over the mesh.  All
parallelism (dp / fsdp / tp / sp) is induced by the sharding rule table
(parallel/sharding.py); XLA inserts the psum/reduce-scatter/all-gather
collectives over ICI.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ray_tpu.models import transformer
from ray_tpu.ops import scopes
from ray_tpu.parallel.sharding import (DEFAULT_RULES, Rules, tree_specs,
                                       tree_shardings, use_mesh)


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt_state: Any


def make_optimizer(learning_rate: float = 3e-4, warmup_steps: int = 100,
                   total_steps: int = 10_000, weight_decay: float = 0.1,
                   b1: float = 0.9, b2: float = 0.95,
                   grad_clip: float = 1.0,
                   mu_dtype="bfloat16",
                   kind: str = "adamw") -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1))
    if kind == "adafactor":
        # Factored second moment, no first moment: ~4 bytes/param of
        # optimizer state vs AdamW's 10 (f32 master + bf16 mu + f32 nu).
        # The T5/PaLM-lineage TPU optimizer — what lets a ~1.2B-param
        # model train on one 16 GB v5e chip, where AdamW's 12.4 GB of
        # state alone would blow HBM.  Adafactor does its own
        # update-magnitude clipping; no global-norm clip in the chain.
        # NOTE: no weight decay here.  optax.adafactor applies
        # `weight_decay_rate` per step WITHOUT lr-scaling (a flat
        # multiplicative shrink), so the AdamW-style 0.1 would shrink
        # every weight 10%/step and destroy training; the classic
        # T5-lineage Adafactor recipe runs without decoupled decay.
        return optax.adafactor(learning_rate=schedule)
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        # bf16 first moment: halves mu's HBM traffic+footprint (~5% step
        # time on v5e, measured); the variance stays f32 — the standard
        # mixed-precision Adam recipe (e.g. T5X/MaxText defaults).
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay,
                    mu_dtype=mu_dtype),
    )


class CompiledTrainStep:
    """Holds the jitted step + sharded state constructors for one model.

    The step differentiates the loss with respect to the parameters as the
    model computes with them: the layers' product weights
    (`transformer.PRODUCT_WEIGHTS`) cast to `cfg.dtype` once, everything
    else (norms, embedding, head, biases, router) as it is kept.  A product
    weight's gradient leaves the MXU in `cfg.dtype` and nothing is added to
    it before the optimizer, so it stays in that precision through the
    backward scan and is widened to the parameter's dtype at the
    optimizer's input and at `grad_norm`: the values the cast's transpose
    gave, bit for bit, in half the bytes while the scan holds them (1.75 GiB
    a chip in the training cells, PERF.md PR 53)."""

    def __init__(self, cfg: transformer.TransformerConfig, mesh,
                 optimizer: Optional[optax.GradientTransformation] = None,
                 rules: Optional[Rules] = None,
                 donate_state: bool = True) -> None:
        self.cfg = cfg
        self.mesh = mesh
        self.rules = rules if rules is not None else DEFAULT_RULES
        self.optimizer = optimizer or make_optimizer()

        params_axes = transformer.logical_axes(cfg)
        self.param_shardings = tree_shardings(params_axes, mesh, self.rules)
        # Data: tokens [B, S+1] shard batch only — S+1 is odd-sized vs the
        # sp axis; activation constraints inside the model shard seq.
        from jax.sharding import NamedSharding
        from ray_tpu.parallel.sharding import spec_for
        self.data_sharding = NamedSharding(
            mesh, spec_for(("batch", None), self.rules, mesh))

        def init_fn(key):
            params = transformer.init_params(cfg, key)
            opt_state = self.optimizer.init(params)
            return TrainState(step=jnp.zeros((), jnp.int32),
                              params=params, opt_state=opt_state)

        # Resolve opt-state shardings from its structure (eval_shape).
        key = jax.random.PRNGKey(0)
        state_shape = jax.eval_shape(init_fn, key)
        self.state_shardings = self._state_shardings(state_shape,
                                                    params_axes)
        self._init = jax.jit(init_fn,
                             out_shardings=self.state_shardings)

        def step_fn(state: TrainState, tokens) -> Tuple[TrainState, Dict]:
            with use_mesh(mesh):
                metrics, grads = self.metrics_and_grads(state.params, tokens)
                with jax.named_scope(scopes.OPTIMIZER):
                    updates, new_opt = self.optimizer.update(
                        grads, state.opt_state, state.params)
                    new_params = optax.apply_updates(state.params, updates)
                    metrics = dict(metrics)
                    metrics["grad_norm"] = optax.global_norm(grads)
                return TrainState(state.step + 1, new_params,
                                  new_opt), metrics

        self._step = jax.jit(
            step_fn,
            in_shardings=(self.state_shardings, self.data_sharding),
            out_shardings=(self.state_shardings, None),
            donate_argnums=(0,) if donate_state else ())

    def metrics_and_grads(self, params, tokens) -> Tuple[Dict, Any]:
        """The loss's metrics and its gradient in `params`' own dtypes, as
        the step takes them (to be traced under `use_mesh(self.mesh)`)."""
        cfg = self.cfg
        grad_fn = jax.value_and_grad(
            lambda p: transformer.loss_fn(p, tokens, cfg, self.mesh),
            has_aux=True)
        (_, metrics), grads = grad_fn(
            transformer.with_product_weights_cast(params, cfg))
        # widened where the optimizer and `grad_norm` read them
        return metrics, jax.tree.map(lambda g, p: g.astype(p.dtype),
                                     grads, params)

    def _state_shardings(self, state_shape, params_axes):
        from jax.sharding import NamedSharding, PartitionSpec

        replicated = NamedSharding(self.mesh, PartitionSpec())
        params_shardings = self.param_shardings
        params_treedef = jax.tree.structure(state_shape.params)
        params_leaves = jax.tree.leaves(state_shape.params)

        def mirrors_params(node) -> bool:
            # Adam mu/nu mirror the params pytree exactly; match by
            # structure + leaf shapes (NOT by flat shape — two equal-shaped
            # params with different rule shardings would alias, ADVICE r1).
            try:
                if jax.tree.structure(node) != params_treedef:
                    return False
                leaves = jax.tree.leaves(node)
                return all(getattr(a, "shape", None) == b.shape
                           for a, b in zip(leaves, params_leaves))
            except Exception:
                return False

        def assign(node):
            if mirrors_params(node):
                return params_shardings
            if isinstance(node, tuple) and hasattr(node, "_fields"):
                return type(node)(*[assign(c) for c in node])
            if isinstance(node, (tuple, list)):
                return type(node)(assign(c) for c in node)
            if isinstance(node, dict):
                return {k: assign(v) for k, v in node.items()}
            return replicated  # scalar counts / schedule state

        return TrainState(
            step=replicated,
            params=params_shardings,
            opt_state=assign(state_shape.opt_state))

    # -- public API --------------------------------------------------------
    def init_state(self, seed: int = 0) -> TrainState:
        return self._init(jax.random.PRNGKey(seed))

    def _cache_size(self) -> int:
        """Compiled-variant count of the jitted step — telemetry's
        compile detector (train/telemetry.py device_step) watches
        this grow to classify a step as `compile` rather than `step`.
        Named like jax's own jit-cache accessor so CompiledTrainStep
        itself can be passed as a telemetry `jit_fns` entry."""
        try:
            return int(self._step._cache_size())
        except Exception:
            return -1

    def flops_per_token(self, seq: int,
                        n_params: Optional[int] = None) -> float:
        """Model FLOPs per trained token for this config (6N +
        attention; shared formula in train/telemetry.py)."""
        from ray_tpu.train.telemetry import transformer_flops_per_token
        if n_params is None:
            n_params = transformer.num_params(jax.eval_shape(
                lambda: transformer.init_params(
                    self.cfg, jax.random.PRNGKey(0))))
        return transformer_flops_per_token(
            n_params, self.cfg.n_layers, seq, self.cfg.d_model)

    def shard_batch(self, tokens) -> jax.Array:
        return jax.device_put(tokens, self.data_sharding)

    def __call__(self, state: TrainState, tokens
                 ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        return self._step(state, tokens)
