"""Pipeline parallelism over the `pp` mesh axis, inside ONE program.

The reference pipelines by orchestrating stage processes and p2p NCCL
sends between them; the TPU-native design keeps the whole GPipe
schedule INSIDE one jitted SPMD program: `shard_map` over the `pp`
axis gives every device its stage's layer stack, microbatch activations
hop stages with `lax.ppermute` (ICI neighbor exchange), and — because
ppermute is differentiable (its transpose is the reverse permute) — the
backward pass is just jax.grad through the schedule: XLA derives the
reverse pipeline instead of a hand-written 1F1B runtime.

Scaling-book recipe; reference contrast: torch pipeline engines
(orchestrated-only per SURVEY §2.3) with explicit send/recv ops.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def split_stages(layer_params, num_stages: int):
    """Reshape stacked layer params [L, ...] -> [num_stages, L/ps, ...]
    so the leading axis shards over `pp`."""
    def r(x):
        L = x.shape[0]
        if L % num_stages:
            raise ValueError(f"{L} layers not divisible by "
                             f"{num_stages} stages")
        return x.reshape(num_stages, L // num_stages, *x.shape[1:])

    return jax.tree.map(r, layer_params)


def pipeline_apply(stage_params, x, mesh, layer_fn: Callable,
                   num_microbatches: int):
    """GPipe forward over the mesh's `pp` axis.

    stage_params: pytree with leading axes [num_stages, layers_per_stage,
    ...] (from split_stages).  x: [B, S, D] activations.  layer_fn(x, p)
    applies ONE layer.  Returns [B, S, D] after all layers.

    Differentiable end-to-end: wrap in jax.grad for pipelined training.
    """
    pp = mesh.shape.get("pp", 1)
    if pp == 1:
        def apply_all(x, sp):
            def scan_fn(h, p):
                return layer_fn(h, p), None
            flat = jax.tree.map(
                lambda a: a.reshape(-1, *a.shape[2:]), sp)
            h, _ = jax.lax.scan(scan_fn, x, flat)
            return h
        return apply_all(x, stage_params)

    B, S, D = x.shape
    M = num_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    mb = B // M
    x_mb = x.reshape(M, mb, S, D)

    # The microbatch's token dim shards over the data axes, so pp
    # composes with dp/fsdp instead of replicating the full batch
    # through every stage.
    data_axes = tuple(a for a in ("dp", "fsdp")
                      if mesh.shape.get(a, 1) > 1)
    xspec = P(None, data_axes if data_axes else None)

    def device_fn(sp, xm):
        # sp: this stage's layers [1, lps, ...]; xm: [M, mb/dp, S, D]
        sp = jax.tree.map(lambda a: a[0], sp)
        stage = jax.lax.axis_index("pp")
        mb_l = xm.shape[1]

        def apply_stage(h):
            def scan_fn(h, p):
                return layer_fn(h, p), None
            h, _ = jax.lax.scan(scan_fn, h, sp)
            return h

        state = jnp.zeros((mb_l, S, D), xm.dtype)
        outs = jnp.zeros((M, mb_l, S, D), xm.dtype)
        recv = state
        for t in range(M + pp - 1):
            # Stage 0 injects microbatch t (while any remain); others
            # consume what the previous stage just sent.
            inj = xm[min(t, M - 1)]
            state = apply_stage(jnp.where(stage == 0, inj, recv))
            # Collect finished microbatch t-(pp-1) from the last stage.
            oi = t - (pp - 1)
            if oi >= 0:
                outs = outs.at[oi].set(
                    jnp.where(stage == pp - 1, state, outs[oi]))
            recv = jax.lax.ppermute(
                state, "pp", [(i, i + 1) for i in range(pp - 1)])
        # Only the last stage holds real outputs: replicate via psum of
        # masked contributions.
        outs = jnp.where(stage == pp - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, "pp")
        return outs

    fn = shard_map(
        device_fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P("pp"), stage_params), xspec),
        out_specs=xspec,
        check_vma=False)
    out = fn(stage_params, x_mb)
    return out.reshape(B, S, D)


def pipeline_forward_hidden(params: Dict[str, Any], tokens, cfg, mesh,
                            num_microbatches: int = 4):
    """Transformer forward_hidden with the layer stack pipelined over
    `pp` (embedding + final norm replicated on all stages)."""
    from ray_tpu.models import transformer as tf

    B, S = tokens.shape
    x = params["tok_embed"][tokens].astype(cfg.dtype)
    # [1, S]: broadcasts against any microbatch size inside the stages.
    positions = jnp.arange(S, dtype=jnp.int32)[None]
    if cfg.arch == "gpt2":
        x = x + params["pos_embed"][:S][None].astype(cfg.dtype)

    pp = mesh.shape.get("pp", 1)
    stage_params = split_stages(params["layers"], pp)

    def layer_fn(h, p):
        h, _aux = tf._layer_body(cfg, None, h, p, positions)
        return h

    x = pipeline_apply(stage_params, x, mesh, layer_fn,
                       num_microbatches)
    rms = cfg.arch == "llama"
    return tf._norm(x, params["final_norm"],
                    params.get("final_norm_b"), cfg.norm_eps, rms)


def pipeline_loss_fn(params, tokens, cfg, mesh,
                     num_microbatches: int = 4):
    """Pipelined next-token loss; grads flow through the schedule."""
    from ray_tpu.models import transformer as tf
    targets = tokens[:, 1:]
    x = pipeline_forward_hidden(params, tokens[:, :-1], cfg, mesh,
                                num_microbatches)
    loss = tf.fused_cross_entropy(x, tf._w_out(params, cfg), targets,
                                  cfg)
    return loss
