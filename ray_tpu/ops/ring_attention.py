"""Ring attention: sequence/context parallelism over an ICI ring.

Not present in the reference (SURVEY.md §2.3: no ring attention, Ulysses
or context parallel anywhere in-tree) — this is new, first-class
capability.  Design (Liu et al. ring attention, blockwise formulation):

* The sequence axis is sharded over mesh axis `sp`; every device holds a
  [B, H, S/n, D] shard of q, k, v.
* Step 0 computes the diagonal block (local q vs local kv, causal mask).
  Then n-1 ring steps: rotate k/v to the next neighbor with
  `jax.lax.ppermute` (XLA lowers to ICI neighbor exchanges overlapped
  with compute) and attend the incoming shard.
* Each step produces a NORMALIZED partial (o_t, lse_t); partials merge
  with the logsumexp rule  lse = logaddexp(lse_a, lse_b),
  o = o_a·e^(lse_a-lse) + o_b·e^(lse_b-lse)  — numerics match exact
  attention.
* Causality across shards is static per step kind: the diagonal step
  runs the causal kernel; rotated steps run the non-causal kernel and a
  future shard's contribution is nullified by setting its lse to -inf
  (SPMD lockstep — every device executes the same program).

The per-step attention uses the pallas flash kernel (with lse output,
differentiable via its custom VJP) on a TPU backend and the einsum
reference path on any other.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import (NEG_INF, attention_reference_with_lse,
                                   flash_attention_with_lse)


def _partial_attn(q, k, v, scale, causal):
    """(o, lse) for one kv shard: the flash kernel on a TPU backend
    (an untileable shard shape raises there), the einsum reference on
    any other backend."""
    if jax.default_backend() == "tpu":
        # save_residuals=False: per-step partials must NOT be tagged
        # remat-saveable — the dots policy would save all R ring steps'
        # partial o/lse instead of only the final combined output.
        return flash_attention_with_lse(q, k, v, causal=causal,
                                        scale=scale, save_residuals=False)
    return attention_reference_with_lse(q, k, v, causal=causal,
                                        scale=scale)


def _merge(o_a, lse_a, o_b, lse_b):
    """Combine two normalized partial attentions (logsumexp weights)."""
    lse = jnp.maximum(lse_a, lse_b)
    # Guard -inf - -inf (a fully-masked pair) => weight 0.
    w_a = jnp.exp(jnp.where(lse_a == NEG_INF, NEG_INF, lse_a - lse))
    w_b = jnp.exp(jnp.where(lse_b == NEG_INF, NEG_INF, lse_b - lse))
    norm = w_a + w_b
    norm = jnp.where(norm == 0.0, 1.0, norm)
    o = (o_a.astype(jnp.float32) * w_a[..., None] +
         o_b.astype(jnp.float32) * w_b[..., None]) / norm[..., None]
    lse_out = lse + jnp.log(norm)
    return o.astype(o_a.dtype), lse_out


def _ring_attention_sharded(q, k, v, *, axis_name, causal, scale):
    """Per-shard body (runs inside shard_map)."""
    n = jax.lax.psum(1, axis_name)
    r = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # Step 0: diagonal block — statically causal.
    o_run, lse_run = _partial_attn(q, k, v, scale, causal=causal)
    o_run = o_run.astype(jnp.float32)

    def step(t, carry):
        o_run, lse_run, k_t, v_t = carry
        # Rotate first: after t rotations this device holds the shard
        # originating from rank (r - t) mod n.
        k_t = jax.lax.ppermute(k_t, axis_name, perm)
        v_t = jax.lax.ppermute(v_t, axis_name, perm)
        src = (r - t) % n
        o_p, lse_p = _partial_attn(q, k_t, v_t, scale, causal=False)
        if causal:
            # Future shard => nullify its contribution via lse = -inf.
            lse_p = jnp.where(src < r, lse_p, NEG_INF)
        o_new, lse_new = _merge(o_run, lse_run, o_p, lse_p)
        return o_new.astype(jnp.float32), lse_new, k_t, v_t

    if n > 1:
        o_run, lse_run, _, _ = jax.lax.fori_loop(
            1, n, step, (o_run, lse_run, k, v))
    return o_run.astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mesh, axis_name: str = "sp", causal: bool = True,
                   scale: Optional[float] = None) -> jax.Array:
    """Sequence-parallel attention over `axis_name` of `mesh`.

    q/k/v: [B, H, S, D] GLOBAL arrays whose S dim is (to be) sharded over
    `axis_name`.  Returns [B, H, S, D] sharded the same way.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    spec = P(None, None, axis_name, None)
    fn = shard_map(
        functools.partial(_ring_attention_sharded, axis_name=axis_name,
                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
