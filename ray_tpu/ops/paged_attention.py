"""Ragged paged attention: decode-time attention over a paged KV cache.

The serving engine (serve/llm.py PagedBatcher) stores KV in fixed-size
blocks from a shared pool instead of one dense [B, M, ...] slab per
slot; each request owns a *block table* mapping its logical block index
to a physical pool block.  Blocks are refcount-shared, so requests with
a common prompt prefix attend the SAME physical prefix blocks (the
radix/prefix cache) — this kernel is what makes that sharing free at
decode time ("Ragged Paged Attention: A High-Performance and Flexible
LLM Inference Kernel for TPU", PAPERS.md).

Two implementations behind one dispatcher:

* `paged_attention_reference` — pure JAX (`jnp.take` gather through the
  block table + masked softmax), runs everywhere and is the numerics
  oracle the CPU tier-1 suite exercises.  Mathematically identical to
  the dense decode attention in models/decoding.py (_gqa_scores +
  length mask), just addressed through the table.
* `paged_attention_kernel` — a Pallas TPU kernel: online softmax
  accumulated block-by-block, with the block table passed as a
  SCALAR-PREFETCH argument so the kv BlockSpec index_map gathers
  physical blocks directly (no materialized [B, M] window in HBM).  The
  grid is (B, Hkv, W); blocks past a sequence's context length are
  skipped with `pl.when` — that is the "ragged" part: compute scales
  with the tokens actually cached, not with the table width.

Shapes (decode: ONE query token per sequence):
  q:            [B, H, D]
  k_pool/v_pool [NB, Hkv, bs, D]   (one layer's pool)
  block_tables  [B, W] int32        (physical block per logical block)
  context_lens  [B]    int32        (valid positions, INCLUSIVE of the
                                     token scattered this step)
  -> out        [B, H, D]

The pool keeps (bs, D) as its two minor dimensions because that is the
tile one kernel program loads: Mosaic requires the last two block
dimensions to be multiples of (8, 128) or the whole array dimension,
which a (1, D) slice of a [.., Hkv, D] pool is not.

Pool block 0 is reserved as a scratch/null block by the engine (table
padding and retired-slot writes are redirected there), so garbage reads
through padded table entries are always masked by context_lens.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import NEG_INF, compiled_on_tpu

# The query-group rows of one kv head are padded up to the f32 sublane
# tile so the (G, D) q block and its (G, bs) scores are whole tiles for
# MHA (G = 1) and narrow GQA alike.
_SUBLANES = 8


# ---------------------------------------------------------------------------
# Reference implementation (works everywhere; the numerics oracle)
# ---------------------------------------------------------------------------
def paged_attention_reference(q: jax.Array, k_pool: jax.Array,
                              v_pool: jax.Array, block_tables: jax.Array,
                              context_lens: jax.Array,
                              scale: Optional[float] = None) -> jax.Array:
    """Gather-based paged attention (the CPU/tier-1 path).

    Gathers each sequence's blocks into a [B, Hkv, W*bs, D] window with
    `jnp.take`, then runs exactly the dense decode attention math:
    f32 scores, -inf mask beyond context_lens, softmax, f32 weighted
    sum — so paged decode matches dense `decode_step` numerics.
    """
    B, H, D = q.shape
    hkv, bs = k_pool.shape[1], k_pool.shape[2]
    W = block_tables.shape[1]
    M = W * bs
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    def window(pool):       # [B, W, Hkv, bs, D] -> [B, Hkv, M, D]
        return jnp.take(pool, block_tables, axis=0).transpose(
            0, 2, 1, 3, 4).reshape(B, hkv, M, D)

    k, v = window(k_pool), window(v_pool)
    groups = H // hkv
    qg = q.reshape(B, hkv, groups, D)
    s = jnp.einsum("bhgk,bhmk->bhgm", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = (jnp.arange(M)[None, :] < context_lens[:, None]
            )[:, None, None, :]                              # [B,1,1,M]
    s = jnp.where(mask, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    # A zero-length row's softmax is all-NaN (every score -inf); the
    # kernel's l==0 guard returns zeros there — match it so both
    # impls stay interchangeable for padded/inactive rows.
    w = jnp.where(mask, w, 0.0)
    o = jnp.einsum("bhgm,bhmk->bhgk", w, v.astype(jnp.float32))
    return o.reshape(B, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------
def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, scale, block_size):
    """One (sequence, kv-head, logical-block) program.

    bt_ref/len_ref are scalar-prefetch refs (the block table routed the
    kv BlockSpecs here before the body ran); the body only masks and
    accumulates.  Scores are (G, bs) with the running max / normalizer
    as (G, 1) columns, so every rescale broadcasts along lanes and the
    body needs no transpose.  Math is f32 throughout, like the
    reference."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    w = pl.program_id(2)
    nw = pl.num_programs(2)
    ctx = len_ref[b]

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(w * block_size < ctx)            # ragged: skip dead blocks
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)                # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)                # (bs, D)
        v = v_ref[0, 0].astype(jnp.float32)                # (bs, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (G, bs)
        kpos = w * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < ctx, s, NEG_INF)
        m_prev = m_ref[...]                                # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                             # (G, bs)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1,
                                                  keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)      # (G, D)

    @pl.when(w == nw - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)       # zero-length row -> zeros
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_fwd(q, k_pool, v_pool, block_tables, context_lens, *, scale,
               interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    hkv, bs = k_pool.shape[1], k_pool.shape[2]
    W = block_tables.shape[1]
    groups = H // hkv
    gp = -(-groups // _SUBLANES) * _SUBLANES
    qg = q.reshape(B, hkv, groups, D)
    if gp != groups:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - groups), (0, 0)))

    # Scalar-prefetch index maps: grid indices first, then the
    # prefetched refs — the kv specs dereference the block table.
    def q_index(b, h, w, bt_ref, len_ref):
        return (b, h, 0, 0)

    def kv_index(b, h, w, bt_ref, len_ref):
        return (bt_ref[b, w], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, hkv, W),
        in_specs=[
            pl.BlockSpec((1, 1, gp, D), q_index),
            pl.BlockSpec((1, 1, bs, D), kv_index),
            pl.BlockSpec((1, 1, bs, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, gp, D), q_index),
        scratch_shapes=[
            pltpu.VMEM((gp, D), jnp.float32),
            pltpu.VMEM((gp, 1), jnp.float32),
            pltpu.VMEM((gp, 1), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, block_size=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, hkv, gp, D), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      qg, k_pool, v_pool)
    return o[:, :, :groups].reshape(B, H, D)


def _validate_paged(q, k_pool, v_pool):
    H, D = q.shape[1], q.shape[2]
    hkv, bs = k_pool.shape[1], k_pool.shape[2]
    if k_pool.shape != v_pool.shape or k_pool.shape[3] != D:
        raise ValueError(
            f"paged attention: pools must be [NB, Hkv, bs, {D}], got "
            f"k {k_pool.shape} v {v_pool.shape}")
    if H % hkv:
        raise ValueError(
            f"paged attention: H={H} must be a multiple of Hkv={hkv}")
    if bs % _SUBLANES:
        raise ValueError(
            f"paged attention kernel: kv block size {bs} must be a "
            f"multiple of {_SUBLANES} (the (bs, D) tile's sublane dim)")


def paged_attention_kernel(q, k_pool, v_pool, block_tables, context_lens,
                           scale: Optional[float] = None) -> jax.Array:
    """Pallas paged attention: the compiled kernel where the program is
    lowered for a TPU, the Pallas interpreter elsewhere (CPU parity
    tests)."""
    _validate_paged(q, k_pool, v_pool)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[2])
    return compiled_on_tpu(functools.partial(_paged_fwd, scale=scale),
                           q, k_pool, v_pool, block_tables, context_lens)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: jax.Array, context_lens: jax.Array,
                    scale: Optional[float] = None,
                    impl: str = "auto") -> jax.Array:
    """Dispatcher.  "auto" is the Pallas kernel on a TPU backend — a
    shape the kernel cannot take raises there, it never quietly becomes
    the gather — and the gather reference on any other backend.

    Decode has no backward pass, so there is no custom VJP — the
    reference path stays differentiable by construction if anyone ever
    scores with it.
    """
    if impl == "reference":
        return paged_attention_reference(q, k_pool, v_pool, block_tables,
                                         context_lens, scale)
    if impl == "kernel" or (impl == "auto"
                            and jax.default_backend() == "tpu"):
        return paged_attention_kernel(q, k_pool, v_pool, block_tables,
                                      context_lens, scale)
    if impl != "auto":
        raise ValueError(f"unknown paged attention impl {impl!r}")
    return paged_attention_reference(q, k_pool, v_pool, block_tables,
                                     context_lens, scale)
