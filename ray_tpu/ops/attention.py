"""Fused attention: pallas TPU flash-attention kernels + reference impl.

The reference framework has NO attention kernels (it orchestrates external
libs; SURVEY.md §2.3 — sequence parallel/ring attention absent).  This is
new TPU-first capability: a blocked online-softmax attention (forward and
backward as pallas kernels, custom VJP) designed around the MXU (128-lane
tiles, f32 accumulation, bf16 inputs) and VMEM residency of one tile at a
time.

Kernel orientation: scores are computed TRANSPOSED, s_T = k @ q^T of shape
(block_k, block_q), so that all per-query statistics (running max m,
normalizer l, logsumexp, delta) are lane-aligned row vectors (1, block_q)
— TPU vectors must keep the 128-wide lane dim last, and this layout makes
every softmax/rescale a broadcast along sublanes with zero in-kernel
transposes.  The attention output accumulates as (head_dim, block_q) and
is swapped back to [.., S, D] once, outside the kernel, by XLA.

GQA is expressed in the kv BlockSpec index_map (kv head = q head //
group): grouped q heads read the same kv tiles, nothing is materialized.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# 512-tile blocks: measured on v5e (B=8,H=12,S=1024,D=64, causal), the
# 12-layer fwd+bwd attention stack drops from 111ms (128x128 grid of 6144
# tiny programs, overhead-bound) to 52ms — identical to the stock
# jax.experimental pallas flash kernel at the same block sizes.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Reference implementation (works everywhere; the numerics oracle)
# ---------------------------------------------------------------------------
def attention_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True,
                        scale: Optional[float] = None) -> jax.Array:
    """q: [B, Hq, Sq, D]; k, v: [B, Hkv, Sk, D]; GQA when Hq > Hkv."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    group = hq // hkv
    qg = q.reshape(b, hkv, group, sq, d)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, sq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernels (transposed orientation — see module docstring)
# ---------------------------------------------------------------------------
def _causal_mask_T(qi, ki, block_q, block_k, offset):
    """mask_T[j, i] = query (qi*bq + i) may attend key (ki*bk + j).

    `offset` = sk - sq aligns the causal triangle bottom-right (the
    reference oracle's tril(k=sk-sq) convention) so cross-length causal
    attention (prefill with cache, sq < sk) is correct."""
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 0)
    qpos = offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 1)
    return qpos >= kpos


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal,
                block_q, block_k, offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = (ki * block_k <= qi * block_q + block_q - 1 + offset) \
        if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0]                               # (bq, D)
        k = k_ref[0]                               # (bk, D)
        s_T = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bk, bq)
        if causal:
            s_T = jnp.where(
                _causal_mask_T(qi, ki, block_q, block_k, offset),
                s_T, NEG_INF)
        m_prev = m_ref[...]                        # (8, bq), rows equal
        l_prev = l_ref[...]
        m_cur = jnp.max(s_T, axis=0, keepdims=True)   # (1, bq)
        m_new = jnp.maximum(m_prev, m_cur)            # (8, bq)
        alpha = jnp.exp(m_prev - m_new)
        p_T = jnp.exp(s_T - m_new[0:1])               # (bk, bq)
        l_ref[...] = alpha * l_prev + jnp.sum(p_T, axis=0, keepdims=True)
        m_ref[...] = m_new
        v_blk = v_ref[0]                           # (bk, D)
        # acc_T (D, bq) += v^T @ p_T
        acc_ref[...] = acc_ref[...] * alpha[0:1] + jax.lax.dot_general(
            v_blk, p_T.astype(v_blk.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        ki_last = jnp.clip(
            (qi * block_q + block_q - 1 + offset) // block_k, 0, nk - 1)
    else:
        ki_last = nk - 1

    @pl.when(ki == ki_last)
    def _finish():
        l = l_ref[...][0:1]                        # (1, bq)
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)   # (D, bq)
        lse_ref[0] = (m_ref[...][0:1] + jnp.log(l))          # (1, bq)


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                     block_q, block_k, offset):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    if causal:
        # First query block that can see this key block (offset-aligned);
        # clipped so _init always fires even for key blocks nobody sees
        # (their accumulators must be written as zeros, not stale VMEM).
        qi_first = jnp.clip((ki * block_k - offset) // block_q, 0, nq - 1)
        run = qi * block_q + block_q - 1 + offset >= ki * block_k
    else:
        qi_first = 0
        run = True

    @pl.when(qi == qi_first)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]                              # (bq, D)
        lse = lse_ref[0][0:1]                       # (1, bq)
        delta = delta_ref[0][0:1]                   # (1, bq)
        s_T = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bk, bq)
        if causal:
            s_T = jnp.where(
                _causal_mask_T(qi, ki, block_q, block_k, offset),
                s_T, NEG_INF)
        p_T = jnp.exp(s_T - lse)                    # (bk, bq)
        # dv (bk, D) += p_T @ do
        dv_acc[...] += jax.lax.dot_general(
            p_T.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dp_T (bk, bq) = v @ do^T
        dp_T = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds_T = p_T * (dp_T - delta) * scale
        # dk (bk, D) += ds_T @ q
        dk_acc[...] += jax.lax.dot_general(
            ds_T.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, scale, causal, block_q, block_k,
                   offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    if causal:
        run = ki * block_k <= qi * block_q + block_q - 1 + offset
        ki_last = jnp.clip(
            (qi * block_q + block_q - 1 + offset) // block_k, 0, nk - 1)
    else:
        run = True
        ki_last = nk - 1

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][0:1]
        delta = delta_ref[0][0:1]
        s_T = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s_T = jnp.where(
                _causal_mask_T(qi, ki, block_q, block_k, offset),
                s_T, NEG_INF)
        p_T = jnp.exp(s_T - lse)
        dp_T = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds_T = p_T * (dp_T - delta) * scale
        # dq (bq, D) += ds_T^T @ k  (contract the bk dim of both)
        dq_acc[...] += jax.lax.dot_general(
            ds_T.astype(k.dtype), k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == ki_last)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------
pl = None
pltpu = None


def _ensure_pallas():
    global pl, pltpu
    if pl is None:
        from jax.experimental import pallas as _pl
        from jax.experimental.pallas import tpu as _pltpu
        pl = _pl
        pltpu = _pltpu


def compiled_on_tpu(fn, *args, gather=None):
    """`fn(*args, interpret=...)`: the compiled Mosaic kernel where the
    program is lowered for a TPU, the Pallas interpreter on any other
    platform.  Chosen per lowering rather than from the process's
    default backend, so an ahead-of-time compile for a TPU topology
    from a CPU host builds the real kernel, and a program lowered for
    the CPU never asks Mosaic for one.

    `platform_dependent` traces every branch, lowered or not, and a
    kernel's body is the costliest thing a serving program traces (~0.2-
    0.4 s a shape on a chip's host, forty of them in the agents cell's
    warm start, PERF.md PR 41).  A caller with a plain-JAX form of the
    same result passes it as `gather`: in a process whose own backend is
    a TPU it is the other platforms' branch, so the body is traced once
    and not a second time for an interpreter that host never runs."""
    other = functools.partial(fn, interpret=True)
    if gather is not None and jax.default_backend() == "tpu":
        other = gather
    return jax.lax.platform_dependent(
        *args, tpu=functools.partial(fn, interpret=False), default=other)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, group):
    return compiled_on_tpu(
        functools.partial(_flash_fwd_call, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, group=group),
        q, k, v)


def _flash_fwd_call(q, k, v, *, scale, causal, block_q, block_k, group,
                    interpret):
    _ensure_pallas()
    bh, sq, d = q.shape
    sk = k.shape[1]
    offset = sk - sq
    nq, nk = sq // block_q, sk // block_k
    grid = (bh, nq, nk)

    def kv_index(b, qi, ki):
        return (b // group, ki, 0)

    o_t, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, offset=offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, d, block_q), lambda b, qi, ki: (b, 0, qi)),
            pl.BlockSpec((1, 1, block_q), lambda b, qi, ki: (b, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, d, sq), q.dtype),      # transposed
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),  # lse
        ],
        scratch_shapes=[
            pltpu.VMEM((d, block_q), jnp.float32),
            pltpu.VMEM((8, block_q), jnp.float32),
            pltpu.VMEM((8, block_q), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return jnp.swapaxes(o_t, 1, 2), lse


def _flash_bwd(q, k, v, o, lse, do, dlse, scale, causal, block_q, block_k,
               group):
    """Shared backward. dlse folds into the delta row constant:
    ds = p * (dp - delta + dlse)  (d lse_i / d s_ij = p_ij)."""
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]  # (bh, 1, sq)
    if dlse is not None:
        delta = delta - dlse
    return compiled_on_tpu(
        functools.partial(_flash_bwd_call, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, group=group),
        q, k, v, do, lse, delta)


def _flash_bwd_call(q, k, v, do, lse, delta, *, scale, causal, block_q,
                    block_k, group, interpret):
    _ensure_pallas()
    bh, sq, d = q.shape
    bhkv, sk = k.shape[0], k.shape[1]
    offset = sk - sq
    nq, nk = sq // block_q, sk // block_k

    def kv_index_kq(b, ki, qi):
        return (b // group, ki, 0)

    # For group > 1 each q head produces its own dk/dv slice (adjacent
    # programs may not accumulate into one output block), reduced after.
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, offset=offset),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_index_kq),
            pl.BlockSpec((1, block_k, d), kv_index_kq),
            pl.BlockSpec((1, block_q, d), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, ki, qi: (b, 0, qi)),
            pl.BlockSpec((1, 1, block_q), lambda b, ki, qi: (b, 0, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    if group > 1:
        dk = dk.reshape(bhkv, group, sk, d).sum(axis=1)
        dv = dv.reshape(bhkv, group, sk, d).sum(axis=1)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, offset=offset),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, qi, ki: (b // group, ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, qi, ki: (b // group, ki, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, qi, ki: (b, 0, qi)),
            pl.BlockSpec((1, 1, block_q), lambda b, qi, ki: (b, 0, qi)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_flat(q, k, v, scale, causal, block_q, block_k):
    group = q.shape[0] // k.shape[0]
    o, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k, group)
    return o


def _flash_flat_fwd(q, k, v, scale, causal, block_q, block_k):
    group = q.shape[0] // k.shape[0]
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k, group)
    # Tag the kernel outputs as remat-saveable where the residuals are
    # actually built: under jax.checkpoint with a save_only_these_names
    # policy, tagging AFTER the custom-vjp call would save a copy while
    # the bwd still consumed the untagged residual — re-running the whole
    # forward kernel in the backward pass.
    o = checkpoint_name(o, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return o, (q, k, v, o, lse)


def _flash_flat_bwd(scale, causal, block_q, block_k, res, do):
    q, k, v, o, lse = res
    group = q.shape[0] // k.shape[0]
    return _flash_bwd(q, k, v, o, lse, do, None, scale, causal,
                      block_q, block_k, group)


_flash_flat.defvjp(_flash_flat_fwd, _flash_flat_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_flat_with_lse(q, k, v, scale, causal, block_q, block_k, tag):
    group = q.shape[0] // k.shape[0]
    return _flash_fwd(q, k, v, scale, causal, block_q, block_k, group)


def _flash_wl_fwd(q, k, v, scale, causal, block_q, block_k, tag):
    group = q.shape[0] // k.shape[0]
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k, group)
    if tag:
        # `tag=False` for per-step ring-attention partials: tagging those
        # would make the dots remat policy save every ring step's partial
        # o/lse (xR memory) instead of only the final combined output.
        o = checkpoint_name(o, "attn_out")
        lse = checkpoint_name(lse, "attn_lse")
    return (o, lse), (q, k, v, o, lse)


def _flash_wl_bwd(scale, causal, block_q, block_k, tag, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    group = q.shape[0] // k.shape[0]
    return _flash_bwd(q, k, v, o, lse, do, dlse, scale, causal,
                      block_q, block_k, group)


_flash_flat_with_lse.defvjp(_flash_wl_fwd, _flash_wl_bwd)


def _pick_block(s: int, b: int) -> int:
    """Largest block <= b that divides s (halving); s<=128 is one block."""
    b0, b = b, min(b, s)
    while s % b and b > 128:
        b //= 2
    if s % b:
        raise ValueError(
            f"flash_attention block size {b0} is incompatible with seq "
            f"length {s}: no halving of it >= 128 divides the length")
    return b


def _validate_flash(q, k, causal, block_q, block_k):
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    if (sq > 128 and sq % 128) or (sk > 128 and sk % 128):
        raise ValueError(
            f"flash_attention requires seq lengths divisible by the "
            f"128-lane tile: sq={sq}, sk={sk} "
            f"(pad inputs or use attention_reference)")
    if d % 64:
        raise ValueError(f"head_dim {d} must be a multiple of 64")
    if hq % hkv:
        raise ValueError(f"Hq={hq} must be a multiple of Hkv={hkv}")
    if causal and sq > sk:
        raise ValueError(
            "causal flash attention requires sq <= sk (rows with no "
            "visible keys are ill-defined); use attention_reference")


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K) -> jax.Array:
    """Pallas TPU flash attention. q: [B,Hq,Sq,D], k/v: [B,Hkv,Sk,D]."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    _validate_flash(q, k, causal, block_q, block_k)
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, sk, d)
    vf = v.reshape(b * hkv, sk, d)
    o = _flash_flat(qf, kf, vf, scale, causal, block_q, block_k)
    return o.reshape(b, hq, sq, d)


def flash_attention_with_lse(q, k, v, causal: bool = True,
                             scale: Optional[float] = None,
                             block_q: int = DEFAULT_BLOCK_Q,
                             block_k: int = DEFAULT_BLOCK_K,
                             save_residuals: bool = True):
    """Like flash_attention but also returns logsumexp [B,Hq,Sq] —
    differentiable in both outputs (the ring-attention building block).
    `save_residuals=False` skips remat-saveable tagging (ring partials)."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    _validate_flash(q, k, causal, block_q, block_k)
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, sk, d)
    vf = v.reshape(b * hkv, sk, d)
    o, lse = _flash_flat_with_lse(qf, kf, vf, scale, causal,
                                  block_q, block_k, save_residuals)
    return (o.reshape(b, hq, sq, d),
            lse.reshape(b, hq, sq))


def attention_reference_with_lse(q, k, v, causal: bool = True,
                                 scale: Optional[float] = None):
    """Reference (o, lse) pair; plain autodiff handles gradients."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    group = hq // hkv
    qg = q.reshape(b, hkv, group, sq, d)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32)
                   ) / l[..., None]
    lse = m + jnp.log(l)
    return (o.reshape(b, hq, sq, d).astype(q.dtype),
            lse.reshape(b, hq, sq))


def uses_flash(impl: str) -> bool:
    """Whether `impl` selects the Pallas kernel.  "auto" means the
    kernel on a TPU backend — where a shape it cannot take raises from
    `_validate_flash` with the shape and the reason — and the reference
    on any other backend."""
    if impl not in ("auto", "flash", "reference"):
        raise ValueError(f"unknown attention impl {impl!r}")
    return impl == "flash" or (impl == "auto"
                               and jax.default_backend() == "tpu")


def attention(q, k, v, causal: bool = True, scale: Optional[float] = None,
              impl: str = "auto",
              block_q: int = DEFAULT_BLOCK_Q,
              block_k: int = DEFAULT_BLOCK_K) -> jax.Array:
    """Dispatcher: pallas flash on TPU, the reference path elsewhere
    (see `uses_flash`); impl="reference" is for callers that mean it."""
    if uses_flash(impl):
        return flash_attention(q, k, v, causal, scale, block_q, block_k)
    return attention_reference(q, k, v, causal, scale)


def _tag_saveable(o, lse):
    o = checkpoint_name(o, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return o, lse


def attention_with_lse(q, k, v, causal: bool = True,
                       scale: Optional[float] = None, impl: str = "auto",
                       block_q: int = DEFAULT_BLOCK_Q,
                       block_k: int = DEFAULT_BLOCK_K):
    """(o, lse) dispatcher; outputs are tagged remat-saveable.

    The flash path tags INSIDE the custom-vjp fwd rule: under a
    save_only_these_names policy, tagging after the call would save a
    copy while the bwd still consumed the untagged residual — re-running
    the whole forward kernel in the backward pass just to regenerate lse.
    The reference path has no custom vjp, so tagging here suffices."""
    if uses_flash(impl):
        return flash_attention_with_lse(q, k, v, causal, scale,
                                        block_q, block_k)
    return _tag_saveable(*attention_reference_with_lse(
        q, k, v, causal, scale))
