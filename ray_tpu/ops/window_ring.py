"""Sliding-window attention with a learned sink, its keys and values kept as
a RING a sequence.

A sliding layer attends to the last `W` positions only (key j is visible to
the query at i iff j <= i and i - j < W), so nothing older is worth holding:
a sequence keeps, per layer, ONE ring of W keys and W values, position p in
slot p mod W (keys carry their rotary embedding, so the order of the slots is
immaterial under a validity mask).  The rings live in pools indexed by a STATE
ID, id 0 scratch (models/decoding.py `PagedDecodeCaches.ring_k` / `ring_v`):
whatever the context, a sequence holds W positions a layer, a checkpoint is a
copy of its rings, and no page of the block pool is touched.

The softmax has one more column a query head, the SINK: a learned scalar b_h
that takes probability and adds no value,

    p_ij = exp(s_ij - m) / (exp(b_h - m) + sum_j exp(s_ij - m)),
    m = max(b_h, max_j s_ij);        o_i = sum_j p_ij v_j

so the running max starts at b_h, the normaliser at exp(b_h - m) and the
accumulator at 0.

Two entry points, each a Pallas kernel on a TPU and a plain `jax.numpy` form
anywhere (`impl=`, as ops/paged_attention.py's dispatchers):

`window_ring_step`   one position of each of B sequences (grid = slots): the
                     ring is read by its scalar-prefetched id, the new k, v
                     take slot p mod W, the query attends over the slots that
                     hold a position, and only the 16 slots around the new one
                     are written back (the pool is aliased in place).
`window_ring_chunk`  N rows of C consecutive positions, rows of one sequence
                     in order: the ring lives in VMEM from row to row, is
                     copied in from the pool at a sequence's first row
                     (`src`) and out where `dst` says, as
                     ops/gated_delta.py `gated_delta_chunk` does with S.  A
                     row starts on a multiple of C and C divides W, so a row's
                     positions are C slots in a row; a query sees the ring as
                     the rows before left it, under `pos > i - W`, and the
                     row's own keys causally.

THE POOLS' LAYOUT.  [ids, Hkv, W, lanes]: a head's keys of 192 values lie in
256 lanes (whole 128-lane rows, zeros past 192, as the paged pools'
`key_lanes`), its values of 128 in 128.  q comes padded likewise; the scale
is taken from the model's width.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import scopes
from ray_tpu.ops.attention import NEG_INF, compiled_on_tpu
from ray_tpu.ops.paged_attention import latent_lanes as lanes_of
from ray_tpu.ops.paged_attention import to_lanes as _to_lanes

_WRITE_SLOTS = 16       # slots a decode step writes back: a bf16 tile's rows
_VMEM_LIMIT = 64 * 1024 * 1024


def ring_shapes(num_states: int, kv_heads: int, window: int, dk: int,
                dv: int) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """The key and the value pool of one sliding layer, scratch id 0
    included."""
    return ((num_states + 1, kv_heads, window, lanes_of(dk)),
            (num_states + 1, kv_heads, window, lanes_of(dv)))


def _softmax_with_sink(s, seen, sink):
    """s [..., T] float32 scores, `seen` which of them count, sink [..., 1]
    -> (p [..., T], normaliser [..., 1]): the sink is one more column whose
    mass is dropped."""
    s = jnp.where(seen, s, NEG_INF)
    m = jnp.maximum(sink, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(seen, jnp.exp(s - m), 0.0)
    return p, jnp.exp(sink - m) + jnp.sum(p, axis=-1, keepdims=True)


def _held_before(start, window: int):
    """The position each of the ring's slots holds before position `start`
    is written: the largest p < start with p mod W == slot (negative: the
    slot was never written)."""
    slots = jnp.arange(window, dtype=jnp.int32)
    return start - 1 - (start - 1 - slots) % window


# ---------------------------------------------------------------------------
# decode: one position a sequence
# ---------------------------------------------------------------------------
def window_ring_step_reference(rk, rv, ids, positions, q, k, v, sink,
                               scale: Optional[float] = None):
    B, H, dk = q.shape
    _, hkv, W, _ = rk.shape
    dv, G = v.shape[-1], H // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    here = (jnp.arange(W)[None, :] == (positions % W)[:, None]
            )[:, None, :, None]                              # [B, 1, W, 1]
    K = jnp.where(here, _to_lanes(k, rk)[:, :, None].astype(rk.dtype),
                  rk[ids])
    V = jnp.where(here, _to_lanes(v, rv)[:, :, None].astype(rv.dtype),
                  rv[ids])
    qg = _to_lanes(q, rk).reshape(B, hkv, G, -1).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhwd->bhgw", qg, K.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST) * scale
    seen = (jnp.arange(W)[None, :] < jnp.minimum(positions + 1, W)[:, None]
            )[:, None, None, :]
    p, l = _softmax_with_sink(
        s, seen, sink.astype(jnp.float32).reshape(1, hkv, G, 1))
    o = jnp.einsum("bhgw,bhwd->bhgd", p, V.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST) / l
    return (o[..., :dv].reshape(B, H, dv).astype(q.dtype),
            rk.at[ids].set(K), rv.at[ids].set(V))


def _step_kernel(ids_ref, pos_ref, q_ref, k_ref, v_ref, sink_ref, rk_in,
                 rv_in, o_ref, rk_out, rv_out, *, scale: float, window: int,
                 group: int):
    from jax.experimental import pallas as pl
    del ids_ref
    b = pl.program_id(0)
    p = pos_ref[b]
    slot = lax.rem(p, window)
    at = lax.broadcasted_iota(jnp.int32, (1, window, 1), 1)
    here = lax.eq(at, slot)
    K = jnp.where(here, k_ref[0].astype(rk_in.dtype), rk_in[0])
    V = jnp.where(here, v_ref[0].astype(rv_in.dtype), rv_in[0])
    s = lax.dot_general(q_ref[0], K, (((2,), (2,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32) * scale
    seen = lax.lt(lax.broadcasted_iota(jnp.int32, s.shape, 2),
                  lax.min(lax.add(p, 1), window))
    s = jnp.where(seen, s, NEG_INF)
    sink = sink_ref[...]
    m = jnp.maximum(sink, jnp.max(s, axis=2, keepdims=True))
    e = jnp.exp(s - m)          # (a masked score: exp(NEG_INF - m) is 0)
    l = jnp.exp(sink - m) + jnp.sum(e, axis=2, keepdims=True)
    acc = lax.dot_general(e, V.astype(jnp.float32),
                          (((2,), (1,)), ((0,), (0,))),
                          preferred_element_type=jnp.float32)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # only the slots around the new one go back to the pool
    first = pl.multiple_of(lax.mul(lax.div(slot, group), group), group)
    near = lax.eq(lax.broadcasted_iota(jnp.int32, (1, group, 1), 1),
                  lax.sub(slot, first))
    rk_out[0] = jnp.where(near, k_ref[0].astype(rk_out.dtype),
                          rk_in[0, :, pl.ds(first, group), :])
    rv_out[0] = jnp.where(near, v_ref[0].astype(rv_out.dtype),
                          rv_in[0, :, pl.ds(first, group), :])


def _step_on_device(rk, rv, ids, positions, q, k, v, sink, *, scale,
                    interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, H, _ = q.shape
    _, hkv, W, lk = rk.shape
    lv, dv, G = rv.shape[3], v.shape[-1], H // hkv
    group = min(_WRITE_SLOTS, W)
    if W % group:
        raise ValueError(f"window ring: a window of {W} is not whole groups "
                         f"of {group} slots")
    Gp = -(-G // 8) * 8         # whole sublane tiles of query rows
    qg = _to_lanes(q, rk).reshape(B, hkv, G, lk).astype(rk.dtype)
    sk = sink.astype(jnp.float32).reshape(hkv, G, 1)
    if Gp != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
        sk = jnp.pad(sk, ((0, 0), (0, Gp - G), (0, 0)))

    def by_slot(b, *_):
        return (b, 0, 0, 0)

    def by_id(b, ids, pos):
        return (ids[b], 0, 0, 0)

    def written(b, ids, pos):
        return (ids[b], 0, (pos[b] % W) // group, 0)

    def new(x, pool):           # [B, Hkv, d] -> [B, Hkv, 1, lanes] float32
        return _to_lanes(x, pool).astype(jnp.float32)[:, :, None]

    o, rk, rv = pl.pallas_call(
        functools.partial(_step_kernel, scale=scale, window=W, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[pl.BlockSpec((1, hkv, Gp, lk), by_slot),
                      pl.BlockSpec((1, hkv, 1, lk), by_slot),
                      pl.BlockSpec((1, hkv, 1, lv), by_slot),
                      pl.BlockSpec((hkv, Gp, 1), lambda b, *_: (0, 0, 0)),
                      pl.BlockSpec((1, hkv, W, lk), by_id),
                      pl.BlockSpec((1, hkv, W, lv), by_id)],
            out_specs=[pl.BlockSpec((1, hkv, Gp, lv), by_slot),
                       pl.BlockSpec((1, hkv, group, lk), written),
                       pl.BlockSpec((1, hkv, group, lv), written)]),
        out_shape=[jax.ShapeDtypeStruct((B, hkv, Gp, lv), q.dtype),
                   jax.ShapeDtypeStruct(rk.shape, rk.dtype),
                   jax.ShapeDtypeStruct(rv.shape, rv.dtype)],
        input_output_aliases={6: 1, 7: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=scopes.WINDOW_RING_STEP,
    )(ids, positions, qg, new(k, rk), new(v, rv), sk, rk, rv)
    return o[:, :, :G, :dv].reshape(B, H, dv), rk, rv


def window_ring_step_kernel(rk, rv, ids, positions, q, k, v, sink, scale):
    return compiled_on_tpu(
        functools.partial(_step_on_device, scale=scale), rk, rv, ids,
        positions, q, k, v, sink,
        gather=functools.partial(window_ring_step_reference, scale=scale))


@functools.partial(jax.jit, static_argnames=("scale", "impl"))
def window_ring_step(rk, rv, ids, positions, q, k, v, sink,
                     scale: Optional[float] = None, impl: str = "auto"):
    """One position of B sequences.  rk [ids, Hkv, W, lanes(dk)], rv [ids,
    Hkv, W, lanes(dv)]; ids [B] (0: scratch: a sequence that is not live);
    positions [B] of the new token; q [B, H, dk], k [B, Hkv, dk], v [B,
    Hkv, dv]; sink [H] -> (o [B, H, dv], rk', rv'): the new k, v written at
    position mod W, the query over the min(position + 1, W) slots that hold
    a position."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    ids, positions = ids.astype(jnp.int32), positions.astype(jnp.int32)
    if _use_kernel(impl, "window ring step"):
        return window_ring_step_kernel(rk, rv, ids, positions, q, k, v, sink,
                                       scale)
    return window_ring_step_reference(rk, rv, ids, positions, q, k, v, sink,
                                      scale)


def _use_kernel(impl: str, what: str) -> bool:
    if impl == "kernel" or (impl == "auto"
                            and jax.default_backend() == "tpu"):
        return True
    if impl not in ("auto", "reference"):
        raise ValueError(f"unknown {what} impl {impl!r}")
    return False


# ---------------------------------------------------------------------------
# prefill: rows of C positions, rows of one sequence in order
# ---------------------------------------------------------------------------
def _check_rows(W: int, C: int) -> None:
    if W % C:
        raise ValueError(
            f"window ring: rows of {C} positions do not divide a window of "
            f"{W} (a row's positions must be slots in a row)")


def window_ring_chunk_reference(rk, rv, src, dst, starts, lives, q, k, v,
                                sink, scale: Optional[float] = None):
    """The same rows under a scan, the ring carried and copied by id."""
    N, C, H, dk = q.shape
    _, hkv, W, _ = rk.shape
    dv, G = v.shape[-1], H // hkv
    _check_rows(W, C)
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    b = sink.astype(jnp.float32).reshape(hkv, G, 1, 1)
    t = jnp.arange(C, dtype=jnp.int32)
    qg = _to_lanes(q, rk).reshape(N, C, hkv, G, -1)
    kl, vl = _to_lanes(k, rk).astype(rk.dtype), _to_lanes(v, rv).astype(
        rv.dtype)

    def row(carry, x):
        K, V, rk, rv = carry                    # K [Hkv, W, lanes]
        s_, d_, p0, n, qr, kr, vr = x
        K = jnp.where(s_ < 0, K, jnp.where(s_ > 0, rk[jnp.maximum(s_, 0)], 0))
        V = jnp.where(s_ < 0, V, jnp.where(s_ > 0, rv[jnp.maximum(s_, 0)], 0))
        kr, vr = jnp.swapaxes(kr, 0, 1), jnp.swapaxes(vr, 0, 1)  # [Hkv, C, .]
        held = _held_before(p0, W)
        seen = jnp.concatenate(
            [(held[None, :] > (p0 + t)[:, None] - W) & (held[None, :] >= 0),
             (t[None, :] <= t[:, None]) & (t[None, :] < n)], axis=1)
        keys = jnp.concatenate([K, kr], axis=1).astype(jnp.float32)
        vals = jnp.concatenate([V, vr], axis=1).astype(jnp.float32)
        s = jnp.einsum("chgd,htd->hgct", qr.astype(jnp.float32), keys,
                       precision=lax.Precision.HIGHEST) * scale
        p, l = _softmax_with_sink(s, seen[None, None], b)
        o = jnp.einsum("hgct,htd->chgd", p / l, vals,
                       precision=lax.Precision.HIGHEST)
        off = p0 % W
        live = (t < n)[None, :, None]
        K = lax.dynamic_update_slice_in_dim(
            K, jnp.where(live, kr, lax.dynamic_slice_in_dim(K, off, C, 1)),
            off, 1)
        V = lax.dynamic_update_slice_in_dim(
            V, jnp.where(live, vr, lax.dynamic_slice_in_dim(V, off, C, 1)),
            off, 1)
        rk = rk.at[d_[0]].set(K).at[d_[1]].set(K)
        rv = rv.at[d_[0]].set(V).at[d_[1]].set(V)
        return (K, V, rk, rv), o[..., :dv].reshape(C, H, dv)

    (_, _, rk, rv), o = lax.scan(
        row, (jnp.zeros(rk.shape[1:], rk.dtype),
              jnp.zeros(rv.shape[1:], rv.dtype), rk, rv),
        (src, dst, starts, lives, qg, kl, vl))
    return o.astype(q.dtype), rk, rv


def _chunk_kernel(src_ref, dst_ref, start_ref, live_ref, qoff_ref, q_ref,
                  k_ref, v_ref, sink_ref, rk_in, rv_in, o_ref, rk_out,
                  rv_out, k_s, v_s, sem, *, scale: float, window: int,
                  rows: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    del rk_in, rv_in            # aliased to the outputs: one buffer each
    n = pl.program_id(0)
    src = src_ref[n]

    def copies(a, b):
        """(k, v) copies a -> b, started together and waited for."""
        cps = [pltpu.make_async_copy(x, y, sem.at[i])
               for i, (x, y) in enumerate(zip(a, b))]
        for cp in cps:
            cp.start()
        for cp in cps:
            cp.wait()

    @pl.when(lax.eq(src, 0))
    def _():
        k_s[...] = jnp.zeros_like(k_s)
        v_s[...] = jnp.zeros_like(v_s)

    @pl.when(lax.gt(src, 0))
    def _():
        copies((rk_out.at[src], rv_out.at[src]), (k_s, v_s))

    p0, live = start_ref[n], live_ref[n]
    q = q_ref[0]                                # (Hkv, C * G, lanes)
    qoff = qoff_ref[...][None]                  # (1, C * G, 1): the query
    sink = sink_ref[...]

    def scores(keys):
        return lax.dot_general(q, keys, (((2,), (2,)), ((0,), (0,))),
                               preferred_element_type=jnp.float32) * scale

    s_old = scores(k_s[...])                    # (Hkv, C * G, W)
    slot = lax.broadcasted_iota(jnp.int32, (1, 1, window), 2)
    before = lax.sub(p0, 1)
    held = lax.sub(before, lax.rem(
        lax.add(lax.sub(before, slot), window), window))
    seen_old = lax.bitwise_and(
        lax.gt(held, lax.sub(lax.add(p0, qoff), window)), lax.ge(held, 0))
    s_old = jnp.where(seen_old, s_old, NEG_INF)
    k_new, v_new = k_ref[0], v_ref[0]           # (Hkv, C, lanes)
    s_new = scores(k_new)
    t = lax.broadcasted_iota(jnp.int32, (1, 1, rows), 2)
    seen_new = lax.bitwise_and(lax.le(t, qoff), lax.lt(t, live))
    s_new = jnp.where(seen_new, s_new, NEG_INF)
    m = jnp.maximum(sink, jnp.maximum(
        jnp.max(s_old, axis=2, keepdims=True),
        jnp.max(s_new, axis=2, keepdims=True)))
    e_old, e_new = jnp.exp(s_old - m), jnp.exp(s_new - m)
    l = (jnp.exp(sink - m) + jnp.sum(e_old, axis=2, keepdims=True)
         + jnp.sum(e_new, axis=2, keepdims=True))

    def weighed(e, vals):
        return lax.dot_general(e, vals.astype(jnp.float32),
                               (((2,), (1,)), ((0,), (0,))),
                               preferred_element_type=jnp.float32)

    o_ref[0] = ((weighed(e_old, v_s[...]) + weighed(e_new, v_new)) / l
                ).astype(o_ref.dtype)
    off = pl.multiple_of(lax.rem(p0, window), rows)
    keep = lax.lt(lax.broadcasted_iota(jnp.int32, (1, rows, 1), 1), live)
    k_s[:, pl.ds(off, rows), :] = jnp.where(
        keep, k_new, k_s[:, pl.ds(off, rows), :])
    v_s[:, pl.ds(off, rows), :] = jnp.where(
        keep, v_new, v_s[:, pl.ds(off, rows), :])
    for j in range(2):
        to = dst_ref[n, j]

        @pl.when(lax.gt(to, 0))
        def _():
            copies((k_s, v_s), (rk_out.at[to], rv_out.at[to]))


def _chunk_on_device(rk, rv, src, dst, starts, lives, q, k, v, sink, *,
                     scale, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    N, C, H, _ = q.shape
    _, hkv, W, lk = rk.shape
    lv, dv, G = rv.shape[3], v.shape[-1], H // hkv
    _check_rows(W, C)
    R = C * G
    # query-major rows: row r of a kv head is query r // G, head r % G
    qg = _to_lanes(q, rk).reshape(N, C, hkv, G, lk).transpose(
        0, 2, 1, 3, 4).reshape(N, hkv, R, lk).astype(rk.dtype)
    kl = jnp.swapaxes(_to_lanes(k, rk), 1, 2).astype(rk.dtype)
    vl = jnp.swapaxes(_to_lanes(v, rv), 1, 2).astype(rv.dtype)
    sk = jnp.tile(sink.astype(jnp.float32).reshape(hkv, 1, G), (1, C, 1)
                  ).reshape(hkv, R, 1)
    qoff = (jnp.arange(R, dtype=jnp.int32) // G)[:, None]

    def by_row(n, *_):
        return (n, 0, 0, 0)

    anywhere = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    o, rk, rv = pl.pallas_call(
        functools.partial(_chunk_kernel, scale=scale, window=W, rows=C),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(N,),
            in_specs=[pl.BlockSpec((R, 1), lambda n, *_: (0, 0)),
                      pl.BlockSpec((1, hkv, R, lk), by_row),
                      pl.BlockSpec((1, hkv, C, lk), by_row),
                      pl.BlockSpec((1, hkv, C, lv), by_row),
                      pl.BlockSpec((hkv, R, 1), lambda n, *_: (0, 0, 0)),
                      anywhere, anywhere],
            out_specs=[pl.BlockSpec((1, hkv, R, lv), by_row),
                       anywhere, anywhere],
            scratch_shapes=[pltpu.VMEM((hkv, W, lk), rk.dtype),
                            pltpu.VMEM((hkv, W, lv), rv.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((N, hkv, R, lv), q.dtype),
                   jax.ShapeDtypeStruct(rk.shape, rk.dtype),
                   jax.ShapeDtypeStruct(rv.shape, rv.dtype)],
        input_output_aliases={9: 1, 10: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=scopes.WINDOW_RING_CHUNK,
    )(src, dst, starts, lives, qoff, qg, kl, vl, sk, rk, rv)
    o = o.reshape(N, hkv, C, G, lv).transpose(0, 2, 1, 3, 4)
    return o.reshape(N, C, H, lv)[..., :dv], rk, rv


def window_ring_chunk_kernel(rk, rv, src, dst, starts, lives, q, k, v, sink,
                             scale):
    return compiled_on_tpu(
        functools.partial(_chunk_on_device, scale=scale), rk, rv, src, dst,
        starts, lives, q, k, v, sink,
        gather=functools.partial(window_ring_chunk_reference, scale=scale))


@functools.partial(jax.jit, static_argnames=("scale", "impl"))
def window_ring_chunk(rk, rv, src, dst, starts, lives, q, k, v, sink,
                      scale: Optional[float] = None, impl: str = "auto"):
    """N rows of C positions, in order.  rk, rv as `window_ring_step`; src
    [N]: the ring a row starts from: -1 the row before it (the same
    sequence's), 0 an empty one (a sequence's start), an id the pool's; dst
    [N, 2]: the ids the ring after the row is written to (0: nowhere);
    starts [N]: a row's first position, a multiple of C; lives [N]: its live
    positions (the others leave the ring as it is); q [N, C, H, dk], k [N, C,
    Hkv, dk], v [N, C, Hkv, dv]; sink [H] -> (o [N, C, H, dv], rk', rv').
    An id read as `src` is no row's `dst` unless it is the same sequence's
    (the engine's rule: serve/llm.py)."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    args = (rk, rv, src.astype(jnp.int32), dst.astype(jnp.int32),
            starts.astype(jnp.int32), lives.astype(jnp.int32), q, k, v, sink)
    if _use_kernel(impl, "window ring chunk"):
        return window_ring_chunk_kernel(*args, scale)
    return window_ring_chunk_reference(*args, scale)
