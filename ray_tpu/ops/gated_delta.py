"""The gated delta rule (Yang et al., "Gated Delta Networks",
arXiv:2412.06464) for serving: a recurrence whose carry is a matrix a head,

    S' = a_t S_{t-1};  u_t = b_t (v_t - S'^T k_t);  S_t = S' + k_t u_t^T;
    o_t = S_t^T q_t                 (S [dk, dv] float32, a in (0, 1], b >= 0)

kept per SEQUENCE in a pool indexed by a state id (models/decoding.py
`PagedDecodeCaches.state_pool`), id 0 scratch.  Two entry points, each a
Pallas kernel on a TPU and a plain `jax.numpy` form anywhere (`impl=`, as
ops/paged_attention.py's dispatchers):

`gated_delta_step`   one position of each of B sequences: read S, decay,
                     one rank-1 update, S^T q, write S back.  4.4 MB of
                     traffic a sequence for 3.9 MFLOP: the state's bytes are
                     its whole cost.
`gated_delta_chunk`  N rows of C consecutive positions, rows of one
                     sequence in order.  The rule over a chunk is exact
                     algebra (`chunk_local`): with g_i = sum_{j<=i} ln a_j and
                     G_ij = exp(g_i - g_j) (i >= j),
                       T = (I + strict_tril(diag(b) (K K^T * G)))^-1 diag(b)
                       W = T (K * e^g);  U = T V
                       V' = U - W S;  O = (Q * e^g) S + tril(Q K^T * G) V'
                       S <- e^{g_C} S + (K * e^{g_C - g})^T V'
                     Everything that does not touch S (T, W, U, the masked
                     products) is batched `jax.numpy` over all rows at once;
                     the kernel is the sequential part: S lives in VMEM from
                     row to row, is copied in from the pool at a sequence's
                     first row and out to the pool where a row is flagged.
                     The inverse is the finite Neumann product
                     (I - A)(I + A^2)(I + A^4)(I + A^8): A is strictly lower
                     triangular, so A^16 = 0 and the product is exact.

THE POOL'S LAYOUT.  A head's state is [dk, dv] = [96, 192] at the cell's
widths: 192 is not whole 128-lane rows, and a [.., 96, 192] float32 array
pads to 256 lanes in HBM (a third more memory and bytes).  `heads_side_by_side`
heads lie side by side in one row of lanes instead: [ids, H / 2, 96, 384],
whole tiles, exactly the 2,211,840 B a layer the model needs.  The kernels
work on such a pair at once and keep the heads apart with lane masks (a
product of one head's [C, dk] with the pair's [dk, 2 dv] is computed for
both halves and the wrong half dropped: twice the arithmetic of a kernel
that is bound by the state's bytes).

A position that is not live (padding of a row, a slot that is not active)
has ln a = 0 and b = 0: it leaves S as it was.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import scopes
from ray_tpu.ops.attention import compiled_on_tpu

_HI = jax.lax.Precision.HIGHEST
_VMEM_LIMIT = 96 * 1024 * 1024


def heads_side_by_side(heads: int, dv: int) -> int:
    """Heads in one row of lanes of the pool: 2 where a head's values are
    not whole 128-lane rows and the heads pair up, else 1."""
    return 2 if dv % 128 and heads % 2 == 0 else 1


def pool_shape(num_states: int, heads: int, dk: int, dv: int
               ) -> Tuple[int, ...]:
    g = heads_side_by_side(heads, dv)
    return (num_states + 1, heads // g, dk, g * dv)


def to_pool(S: jax.Array, g: int) -> jax.Array:
    """[..., H, dk, dv] -> [..., H / g, dk, g * dv]."""
    *lead, H, dk, dv = S.shape
    S = S.reshape(*lead, H // g, g, dk, dv)
    return jnp.swapaxes(S, -3, -2).reshape(*lead, H // g, dk, g * dv)


def from_pool(S: jax.Array, g: int) -> jax.Array:
    """[..., H / g, dk, g * dv] -> [..., H, dk, dv]."""
    *lead, Hg, dk, gdv = S.shape
    S = S.reshape(*lead, Hg, dk, g, gdv // g)
    return jnp.swapaxes(S, -3, -2).reshape(*lead, Hg * g, dk, gdv // g)


# ---------------------------------------------------------------------------
# the rule itself, plain
# ---------------------------------------------------------------------------
def step_rule(S, q, k, v, log_a, beta):
    """One position.  S [..., H, dk, dv] float32; q, k [..., H, dk];
    v [..., H, dv]; log_a, beta [..., H] -> (S', o [..., H, dv])."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    S = S * jnp.exp(log_a)[..., None, None]
    u = beta[..., None] * (v - jnp.einsum("...kv,...k->...v", S, k,
                                          precision=_HI))
    S = S + k[..., :, None] * u[..., None, :]
    return S, jnp.einsum("...kv,...k->...v", S, q, precision=_HI)


def _unit_lower_inverse(A: jax.Array) -> jax.Array:
    """(I + A)^-1 for A [..., C, C] strictly lower triangular.  Up to 16
    positions the finite Neumann product (I - A)(I + A^2)(I + A^4)(I + A^8),
    exact because A^C = 0 and all of it batched products; a wider chunk by
    halves, [[X, 0], [-Z A21 X, Z]] with X, Z the halves' inverses (the
    product's powers of a wide A grow and cancel: 1e-3 at 64 positions in
    float32 where this reads 1e-6)."""
    C = A.shape[-1]
    if C > 16:
        h = C // 2
        X = _unit_lower_inverse(A[..., :h, :h])
        Z = _unit_lower_inverse(A[..., h:, h:])
        low = -jnp.matmul(Z, jnp.matmul(A[..., h:, :h], X, precision=_HI),
                          precision=_HI)
        top = jnp.concatenate([X, jnp.zeros_like(A[..., :h, h:])], axis=-1)
        return jnp.concatenate(
            [top, jnp.concatenate([low, Z], axis=-1)], axis=-2)
    P = -A
    R = jnp.eye(C, dtype=A.dtype) + P
    for _ in range(max(0, math.ceil(math.log2(max(C, 2))) - 1)):
        P = jnp.matmul(P, P, precision=_HI)
        R = R + jnp.matmul(R, P, precision=_HI)
    return R


def chunk_local(q, k, v, log_a, beta):
    """What the chunk form needs that does not touch the state, for R chunks
    of C positions at once.  q, k [R, H, C, dk], v [R, H, C, dv], log_a,
    beta [R, H, C], all float32 -> dict of W, Qg, Kd [R, H, C, dk],
    U [R, H, C, dv], M [R, H, C, C], decay [R, H]."""
    C = q.shape[2]
    g = jnp.cumsum(log_a, axis=-1)
    i, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    diff = g[..., :, None] - g[..., None, :]
    G = jnp.where(i >= j, jnp.exp(jnp.where(i >= j, diff, 0.0)), 0.0)
    kk = jnp.einsum("rhid,rhjd->rhij", k, k, precision=_HI)
    A = jnp.where(i > j, beta[..., :, None] * kk * G, 0.0)
    T = _unit_lower_inverse(A) * beta[..., None, :]
    eg = jnp.exp(g)[..., None]
    return {
        "W": jnp.matmul(T, k * eg, precision=_HI),
        "U": jnp.matmul(T, v, precision=_HI),
        "Qg": q * eg,
        "Kd": k * jnp.exp(g[..., -1:] - g)[..., None],
        "M": jnp.einsum("rhid,rhjd->rhij", q, k, precision=_HI) * G,
        "decay": jnp.exp(g[..., -1]),
    }


def chunk_apply(S, loc):
    """One chunk's part that touches the state.  S [..., H, dk, dv];
    `loc` one chunk of `chunk_local` (no R axis, or the same leading axes as
    S) -> (S', O [..., H, C, dv])."""
    Vn = loc["U"] - jnp.matmul(loc["W"], S, precision=_HI)
    O = (jnp.matmul(loc["Qg"], S, precision=_HI)
         + jnp.matmul(loc["M"], Vn, precision=_HI))
    S = loc["decay"][..., None, None] * S + jnp.einsum(
        "...ck,...cv->...kv", loc["Kd"], Vn, precision=_HI)
    return S, O


def delta_sequence(q, k, v, log_a, beta, chunk: int = 64,
                   S0: Optional[jax.Array] = None):
    """Whole sequences from state S0 (zeros) in chunks of `chunk`: q, k
    [B, S, H, dk], v [B, S, H, dv], log_a, beta [B, S, H] -> (o [B, S, H,
    dv] float32, the state after the last position [B, H, dk, dv]).
    chunk 1: the step rule under a scan over positions."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    if S0 is None:
        S0 = jnp.zeros((B, H, dk, dv), jnp.float32)
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    if chunk == 1:
        def one(Sc, x):
            Sc, o = step_rule(Sc, *x)
            return Sc, o
        Sn, o = jax.lax.scan(one, S0, tuple(
            jnp.swapaxes(x, 0, 1) for x in (q, k, v, log_a, beta)))
        return jnp.swapaxes(o, 0, 1), Sn
    R = -(-S // chunk)
    pad = R * chunk - S

    def chunks(x):
        """[B, S, H, ...] -> [R, B, H, chunk, ...]"""
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(B, R, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    lc, bc = chunks(log_a), chunks(beta)
    loc = chunk_local(*(x.reshape(R * B, *x.shape[2:])
                        for x in (qc, kc, vc, lc, bc)))
    loc = {n: x.reshape(R, B, *x.shape[1:]) for n, x in loc.items()}
    Sn, O = jax.lax.scan(chunk_apply, S0, loc)       # O [R, B, H, chunk, dv]
    o = jnp.moveaxis(O, 0, 1).swapaxes(2, 3).reshape(B, R * chunk, H, dv)
    return o[:, :S], Sn


# ---------------------------------------------------------------------------
# decode: one position a sequence, the state in the pool
# ---------------------------------------------------------------------------
def gated_delta_step_reference(pool, ids, q, k, v, log_a, beta):
    g = pool.shape[3] // v.shape[-1]
    S, o = step_rule(from_pool(pool[ids], g), q, k, v, log_a, beta)
    return o, pool.at[ids].set(to_pool(S, g))


def _lanes(x, dv: int, g: int):
    """A per-head number [B, H] as the pool's lanes: [B, H / g, 1, g * dv]."""
    B, H = x.shape
    return jnp.repeat(x.reshape(B, H // g, g), dv, axis=-1)[:, :, None, :]


def _step_kernel(ids_ref, kq_ref, v_ref, a_ref, b_ref, s_in, o_ref, s_out,
                 *, g: int, dv: int):
    del ids_ref
    pairs, dk, lanes = s_in.shape[1:]
    hp = kq_ref.shape[3]
    head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) // dv
    col = jax.lax.broadcasted_iota(jnp.int32, (1, hp), 1)

    def expand(xT, p):
        """xT [dk, Hp] (a head a lane) -> the pair's [dk, g * dv]: head
        g * p + i's column along its own lanes."""
        out = jnp.zeros((dk, lanes), jnp.float32)
        for i in range(g):
            c = jnp.sum(jnp.where(col == g * p + i, xT, 0.0), axis=1,
                        keepdims=True)                       # [dk, 1]
            out = jnp.where(head_of_lane == i, c, out)
        return out

    def one_pair(p, carry):
        K = expand(kq_ref[0, 0], p)
        Q = expand(kq_ref[0, 1], p)
        S = s_in[0, p] * a_ref[0, p]                         # [dk, lanes]
        r = jnp.sum(S * K, axis=0, keepdims=True)            # [1, lanes]
        u = b_ref[0, p] * (v_ref[0, p] - r)
        S = S + K * u
        s_out[0, p] = S
        o_ref[0, p] = jnp.sum(S * Q, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, pairs, one_pair, 0)


def _step_call(pool, ids, kq, v, a, b, *, g, dv, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B = ids.shape[0]
    _, pairs, dk, lanes = pool.shape

    def by_slot(b, ids):
        return (b, 0, 0, 0)

    def by_id(b, ids):
        return (ids[b], 0, 0, 0)

    row = pl.BlockSpec((1, pairs, 1, lanes), by_slot)
    state = pl.BlockSpec((1, pairs, dk, lanes), by_id)
    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, g=g, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B,),
            in_specs=[pl.BlockSpec((1, 2, dk, kq.shape[3]), by_slot),
                      row, row, row, state],
            out_specs=[row, state]),
        out_shape=[jax.ShapeDtypeStruct((B, pairs, 1, lanes), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=scopes.GATED_DELTA_STEP,
    )(ids, kq, v, a, b, pool)
    return o, pool


def _step_on_device(pool, ids, q, k, v, log_a, beta, *, interpret):
    B, H, dk = q.shape
    dv = v.shape[-1]
    g = pool.shape[3] // dv
    hp = -(-H // 128) * 128
    # keys and queries a head a lane, [B, 2, dk, Hp]: the kernel takes a
    # head's column out with a mask and a sum along lanes
    kq = jnp.stack([k, q], axis=1).astype(jnp.float32).swapaxes(2, 3)
    kq = jnp.pad(kq, ((0, 0), (0, 0), (0, 0), (0, hp - H)))
    vl = v.astype(jnp.float32).reshape(B, H // g, 1, g * dv)
    o, pool = _step_call(pool, ids.astype(jnp.int32), kq, vl,
                         _lanes(jnp.exp(log_a), dv, g), _lanes(beta, dv, g),
                         g=g, dv=dv, interpret=interpret)
    return o.reshape(B, H, dv), pool


def gated_delta_step_kernel(pool, ids, q, k, v, log_a, beta):
    return compiled_on_tpu(_step_on_device, pool, ids, q, k, v, log_a, beta,
                           gather=gated_delta_step_reference)


@functools.partial(jax.jit, static_argnames=("impl",))
def gated_delta_step(pool, ids, q, k, v, log_a, beta, impl: str = "auto"):
    """One position of B sequences.  pool [ids, H / g, dk, g * dv] float32;
    ids [B] (0: scratch: a sequence that is not live, whose log_a is 0 and
    beta 0); q, k [B, H, dk], v [B, H, dv]; log_a, beta [B, H] float32
    -> (o [B, H, dv] float32, pool')."""
    fn = _choose(impl, gated_delta_step_kernel, gated_delta_step_reference,
                 "gated delta step")
    return fn(pool, ids, q, k, v, log_a, beta)


def _choose(impl: str, kernel, reference, what: str):
    if impl == "kernel" or (impl == "auto"
                            and jax.default_backend() == "tpu"):
        return kernel
    if impl not in ("auto", "reference"):
        raise ValueError(f"unknown {what} impl {impl!r}")
    return reference


# ---------------------------------------------------------------------------
# prefill: rows of C positions, rows of one sequence in order
# ---------------------------------------------------------------------------
def _row_operands(q, k, v, log_a, beta, g: int):
    """[N, C, H, ..] rows -> `chunk_local`'s arrays, the value-wide ones in
    the pool's lanes: U [N, H / g, C, g * dv], decay [N, H / g, 1, g * dv]."""
    N, C, H, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    loc = chunk_local(*(jnp.moveaxis(x.astype(f32), 2, 1)
                        for x in (q, k, v, log_a, beta)))
    U = loc["U"].reshape(N, H // g, g, C, dv).swapaxes(2, 3)
    loc["U"] = U.reshape(N, H // g, C, g * dv)
    loc["decay"] = _lanes(loc["decay"], dv, g)
    return loc


def _from_lanes(O, g: int):
    """[N, H / g, C, g * dv] -> [N, C, H, dv]."""
    N, Hg, C, gdv = O.shape
    return O.reshape(N, Hg, C, g, gdv // g).transpose(0, 2, 1, 3, 4).reshape(
        N, C, Hg * g, gdv // g)


def _chunk_reference(pool, src, dst, q, k, v, log_a, beta):
    """The same rows through `chunk_apply` under a scan: the operands the
    kernel takes (`_row_operands`), the state carried and copied by id."""
    N, C, H, _ = q.shape
    dv = v.shape[-1]
    g = pool.shape[3] // dv
    loc = _row_operands(q, k, v, log_a, beta, g)

    def row(carry, x):
        S, pool = carry                              # S in the pool's layout
        l, s, d = x
        S = jnp.where(s < 0, S, jnp.where(s > 0, pool[jnp.maximum(s, 0)],
                                          0.0))
        U = l["U"].reshape(H // g, C, g, dv).swapaxes(1, 2).reshape(
            H, C, dv)
        Sn, O = chunk_apply(from_pool(S, g), dict(
            l, U=U, decay=l["decay"][:, 0].reshape(H, dv)[:, 0]))
        S = to_pool(Sn, g)
        pool = pool.at[d[0]].set(S).at[d[1]].set(S)
        return (S, pool), O

    (_, pool), O = jax.lax.scan(
        row, (jnp.zeros(pool.shape[1:], pool.dtype), pool), (loc, src, dst))
    return O.swapaxes(1, 2), pool                    # [N, C, H, dv]


def _chunk_kernel(src_ref, dst_ref, w_ref, qg_ref, kd_ref, m_ref, u_ref,
                  d_ref, pool_in, o_ref, pool_out, s_ref, sem, *, g: int,
                  dv: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    del pool_in                 # aliased to pool_out: one buffer
    n = pl.program_id(0)
    pairs, dk, lanes = s_ref.shape
    src = src_ref[n]

    @pl.when(src == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(src > 0)
    def _():
        cp = pltpu.make_async_copy(pool_out.at[src], s_ref, sem)
        cp.start()
        cp.wait()

    head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) // dv

    def halves(fn):
        """fn(i) -> [C, lanes] for head g * p + i; each head's own lanes."""
        out = fn(0)
        for i in range(1, g):
            out = jnp.where(head_of_lane == i, fn(i), out)
        return out

    def one_pair(p, carry):
        S = s_ref[p]                                         # [dk, lanes]

        def dot(a, b):
            return jnp.dot(a, b, precision=_HI,
                           preferred_element_type=jnp.float32)

        Vn = u_ref[0, p] - halves(lambda i: dot(w_ref[0, g * p + i], S))
        o_ref[0, p] = halves(lambda i: dot(qg_ref[0, g * p + i], S)
                             + dot(m_ref[0, g * p + i], Vn))
        S = d_ref[0, p] * S
        for i in range(g):
            S = S + jax.lax.dot_general(
                kd_ref[0, g * p + i],
                jnp.where(head_of_lane == i, Vn, 0.0),
                (((0,), (0,)), ((), ())), precision=_HI,
                preferred_element_type=jnp.float32)
        s_ref[p] = S
        return carry

    jax.lax.fori_loop(0, pairs, one_pair, 0)

    for j in range(2):
        to = dst_ref[n, j]

        @pl.when(to > 0)
        def _():
            cp = pltpu.make_async_copy(s_ref, pool_out.at[to], sem)
            cp.start()
            cp.wait()


def _chunk_call(pool, src, dst, W, Qg, Kd, M, U, decay, *, g, dv,
                interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    N, H, C, dk = W.shape
    _, pairs, _, lanes = pool.shape

    def by_row(n, *_):
        return (n, 0, 0, 0)

    heads = pl.BlockSpec((1, H, C, dk), by_row)
    wide = pl.BlockSpec((1, pairs, C, lanes), by_row)
    o, pool = pl.pallas_call(
        functools.partial(_chunk_kernel, g=g, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(N,),
            in_specs=[heads, heads, heads,
                      pl.BlockSpec((1, H, C, C), by_row), wide,
                      pl.BlockSpec((1, pairs, 1, lanes), by_row),
                      pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
            out_specs=[wide, pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
            scratch_shapes=[pltpu.VMEM((pairs, dk, lanes), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct((N, pairs, C, lanes), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=scopes.GATED_DELTA_CHUNK,
    )(src, dst, W, Qg, Kd, M, U, decay, pool)
    return o, pool


@functools.partial(jax.jit, static_argnames=("impl",))
def gated_delta_chunk(pool, src, dst, q, k, v, log_a, beta,
                      impl: str = "auto"):
    """N rows of C positions, in order.  pool [ids, H / g, dk, g * dv]
    float32; src [N]: the state a row starts from: -1 the row before it
    (the same sequence's), 0 zeros (a sequence's start), an id the pool's;
    dst [N, 2]: the ids the state after the row is written to (0: nowhere);
    q, k [N, C, H, dk], v [N, C, H, dv]; log_a, beta [N, C, H] float32, 0
    at a position that is not live -> (o [N, C, H, dv] float32, pool').
    An id read as `src` is no row's `dst` unless it is the same
    sequence's (the engine's rule: serve/llm.py)."""
    fn = _choose(impl, gated_delta_chunk_kernel, _chunk_reference,
                 "gated delta chunk")
    return fn(pool, src.astype(jnp.int32), dst.astype(jnp.int32), q, k, v,
              log_a, beta)


def gated_delta_chunk_kernel(pool, src, dst, q, k, v, log_a, beta):
    return compiled_on_tpu(_chunk_on_device, pool, src, dst, q, k, v, log_a,
                           beta, gather=_chunk_reference)


def _chunk_on_device(pool, src, dst, q, k, v, log_a, beta, *, interpret):
    g = pool.shape[3] // v.shape[-1]
    loc = _row_operands(q, k, v, log_a, beta, g)
    O, pool = _chunk_call(pool, src, dst, loc["W"], loc["Qg"], loc["Kd"],
                          loc["M"], loc["U"], loc["decay"], g=g,
                          dv=v.shape[-1], interpret=interpret)
    return _from_lanes(O, g), pool
