"""Expert feed-forward layers as ONE grouped product: no capacity, no
dropped token.

Every (token, pick) pair is a row.  Rows are sorted by expert and each
expert's group is padded up to whole tiles of `tm` rows, so a tile belongs
to exactly one expert and the kernel is a plain tiled FFN whose weight
blocks are addressed through a scalar-prefetched `tile -> expert` table:

  h = silu(x Wgate[e]) * (x Wup[e]);   y = h Wdown[e]      (one program a tile)

Consecutive tiles of one expert keep their weight blocks (same block index:
no new DMA), tiles past the last used one do nothing and move nothing, so
the work follows the rows that were really routed: 256 rows of a decode
step read the weights of the experts they touch, a prefill chunk whose rows
are mostly padding pays for its real rows.  Rows that are not `valid`
(prefill padding, retired decode slots) are routed NOWHERE: they sort
behind the last expert, enter no group and no count, and come out as zeros.
`valid` may also be [T, K], one flag a (token, pick) pair: a layer that
holds a share of its router's experts (models/afmoe.py `experts`) routes a
pair whose expert lies on another chip nowhere, in the same way.

An expert's three matrices are ONE VMEM block each where two sets of them
fit (`_f_blocks`); wider experts (hidden 7168 x width 2048 is 88 MB an
expert in bf16) go through a second, inner grid axis over blocks of the
expert width F, the down-projection accumulated over them in float32.

The sort, the padding plan and the weighted sum back into token order are
`jax.numpy` (gathers, under the `moe_route` scope); only the tiled FFN is
the Pallas kernel, named `moe_experts_decode` or `moe_experts_prefill` by
its caller.  `impl="reference"` runs the same plan with a gathered einsum in
place of the kernel (any backend; what the CPU tests compare the kernel
with).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import compiled_on_tpu

# Rows a tile holds: one packed bf16 sublane tile for a decode step's few
# rows per expert, a full MXU pass for a prefill chunk's many.
_TM_SMALL, _TM_LARGE = 16, 256
_SMALL_ROWS = 4096          # (token, pick) pairs up to which tiles are small
_VMEM_LIMIT = 64 * 2 ** 20  # two sets of one expert's three matrices
# Wider experts: two sets of the three matrices' BLOCKS may take this much,
# under a limit that leaves the row tiles and the accumulator their room.
_BLOCK_BUDGET = 48 * 2 ** 20
_VMEM_LIMIT_BLOCKED = 100 * 2 ** 20


def tile_rows(pairs: int) -> int:
    return _TM_SMALL if pairs <= _SMALL_ROWS else _TM_LARGE


def _f_blocks(D: int, F: int, itemsize: int) -> int:
    """How many blocks of the expert width the tile kernel walks: 1 where
    two sets of an expert's three [D, F] matrices fit `_VMEM_LIMIT` (every
    expert layer before hidden 7168), else the fewest blocks of whole
    128-lane rows that divide F and fit `_BLOCK_BUDGET` twice."""
    if 2 * 3 * D * F * itemsize <= _VMEM_LIMIT:
        return 1
    n = 2
    while F % (n * 128) or 2 * 3 * D * (F // n) * itemsize > _BLOCK_BUDGET:
        n += 1
        if n * 128 > F:
            raise ValueError(f"grouped_ffn: no block of an expert of "
                             f"{D} x {F} fits VMEM")
    return n


def _per_pair(valid: jax.Array) -> jax.Array:
    """valid [T] (a flag a token) or [T, K] (a flag a pair) against [T, K]."""
    return valid if valid.ndim == 2 else valid[:, None]


def _plan(idx: jax.Array, valid: jax.Array, n_experts: int, tm: int):
    """Where every (token, pick) pair goes.  idx [T, K] expert ids, valid
    [T] or [T, K].  Returns (row_token [R], dest [T, K], tile_expert [n_tiles],
    n_used [1], group_sizes [E]); R = n_tiles * tm rows, the last tile is
    never used: it takes what is routed nowhere."""
    T, K = idx.shape
    E = n_experts
    pairs = T * K
    n_tiles = -(-(pairs + E * (tm - 1)) // tm) + 1
    e = jnp.where(_per_pair(valid), idx, E).reshape(pairs).astype(jnp.int32)
    order = jnp.argsort(e, stable=True).astype(jnp.int32)  # sorted -> pair
    sizes = jnp.zeros((E + 1,), jnp.int32).at[e].add(1)[:E]
    tiles_per = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(tiles_per)
    n_used = tile_end[-1]
    start = jnp.cumsum(sizes) - sizes            # first sorted position
    pstart = (tile_end - tiles_per) * tm         # first padded row
    # tile -> expert; a tile past the last used one keeps the last expert
    # (its weight blocks are then not fetched again).
    tiles = jnp.arange(n_tiles, dtype=jnp.int32)
    raw = jnp.searchsorted(tile_end, tiles, side="right").astype(jnp.int32)
    last = raw[jnp.maximum(n_used - 1, 0)]
    tile_expert = jnp.minimum(jnp.where(tiles < n_used, raw, last), E - 1)
    # padded row -> the token it holds (token 0 where it holds none)
    rows = jnp.arange(n_tiles * tm, dtype=jnp.int32)
    g = jnp.minimum(raw[rows // tm], E - 1)
    rank = rows - pstart[g]
    held = (rows // tm < n_used) & (rank < sizes[g])
    src = jnp.clip(start[g] + rank, 0, pairs - 1)
    row_token = jnp.where(held, order[src] // K, 0)
    # pair -> its padded row (the unused last tile where routed nowhere)
    inv = jnp.zeros((pairs,), jnp.int32).at[order].set(
        jnp.arange(pairs, dtype=jnp.int32))
    ge = jnp.minimum(e, E - 1)
    dest = jnp.where(e < E, pstart[ge] + inv - start[ge],
                     n_tiles * tm - 1).reshape(T, K)
    return row_token, dest, tile_expert, n_used[None], sizes


def _ffn_tile_kernel(te_ref, nu_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) < nu_ref[0])
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(gate) * up).astype(x.dtype)
        o_ref[...] = jnp.dot(h, wd_ref[...],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)


def _ffn_block_kernel(te_ref, nu_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
                      acc_ref):
    """One (tile, block of F) program: the block's part of the
    down-projection is added up in float32 over the inner axis."""
    from jax.experimental import pallas as pl

    j = pl.program_id(1)

    @pl.when(pl.program_id(0) < nu_ref[0])
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(gate) * up).astype(x.dtype)
        part = jnp.dot(h, wd_ref[...], preferred_element_type=jnp.float32)

        @pl.when(j == 0)
        def _():
            acc_ref[...] = part

        @pl.when(j > 0)
        def _():
            acc_ref[...] += part

        @pl.when(j == pl.num_programs(1) - 1)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _ffn_tiles_kernel(xs, tile_expert, n_used, w_gate, w_up, w_down, *, tm,
                      name, interpret, f_blocks=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, D = xs.shape
    F = w_gate.shape[2]
    n_tiles = R // tm
    nf = f_blocks or _f_blocks(D, F, w_gate.dtype.itemsize)
    if nf > 1:
        bf = F // nf

        def tile(i, nu):            # an unused tile keeps the last used one
            return jnp.minimum(i, jnp.maximum(nu[0] - 1, 0))

        def block(i, j, nu):        # ... and the last block it fetched
            return jnp.where(i < nu[0], j, nf - 1)

        return pl.pallas_call(
            _ffn_block_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(n_tiles, nf),
                in_specs=[
                    pl.BlockSpec((tm, D), lambda i, j, te, nu:
                                 (tile(i, nu), 0)),
                    pl.BlockSpec((None, D, bf), lambda i, j, te, nu:
                                 (te[i], 0, block(i, j, nu))),
                    pl.BlockSpec((None, D, bf), lambda i, j, te, nu:
                                 (te[i], 0, block(i, j, nu))),
                    pl.BlockSpec((None, bf, D), lambda i, j, te, nu:
                                 (te[i], block(i, j, nu), 0))],
                out_specs=pl.BlockSpec(
                    (tm, D), lambda i, j, te, nu:
                    (jnp.where(i < nu[0], i, n_tiles - 1), 0)),
                scratch_shapes=[pltpu.VMEM((tm, D), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((R, D), xs.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT_BLOCKED),
            interpret=interpret,
            name=name,
        )(tile_expert, n_used, xs, w_gate, w_up, w_down)

    def x_index(i, te, nu):        # an unused tile keeps the last used one
        return (jnp.minimum(i, jnp.maximum(nu[0] - 1, 0)), 0)

    def w_index(i, te, nu):
        return (te[i], 0, 0)

    def o_index(i, te, nu):        # unused tiles all land on the last tile
        return (jnp.where(i < nu[0], i, n_tiles - 1), 0)

    return pl.pallas_call(
        _ffn_tile_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_tiles,),
            in_specs=[pl.BlockSpec((tm, D), x_index),
                      pl.BlockSpec((None, D, F), w_index),
                      pl.BlockSpec((None, D, F), w_index),
                      pl.BlockSpec((None, F, D), w_index)],
            out_specs=pl.BlockSpec((tm, D), o_index)),
        out_shape=jax.ShapeDtypeStruct((R, D), xs.dtype),
        # In order: consecutive tiles of one expert share its weights.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(tile_expert, n_used, xs, w_gate, w_up, w_down)


def _ffn_tiles_reference(xs, tile_expert, n_used, w_gate, w_up, w_down, *,
                         tm):
    """The tiled FFN with each tile's weights gathered: small sizes only."""
    R, D = xs.shape
    xt = xs.reshape(R // tm, tm, D)
    f32 = jnp.float32
    gate = jnp.einsum("ntd,ndf->ntf", xt, w_gate[tile_expert],
                      preferred_element_type=f32)
    up = jnp.einsum("ntd,ndf->ntf", xt, w_up[tile_expert],
                    preferred_element_type=f32)
    h = (jax.nn.silu(gate) * up).astype(xs.dtype)
    y = jnp.einsum("ntf,nfd->ntd", h, w_down[tile_expert],
                   preferred_element_type=f32)
    return y.reshape(R, D).astype(xs.dtype)


@functools.partial(jax.jit, static_argnames=("name", "impl"))
def grouped_ffn(x: jax.Array, idx: jax.Array, weights: jax.Array,
                valid: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                w_down: jax.Array, name: str = "moe_experts_prefill",
                impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """sum_k weights[t, k] * FFN_{idx[t, k]}(x[t]) for every valid token.

    x [T, D]; idx, weights [T, K]; valid [T] (or [T, K], a flag a pair)
    bool; w_gate, w_up [E, D, F]; w_down [E, F, D] -> (y [T, D] in x's
    dtype, rows per expert [E] int32).
    """
    T, K = idx.shape
    E = w_gate.shape[0]
    tm = tile_rows(T * K)
    w_gate, w_up, w_down = (w.astype(x.dtype)
                            for w in (w_gate, w_up, w_down))
    with jax.named_scope("moe_route"):
        row_token, dest, tile_expert, n_used, sizes = _plan(idx, valid, E,
                                                            tm)
        xs = x[row_token]
    if impl == "kernel" or (impl == "auto"
                            and jax.default_backend() == "tpu"):
        ys = compiled_on_tpu(
            functools.partial(_ffn_tiles_kernel, tm=tm, name=name),
            xs, tile_expert, n_used, w_gate, w_up, w_down)
    elif impl in ("auto", "reference"):
        ys = _ffn_tiles_reference(xs, tile_expert, n_used, w_gate, w_up,
                                  w_down, tm=tm)
    else:
        raise ValueError(f"unknown grouped_ffn impl {impl!r}")
    with jax.named_scope("moe_route"):
        w = jnp.where(_per_pair(valid), weights, 0.0).astype(jnp.float32)
        # A row routed nowhere reads the unused tile, which holds whatever
        # was there: its weight is 0 and the product must not be NaN.
        picked = jnp.where((w != 0.0)[..., None],
                           ys[dest].astype(jnp.float32), 0.0)
        y = jnp.sum(picked * w[..., None], axis=1)
    return y.astype(x.dtype), sizes
