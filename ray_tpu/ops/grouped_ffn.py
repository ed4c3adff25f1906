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

The padding plan and the weighted sum back into token order are
`jax.numpy` under the `moe_route` scope (`_plan`): ONE sort (of the keys
`expert * T + token`), no scatter, counts and running counts over [T, E] in
place of an inverse permutation, and two gathers of rows (x into the tiles,
the tiles' output back to the pairs).  Only the tiled FFN is the Pallas
kernel, named `moe_experts_decode` or `moe_experts_prefill` by its caller.
`impl="reference"` runs the same plan with a gathered einsum in place of
the kernel (any backend; what the CPU tests compare the kernel with).

The tile follows the rows an expert of the ROUTER gets, from shapes alone
(`tile_rows`, the one place it is decided: the product and the
`moe.padded_rows` counter of models/afmoe.py both ask it): the plan pads
every held expert's group to whole tiles and lays out `pairs + E * (tm - 1)`
rows whatever was routed here, so a tile far over the mean group is rows
gathered and computed for nothing; a tile under it is more programs, each
of which pushes the expert's weights through the MXU again.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import scopes
from ray_tpu.ops.attention import compiled_on_tpu

# Rows a tile may hold: from one packed bf16 sublane tile (a decode step's
# few rows an expert) to two MXU passes.
_TILES = (16, 32, 64, 128, 256)
_VMEM_LIMIT = 64 * 2 ** 20  # two sets of one expert's three matrices
# Wider experts: two sets of the three matrices' BLOCKS may take this much,
# under a limit that leaves the row tiles and the accumulator their room.
_BLOCK_BUDGET = 48 * 2 ** 20
_VMEM_LIMIT_BLOCKED = 100 * 2 ** 20


def tile_rows(pairs: int, router_width: int) -> int:
    """Rows a tile holds, for `pairs` (token, pick) pairs picked over a
    router `router_width` experts wide: of `_TILES` the one nearest (as a
    ratio) to the mean rows an expert of the router gets.  Fitted on the
    chip at the four expert configurations' decode steps and fused rungs
    (tests_tpu/expert_sweep.py; PERF.md, PR 52): means of 1-19 rows ran
    fastest at 16, 29-42 at 32 or 64, 58 at 64, 88-130 at 128, the rule's
    own tile within 2.2 % of the best everywhere."""
    mean = max(pairs / router_width, 1.0)
    return min(_TILES, key=lambda tm: max(tm, mean) / min(tm, mean))


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
    never used: it takes what is routed nowhere.

    An expert's rows are its pairs in (token, pick) order.  The per-pair
    work is counting, the per-row work one sort:
      pair -> row: a pair's rank inside its expert is the picks of that
        expert by the tokens before it (a running count down [T, E]) plus
        those among its own token's earlier picks; no inverse permutation.
      row -> token: the keys `expert * T + token` sorted ONCE lay every
        expert's tokens side by side in that order; a tile reads `tm` of
        them from its first sorted position, as far as it holds rows.
        What a tile needs (its expert, its first position, where its
        expert's rows end) is tables of E and n_tiles entries, spread over
        its rows by broadcast.
    No scatter; beside the rows' own two gathers (x into tiles, the tiles'
    output back to pairs) ONE gather is as long as the padded rows (the
    sorted keys), the others have n_tiles indices.  (A gather of n_tiles
    windows of `tm` keys is a loop of n_tiles copies on the chip.)"""
    T, K = idx.shape
    E = n_experts
    pairs = T * K
    n_tiles = -(-(pairs + E * (tm - 1)) // tm) + 1
    if (E + 1) * T >= 2 ** 31:
        raise ValueError(f"grouped_ffn: {E} experts x {T} tokens do not "
                         f"fit the int32 sort key")
    i32 = jnp.int32
    experts = jnp.arange(E, dtype=i32)
    e = jnp.where(_per_pair(valid), idx, E).astype(i32)   # E: nowhere
    hot = e[:, :, None] == experts                        # [T, K, E]
    picks = jnp.sum(hot, axis=1, dtype=i32)               # [T, E]
    before = jnp.cumsum(picks, axis=0) - picks    # by the tokens before t
    sizes = before[-1] + picks[-1]
    tiles_per = (sizes + tm - 1) // tm
    upto = experts[:, None] >= experts            # running sums over E
    tile_end = jnp.sum(jnp.where(upto, tiles_per, 0), axis=1)
    stop = jnp.sum(jnp.where(upto, sizes, 0), axis=1)  # past the last sorted
    n_used = jnp.sum(tiles_per)
    pstart = (tile_end - tiles_per) * tm          # first padded row
    # pair -> its padded row (the unused last tile where routed nowhere)
    earlier = jnp.sum((e[:, :, None] == e[:, None, :])
                      & (jnp.arange(K)[:, None] > jnp.arange(K)), axis=2,
                      dtype=i32)
    dest = jnp.sum(jnp.where(hot, (before + pstart)[:, None, :], 0), axis=2)
    dest = jnp.where(e < E, dest + earlier, n_tiles * tm - 1)
    # tile -> expert; a tile past the last used one keeps the last expert
    # (its weight blocks are then not fetched again).
    tiles = jnp.arange(n_tiles, dtype=i32)
    used = tiles < n_used
    past = tile_end[:-1] <= tiles[:, None]        # [n_tiles, E - 1]: e < g

    def of_tile(table):     # table[g], as a sum of its steps up to g
        return table[0] + jnp.sum(
            jnp.where(past, table[1:] - table[:-1], 0), axis=1)

    g = of_tile(experts)
    last = jnp.max(jnp.where(sizes > 0, experts, 0))
    tile_expert = jnp.where(used, g, last)
    # padded row -> the token it holds (token 0 where it holds none): row r
    # of expert g is sorted position r - pstart[g] + start[g].
    keys = jax.lax.sort((e * T + jnp.arange(T, dtype=i32)[:, None]
                         ).reshape(pairs), is_stable=False)
    at = (tiles * tm + of_tile(stop - sizes - pstart))[:, None] \
        + jnp.arange(tm, dtype=i32)
    held = used[:, None] & (at < of_tile(stop)[:, None])
    src = jnp.where(held, at, 0).reshape(n_tiles * tm)
    row_token = jnp.where(held, keys[src].reshape(n_tiles, tm)
                          - g[:, None] * T, 0).reshape(n_tiles * tm)
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


@functools.partial(jax.jit,
                   static_argnames=("name", "impl", "router_width"))
def grouped_ffn(x: jax.Array, idx: jax.Array, weights: jax.Array,
                valid: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                w_down: jax.Array, name: str = scopes.MOE_EXPERTS_PREFILL,
                impl: str = "auto", router_width: Optional[int] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """sum_k weights[t, k] * FFN_{idx[t, k]}(x[t]) for every valid token.

    x [T, D]; idx, weights [T, K]; valid [T] (or [T, K], a flag a pair)
    bool; w_gate, w_up [E, D, F]; w_down [E, F, D] -> (y [T, D] in x's
    dtype, rows per expert [E] int32).  `router_width`: how many experts
    the picks were made over, where these E are a share of them.
    """
    T, K = idx.shape
    E = w_gate.shape[0]
    tm = tile_rows(T * K, router_width or E)
    w_gate, w_up, w_down = (w.astype(x.dtype)
                            for w in (w_gate, w_up, w_down))
    with jax.named_scope(scopes.MOE_ROUTE):
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
    with jax.named_scope(scopes.MOE_ROUTE):
        # [K, T]: the picks are added up over the MAJOR axis, slabs of
        # [T, D]; K rows side by side in the sublanes of a tile are laid out
        # again first where K is not a multiple of 8 (1.2 ms of Qwen3-Next's
        # widest pass: PERF.md, PR 52).
        w = jnp.where(_per_pair(valid), weights, 0.0).astype(jnp.float32).T
        dest = dest.T
        if T % _TILES[0]:   # whole packed tiles of tokens: a slab of another
            # length did not come back from the chip (PR 52); what is added
            # is pairs routed nowhere
            more = _TILES[0] - T % _TILES[0]
            w = jnp.pad(w, ((0, 0), (0, more)))
            dest = jnp.pad(dest, ((0, 0), (0, more)),
                           constant_values=xs.shape[0] - 1)
        # A row routed nowhere reads the unused tile, which holds whatever
        # was there: its weight is 0 and the product must not be NaN.  (The
        # rows are chosen in their own dtype, so that the conversion is part
        # of the sum and not a float32 copy of them.)
        picked = jnp.where((w != 0.0)[..., None], ys[dest],
                           jnp.zeros((), ys.dtype))
        y = jnp.sum(picked.astype(jnp.float32) * w[..., None], axis=0)[:T]
    return y.astype(x.dtype), sizes
