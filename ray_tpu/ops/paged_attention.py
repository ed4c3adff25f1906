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
* `paged_attention_kernel` — a Pallas TPU kernel whose work follows the
  positions that are cached, not the table's width.  The grid is the
  batch: ONE program per sequence, all kv heads inside it.  The pools
  stay in HBM; the program copies whole physical pages (all heads of
  one page are contiguous: Hkv * bs * D elements), addressed through
  the scalar-prefetched block table, into double-buffered VMEM, a
  group of pages (128 positions) at a time, and runs an online softmax
  over ceil(context / group) groups — the next group's pages, or the
  next sequence's first ones, are in flight while a group is computed.
  A page beyond the context starts no DMA and a sequence of length 0
  does nothing, so 32 slots with 48-page tables are 32 programs and
  as many 32 KB copies as there are live pages (the (B, Hkv, W) grid
  this replaced ran 12,288 one-tile programs whatever was cached).
  Mosaic cannot slice a page out of an HBM pool whose head size is
  not a multiple of 128 lanes; for those (D 64, 72, 192) the same
  group step runs on a (B, groups) grid whose pages arrive as whole-
  page blocks through block-table index maps (`_paged_kernel_narrow`).

Shapes (decode: ONE query token per sequence):
  q:            [B, H, D]
  k_pool/v_pool [NB, Hkv, bs, D]   (one layer's pool)
  block_tables  [B, W] int32        (physical block per logical block)
  context_lens  [B]    int32        (valid positions, INCLUSIVE of the
                                     token scattered this step)
  -> out        [B, H, D]

The pool keeps (bs, D) as its two minor dimensions because Mosaic
tiles the last two dimensions of everything it copies or loads, in
(8, 128) or (16, 128) tiles: a page's (bs, D) slab per head is whole
tiles, which a (1, D) slice of a [.., Hkv, D] pool is not.

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
_LANES = 128
# Positions one DMA group spans (one lane-wide score tile per kv head),
# and what its four buffers (k, v, two each) may take of VMEM.
_GROUP_POSITIONS = 128
_VMEM_BUDGET = 8 * 2 ** 20


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
def _pages_per_group(W, hkv, bs, D, itemsize):
    """Pages one group holds, from static shapes: enough pages for one
    lane-wide (G, 128) score tile per kv head, no more than the table
    has, and two k and two v buffers inside the VMEM budget."""
    pages = max(1, min(_GROUP_POSITIONS // bs, W))
    while pages > 1 and 4 * pages * hkv * bs * D * itemsize > _VMEM_BUDGET:
        pages //= 2
    return pages


def _attend_group(q, k, v, first_pos, ctx, scale, m_ref, l_ref, acc_ref):
    """Online-softmax update with one group of cached positions.
    q: (Hkv, G, D); k, v: (Hkv, span, D), position `first_pos` first.
    Scores are (Hkv, G, span) with the running max / normalizer as
    (Hkv, G, 1) columns, so every rescale broadcasts along lanes.
    Softmax, accumulation and p . v are f32, like the reference; q and
    k meet on the MXU in their own dtype, which gives the same
    products as f32 copies of them would."""
    s = jnp.einsum("hgd,htd->hgt", q, k.astype(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    kpos = first_pos + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(kpos < ctx, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=2, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
        "hgt,htd->hgd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32)


def _reset(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _write_out(o_ref, l_ref, acc_ref):
    l = l_ref[...]
    l = jnp.where(l == 0.0, 1.0, l)           # zero-length row -> zeros
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sem, cursor, m_ref, l_ref, acc_ref, *,
                  scale, block_size, pages):
    """One sequence: every kv head, a group of `pages` pages at a time.

    The grid is the batch, run in order, and the (sequence, group)
    pairs form ONE stream through two VMEM buffers: while a group is
    computed the next one's pages are in flight — the same sequence's
    next group or, at its last group, the first group of the next
    sequence (`cursor` carries the buffer parity across programs).  A
    page with no live position starts no DMA and a sequence of length 0
    runs no group at all, so DMAs and loop trips follow what is
    cached, not the table's width."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    last_b = pl.num_programs(0) - 1
    span = pages * block_size                 # positions per group
    ctx = len_ref[b]
    n_groups = pl.cdiv(ctx, span)

    def copy_group(row, group, slot, wait=False):
        """Start (or wait for) the copies of the live pages of `row`'s
        `group` into buffer `slot`: a loop over what is live, not over
        the group's width, in the kernel and in its trace."""
        first = group * pages
        live = jnp.clip(pl.cdiv(len_ref[row], block_size) - first, 0, pages)

        def page(j, carry):
            phys = bt_ref[row, first + j]
            dst = pl.ds(pl.multiple_of(j * block_size, block_size),
                        block_size)
            for i, (pool, buf) in enumerate(((k_hbm, k_buf),
                                             (v_hbm, v_buf))):
                copy = pltpu.make_async_copy(
                    pool.at[phys], buf.at[slot, :, dst, :], sem.at[i, slot])
                if wait:
                    copy.wait()
                else:
                    copy.start()
            return carry

        jax.lax.fori_loop(0, live, page, None)

    first_slot = jnp.where(b == 0, 0, cursor[0])
    next_row = jnp.minimum(b + 1, last_b)

    @pl.when(b == 0)
    def _first():
        # Page slots a partly live group leaves unfilled are read under
        # the mask with p == 0: they must hold numbers (0 * NaN), which
        # fresh VMEM need not.  Later they hold older pool pages.
        v_buf[...] = jnp.zeros_like(v_buf)

    # Every sequence finds its first group in flight: its predecessor
    # started it — at its own last group or, having nothing cached,
    # here — and the first sequence starts its own.
    @pl.when(jnp.where(n_groups == 0, b < last_b, b == 0))
    def _():
        copy_group(jnp.where(n_groups == 0, next_row, b), 0, first_slot)

    _reset(m_ref, l_ref, acc_ref)

    def group_step(g, carry):
        slot = (first_slot + g) % 2
        more = g + 1 < n_groups

        @pl.when(jnp.logical_or(more, b < last_b))
        def _():
            copy_group(jnp.where(more, b, next_row),
                       jnp.where(more, g + 1, 0), 1 - slot)

        copy_group(b, g, slot, wait=True)
        _attend_group(q_ref[0], k_buf[slot], v_buf[slot], g * span, ctx,
                      scale, m_ref, l_ref, acc_ref)
        return carry

    jax.lax.fori_loop(0, n_groups, group_step, None)
    cursor[0] = (first_slot + n_groups) % 2
    _write_out(o_ref, l_ref, acc_ref)


def _paged_kernel_narrow(bt_ref, len_ref, q_ref, *refs, scale, block_size,
                         pages):
    """One (sequence, page group) program for a head size Mosaic cannot
    slice out of an HBM pool (D not a multiple of 128 lanes).  The
    group's pages are `pages` whole-page k and v blocks the pipeline
    fetched through the block table; a dead page's index map stays on
    the block it held, so it is not fetched again, and a dead group
    does no arithmetic.  Same mathematics as `_paged_kernel`."""
    from jax.experimental import pallas as pl

    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pages:]
    b, g = pl.program_id(0), pl.program_id(1)
    span = pages * block_size
    ctx = len_ref[b]

    @pl.when(g == 0)
    def _init():
        _reset(m_ref, l_ref, acc_ref)

    @pl.when(g * span < ctx)
    def _body():
        k, v = (jnp.concatenate([r[0] for r in rs], axis=1)
                for rs in (k_refs, v_refs))
        _attend_group(q_ref[0], k, v, g * span, ctx, scale,
                      m_ref, l_ref, acc_ref)

    @pl.when(g == pl.num_programs(1) - 1)
    def _finish():
        _write_out(o_ref, l_ref, acc_ref)


def _paged_fwd(q, k_pool, v_pool, block_tables, context_lens, *, scale,
               interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    hkv, bs = k_pool.shape[1], k_pool.shape[2]
    W = block_tables.shape[1]
    groups = H // hkv
    gp = -(-groups // _SUBLANES) * _SUBLANES
    pages = _pages_per_group(W, hkv, bs, D, k_pool.dtype.itemsize)
    qg = q.reshape(B, hkv, groups, D).astype(
        jnp.promote_types(q.dtype, k_pool.dtype))
    if gp != groups:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - groups), (0, 0)))
    softmax_state = [pltpu.VMEM((hkv, gp, 1), jnp.float32),
                     pltpu.VMEM((hkv, gp, 1), jnp.float32),
                     pltpu.VMEM((hkv, gp, D), jnp.float32)]

    def q_index(b, *_):
        return (b, 0, 0, 0)

    if D % _LANES == 0:
        # The pools stay in HBM: the body copies whole pages, addressed
        # through the scalar-prefetched block table.
        kernel, grid = _paged_kernel, (B,)
        kv_specs = [pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)] * 2
        kv_args = [k_pool, v_pool]
        scratch = [pltpu.VMEM((2, hkv, pages * bs, D), k_pool.dtype),
                   pltpu.VMEM((2, hkv, pages * bs, D), v_pool.dtype),
                   pltpu.SemaphoreType.DMA((2, 2)),
                   pltpu.SMEM((1,), jnp.int32)] + softmax_state
    else:
        def page_index(j):
            def index(b, g, bt_ref, len_ref):
                # Page j of group g or, where that page is dead, the
                # last live page this operand held in the row (page j
                # itself in a row too short to have one).
                live = pl.cdiv(len_ref[b], bs)
                held = jnp.maximum((live - 1 - j) // pages * pages + j, j)
                return (bt_ref[b, jnp.minimum(g * pages + j, held)],
                        0, 0, 0)
            return index

        kernel, grid = _paged_kernel_narrow, (B, -(-W // pages))
        kv_specs = [pl.BlockSpec((1, hkv, bs, D), page_index(j))
                    for j in range(pages)] * 2
        kv_args = [k_pool] * pages + [v_pool] * pages
        scratch = softmax_state

    # The kernels walk the table as far as the lengths say.
    context_lens = jnp.minimum(context_lens.astype(jnp.int32), W * bs)
    o = pl.pallas_call(
        functools.partial(kernel, scale=scale, block_size=bs, pages=pages),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=[pl.BlockSpec((1, hkv, gp, D), q_index)] + kv_specs,
            out_specs=pl.BlockSpec((1, hkv, gp, D), q_index),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((B, hkv, gp, D), q.dtype),
        # Softmax state (and the DMA stream) cross programs: in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid)),
        interpret=interpret,
        name="paged_attention",
    )(block_tables.astype(jnp.int32), context_lens, qg, *kv_args)
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


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_attention_kernel(q, k_pool, v_pool, block_tables, context_lens,
                           scale: Optional[float] = None) -> jax.Array:
    """Pallas paged attention: the compiled kernel where the program is
    lowered for a TPU, the Pallas interpreter elsewhere (CPU parity
    tests).  Jitted so that a process traces the kernel once per shape:
    a decode program reaches it through a layer scan inside a step scan
    and once per platform branch, and an engine warms up six such
    programs — traced each time, the kernel was most of a warm start."""
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
