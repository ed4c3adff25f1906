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
  the attention of transformer.forward at one query position (GQA
  scores under a length mask), just addressed through the table.
* `paged_attention_kernel` — a Pallas TPU kernel whose work follows the
  positions that are cached, not the table's width.  The grid is the
  batch: ONE program per sequence, all kv heads inside it.  The pools
  stay in HBM; the (sequence, group of 8 pages = 128 positions) pairs of
  the whole batch form ONE stream of whole-page copies, addressed
  through the scalar-prefetched block table, into a ring of VMEM slots
  (16 groups for Trinity-Mini's and LFM2's pools, 8 for Mistral's:
  `_ring_shape`), so a sequence finds its first groups landed when its
  program starts.  A descriptor costs the core the same ~25 ns whether
  it moves one page or eight, and the core issues nothing while it
  computes: a full group whose pages lie in a row in the pool (a
  prompt's blocks come from one allocation) is one descriptor a pool.
  The online softmax runs over ceil(context / 128) groups, a quarter
  of the ring (512 positions) an update where the context has them, a
  group at a time for what is left.  A page beyond the context starts
  no DMA and a sequence of length 0 does nothing, so 32 slots with
  48-page tables are 32 programs and as many page copies as there are
  live pages (the (B, Hkv, W) grid this replaced ran 12,288 one-tile
  programs whatever was cached).
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

Heads side by side.  A pool may hold f = 128 / D narrow heads in one row
of lanes, [NB, Hkv / f, bs, f * D] (kv head h' * f + j in lanes j * D ..
(j + 1) * D of row h': the [.., Hkv, D] a layer writes, seen as [.., Hkv /
f, f * D]); every function here tells it from the shapes (the pool's
minor dimension against q's).  The kernels then run as they are on heads
of f * D: a query head's values are placed in its kv head's lanes with
zeros in the others, so its scores are its own, and of the f * D values
it gets back, its kv head's lanes are its output (`_side_by_side`).  The
MXU contracts over 128 lanes whatever D is, so the zeros cost nothing,
and the pages are copied out of HBM as for D = 128: the work follows what
is cached, in decode and in prefill, where the narrow form below walks
the table's whole width and a prefill had only the gather.

A sliding-window layer passes `window`: only the last `window` positions
are attended, and the kernel's page stream starts at the group that
holds the first of them.

A prefix that several slots share is read once.  Slots whose tables agree
over their first blocks (a tenant's system prompt, shared through the radix
cache) come as `SharedRows`: the decode step of a sequence is then its
attention over the positions that are its own (the same kernel, its page
stream starting at the sequence's first own position, the softmax left
unnormalised) merged by the softmax statistics with its set's attention over
the shared positions (the same kernel again, one program per up to 8 members:
their queries side by side in the q block, the leader's table row read to
the shared length).  The same softmax over the same positions, so the
reference forms are the oracle as they are; a step with no set skips the
second call and the merge behind a `lax.cond`.  The second half of this file is
`prefix_attention`: a prefill chunk's queries over their paged prefix,
group by group with a running softmax, for prompts longer than one
prefill.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import scopes
from ray_tpu.ops.attention import NEG_INF, compiled_on_tpu

# The query-group rows of one kv head are padded up to the f32 sublane
# tile so the (G, D) q block and its (G, bs) scores are whole tiles for
# MHA (G = 1) and narrow GQA alike.
_SUBLANES = 8
_LANES = 128
# Positions one DMA group spans (one lane-wide score tile per kv head), the
# most groups the ring of the decode kernel holds, and what the ring's k and
# v buffers together may take of VMEM.
_GROUP_POSITIONS = 128
_RING_GROUPS = 16
_VMEM_BUDGET = 4 * 2 ** 20
# Slots one program of a shared prefix holds: with one query head a kv head
# the q block is a sublane tile of 8 rows anyway, seven of them padding.
SHARED_MEMBERS = _SUBLANES
# The shortest prefix worth a program: the kernel's own DMA group.
SHARED_MIN_POSITIONS = _GROUP_POSITIONS


class SharedPrefixes(NamedTuple):
    """Sets of decoding slots whose block tables agree over their first
    blocks, as the engine found them (serve/llm.py), in P programs of up to
    `SHARED_MEMBERS` slots (a set of nine is two programs).  A slot is in at
    most one program."""

    members: jax.Array       # [P, SHARED_MEMBERS] slot ids, -1: nobody
    leader: jax.Array        # [P] the slot whose table row the program reads
    shared_len: jax.Array    # [P] positions its members share, 0: no program


class SharedRows(NamedTuple):
    """`SharedPrefixes` as one decode step's attention calls take them."""

    members: jax.Array       # [P, SHARED_MEMBERS] slot ids (nobody: slot 0)
    tables: jax.Array        # [P, W] each program's leader's table row
    lens: jax.Array          # [P] positions shared
    place: jax.Array         # [B] a slot's p * SHARED_MEMBERS + j, -1: none
    starts: jax.Array        # [B] a slot's first position of its own
    some: jax.Array          # [] bool: there is a program (once a step, not
    #                          once a layer)


def no_shared_prefixes(num_slots: int) -> SharedPrefixes:
    P = num_slots // 2          # a set has two members or more
    return SharedPrefixes(jnp.full((P, SHARED_MEMBERS), -1, jnp.int32),
                          jnp.zeros((P,), jnp.int32),
                          jnp.zeros((P,), jnp.int32))


def shared_rows(shared: SharedPrefixes, block_tables: jax.Array,
                context_lens: jax.Array) -> SharedRows:
    """One step's `SharedRows`.  A member keeps its place only where it
    attends beyond what its program shares (a slot that is not active
    attends to nothing): every position of a sequence is then read by
    exactly one of the two calls, whatever the engine handed in."""
    B = block_tables.shape[0]
    flat = shared.members.reshape(-1)
    place = jnp.full((B,), -1, jnp.int32).at[
        jnp.where(flat < 0, B, flat)].set(
        jnp.arange(flat.shape[0], dtype=jnp.int32), mode="drop")
    mine = shared.shared_len[jnp.maximum(place, 0) // SHARED_MEMBERS]
    member = (place >= 0) & (mine < context_lens)
    return SharedRows(jnp.maximum(shared.members, 0),
                      block_tables[shared.leader], shared.shared_len,
                      jnp.where(member, place, -1),
                      jnp.where(member, mine, 0),
                      jnp.any(shared.shared_len > 0))


def _pool_heads(pages: jax.Array, D: int) -> jax.Array:
    """pages [..., Hkv / f, bs, f * D] -> [..., Hkv, bs, D]."""
    *lead, rows, bs, lanes = pages.shape
    f = lanes // D
    if f == 1:
        return pages
    return jnp.moveaxis(pages.reshape(*lead, rows, bs, f, D), -2, -3
                        ).reshape(*lead, rows * f, bs, D)


def _side_by_side(fwd, q: jax.Array, k_pool: jax.Array, *args) -> jax.Array:
    """`fwd(q', k_pool, *args)` for query heads q [..., H, D] over a pool
    whose rows hold f heads of D: q' [..., H, f * D] has each head's values
    in the lanes of its kv head, and of the result each head keeps those."""
    H, D = q.shape[-2:]
    f = k_pool.shape[3] // D
    if f == 1:
        return fwd(q, k_pool, *args)
    G = H // (k_pool.shape[1] * f)
    lanes = jax.nn.one_hot((jnp.arange(H) // G) % f, f, dtype=q.dtype)
    wide = (q[..., None, :] * lanes[:, :, None]).reshape(
        *q.shape[:-1], f * D)
    o = fwd(wide, k_pool, *args)
    return jnp.sum(o.reshape(*q.shape[:-1], f, D) * lanes[:, :, None],
                   axis=-2)


def key_lanes(q: jax.Array, k_pool: jax.Array) -> jax.Array:
    """q [..., H, D] with zeros up to the key pool's lanes where a key head
    of D values lies alone in whole rows of lanes that D does not fill (192
    in 256: the lanes past D are zero in the pool, `decoding._write_rows`);
    any other q as it is.  The scale is taken from D BEFORE this."""
    return q if k_pool.shape[3] % q.shape[-1] == 0 else to_lanes(q, k_pool)


def _value_dim(D: int, k_pool: jax.Array, v_pool: jax.Array) -> int:
    """A value head's width: the key head's, unless the value pool has rows
    of lanes of its own (keys of 192 in 256 lanes beside values of 128)."""
    hkv = k_pool.shape[1] * k_pool.shape[3] // D
    return v_pool.shape[1] * v_pool.shape[3] // hkv


# ---------------------------------------------------------------------------
# Reference implementation (works everywhere; the numerics oracle)
# ---------------------------------------------------------------------------
def paged_attention_reference(q: jax.Array, k_pool: jax.Array,
                              v_pool: jax.Array, block_tables: jax.Array,
                              context_lens: jax.Array,
                              scale: Optional[float] = None,
                              window: Optional[int] = None) -> jax.Array:
    """Gather-based paged attention (the CPU/tier-1 path).

    Gathers each sequence's blocks into a [B, Hkv, W*bs, D] window with
    `jnp.take`, then runs plain attention over it: f32 scores, -inf
    mask beyond context_lens, softmax, f32 weighted sum — so a paged
    decode step matches `transformer.forward` at that position.
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[2])
    q = key_lanes(q, k_pool)
    B, H, D = q.shape
    hkv, bs = k_pool.shape[1] * k_pool.shape[3] // D, k_pool.shape[2]
    dv = _value_dim(D, k_pool, v_pool)
    W = block_tables.shape[1]
    M = W * bs

    def rows(pool, d):      # [B, W, Hkv, bs, d] -> [B, Hkv, M, d]
        return _pool_heads(jnp.take(pool, block_tables, axis=0), d
                           ).transpose(0, 2, 1, 3, 4).reshape(B, hkv, M, d)

    k, v = rows(k_pool, D), rows(v_pool, dv)
    groups = H // hkv
    qg = q.reshape(B, hkv, groups, D)
    s = jnp.einsum("bhgk,bhmk->bhgm", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = jnp.arange(M)[None, :] < context_lens[:, None]
    if window is not None:      # the query sits at context_lens - 1
        mask &= jnp.arange(M)[None, :] >= context_lens[:, None] - window
    mask = mask[:, None, None, :]                            # [B,1,1,M]
    s = jnp.where(mask, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    # A zero-length row's softmax is all-NaN (every score -inf); the
    # kernel's l==0 guard returns zeros there — match it so both
    # impls stay interchangeable for padded/inactive rows.
    w = jnp.where(mask, w, 0.0)
    o = jnp.einsum("bhgm,bhmk->bhgk", w, v.astype(jnp.float32))
    return o.reshape(B, H, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------
def _ring_shape(W, hkv, bs, D, itemsize, pools=2):
    """(pages, depth, tiles) of the decode kernel's page stream, from static
    shapes (`pools` 1: a latent pool, whose values are its keys' lanes).
    `pages` a group: enough for one lane-wide (G, 128) score tile per kv
    head, no more than the table has, and two groups (k and v) inside the
    VMEM budget.  `depth` groups in the ring: the largest power
    of two (the kernel wraps a slot index with a mask), up to
    `_RING_GROUPS`, that the budget holds.  `tiles` groups
    one softmax update spans where a context has them: a quarter of the
    ring, so that three quarters stay in flight under the arithmetic."""
    page = pools * hkv * bs * D * itemsize      # its k and its v
    pages = max(1, min(_GROUP_POSITIONS // bs, W))
    while pages > 1 and 2 * pages * page > _VMEM_BUDGET:
        pages //= 2
    depth = 2
    while depth < _RING_GROUPS and 2 * depth * pages * page <= _VMEM_BUDGET:
        depth *= 2
    return pages, depth, max(1, depth // 4)


def _attend_group(q, k, v, first_pos, ctx, scale, m_ref, l_ref, acc_ref,
                  lo=None, pv_in=jnp.float32):
    """Online-softmax update with one group of cached positions.
    q: (Hkv, G, D); k, v: (Hkv, span, D), position `first_pos` first;
    positions in [lo, ctx) are attended (lo None: from the first).
    Scores are (Hkv, G, span) with the running max / normalizer as
    (Hkv, G, 1) columns, so every rescale broadcasts along lanes.
    Softmax, accumulation and p . v are f32, like the reference; q and
    k meet on the MXU in their own dtype, which gives the same
    products as f32 copies of them would."""
    s = _scores(q, k) * scale
    kpos = first_pos + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    seen = kpos < ctx if lo is None else (kpos < ctx) & (kpos >= lo)
    _softmax_update(s, seen, v, m_ref, l_ref, acc_ref, pv_in=pv_in)


def _scores(q, k):
    """q (Hkv, R, D) . k (Hkv, T, D) -> (Hkv, R, T) f32, on the MXU in q's
    dtype."""
    return lax.dot_general(q, k.astype(q.dtype), (((2,), (2,)), ((0,), (0,))),
                           preferred_element_type=jnp.float32)


def _softmax_update(s, seen, v, m_ref, l_ref, acc_ref, rows_differ=False,
                    pv_in=jnp.float32):
    """s: (Hkv, R, span) f32 scores, `seen` which of them count; `pv_in`
    the type p and v meet in (accumulated in f32 either way).  Where
    every row sees something in the first group it meets (one query a
    sequence), a masked score's exp(NEG_INF - m) is 0 by itself; with
    `rows_differ` (a chunk's queries, each with its own causal and window
    bounds) a row may have seen nothing yet, keeps m at NEG_INF, and its
    masked scores would count exp(0): they are set to 0."""
    s = jnp.where(seen, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    if rows_differ:
        p = jnp.where(seen, p, 0.0)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=2, keepdims=True)
    m_ref[...] = m_new
    if pv_in != jnp.float32:
        p = p.astype(pv_in)
    acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
        p, v.astype(pv_in), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)         # hgt,htd->hgd


def _reset(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _write_out(o_ref, l_ref, acc_ref):
    l = l_ref[...]
    l = jnp.where(l == 0.0, 1.0, l)           # zero-length row -> zeros
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _cdiv(a, b: int):
    """ceil(a / b) of a count a >= 0.  Scalar arithmetic inside the
    kernels is `lax` primitives, not operators: an operator on a traced
    value goes through a jitted `jnp` wrapper, four times the price, and
    a kernel's body is traced in Python once for every shape it meets,
    in every process (a prefill's kernel for every rung: with operators
    the bodies' tracing was +2.3 s of a Mistral cell's warm start and
    +11 s of the agents cell's, PERF.md PR 41)."""
    return lax.div(lax.add(a, b - 1), b)


def _copy_group(bt_ref, row, first, live, pools, bufs, sem, slot, wait):
    """Start (or wait for) the copies of `live` pages of `row`'s table,
    from page `first` on, out of the HBM pools into slot `slot` of the
    page-major buffers [slots, pages, Hkv, bs, D]: a page lands whole, as
    it lies in the pool.  Issuing a descriptor holds the core for ~25 ns
    whatever it moves (PERF.md, PR 41), so a full group whose pages lie in
    a row in the pool (a prompt's blocks come from one allocation) is ONE
    descriptor a pool, and a full group is waited for with one wait a pool
    (a DMA semaphore counts bytes); any other group is a loop over what is
    live, not over a group's width."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pages = bufs[0].shape[1]
    whole = lax.eq(live, pages)
    if not wait:
        # (a group that is not full may reach past the table: its columns
        # are read where they are in range, and `whole` is false already)
        column = lax.min(first, bt_ref.shape[1] - pages)
        phys0 = bt_ref[row, column]
        for j in range(1, pages):
            whole = lax.bitwise_and(whole, lax.eq(
                bt_ref[row, lax.add(column, j)], lax.add(phys0, j)))

    @pl.when(whole)
    def _():
        if wait:
            _wait_group(bufs, sem, slot)
        else:
            for i, (pool, buf) in enumerate(zip(pools, bufs)):
                pltpu.make_async_copy(pool.at[pl.ds(phys0, pages)],
                                      buf.at[slot], sem.at[i, slot]).start()

    @pl.when(lax.bitwise_not(whole))
    def _():
        def page(j, carry):
            phys = bt_ref[row, lax.add(first, j)]
            for i, (pool, buf) in enumerate(zip(pools, bufs)):
                copy = pltpu.make_async_copy(pool.at[phys], buf.at[slot, j],
                                             sem.at[i, slot])
                if wait:
                    copy.wait()
                else:
                    copy.start()
            return carry

        lax.fori_loop(0, live, page, None)


def _wait_group(bufs, sem, slot):
    """Wait for a full group in slot `slot`, however it was started: one
    wait a pool (it reads only the destination's size)."""
    from jax.experimental.pallas import tpu as pltpu

    for i, buf in enumerate(bufs):
        pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                              sem.at[i, slot]).wait()


def _group_heads(buf, slot):
    """Slot `slot` of a page-major buffer as (Hkv, pages * bs, D): a
    head's (bs, D) slab of a page is whole tiles, so this moves nothing."""
    _, pages, hkv, bs, D = buf.shape
    return lax.reshape(lax.transpose(buf[slot], (1, 0, 2, 3)),
                       (hkv, pages * bs, D))


def _paged_kernel(bt_ref, len_ref, *refs, scale, block_size, pages,
                  tiles, window, v_lanes=None, partial=False):
    """One sequence: every kv head, groups of `pages` pages.  `refs`: the
    queries, the HBM pools, the output, a page-major buffer a pool, then the
    DMA semaphores, the ring's state and the softmax state.  Two pools are
    keys and values; ONE is a latent pool (`v_lanes`): the values are the
    first `v_lanes` lanes of the keys' own buffer, so nothing is copied
    twice, and p and v meet in the pool's type (64 query heads share every
    position: the product is as large as the scores').

    `partial`: one of the two calls of a step with shared prefixes.  `refs`
    then start with a third prefetched scalar a sequence, its first attended
    position (where a `window` has context - window), and two outputs follow
    the first, the softmax as it stands: the accumulator, and the running max
    (lane 0) and normalizer (the other lanes) in one row of lanes.

    The grid is the batch, run in order, and the (sequence, group) pairs
    form ONE stream through a ring of VMEM slots (`ring`, in SMEM, holds
    the stream's head: the next pair to start, the slot it goes to, the
    slot the next group is read from, and how many slots are free).
    Every step first gives the free slots to the stream's next groups,
    this sequence's or a later one's (the first step of the call fills
    the whole ring), so a sequence finds its first groups in flight or
    landed, and an empty sequence neither starts nor waits for anything.
    A page with no live position starts no DMA, so DMAs and trips follow
    what is cached, not the table's width.

    A trip is a serial chain (scores -> max -> exp -> sum -> p . v) that
    nothing overlaps across trips, so where a context has them one
    softmax update spans `tiles` resident groups (one max, one rescale of
    the accumulator); what is left over goes a group at a time, and a
    short context pays for no position it does not hold.  With a `window`
    (a sliding layer) the stream of a sequence starts at the group that
    holds its first attended position, context - window, and that group
    is masked."""
    from jax.experimental import pallas as pl

    start_ref = None
    if partial:
        start_ref, *refs = refs
    q_ref, *refs = refs
    outs = 3 if partial else 1
    np_ = (len(refs) - outs - 5) // 2         # pools
    pools, o_refs = refs[:np_], refs[np_:np_ + outs]
    bufs = refs[np_ + outs:2 * np_ + outs]
    sem, ring, m_ref, l_ref, acc_ref = refs[2 * np_ + outs:]
    pv_in = jnp.float32 if v_lanes is None else bufs[0].dtype
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    depth = bufs[0].shape[0]
    span = pages * block_size                 # positions per group
    ctx = len_ref[b]

    def first_group(row):
        if partial:
            return lax.div(start_ref[row], span)
        if window is None:
            return jnp.int32(0)
        return lax.div(lax.max(lax.sub(len_ref[row], window), 0), span)

    def end_group(row):
        return _cdiv(len_ref[row], span)

    def copy_group(row, group, slot, wait=False):
        first = lax.mul(group, pages)
        live = lax.clamp(0, lax.sub(_cdiv(len_ref[row], block_size), first),
                         pages)
        _copy_group(bt_ref, row, first, live, pools, bufs, sem, slot, wait)

    def live_row(row):
        """The first row from `row` on that has anything cached."""
        return lax.while_loop(
            lambda r: lax.bitwise_and(
                lax.lt(r, rows),
                lax.eq(len_ref[lax.min(r, lax.sub(rows, 1))], 0)),
            lambda r: lax.add(r, 1), row)

    def start_next(*_):
        row, group, slot = ring[0], ring[1], ring[2]

        @pl.when(lax.lt(row, rows))
        def _():
            copy_group(row, group, slot)
            after_group = lax.add(group, 1)
            more = lax.lt(after_group, end_group(row))
            after = live_row(lax.select(more, row, lax.add(row, 1)))
            ring[0] = after
            ring[1] = lax.select(
                more, after_group,
                first_group(lax.min(after, lax.sub(rows, 1))))
            ring[2] = lax.bitwise_and(lax.add(slot, 1), depth - 1)

    @pl.when(lax.eq(b, 0))
    def _first():
        # Page slots a partly live group leaves unfilled are read under
        # the mask with p == 0: they must hold numbers (0 * NaN), which
        # fresh VMEM need not.  Later they hold older pool pages.
        bufs[-1][...] = jnp.zeros_like(bufs[-1])
        row = live_row(jnp.int32(0))
        ring[0] = row
        ring[1] = first_group(lax.min(row, lax.sub(rows, 1)))
        ring[2] = 0
        ring[3] = 0
        ring[4] = depth

    _reset(m_ref, l_ref, acc_ref)
    lo = None if window is None else lax.sub(ctx, window)
    if partial:
        lo = start_ref[b]

    def step(n):
        def attend(g):
            first = ring[3]
            slots = [lax.bitwise_and(lax.add(first, t), depth - 1)
                     for t in range(n)]
            for slot in slots[:-1]:     # all but a sequence's last are full
                _wait_group(bufs, sem, slot)
            copy_group(b, lax.add(g, n - 1), slots[-1], wait=True)
            mats = [jnp.concatenate([_group_heads(buf, slot)
                                     for slot in slots], axis=1)
                    for buf in bufs]
            k = mats[0]
            v = mats[-1] if v_lanes is None else k[..., :v_lanes]
            _attend_group(q_ref[0], k, v, lax.mul(g, span), ctx, scale,
                          m_ref, l_ref, acc_ref, lo, pv_in)
            ring[3] = lax.bitwise_and(lax.add(first, n), depth - 1)
            ring[4] = n
            return lax.add(g, n)
        return attend

    g0 = first_group(b)
    trips = lax.sub(end_group(b), g0)
    wide = lax.div(trips, tiles)        # steps of `tiles` groups, then of 1

    def trip(i, g):
        lax.fori_loop(0, ring[4], start_next, None)
        if tiles == 1:
            return step(1)(g)
        return lax.cond(lax.lt(i, wide), step(tiles), step(1), g)

    lax.fori_loop(0, lax.sub(lax.add(wide, trips), lax.mul(wide, tiles)),
                  trip, g0)
    _write_out(o_refs[0], l_ref, acc_ref)
    if partial:
        _, acc_out, ml_out = o_refs
        acc_out[0] = acc_ref[...]
        lane = lax.broadcasted_iota(jnp.int32, ml_out.shape[1:], 2)
        ml_out[0] = jnp.where(lax.eq(lane, 0), m_ref[...], l_ref[...])


def _paged_kernel_narrow(bt_ref, len_ref, q_ref, *refs, scale, block_size,
                         pages, window):
    """One (sequence, page group) program for a head size Mosaic cannot
    slice out of an HBM pool (D not a multiple of 128 lanes).  The
    group's pages are `pages` whole-page k and v blocks the pipeline
    fetched through the block table; a dead page's index map stays on
    the block it held, so it is not fetched again, and a dead group
    does no arithmetic.  Same mathematics as `_paged_kernel` (a `window`
    masks here, and skips the arithmetic of the groups before it, not
    their fetches)."""
    from jax.experimental import pallas as pl

    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pages:]
    b, g = pl.program_id(0), pl.program_id(1)
    span = pages * block_size
    ctx = len_ref[b]

    @pl.when(g == 0)
    def _init():
        _reset(m_ref, l_ref, acc_ref)

    lo = None if window is None else ctx - window
    live = g * span < ctx
    if window is not None:
        live = jnp.logical_and(live, (g + 1) * span > lo)

    @pl.when(live)
    def _body():
        k, v = (jnp.concatenate([r[0] for r in rs], axis=1)
                for rs in (k_refs, v_refs))
        _attend_group(q_ref[0], k, v, g * span, ctx, scale,
                      m_ref, l_ref, acc_ref, lo)

    @pl.when(g == pl.num_programs(1) - 1)
    def _finish():
        _write_out(o_ref, l_ref, acc_ref)


def _stream_call(qg, pools, block_tables, context_lens, starts=None, *,
                 scale, interpret, name, window=None, v_lanes=None):
    """`_paged_kernel` over qg [N, Hkv, R, D] (R whole sublane tiles: the
    rows one program holds of every kv head) and the N rows of the table
    -> o [N, Hkv, R, Dv].  With `starts` [N] the call is `partial`: -> (o,
    accumulator [N, Hkv, R, Dv], max and normalizer [N, Hkv, R, 128]), the
    last two float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, hkv, R, D = qg.shape
    pool = pools[0]
    bs, W = pool.shape[2], block_tables.shape[1]
    # (a value pool may have rows of lanes of its own: keys of 192 in 256
    # lanes beside values of 128)
    dv = pools[-1].shape[3] if v_lanes is None else v_lanes
    pages, depth, tiles = _ring_shape(
        W, hkv, bs, sum(p.shape[3] for p in pools) // len(pools),
        pool.dtype.itemsize, pools=len(pools))

    def q_index(b, *_):
        return (b, 0, 0, 0)

    def rows(lanes):
        return pl.BlockSpec((1, hkv, R, lanes), q_index)

    partial = starts is not None
    # A q block of hundreds of rows (the latent pool's 8 members x 64 heads)
    # takes more than the default scoped VMEM, as `_mla_prefix_fwd`'s does.
    # Asked for only there: with it on every shared call the TPU compiler
    # crashed on LFM2's fused programs (its memory-space assignment; PR 49).
    vmem_limit = 48 * 2 ** 20 if hkv * R * dv * 4 >= 2 ** 20 else None
    scalars = (block_tables.astype(jnp.int32), context_lens)
    out_specs = rows(dv)
    out_shape = jax.ShapeDtypeStruct((N, hkv, R, dv), qg.dtype)
    if partial:
        scalars += (starts.astype(jnp.int32),)
        out_specs = [out_specs, rows(dv), rows(_LANES)]
        out_shape = [out_shape] + [
            jax.ShapeDtypeStruct((N, hkv, R, lanes), jnp.float32)
            for lanes in (dv, _LANES)]
    # The pools stay in HBM: the body copies whole pages, addressed
    # through the scalar-prefetched block table.
    return pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, block_size=bs,
                          pages=pages, tiles=tiles, window=window,
                          v_lanes=v_lanes, partial=partial),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(N,),
            in_specs=[rows(D)] + [pl.BlockSpec(
                memory_space=pltpu.MemorySpace.ANY)] * len(pools),
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((depth, pages, hkv, bs, p.shape[3]), p.dtype)
                for p in pools] + [
                pltpu.SemaphoreType.DMA((len(pools), depth)),
                pltpu.SMEM((5,), jnp.int32),
                pltpu.VMEM((hkv, R, 1), jnp.float32),
                pltpu.VMEM((hkv, R, 1), jnp.float32),
                pltpu.VMEM((hkv, R, dv), jnp.float32)]),
        out_shape=out_shape,
        # Softmax state and the DMA stream cross programs: in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name=name,
    )(*scalars, qg, *pools)


def _sublane_rows(qg):
    """qg [.., R, D] with zero rows up to whole sublane tiles."""
    pad = -qg.shape[-2] % _SUBLANES
    if not pad:
        return qg
    return jnp.pad(qg, [(0, 0)] * (qg.ndim - 2) + [(0, pad), (0, 0)])


def _attend_shared(qg, pools, block_tables, context_lens, shared: SharedRows,
                   **kw):
    """qg [B, Hkv, G, D] -> [B, Hkv, G, Dv]: every sequence over what is
    its own, every program of `shared` over what its members share (their
    queries side by side in its q block: row j * G + g is member j's),
    merged by the softmax statistics in float32.  A step with no program is
    the first call alone, normalised by the kernel as ever."""
    (B, hkv, G, D), (P, K) = qg.shape, shared.members.shape
    o, acc, ml = _stream_call(_sublane_rows(qg), pools, block_tables,
                              context_lens, shared.starts, **kw)

    def merged():
        mine, m, l = acc[:, :, :G], ml[:, :, :G, :1], ml[:, :, :G, 1:2]
        qs = qg[shared.members].transpose(0, 2, 1, 3, 4).reshape(
            P, hkv, K * G, D)
        _, acc_s, ml_s = _stream_call(
            qs, pools, shared.tables, shared.lens,
            jnp.zeros_like(shared.lens), **kw)

        def of_slots(a, none):      # [P, Hkv, K * G, x] -> [B, Hkv, G, x]
            a = a.reshape(P, hkv, K, G, -1).transpose(0, 2, 1, 3, 4).reshape(
                P * K, hkv, G, -1)[jnp.maximum(shared.place, 0)]
            return jnp.where((shared.place >= 0)[:, None, None, None], a,
                             none)

        m_s, l_s = of_slots(ml_s[..., :1], NEG_INF), of_slots(ml_s[..., 1:2],
                                                              0.0)
        m_new = jnp.maximum(m, m_s)
        a, b = jnp.exp(m - m_new), jnp.exp(m_s - m_new)
        l_new = l * a + l_s * b
        return ((mine * a + of_slots(acc_s, 0.0) * b)   # zero-length row ->
                / jnp.where(l_new == 0.0, 1.0, l_new)).astype(o.dtype)  # 0

    return lax.cond(shared.some, merged, lambda: o[:, :, :G])


def _paged_fwd(q, k_pool, v_pool, block_tables, context_lens, *shared, scale,
               window, interpret):
    B, H, D = q.shape
    hkv, bs = k_pool.shape[1], k_pool.shape[2]
    W = block_tables.shape[1]
    groups = H // hkv
    qg = q.reshape(B, hkv, groups, D).astype(
        jnp.promote_types(q.dtype, k_pool.dtype))
    # The kernels walk the table as far as the lengths say.
    context_lens = jnp.minimum(context_lens.astype(jnp.int32), W * bs)
    kw = dict(scale=scale, interpret=interpret, name=scopes.PAGED_ATTENTION)
    if shared:
        o = _attend_shared(qg, (k_pool, v_pool), block_tables, context_lens,
                           SharedRows(*shared), **kw)
    elif D % _LANES == 0:
        o = _stream_call(_sublane_rows(qg), (k_pool, v_pool), block_tables,
                         context_lens, window=window, **kw)
    else:
        o = _narrow_call(_sublane_rows(qg), k_pool, v_pool, block_tables,
                         context_lens, scale=scale, window=window,
                         interpret=interpret)
    return o[:, :, :groups].reshape(B, H, o.shape[-1]).astype(q.dtype)


def _narrow_call(qg, k_pool, v_pool, block_tables, context_lens, *, scale,
                 window, interpret):
    """`_paged_kernel_narrow` over qg [B, Hkv, R, D]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, hkv, gp, D = qg.shape
    bs, W = k_pool.shape[2], block_tables.shape[1]
    pages = _ring_shape(W, hkv, bs, D, k_pool.dtype.itemsize)[0]

    def q_index(b, *_):
        return (b, 0, 0, 0)

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

    return pl.pallas_call(
        functools.partial(_paged_kernel_narrow, scale=scale, block_size=bs,
                          pages=pages, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, -(-W // pages)),
            in_specs=[pl.BlockSpec((1, hkv, gp, D), q_index)] + [
                pl.BlockSpec((1, hkv, bs, D), page_index(j))
                for j in range(pages)] * 2,
            out_specs=pl.BlockSpec((1, hkv, gp, D), q_index),
            scratch_shapes=[pltpu.VMEM((hkv, gp, 1), jnp.float32),
                            pltpu.VMEM((hkv, gp, 1), jnp.float32),
                            pltpu.VMEM((hkv, gp, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, hkv, gp, D), qg.dtype),
        # Softmax state crosses programs: in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=scopes.PAGED_ATTENTION,
    )(block_tables.astype(jnp.int32), context_lens, qg,
      *([k_pool] * pages + [v_pool] * pages))


def _validate_paged(q, k_pool, v_pool):
    H, D = q.shape[1], q.shape[2]
    bs, lanes = k_pool.shape[2], k_pool.shape[3]
    # heads side by side (f of D in a row of lanes) share one layout for
    # keys and values; a head alone in its rows of lanes (D, or D padded
    # with zeros: `key_lanes`) may have values of another width
    alone = lanes % D != 0 or lanes == D
    if k_pool.shape[:3] != v_pool.shape[:3] or lanes < D or (
            not alone and k_pool.shape != v_pool.shape):
        raise ValueError(
            f"paged attention: pools must be [NB, Hkv / f, bs, f * {D}] (or "
            f"keys [NB, Hkv, bs, >= {D}] beside values [NB, Hkv, bs, Dv]), "
            f"got k {k_pool.shape} v {v_pool.shape}")
    hkv = k_pool.shape[1] * (1 if alone else lanes // D)
    if H % hkv:
        raise ValueError(
            f"paged attention: H={H} must be a multiple of Hkv={hkv}")
    if bs % _SUBLANES:
        raise ValueError(
            f"paged attention kernel: kv block size {bs} must be a "
            f"multiple of {_SUBLANES} (the (bs, D) tile's sublane dim)")


def _unshared(reference):
    """A reference form as the other platforms' branch of a kernel that takes
    `SharedRows`: a grouping changes no result."""
    def gather(q, *args):
        return reference(q, *args[:len(args) - len(SharedRows._fields)])
    return gather


@functools.partial(jax.jit, static_argnames=("scale", "window"))
def paged_attention_kernel(q, k_pool, v_pool, block_tables, context_lens,
                           scale: Optional[float] = None,
                           window: Optional[int] = None,
                           shared: Optional[SharedRows] = None) -> jax.Array:
    """Pallas paged attention: the compiled kernel where the program is
    lowered for a TPU, the Pallas interpreter elsewhere off a TPU host (CPU
    parity tests; `compiled_on_tpu`).  Jitted so that a process traces
    the kernel once per shape: a decode program reaches it through a layer
    scan inside a step scan, and an engine warms up six such programs —
    traced each time, the kernel was most of a warm start."""
    _validate_paged(q, k_pool, v_pool)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[2])
    q = key_lanes(q, k_pool)
    kw = dict(scale=scale, window=window)
    reference = functools.partial(paged_attention_reference, **kw)
    if window is not None or k_pool.shape[3] % _LANES:
        shared = None       # a sliding layer and the narrow form: as they were
    if shared is not None:
        reference = _unshared(reference)
    return _side_by_side(
        functools.partial(
            compiled_on_tpu, functools.partial(_paged_fwd, **kw),
            gather=reference),
        q, k_pool, v_pool, block_tables, context_lens, *(shared or ()))


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: jax.Array, context_lens: jax.Array,
                    scale: Optional[float] = None,
                    impl: str = "auto",
                    window: Optional[int] = None,
                    shared: Optional[SharedRows] = None) -> jax.Array:
    """Dispatcher.  "auto" is the Pallas kernel on a TPU backend — a
    shape the kernel cannot take raises there, it never quietly becomes
    the gather — and the gather reference on any other backend.  With a
    `window` only the last `window` positions are attended (the query,
    at context_lens - 1, among them).  `shared`: the prefixes that sets of
    the B sequences share, which the kernel then reads once a set (the
    reference gathers every sequence's own table whatever is shared).

    Decode has no backward pass, so there is no custom VJP — the
    reference path stays differentiable by construction if anyone ever
    scores with it.
    """
    if impl == "kernel" or (impl == "auto"
                            and jax.default_backend() == "tpu"):
        return paged_attention_kernel(q, k_pool, v_pool, block_tables,
                                      context_lens, scale, window, shared)
    if impl not in ("auto", "reference"):
        raise ValueError(f"unknown paged attention impl {impl!r}")
    return paged_attention_reference(q, k_pool, v_pool, block_tables,
                                     context_lens, scale, window)


# ===========================================================================
# Prefix attention: a prefill chunk's queries over their paged prefix
# ===========================================================================
# A chunk of P new positions per sequence attends to what the sequence has
# cached (its prefix, possibly thousands of positions, shared pages among
# them) and causally to itself.  The chunk's own K/V are scattered into
# the pool BEFORE this runs, so every key is read from the pool through
# the block table and one position rule covers both parts:
#
#   key j is visible to the query at position i  iff  j <= i
#                                   and (no window or  i - j < window)
#
# q:           [N, P, H, D]       queries of the chunk, position
#                                 prefix_lens[n] + p
# k/v pool:    [NB, Hkv, bs, D]   one layer's pool
# block_tables [N, W]             each row's table
# prefix_lens, suffix_lens [N]    cached before the chunk; live queries
# -> out       [N, P, H, D]       (rows p >= suffix_lens[n]: unspecified)
_PREFIX_SPAN = 256          # cached positions one DMA group spans
_PREFIX_QUERIES = 128       # queries one program holds (x the GQA group)


def prefix_attention_reference(q, k_pool, v_pool, block_tables,
                               prefix_lens, suffix_lens,
                               scale: Optional[float] = None,
                               window: Optional[int] = None) -> jax.Array:
    """Gather the whole table window and mask by position (small sizes:
    the scores are [N, H, P, W * bs] float32)."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[3])
    q = key_lanes(q, k_pool)
    N, P, H, D = q.shape
    hkv, bs = k_pool.shape[1] * k_pool.shape[3] // D, k_pool.shape[2]
    dv = _value_dim(D, k_pool, v_pool)
    M = block_tables.shape[1] * bs

    def rows(pool, d):      # [N, W, Hkv, bs, d] -> [N, Hkv, M, d]
        return _pool_heads(jnp.take(pool, block_tables, axis=0), d
                           ).transpose(0, 2, 1, 3, 4).reshape(
            N, hkv, M, d).astype(jnp.float32)

    k, v = rows(k_pool, D), rows(v_pool, dv)
    qg = q.reshape(N, P, hkv, H // hkv, D).astype(jnp.float32)
    s = jnp.einsum("nphgd,nhmd->nhgpm", qg, k) * scale
    qpos = prefix_lens[:, None] + jnp.arange(P)[None, :]        # [N, P]
    kpos = jnp.arange(M)[None, None, :]
    seen = (kpos <= qpos[..., None]) \
        & (kpos < (prefix_lens + suffix_lens)[:, None, None])
    if window is not None:
        seen &= kpos > qpos[..., None] - window
    seen = seen[:, None, None]                                  # [N,1,1,P,M]
    w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    w = jnp.where(seen, w, 0.0)             # a dead query row -> zeros
    o = jnp.einsum("nhgpm,nhmd->nphgd", w, v)
    return o.reshape(N, P, H, dv).astype(q.dtype)


def _prefix_kernel(bt_ref, pre_ref, suf_ref, qoff_ref, q_ref, *refs,
                   scale, block_size, pages, tq, window, v_lanes=None):
    """One (sequence, tile of `tq` queries) program: every kv head, the
    tile's queries of every head of the group as rows (query-major), a
    group of `pages` pages at a time from the first position any of the
    tile's live queries may see to the last, double-buffered.  A tile
    with no live query does nothing.  `refs`: the HBM pools, the output, a
    buffer a pool, the semaphores and the softmax state; ONE pool is a
    latent pool, as in `_paged_kernel`."""
    from jax.experimental import pallas as pl

    np_ = (len(refs) - 5) // 2                # pools
    pools, o_ref, bufs = refs[:np_], refs[np_], refs[np_ + 1:2 * np_ + 1]
    sem, m_ref, l_ref, acc_ref = refs[2 * np_ + 1:]
    pv_in = jnp.float32 if v_lanes is None else bufs[0].dtype
    n, t = pl.program_id(0), pl.program_id(1)
    span = pages * block_size
    pre = pre_ref[n]
    first_q = lax.add(pre, lax.mul(t, tq))    # position of the first query
    live_q = lax.clamp(0, lax.sub(suf_ref[n], lax.mul(t, tq)), tq)
    hi = lax.select(lax.gt(live_q, 0), lax.add(first_q, live_q),
                    jnp.int32(0))                 # keys below hi
    lo = 0 if window is None else lax.max(lax.sub(first_q, window - 1), 0)
    g0 = 0 if window is None else lax.div(lo, span)
    trips = lax.max(lax.sub(_cdiv(hi, span), g0), 0)

    def copy_group(group, slot, wait=False):
        first = lax.mul(group, pages)
        live = lax.clamp(0, lax.sub(_cdiv(hi, block_size), first), pages)
        _copy_group(bt_ref, n, first, live, pools, bufs, sem, slot, wait)

    @pl.when(lax.bitwise_and(lax.eq(n, 0), lax.eq(t, 0)))
    def _first():
        # Page slots a partly live group leaves unfilled are read under
        # the mask with p == 0: they must hold numbers, not NaN.
        bufs[-1][...] = jnp.zeros_like(bufs[-1])

    _reset(m_ref, l_ref, acc_ref)
    q = q_ref[0]                              # (Hkv, tq * G, D)
    qpos = first_q + qoff_ref[...]            # (tq * G, 1)

    def group_step(i, carry):
        """Trip -1 only starts the first group: the stream has one place
        where copies start."""
        g = lax.add(g0, i)
        slot = lax.bitwise_and(i, 1)

        @pl.when(lax.lt(lax.add(i, 1), trips))
        def _():
            copy_group(lax.add(g, 1), lax.sub(1, slot))

        @pl.when(lax.ge(i, 0))
        def _():
            copy_group(g, slot, wait=True)
            k = _group_heads(bufs[0], slot)
            s = _scores(q, k) * scale
            kpos = g * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            seen = (kpos <= qpos[None]) & (kpos < hi)
            if window is not None:
                seen &= kpos > qpos[None] - window
            v = (_group_heads(bufs[1], slot) if v_lanes is None
                 else k[..., :v_lanes])
            _softmax_update(s, seen, v, m_ref, l_ref, acc_ref,
                            rows_differ=True, pv_in=pv_in)

        return carry

    lax.fori_loop(-1, trips, group_step, None)
    _write_out(o_ref, l_ref, acc_ref)


def _prefix_fwd(q, k_pool, v_pool, block_tables, prefix_lens, suffix_lens,
                *, scale, window, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, P, H, D = q.shape
    hkv, bs = k_pool.shape[1], k_pool.shape[2]
    dv = v_pool.shape[3]        # (its own rows of lanes: `_value_dim`)
    W = block_tables.shape[1]
    G = H // hkv
    tq = min(_PREFIX_QUERIES, P)
    if P % tq:
        raise ValueError(f"prefix attention kernel: a chunk of {P} queries "
                         f"must be a multiple of {tq}")
    # (Mosaic slices a page out of an HBM pool only where the head size is
    # a multiple of 128 lanes, and refuses any other when it compiles.)
    pages = max(1, min(_PREFIX_SPAN // bs, W))
    rows = tq * G
    # query-major rows: row r of a tile is query r // G, head r % G
    qg = q.reshape(N, P, hkv, G, D).transpose(0, 2, 1, 3, 4).reshape(
        N, hkv, P * G, D).astype(jnp.promote_types(q.dtype, k_pool.dtype))
    qoff = (jnp.arange(rows, dtype=jnp.int32) // G)[:, None]

    def q_index(n, t, *_):
        return (n, 0, t, 0)

    hi_all = jnp.minimum(prefix_lens + suffix_lens, W * bs).astype(jnp.int32)
    prefix_lens = jnp.minimum(prefix_lens.astype(jnp.int32), hi_all)
    o = pl.pallas_call(
        functools.partial(_prefix_kernel, scale=scale, block_size=bs,
                          pages=pages, tq=tq, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N, P // tq),
            in_specs=[pl.BlockSpec((rows, 1), lambda n, t, *_: (0, 0)),
                      pl.BlockSpec((1, hkv, rows, D), q_index),
                      pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
                      pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
            out_specs=pl.BlockSpec((1, hkv, rows, dv), q_index),
            scratch_shapes=[
                pltpu.VMEM((2, pages, hkv, bs, D), k_pool.dtype),
                pltpu.VMEM((2, pages, hkv, bs, dv), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hkv, rows, 1), jnp.float32),
                pltpu.VMEM((hkv, rows, 1), jnp.float32),
                pltpu.VMEM((hkv, rows, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((N, hkv, P * G, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 2 ** 20),
        interpret=interpret,
        name=scopes.PREFIX_ATTENTION,
    )(block_tables.astype(jnp.int32), prefix_lens,
      (hi_all - prefix_lens).astype(jnp.int32), qoff, qg, k_pool, v_pool)
    return o.reshape(N, hkv, P, G, dv).transpose(0, 2, 1, 3, 4).reshape(
        N, P, H, dv)


@functools.partial(jax.jit, static_argnames=("scale", "window"))
def prefix_attention_kernel(q, k_pool, v_pool, block_tables, prefix_lens,
                            suffix_lens, scale: Optional[float] = None,
                            window: Optional[int] = None) -> jax.Array:
    _validate_paged(q[:, 0], k_pool, v_pool)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[3])
    q = key_lanes(q, k_pool)
    kw = dict(scale=scale, window=window)
    return _side_by_side(
        functools.partial(
            compiled_on_tpu, functools.partial(_prefix_fwd, **kw),
            gather=functools.partial(prefix_attention_reference, **kw)),
        q, k_pool, v_pool, block_tables, prefix_lens, suffix_lens)


def prefix_attention(q, k_pool, v_pool, block_tables, prefix_lens,
                     suffix_lens, scale: Optional[float] = None,
                     impl: str = "auto",
                     window: Optional[int] = None) -> jax.Array:
    """Dispatcher: "auto" is the kernel on a TPU backend where the pool's
    rows are whole 128-lane tiles (one head, or narrow heads side by side:
    Mosaic slices a page out of an HBM pool at no other), the gather
    elsewhere and for every other pool: the prefill of arch "llama" /
    "gpt2" gathered every row's table at every head size before it came
    here."""
    args = (q, k_pool, v_pool, block_tables, prefix_lens, suffix_lens,
            scale, window)
    if impl == "kernel" or (impl == "auto"
                            and jax.default_backend() == "tpu"
                            and k_pool.shape[3] % _LANES == 0):
        return prefix_attention_kernel(*args)
    if impl not in ("auto", "reference"):
        raise ValueError(f"unknown prefix attention impl {impl!r}")
    return prefix_attention_reference(*args)


# ===========================================================================
# Latent rows (MLA, absorbed): ONE pool, no head axis
# ===========================================================================
# A position of a latent-attention layer leaves one row behind: c values of
# compressed keys-and-values and r rotated key values that every head shares
# (models/axk1.py).  In the absorbed form every one of the H query heads
# scores its [c + r] query against that row, and what it gets back is the
# softmax-weighted sum of the rows' first c values: multi-query attention
# over ONE key head whose value is the key's first `v_dim` lanes.
#
# pool:  [NB, 1, bs, Dp]   a row in Dp >= c + r lanes, Dp whole 128-lane
#                          rows (576 values lie in 640: Mosaic copies a page
#                          out of an HBM pool only at whole rows of lanes),
#                          the lanes past c + r zero
# q:     [.., H, c + r]    (padded with zeros to Dp here)
# -> out [.., H, v_dim]
#
# The kernels are `_paged_kernel` and `_prefix_kernel` with one pool: the
# same page stream, ring and `_copy_group`, one DMA a page, and p . v taken
# from the buffer the scores were taken from.  `scale` is the caller's (the
# model's: it is not the row's width to the -1/2).
def latent_lanes(width: int) -> int:
    """The lanes a latent row of `width` values takes in the pool."""
    return -(-width // _LANES) * _LANES


def _validate_latent(q, pool, v_dim):
    if pool.ndim != 4 or pool.shape[1] != 1 or pool.shape[3] < q.shape[-1] \
            or not 0 < v_dim <= pool.shape[3]:
        raise ValueError(
            f"latent attention: the pool must be [NB, 1, bs, >= "
            f"{q.shape[-1]}] and hold {v_dim} value lanes, got {pool.shape}")


def to_lanes(x, pool):
    """x [..., width] (a query, or rows to write) with zeros up to the
    pool's lanes."""
    pad = pool.shape[3] - x.shape[-1]
    return x if pad == 0 else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def mla_paged_attention_reference(q, pool, block_tables, context_lens, *,
                                  scale: float, v_dim: int) -> jax.Array:
    """Gather-based (any backend): q [B, H, Dq] over each sequence's rows."""
    B, H, _ = q.shape
    bs, Dp = pool.shape[2], pool.shape[3]
    M = block_tables.shape[1] * bs
    k = jnp.take(pool[:, 0], block_tables, axis=0).reshape(
        B, M, Dp).astype(jnp.float32)
    s = jnp.einsum("bhd,bmd->bhm", to_lanes(q, pool).astype(jnp.float32),
                   k) * scale
    mask = (jnp.arange(M)[None, :] < context_lens[:, None])[:, None]
    w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    w = jnp.where(mask, w, 0.0)             # a zero-length row -> zeros
    return jnp.einsum("bhm,bmc->bhc", w, k[..., :v_dim]).astype(q.dtype)


def mla_prefix_attention_reference(q, pool, block_tables, prefix_lens,
                                   suffix_lens, *, scale: float,
                                   v_dim: int) -> jax.Array:
    """Gather the whole table window and mask by position (small sizes: the
    scores are [N, H, P, W * bs] float32)."""
    N, P, H, _ = q.shape
    bs, Dp = pool.shape[2], pool.shape[3]
    M = block_tables.shape[1] * bs
    k = jnp.take(pool[:, 0], block_tables, axis=0).reshape(
        N, M, Dp).astype(jnp.float32)
    s = jnp.einsum("nphd,nmd->nhpm", to_lanes(q, pool).astype(jnp.float32),
                   k) * scale
    qpos = prefix_lens[:, None] + jnp.arange(P)[None, :]        # [N, P]
    kpos = jnp.arange(M)[None, None, :]
    seen = ((kpos <= qpos[..., None])
            & (kpos < (prefix_lens + suffix_lens)[:, None, None]))[:, None]
    w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    w = jnp.where(seen, w, 0.0)             # a dead query row -> zeros
    return jnp.einsum("nhpm,nmc->nphc", w, k[..., :v_dim]).astype(q.dtype)


def _mla_paged_fwd(q, pool, block_tables, context_lens, *shared, scale,
                   v_dim, interpret):
    B, H, _ = q.shape
    bs, Dp = pool.shape[2], pool.shape[3]
    W = block_tables.shape[1]
    if Dp % _LANES or v_dim % _LANES or bs % _SUBLANES:
        raise ValueError(
            f"latent paged attention kernel: pool rows of {Dp} lanes, values "
            f"of {v_dim}, blocks of {bs}: whole 128-lane rows and whole "
            f"{_SUBLANES}-row tiles only")
    qg = to_lanes(q, pool).astype(jnp.promote_types(q.dtype, pool.dtype))[
        :, None]                                            # [B, 1, H, Dp]
    context_lens = jnp.minimum(context_lens.astype(jnp.int32), W * bs)
    kw = dict(scale=scale, v_lanes=v_dim, interpret=interpret,
              name=scopes.MLA_PAGED_ATTENTION)
    if shared:
        o = _attend_shared(qg, (pool,), block_tables, context_lens,
                           SharedRows(*shared), **kw)
    else:
        o = _stream_call(_sublane_rows(qg), (pool,), block_tables,
                         context_lens, **kw)
    return o[:, 0, :H].astype(q.dtype)


# Query rows (queries x heads) one program of the latent prefix kernel
# holds: with 64 heads a query, 16 queries.  A suffix of a few tens of
# tokens then pays for few dead queries' arithmetic (139 kFLOP a query and
# position, against 1.3 KB read), at the price of streaming the prefix once
# a tile.
_LATENT_PREFIX_ROWS = 1024


def _mla_prefix_fwd(q, pool, block_tables, prefix_lens, suffix_lens, *,
                    scale, v_dim, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, P, H, _ = q.shape
    bs, Dp = pool.shape[2], pool.shape[3]
    W = block_tables.shape[1]
    if Dp % _LANES or v_dim % _LANES:
        raise ValueError(
            f"latent prefix attention kernel: pool rows of {Dp} lanes, "
            f"values of {v_dim}: whole 128-lane rows only")
    tq = min(P, max(_SUBLANES, _LATENT_PREFIX_ROWS // H))
    if P % tq:
        raise ValueError(f"latent prefix attention kernel: a chunk of {P} "
                         f"queries must be a multiple of {tq}")
    pages = max(1, min(_PREFIX_SPAN // bs, W))
    rows = tq * H
    # query-major rows: row r of a tile is query r // H, head r % H
    qg = to_lanes(q, pool).reshape(N, 1, P * H, Dp).astype(
        jnp.promote_types(q.dtype, pool.dtype))
    qoff = (jnp.arange(rows, dtype=jnp.int32) // H)[:, None]

    def q_index(n, t, *_):
        return (n, 0, t, 0)

    hi_all = jnp.minimum(prefix_lens + suffix_lens, W * bs).astype(jnp.int32)
    prefix_lens = jnp.minimum(prefix_lens.astype(jnp.int32), hi_all)
    o = pl.pallas_call(
        functools.partial(_prefix_kernel, scale=scale, block_size=bs,
                          pages=pages, tq=tq, window=None, v_lanes=v_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N, P // tq),
            in_specs=[pl.BlockSpec((rows, 1), lambda n, t, *_: (0, 0)),
                      pl.BlockSpec((1, 1, rows, Dp), q_index),
                      pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
            out_specs=pl.BlockSpec((1, 1, rows, v_dim), q_index),
            scratch_shapes=[
                pltpu.VMEM((2, pages, 1, bs, Dp), pool.dtype),
                pltpu.SemaphoreType.DMA((1, 2)),
                pltpu.VMEM((1, rows, 1), jnp.float32),
                pltpu.VMEM((1, rows, 1), jnp.float32),
                pltpu.VMEM((1, rows, v_dim), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((N, 1, P * H, v_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 2 ** 20),
        interpret=interpret,
        name=scopes.MLA_PREFIX_ATTENTION,
    )(block_tables.astype(jnp.int32), prefix_lens,
      (hi_all - prefix_lens).astype(jnp.int32), qoff, qg, pool)
    return o.reshape(N, P, H, v_dim)


@functools.partial(jax.jit, static_argnames=("scale", "v_dim"))
def mla_paged_attention_kernel(q, pool, block_tables, context_lens, *,
                               scale: float, v_dim: int,
                               shared: Optional[SharedRows] = None
                               ) -> jax.Array:
    _validate_latent(q, pool, v_dim)
    kw = dict(scale=scale, v_dim=v_dim)
    reference = functools.partial(mla_paged_attention_reference, **kw)
    if shared is not None:
        reference = _unshared(reference)
    return compiled_on_tpu(
        functools.partial(_mla_paged_fwd, **kw), q, pool, block_tables,
        context_lens, *(shared or ()), gather=reference)


@functools.partial(jax.jit, static_argnames=("scale", "v_dim"))
def mla_prefix_attention_kernel(q, pool, block_tables, prefix_lens,
                                suffix_lens, *, scale: float,
                                v_dim: int) -> jax.Array:
    _validate_latent(q, pool, v_dim)
    kw = dict(scale=scale, v_dim=v_dim)
    return compiled_on_tpu(
        functools.partial(_mla_prefix_fwd, **kw), q, pool, block_tables,
        prefix_lens, suffix_lens,
        gather=functools.partial(mla_prefix_attention_reference, **kw))


def _latent_impl(impl: str, kernel, reference, what: str):
    if impl == "kernel" or (impl == "auto"
                            and jax.default_backend() == "tpu"):
        return kernel
    if impl not in ("auto", "reference"):
        raise ValueError(f"unknown {what} impl {impl!r}")
    return reference


def mla_paged_attention(q, pool, block_tables, context_lens, *, scale: float,
                        v_dim: int, impl: str = "auto",
                        shared: Optional[SharedRows] = None) -> jax.Array:
    """One query a sequence, q [B, H, c + r], over the sequence's latent
    rows -> [B, H, v_dim].  Dispatcher as `paged_attention`: "auto" is the
    kernel on a TPU backend, the gather on any other; `shared` as there (a
    program's q block is then 8 members x H heads over the one pool)."""
    _validate_latent(q, pool, v_dim)
    attend = _latent_impl(impl, mla_paged_attention_kernel,
                          mla_paged_attention_reference,
                          "latent paged attention")
    kw = dict(scale=scale, v_dim=v_dim)
    if attend is mla_paged_attention_kernel:
        kw.update(shared=shared)
    return attend(q, pool, block_tables, context_lens, **kw)


def mla_prefix_attention(q, pool, block_tables, prefix_lens, suffix_lens, *,
                         scale: float, v_dim: int,
                         impl: str = "auto") -> jax.Array:
    """A chunk's queries q [N, P, H, c + r], the query at position
    prefix_lens[n] + p, over the rows before and among them -> [N, P, H,
    v_dim] (rows p >= suffix_lens[n]: unspecified)."""
    _validate_latent(q, pool, v_dim)
    return _latent_impl(impl, mla_prefix_attention_kernel,
                        mla_prefix_attention_reference,
                        "latent prefix attention")(
        q, pool, block_tables, prefix_lens, suffix_lens, scale=scale,
        v_dim=v_dim)
