"""KV-cache autoregressive decoding for serving.

The reference serves LLMs by delegating to vLLM on GPU (e.g.
doc/source/serve/doc_code/vllm_example.py); the TPU-native build owns
the decode loop itself, shaped for XLA:

* FIXED shapes everywhere: each prefill width and each decode chunk
  compiles ONCE and is reused for the server's lifetime.
* One cache layout, PagedDecodeCaches: a [NB, Hkv, bs, Dh] block pool
  per layer addressed through per-slot block tables, so memory scales
  with the tokens actually cached and full blocks are shareable across
  requests.
* A decode step advances every active slot one token per call (the inner
  loop of continuous batching): one [B,1,D] layer pass, the new k/v
  written into the pool through the block table, attention over the
  slot's pages under its length.  Greedy argmax and the last-token
  feedback happen ON DEVICE, so the host costs one small [B]-int
  transfer per read.
* A prefill runs rows of a prompt's uncached SUFFIX through the layers
  (causal within the prompt), against what the request already has in
  the pool: the cached prefix is never recomputed.
* A fused dispatch's prefill pass IS its first decode step: beside the
  prompt rows it carries the next position of every slot that was already
  decoding, so every dense product of a layer reads its weights once for
  both (only attention is two calls, one per kind of row), and
  `num_steps` tokens a slot cost `num_steps` walks of the weights, as in
  a decode-only dispatch.

Everything reuses transformer.py's parameter layout (init_params),
norms and RoPE, so any trained checkpoint serves unchanged, and the
tests hold every step to greedy transformer.forward.

Architectures of unrolled layers (`cfg.layer_kinds`: models/afmoe.py,
window and full attention layers mixed; models/lfm2.py, short convolutions
and attention layers mixed; models/axk1.py, latent attention whose cache
row has no head axis; all with expert layers; models/olmo_hybrid.py, gated
delta-rule layers whose state is a matrix a head a SEQUENCE, kept by state
id beside the pages; models/qwen3_next.py, that state beside gated attention
at heads of 256, an expert layer in every layer; models/mimo_v2.py, window
layers whose keys and values are a ring a sequence, by the same state ids,
beside full layers with keys wider than values) have their paged
steps at the end of this file, built from their module's one layer
definition; the two programs an engine runs (paged_prefill_decode_packed,
paged_decode_steps) branch to them.  Both return (caches', tokens
[num_steps, B], counts): the expert layers' counts (afmoe.MOE_COUNTS), None
where a model has no unrolled layers.  What the host uploads to the first is
laid out by `FusedUpload`, here and nowhere else.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.transformer import (TransformerConfig, _norm, _rope,
                                        _w_out, unrolled)
from ray_tpu.ops import scopes


@jax.named_scope(scopes.ATTN_QKV)
def _qkv(p, h, cfg: TransformerConfig, positions):
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(h.dtype))
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"].astype(h.dtype))
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"].astype(h.dtype))
    if cfg.arch == "llama":
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mlp(p, x, cfg: TransformerConfig):
    rms = cfg.arch == "llama"
    with jax.named_scope(scopes.NORM):
        h = _norm(x, p["mlp_norm"], p.get("mlp_norm_b"), cfg.norm_eps, rms)
    with jax.named_scope(scopes.FFN_GATE_UP):
        if cfg.arch == "llama":
            gate = jnp.einsum("bsd,df->bsf", h, p["w_gate"].astype(h.dtype))
            up = jnp.einsum("bsd,df->bsf", h, p["w_up"].astype(h.dtype))
            act = jax.nn.silu(gate.astype(jnp.float32)).astype(h.dtype) * up
        else:
            up = jnp.einsum("bsd,df->bsf", h, p["w_up"].astype(h.dtype))
            up = up + p["b_up"].astype(h.dtype)
            act = jax.nn.gelu(up.astype(jnp.float32)).astype(h.dtype)
    with jax.named_scope(scopes.FFN_DOWN):
        down = jnp.einsum("bsf,fd->bsd", act, p["w_down"].astype(act.dtype))
        if cfg.arch == "gpt2":
            down = down + p["b_down"].astype(down.dtype)
        return x + down


# ===========================================================================
# Paged KV cache (block pool + per-slot block tables)
# ===========================================================================
# KV lives in fixed-size blocks from a shared pool, addressed through
# per-slot block tables: a sequence uses blocks in proportion to its
# length (no slot reserves max_len positions), and FULL prompt blocks are
# refcount-shareable across requests (the serve/llm.py prefix cache).
# Attention goes through ops/paged_attention.py: `paged_attention` for a
# decode step, `prefix_attention` for a prefill's rows (Pallas kernels on
# a TPU backend, jnp.take gather references on any other).
#
# Invariants the engine (serve/llm.py) maintains, which these kernels
# rely on:
#   * pool block 0 is a reserved scratch block: never allocated, table
#     padding points at it, and gated/over-capacity writes are
#     redirected to it — so garbage positions are always masked by
#     length and nothing reads the scratch block unmasked;
#   * a request's cached prefix is a multiple of the block size (only
#     FULL blocks are shared), so every prefill/decode write lands in a
#     block owned exclusively by that slot;
#   * admission pre-allocates blocks for prompt + max_new tokens, so
#     decode never needs to allocate (and never runs out mid-decode).


class PagedDecodeCaches(NamedTuple):
    """Block-pool KV + per-slot tables (all fixed-shape)."""

    kp: jax.Array            # [L, NB, Hkv, bs, Dh] block pool — (bs, Dh)
    vp: jax.Array            # minor: the tile the paged kernel loads
    # (unrolled layers: a tuple of L pools, each its own buffer, written in
    # place: `unrolled_pool_shape`; None at a layer that has no keys; a
    # latent layer has ONE pool, `kp`, of rows with no head axis, and its
    # `vp` is None)
    block_tables: jax.Array  # [B, W] int32 — physical block per logical
    lengths: jax.Array       # [B] int32 — tokens currently cached
    last_token: jax.Array    # [B] int32 — input to the next decode step
    # Conv layers' state, where a model has any: per layer (None at the
    # others) the conv's input `u` at the last K - 1 positions
    #   tail_pool [NB, (K-1) * D] of every COMPLETED block, under the
    #                             block's own id (one row of lanes a block:
    #                             written and read as rows, never reshaped
    #                             whole): what a prefill row, which
    #                             starts on a block boundary, starts from
    #                             (the block before it: an earlier row's, an
    #                             earlier dispatch's, or a prefix hit's)
    #   slot_tail [B, K - 1, D]   of every slot: what its next decode step
    #                             starts from, set by the row that ends its
    #                             prompt
    tail_pool: Tuple = ()
    slot_tail: Tuple = ()
    # Linear (gated delta-rule) layers' state, where a model has any: what
    # a SEQUENCE leaves behind whatever its length, indexed by a STATE ID
    # (id 0 scratch; the engine hands them out: a slot owns one while it
    # lives, the rest are checkpoints the radix cache owns), per layer (None
    # at the others)
    #   state_pool [NS + 1, H / g, dk, g * dv] float32: the recurrence's
    #                             carry, g heads side by side in whole rows
    #                             of lanes (ops/gated_delta.py)
    #   conv_pool  [NS + 1, K - 1, C]: the convolution's input at the last
    #                             K - 1 positions
    # and slot_state [B] int32: the id each slot decodes from, set by the
    # row that ends its prompt.
    state_pool: Tuple = ()
    conv_pool: Tuple = ()
    slot_state: Optional[jax.Array] = None
    # Ring (sliding-window) layers' keys and values, where a model has any:
    # the last `sliding_window` positions of a SEQUENCE, position p in slot
    # p mod window, by the same state ids (ops/window_ring.py), per layer
    # (None at the others); such a layer has no pages
    #   ring_k [NS + 1, Hkv, window, lanes(dk)],  ring_v [.., lanes(dv)]
    ring_k: Tuple = ()
    ring_v: Tuple = ()
    # The sets of slots whose tables agree over their first blocks
    # (ops/paged_attention.py SharedPrefixes), as the engine found them when
    # it last changed a table: a decode step reads such a prefix once a set.
    shared: Optional[Any] = None


def paged_table_width(max_len: int, block_size: int) -> int:
    """Logical blocks per slot (ceil)."""
    return -(-max_len // block_size)


# The mixers whose layers keep state by SEQUENCE, under a state id the
# engine hands out (serve/llm.py StateAllocator), beside or in place of pages.
STATE_MIXERS = ("linear", "ring")


def unrolled_pool_shape(cfg: TransformerConfig, num_blocks: int,
                        block_size: int, mixer: str = "full",
                        values: bool = False) -> Tuple[int, ...]:
    """One unrolled attention layer's K (`values`: V) pool, scratch block
    included.  Heads narrower than the 128 lanes lie side by side in one
    row of lanes where they fill it whole ([NB, Hkv / f, bs, f * Dh], f =
    128 / Dh): the paged kernels copy pages out of an HBM pool only at
    whole rows of lanes, and tell the layout from the shapes
    (ops/paged_attention.py).  A "latent" layer's one pool holds a row a
    position with no head axis, kv_lora_rank + qk_rope_dim values in whole
    rows of lanes ([NB, 1, bs, 640] for 576: a tenth more cache and reads
    than the model needs, for ONE page stream and a value that is the key's
    own lanes; the other layout, the latent and the rotated part in pools of
    their own, is two streams and two writes a layer).  Values `v_head_dim`
    wide have a pool of that width; a head wider than 128 lanes that does not
    fill whole rows of them lies alone in the next whole row, zeros past its
    width (keys of 192 in 256 lanes: a third more key bytes, for the same
    page stream as any other head; ops/paged_attention.py `key_lanes`)."""
    from ray_tpu.ops.paged_attention import latent_lanes
    if mixer == "latent":
        return (num_blocks + 1, 1, block_size,
                latent_lanes(cfg.kv_lora_rank + cfg.qk_rope_dim))
    dh, hkv = cfg.head_dim, cfg.kv_heads
    if values and cfg.v_head_dim:
        dh = cfg.v_head_dim
    if dh > 128:
        dh = latent_lanes(dh)
    f = 128 // dh if dh < 128 and 128 % dh == 0 else 1
    if hkv % f:
        f = 1
    return (num_blocks + 1, hkv // f, block_size, dh * f)


def block_size_of(caches: PagedDecodeCaches) -> int:
    if isinstance(caches.kp, tuple):
        return next(p for p in caches.kp if p is not None).shape[2]
    return caches.kp.shape[3]


def init_paged_caches(cfg: TransformerConfig, num_slots: int,
                      num_blocks: int, block_size: int,
                      max_len: int, num_states: int = 0
                      ) -> PagedDecodeCaches:
    """`num_blocks` USABLE blocks; one extra scratch block (id 0) is
    added internally, so pool ids run 0..num_blocks inclusive.  Unrolled
    layers get the state their mixer has: keys and values, a latent
    layer's one pool of rows, a conv's tails, or a linear layer's
    `num_states` USABLE states (and scratch id 0)."""
    w = paged_table_width(max_len, block_size)
    state = {}
    if cfg.layer_kinds is None:
        shape = (cfg.n_layers, num_blocks + 1, cfg.kv_heads, block_size,
                 cfg.head_dim)
        state.update(kp=jnp.zeros(shape, cfg.dtype),
                     vp=jnp.zeros(shape, cfg.dtype))
    else:
        conv = [m == "conv" for m, _ in cfg.layer_kinds]
        linear = [m == "linear" for m, _ in cfg.layer_kinds]
        ring = [m == "ring" for m, _ in cfg.layer_kinds]
        for name, none in (("kp", ("conv", "linear", "ring")),
                           ("vp", ("conv", "latent", "linear", "ring"))):
            state[name] = tuple(
                None if m in none else jnp.zeros(unrolled_pool_shape(
                    cfg, num_blocks, block_size, m, name == "vp"), cfg.dtype)
                for m, _ in cfg.layer_kinds)
        if any(conv):
            k1, d = cfg.conv_kernel - 1, cfg.d_model
            for name, shape in (("tail_pool", (num_blocks + 1, k1 * d)),
                                ("slot_tail", (num_slots, k1, d))):
                state[name] = tuple(
                    jnp.zeros(shape, cfg.dtype) if c else None for c in conv)
        if any(linear):
            from ray_tpu.ops import gated_delta
            shapes = (("state_pool", gated_delta.pool_shape(
                num_states, cfg.linear_heads, cfg.linear_key_dim,
                cfg.linear_value_dim), jnp.float32),
                ("conv_pool", (num_states + 1, cfg.conv_kernel - 1,
                               unrolled(cfg).conv_width(cfg)), cfg.dtype))
            for name, shape, dtype in shapes:
                state[name] = tuple(
                    jnp.zeros(shape, dtype) if c else None for c in linear)
        if any(ring):
            from ray_tpu.ops import window_ring
            shapes = window_ring.ring_shapes(
                num_states, cfg.sliding_kv_heads or cfg.kv_heads,
                cfg.sliding_window, cfg.head_dim,
                cfg.v_head_dim or cfg.head_dim)
            for name, shape in zip(("ring_k", "ring_v"), shapes):
                state[name] = tuple(
                    jnp.zeros(shape, cfg.dtype) if c else None for c in ring)
        if any(linear) or any(ring):
            state["slot_state"] = jnp.zeros((num_slots,), jnp.int32)
    if num_slots > 1:
        from ray_tpu.ops.paged_attention import no_shared_prefixes
        state["shared"] = no_shared_prefixes(num_slots)
    return PagedDecodeCaches(
        block_tables=jnp.zeros((num_slots, w), jnp.int32),
        lengths=jnp.zeros((num_slots,), jnp.int32),
        last_token=jnp.zeros((num_slots,), jnp.int32), **state)


# A request's paged prefix is streamed once per this many of its queries:
# rows narrower than this that follow each other in one request attend as
# ONE row (QueryGroups), so that what a row rounds a request up to for the
# dense products (serve/llm.py PREFILL_TILE) does not multiply the reads of
# a long prefix.
ATTENTION_ROW = 64


class QueryGroups(NamedTuple):
    """The R attention rows of a prefill whose N rows of P are narrower
    than ATTENTION_ROW: each is up to K = ATTENTION_ROW // P rows of one
    slot that follow each other (a row that starts where the one before it,
    full, ended)."""

    take: jax.Array          # [R, K] the rows an attention row is made of
    tables: jax.Array        # [R, W]
    prefix_lens: jax.Array   # [R] cached before its first row
    suffix_lens: jax.Array   # [R] live queries, 0: no attention row
    back: jax.Array          # [N] each row's place among the R * K


class PrefillRows(NamedTuple):
    """What every layer of one prefill needs of its rows (N rows of P)."""

    positions: jax.Array     # [N, P] absolute positions
    tables: jax.Array        # [N, W] each row's block table
    prefix_lens: jax.Array   # [N] cached before this chunk
    suffix_lens: jax.Array   # [N] live positions of the chunk, 0: no row
    live: jax.Array          # [N, P] bool: a real token of a valid row
    blocks: jax.Array        # [N, P] pool block each position is written to
    offsets: jax.Array       # [N, P] and where in it (scratch 0: not live)
    groups: Optional[QueryGroups] = None    # rows that attend together
    # For conv layers.  A row starts on a block boundary (the engine cuts
    # prompts into whole blocks, and a hit is whole blocks), so what lies
    # before it is the tail of the block before it.
    tail_at: Optional[jax.Array] = None      # [m, K-1] a row's positions
    #                          that are the tails of the m blocks it spans
    tail_blocks: Optional[jax.Array] = None  # [N, m] the blocks a row
    #                          completes (scratch 0: not completed)
    before_block: Optional[jax.Array] = None  # [N] the block before the row
    close_slots: Optional[jax.Array] = None  # [N] the slot whose prompt the
    #                          row ends (num_slots, dropped: none)
    # For linear layers: rows of one request follow each other in order.
    state_from: Optional[jax.Array] = None   # [N] what a row starts from: -1
    #                          the row before it, 0 zeros (position 0), else
    #                          a state id (a checkpoint after a hit; its slot's
    #                          where an earlier dispatch left the prompt)
    state_to: Optional[jax.Array] = None     # [N, 2] the ids a row's END state
    #                          is written to (0: nowhere): its slot's where the
    #                          row ends this call's share of the prompt, a
    #                          fresh checkpoint's where the engine asked


class DecodeRows(NamedTuple):
    """What every layer of one decode step needs of its B slots."""

    positions: jax.Array     # [B, 1]
    tables: jax.Array        # [B, W]
    context_lens: jax.Array  # [B] positions attended, the new one among
    active: jax.Array        # [B] bool    them; 0 for a slot that is not
    blocks: jax.Array        # [B] where the new position is written
    offsets: jax.Array       # [B]
    tail_blocks: Optional[jax.Array] = None  # [B] `blocks` where the new
    #                          position completes its block, else scratch 0
    state_ids: Optional[jax.Array] = None    # [B] a slot's state id, scratch
    #                          0 where it is not active (linear layers)
    shared: Optional[Any] = None    # ops/paged_attention.py SharedRows: the
    #                          prefixes that sets of the slots share


def prefill_rows(tables, prefix_lens, suffix_lens, valid, P: int,
                 block_size: int, slots=None, num_slots: int = 0,
                 closes=None, conv_kernel: int = 0,
                 states=None) -> PrefillRows:
    """`slots` [N] (which of `num_slots` requests a row belongs to) lets
    rows narrower than ATTENTION_ROW attend in groups; without it every
    row attends alone.  `conv_kernel` > 1: what conv layers need of the
    rows, and with `closes` [N] (the row ends its slot's prompt) which
    slots' tails the rows set.  `states` (state_from [N], state_to [N, 2]):
    what linear layers need of the rows."""
    M = tables.shape[1] * block_size
    positions = prefix_lens[:, None] + jnp.arange(P, dtype=jnp.int32)
    live = valid[:, None] & (jnp.arange(P)[None, :] < suffix_lens[:, None])
    abs_pos = jnp.minimum(positions, M - 1)
    blocks = jnp.take_along_axis(tables, abs_pos // block_size, axis=1)
    suffix_lens = jnp.where(valid, suffix_lens, 0)
    groups = None
    if slots is not None and tables.shape[0] > 1 and ATTENTION_ROW // P > 1:
        groups = _query_groups(tables, prefix_lens, suffix_lens, valid,
                               slots, P, num_slots)
    conv = {}
    if conv_kernel > 1:
        ends = jnp.arange(block_size - 1, P, block_size)     # [m]
        conv = dict(
            tail_at=ends[:, None] - jnp.arange(conv_kernel - 2, -1, -1),
            tail_blocks=jnp.where(live[:, ends], blocks[:, ends], 0),
            before_block=jnp.take_along_axis(
                tables, jnp.maximum(prefix_lens // block_size - 1, 0)[:, None],
                axis=1)[:, 0])
        if closes is not None:
            conv["close_slots"] = jnp.where(closes & valid, slots, num_slots)
    if states is not None:
        conv.update(
            state_from=jnp.where(valid, states[0], -1),
            state_to=jnp.where(valid[:, None], states[1], 0))
    return PrefillRows(positions, tables, prefix_lens, suffix_lens, live,
                       jnp.where(live, blocks, 0), abs_pos % block_size,
                       groups, **conv)


def _query_groups(tables, prefix_lens, suffix_lens, valid, slots,
                  P: int, num_slots: int) -> QueryGroups:
    N, K = slots.shape[0], ATTENTION_ROW // P
    n = jnp.arange(N, dtype=jnp.int32)

    def before(a):
        return jnp.roll(a, 1)

    follows = (valid & before(valid) & (slots == before(slots))
               & (before(suffix_lens) == P)
               & (prefix_lens == before(prefix_lens) + P)).at[0].set(False)
    place = (n - jax.lax.cummax(jnp.where(follows, 0, n))) % K
    heads = valid & (place == 0)
    group = jnp.cumsum(heads, dtype=jnp.int32) - 1
    # every request's rows in groups of K, the last of them partial
    R = min(N, (N + num_slots * (K - 1)) // K)
    first = jnp.zeros((R,), jnp.int32).at[
        jnp.where(heads, group, R)].set(n, mode="drop")
    return QueryGroups(
        take=jnp.minimum(first[:, None] + jnp.arange(K, dtype=jnp.int32),
                         N - 1),
        tables=tables[first], prefix_lens=prefix_lens[first],
        suffix_lens=jnp.zeros((R,), jnp.int32).at[
            jnp.where(valid, group, R)].add(suffix_lens, mode="drop"),
        back=jnp.clip(group * K + place, 0, R * K - 1))


def _pools(k_pool, v_pool, plain, latent):
    """The attention function of a layer's state and the pools it takes:
    keys and values, or a latent layer's one pool (`v_pool` None)."""
    if v_pool is None:
        return latent, (k_pool,)
    return plain, (k_pool, v_pool)


def _attend_rows(q, k_pool, v_pool, rows: PrefillRows, first_block=0,
                 **kw):
    """prefix_attention of a prefill's queries [N, P, H, D], in the rows'
    groups where they have any; `first_block` is added to the tables (a
    layer's pool inside the stacked one)."""
    from ray_tpu.ops import paged_attention as _pa
    attend, pools = _pools(k_pool, v_pool, _pa.prefix_attention,
                           _pa.mla_prefix_attention)
    g = rows.groups
    if g is None:
        return attend(q, *pools, first_block + rows.tables,
                      rows.prefix_lens, rows.suffix_lens, **kw)
    (R, K), (N, P, H, D) = g.take.shape, q.shape
    o = attend(q[g.take].reshape(R, K * P, H, D), *pools,
               first_block + g.tables, g.prefix_lens, g.suffix_lens, **kw)
    return o.reshape(R * K, P, H, -1)[g.back]


def decode_rows(tables, lengths, active, block_size: int,
                slot_state=None, shared=None) -> DecodeRows:
    """`shared`: the caches' SharedPrefixes (None: every slot attends
    alone)."""
    B = lengths.shape[0]
    M = tables.shape[1] * block_size
    pos_c = jnp.minimum(lengths, M - 1)
    blocks = jnp.where(active, tables[jnp.arange(B), pos_c // block_size], 0)
    offsets = pos_c % block_size
    context_lens = jnp.where(active, jnp.minimum(lengths + 1, M), 0)
    if shared is not None:
        from ray_tpu.ops.paged_attention import shared_rows
        shared = shared_rows(shared, tables, context_lens)
    return DecodeRows(lengths[:, None], tables, context_lens,
                      active, blocks, offsets,
                      jnp.where(offsets == block_size - 1, blocks, 0),
                      None if slot_state is None
                      else jnp.where(active, slot_state, 0), shared)


def _pass_tokens(rows: PrefillRows, step: Optional[DecodeRows]):
    """The T = N * P + B tokens one pass over the layers sees side by side:
    the prompt rows' positions, then the next position of each of `step`'s B
    slots (None: B = 0) -> (positions [1, T], valid [1, T], blocks [T],
    offsets [T])."""
    fields = [(rows.positions, rows.live, rows.blocks, rows.offsets)]
    if step is not None:
        fields.append((step.positions, step.active, step.blocks,
                       step.offsets))
    positions, valid, blocks, offsets = (
        jnp.concatenate([a.reshape(-1) for a in field])
        for field in zip(*fields))
    return positions[None], valid[None], blocks, offsets


@jax.named_scope(scopes.ATTN)
def _attend_pass(q, k_pool, v_pool, rows: PrefillRows,
                 step: Optional[DecodeRows], first_block=0, **kw):
    """Attention of one pass's queries [1, T, H, D], its K/V already in the
    pool: the prompt rows' through `_attend_rows`, the decode rows' through
    paged_attention, each kernel called as a prefill or a decode step alone
    calls it."""
    from ray_tpu.ops import paged_attention as _pa
    (N, P), (H, D) = rows.positions.shape, q.shape[2:]
    o = _attend_rows(q[0, :N * P].reshape(N, P, H, D), k_pool, v_pool, rows,
                     first_block, **kw).reshape(N * P, H, -1)
    if step is not None:
        attend, pools = _pools(k_pool, v_pool, _pa.paged_attention,
                               _pa.mla_paged_attention)
        o = jnp.concatenate([o, attend(
            q[0, N * P:], *pools, first_block + step.tables,
            step.context_lens, shared=_shared_from(step.shared, first_block),
            **kw).astype(o.dtype)])
    return o[None]


def _shared_from(shared, first_block):
    """A step's SharedRows over a layer's pool inside the stacked one."""
    if shared is None:
        return None
    return shared._replace(tables=first_block + shared.tables)


@jax.named_scope(scopes.KV_WRITE)
def _write_rows(pool, blocks, offsets, new, prompt=(0, 1)):
    """pool [NB, Hkv', bs, lanes] with new [T, Hkv, D] written at (blocks,
    :, offsets) [T]: ([Hkv, D] is the pool's [Hkv', lanes] row by row, heads
    side by side or not).  The first N * P of the T are `prompt` = (N, P):
    N prefill rows of P positions; the others are one position of a slot
    each.  The pool is written BY PAGE, indexed by the block alone (its
    dimension 0: a scatter indexed on dimensions 0 and 2 makes XLA keep the
    pool in a layout of its own and copy the WHOLE pool to and from the
    kernel's on every layer and step, and one of D-wide rows costs the
    device by the row, not by the byte):

    * a prefill row's P / bs blocks go in as slabs [Hkv', bs, lanes], one
      update a block.  That rests on what the engine keeps (serve/llm.py):
      (i) a block that a row writes belongs to that row's request alone: a
      prefix hit is whole blocks BEFORE `prefix_lens` and is never written;
      (ii) a row (or a block of one) with no live position goes to the
      scratch block 0, which nothing reads unmasked; (iii) the dead
      positions of a request's last, partly filled block are written with
      what the padding produced: nothing reads a position at or beyond its
      sequence's length, and decode writes each before the length passes
      it; (iv) a conv layer's `tail_pool` and `slot_tail` are not pools of
      positions and do not come here.  A row starts on a block boundary
      (`PrefillRows`) and P is whole blocks (static, as `bs` is; any other
      P is refused), so the slabs are the row's tokens as they lie;
    * a slot's one position is a sixteenth of a page: the slot's page is
      read by its block id, the new row selected in at its offset, and the
      page written back, one update a slot (an inactive slot's goes to the
      scratch block, like any position that is not live)."""
    _, hkv, bs, D = pool.shape
    if new.shape[-1] < D and D % new.shape[-1]:
        # a head alone in rows of lanes it does not fill: zeros past it
        from ray_tpu.ops.paged_attention import to_lanes
        new = to_lanes(new, pool)
    new = new.reshape(-1, hkv, D).astype(pool.dtype)
    blocks, offsets = blocks.reshape(-1), offsets.reshape(-1)
    (N, P), T = prompt, blocks.shape[0]
    if N and P % bs:
        raise ValueError(
            f"prefill rows of {P} positions are not whole blocks of {bs}: "
            f"the pools are written by page (serve/llm.py prefill_shapes "
            f"cuts prompts into tiles of whole blocks)")
    if N:
        pool = pool.at[blocks[:N * P:bs]].set(
            new[:N * P].reshape(-1, bs, hkv, D).transpose(0, 2, 1, 3))
    if T > N * P:
        at = blocks[N * P:]
        here = (jnp.arange(bs) == offsets[N * P:, None])[:, None, :, None]
        pool = pool.at[at].set(
            jnp.where(here, new[N * P:, :, None], pool[at]))
    return pool


def _write_latent(pool, blocks, offsets, rows, prompt=(0, 1)):
    """A latent layer's ONE write: rows [..., 1, c + r] into pool [NB, 1,
    bs, Dp] at (blocks, offsets), the lanes past c + r zero."""
    from ray_tpu.ops.paged_attention import to_lanes
    with jax.named_scope(scopes.MLA_KV):
        return _write_rows(pool, blocks, offsets, to_lanes(rows, pool),
                           prompt)


def _scan_layers(layer, x, layers, caches: PagedDecodeCaches):
    """The layer scan of arch "llama" / "gpt2" over the stacked pool
    [L, NB, Hkv, bs, Dh], carried as ONE pool of L * NB blocks: `layer`
    ((x, k_pool, v_pool), (p, first)) addresses layer i's block b as
    first + b (first = i * NB) in its writes and in the tables it hands
    the kernels.  Nothing slices a layer's pool out of the stack or puts
    it back: the scatter updates the carry in place and the kernels read
    pages through the table.  -> (x', kp', vp')."""
    shape = caches.kp.shape
    L, NB = shape[:2]
    flat = (L * NB,) + shape[2:]
    (x, kp, vp), _ = jax.lax.scan(
        layer, (x, caches.kp.reshape(flat), caches.vp.reshape(flat)),
        (layers, jnp.arange(L, dtype=jnp.int32) * NB))
    return x, kp.reshape(shape), vp.reshape(shape)


def _paged_decode_core(params: Dict[str, Any], caches: PagedDecodeCaches,
                       active: jax.Array, cfg: TransformerConfig,
                       attn_impl: str = "auto"
                       ) -> Tuple[PagedDecodeCaches, jax.Array]:
    """One decode step over the block pool (traceable): the write is
    routed through the block table and attention goes through
    ops.paged_attention.  Safe to run extra steps
    on retired/drained slots: a slot past its allocation writes into its
    own last position, an inactive one into scratch block 0 and attends
    to nothing (a retired slot keeps its last length until the next
    prefill, and the kernel's work follows the lengths it is given); the
    host drops their outputs."""
    from ray_tpu.ops import paged_attention as _pa

    rows = decode_rows(caches.block_tables, caches.lengths, active,
                       caches.kp.shape[3], shared=caches.shared)
    with jax.named_scope(scopes.EMBED):
        x = params["tok_embed"][caches.last_token[:, None]].astype(cfg.dtype)
        if cfg.arch == "gpt2":
            x = x + params["pos_embed"][jnp.clip(
                rows.positions, 0, cfg.max_seq - 1)].astype(cfg.dtype)
    rms = cfg.arch == "llama"

    def layer(carry, inputs):
        x, k_pool, v_pool = carry
        p, first = inputs
        with jax.named_scope(scopes.NORM):
            h = _norm(x, p["attn_norm"], p.get("attn_norm_b"),
                      cfg.norm_eps, rms)
        q, k_new, v_new = _qkv(p, h, cfg, rows.positions)
        # one [Hkv, Dh] row per slot
        k_pool = _write_rows(k_pool, first + rows.blocks, rows.offsets,
                             k_new[:, 0])
        v_pool = _write_rows(v_pool, first + rows.blocks, rows.offsets,
                             v_new[:, 0])
        with jax.named_scope(scopes.ATTN):
            o = _pa.paged_attention(q[:, 0], k_pool, v_pool,
                                    first + rows.tables, rows.context_lens,
                                    impl=attn_impl,
                                    shared=_shared_from(rows.shared, first))
        with jax.named_scope(scopes.ATTN_OUT):
            attn = x + jnp.einsum(
                "bshk,hkd->bsd", o[:, None].astype(cfg.dtype),
                p["wo"].astype(cfg.dtype))
        return (_mlp(p, attn, cfg), k_pool, v_pool), None

    x, kp_all, vp_all = _scan_layers(layer, x, params["layers"], caches)
    with jax.named_scope(scopes.NORM):
        x = _norm(x, params["final_norm"], params.get("final_norm_b"),
                  cfg.norm_eps, rms)
    with jax.named_scope(scopes.HEAD):
        logits = jnp.einsum(
            "bsd,dv->bsv", x.astype(jnp.float32),
            _w_out(params, cfg).astype(jnp.float32))[:, 0]       # [B,V]
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    new_last = jnp.where(active, next_tok, caches.last_token)
    new_len = jnp.where(active, caches.lengths + 1, caches.lengths)
    return caches._replace(kp=kp_all, vp=vp_all, lengths=new_len,
                           last_token=new_last), next_tok


def _decode_scan(params, caches: PagedDecodeCaches, active, cfg,
                 num_steps: int, attn_impl):
    """`num_steps` decode steps of the active slots (traceable) -> (caches',
    tokens [num_steps, B], unrolled layers' expert counts or None)."""
    if cfg.layer_kinds is not None:
        return _unrolled_decode_scan(params, caches, active, cfg, num_steps,
                                     attn_impl)

    def body(c, _):
        return _paged_decode_core(params, c, active, cfg, attn_impl)

    caches, toks = jax.lax.scan(body, caches, None, length=num_steps)
    return caches, toks, None


@functools.partial(jax.jit,
                   static_argnames=("cfg", "num_steps", "attn_impl"),
                   donate_argnums=(1,))
def paged_decode_steps(params: Dict[str, Any], caches: PagedDecodeCaches,
                       active: jax.Array, cfg: TransformerConfig,
                       num_steps: int, attn_impl: str = "auto"
                       ) -> Tuple[PagedDecodeCaches, jax.Array, Any]:
    """num_steps tokens per slot in ONE dispatch (lax.scan): returns
    (caches', tokens [num_steps, B], counts): unrolled layers' expert
    counts (afmoe.MOE_COUNTS), else None."""
    return _decode_scan(params, caches, active, cfg, num_steps, attn_impl)


def _paged_prefill_core(params: Dict[str, Any],
                        caches: PagedDecodeCaches, tokens: jax.Array,
                        suffix_lens: jax.Array, prefix_lens: jax.Array,
                        slots: jax.Array, valid: jax.Array,
                        closes: jax.Array, new_bt: jax.Array,
                        cfg: TransformerConfig, attn_impl: str = "auto",
                        carried: Optional[jax.Array] = None,
                        states=None):
    """Prefill of N rows of P tokens against what their requests have in
    the pool (traceable) -> (caches', first tokens [N], an expert model's
    counts or None, the carried slots' next tokens [B] or None).

    `carried` [B] bool: the slots whose next decode step rides in this pass
    (none of them a slot that a row of this call closes).  Their one
    position each lies beside the rows' N * P in every product of every
    layer, so a weight is read once for both; they are written, attended
    and moved on as `_paged_decode_core` does it.  Slots not carried write
    to the scratch block and are routed to no expert.

    A row is a TILE of one request's uncached tokens: tokens [N, P] hold
    suffix_lens[n] of them, at absolute positions prefix_lens[n] + i (RoPE /
    learned positions stay correct), and new_bt[n] is the request's block
    table.  What lies before a row (the cached prefix: whole blocks shared
    through the table and never recomputed, this is where a prefix-cache
    hit saves its FLOPs; and the tiles before it, of an earlier call or of
    this one) is read from the pool: every layer writes the whole call's
    K/V (`_write_rows`) BEFORE it attends (`prefix_attention`), so a later
    tile of the same call sees an earlier one as prefix.  Several rows may
    therefore name one slot, and those that follow each other attend as
    one row of up to ATTENTION_ROW queries (QueryGroups: the prefix is
    streamed once for them); only the row that ends its prompt (`closes`)
    yields the request's first token and hands the slot its table and
    length.  A row that is not `valid` writes to the scratch block only.
    `states` (state_from [N], state_to [N, 2]: PrefillRows), where the model
    has linear layers: the closing row's first `state_to` is its slot's id
    from then on."""
    N, P = tokens.shape
    B = caches.lengths.shape[0]
    bs = block_size_of(caches)
    rows = prefill_rows(new_bt, prefix_lens, suffix_lens, valid, P, bs,
                        slots, B, closes,
                        cfg.conv_kernel if caches.tail_pool else 0, states)
    # The pass's tokens [1, T] and which of them yield one: every row's
    # last live position, then every decode row.
    tokens = tokens.reshape(1, N * P)
    yields = jnp.arange(N) * P + jnp.clip(suffix_lens - 1, 0, P - 1)
    step = None
    if carried is not None:
        # (under the sets the caches hold: the slots that were active have
        # the tables they had when the engine found them)
        step = decode_rows(caches.block_tables, caches.lengths, carried, bs,
                           caches.slot_state, caches.shared)
        tokens = jnp.concatenate([tokens, caches.last_token[None]], axis=1)
        yields = jnp.concatenate([yields, N * P + jnp.arange(B)])
    if cfg.layer_kinds is not None:
        model = unrolled(cfg)
        x = model.embed(cfg, params["tok_embed"], tokens)
        x, state, counts = _unrolled_layers(
            cfg, params, caches, x, rows,
            functools.partial(paged_prefill_layer, step=step), attn_impl)
        logits = model.logits(cfg, params, x[0, yields])
    else:
        x, kp, vp = _dense_prefill_layers(cfg, params, caches, tokens, rows,
                                          step, attn_impl)
        state, counts = dict(kp=kp, vp=vp), None
        with jax.named_scope(scopes.NORM):
            last = _norm(x[0, yields], params["final_norm"],
                         params.get("final_norm_b"), cfg.norm_eps,
                         cfg.arch == "llama")                # [N + B, D]
        with jax.named_scope(scopes.HEAD):
            logits = last.astype(jnp.float32) @ _w_out(params, cfg).astype(
                jnp.float32)
    with jax.named_scope(scopes.HEAD):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    first_tok, step_tok = tok[:N], None
    lengths, last_token = caches.lengths, caches.last_token
    if carried is not None:
        step_tok = tok[N:]
        lengths = jnp.where(carried, lengths + 1, lengths)
        last_token = jnp.where(carried, step_tok, last_token)
    # A scatter whose in-range indices are distinct: at most one row of a
    # slot closes, and every other row is sent out of range and dropped.
    at = jnp.where(closes, slots, B)
    if states is not None:
        state["slot_state"] = caches.slot_state.at[at].set(
            states[1][:, 0], mode="drop")
    return caches._replace(
        block_tables=caches.block_tables.at[at].set(new_bt, mode="drop"),
        lengths=lengths.at[at].set(prefix_lens + suffix_lens, mode="drop"),
        last_token=last_token.at[at].set(first_tok, mode="drop"),
        **state), first_tok, counts, step_tok


def _dense_prefill_layers(cfg, params, caches, tokens, rows: PrefillRows,
                          step: Optional[DecodeRows], attn_impl):
    """Arch "llama" / "gpt2": the layer scan of one pass (`_pass_tokens`:
    tokens [1, T]) over the stacked pool -> (x' [1, T, D], kp', vp')."""
    positions, _, blocks, offsets = _pass_tokens(rows, step)
    prompt = rows.positions.shape       # (N, P)
    with jax.named_scope(scopes.EMBED):
        x = params["tok_embed"][tokens].astype(cfg.dtype)    # [1,T,D]
        if cfg.arch == "gpt2":
            x = x + params["pos_embed"][
                jnp.clip(positions, 0, cfg.max_seq - 1)].astype(cfg.dtype)
    rms = cfg.arch == "llama"

    def layer(carry, inputs):
        x, k_pool, v_pool = carry
        p, first = inputs
        with jax.named_scope(scopes.NORM):
            h = _norm(x, p["attn_norm"], p.get("attn_norm_b"),
                      cfg.norm_eps, rms)
        q, k, v = _qkv(p, h, cfg, positions)
        k_pool = _write_rows(k_pool, first + blocks, offsets, k[0], prompt)
        v_pool = _write_rows(v_pool, first + blocks, offsets, v[0], prompt)
        o = _attend_pass(q, k_pool, v_pool, rows, step, first,
                         impl=attn_impl)                     # [1,T,H,Dh]
        with jax.named_scope(scopes.ATTN_OUT):
            attn = x + jnp.einsum("bshk,hkd->bsd", o.astype(cfg.dtype),
                                  p["wo"].astype(cfg.dtype))
        return (_mlp(p, attn, cfg), k_pool, v_pool), None

    return _scan_layers(layer, x, params["layers"], caches)


class FusedUpload(NamedTuple):
    """The fused dispatch's ONE upload, [N + 1, width] int32: every host
    input of `paged_prefill_decode_packed` in one host->device transfer.
    The engine (serve/llm.py `_fused_dispatch`) writes it through these
    columns and the program reads it back through them.

      rows 0..N-1: [tokens[0:P] | suffix_len | prefix_len | slot | flag |
                    table[0:W]] and, where the caches hold linear layers'
                   states, [state_from | state_to[0] | state_to[1]]
                   (PrefillRows)
      row  N:      [active[0:B]] and, where the upload carries them (`sets`),
                   the prefixes that sets of the slots share once the rows
                   have set their tables (ops/paged_attention.py
                   SharedPrefixes, B // 2 programs): [members as slot + 1
                   [B // 2 * 8] | leader [B // 2] | shared_len [B // 2]],
                   zeros: none

    A row is a tile of P of one request's uncached tokens, `suffix_len` of
    them live, at positions `prefix_len` on, under the request's block
    `table`; `flag` says what the row is (NO_ROW, CLOSES, MORE).  An upload
    narrower than the last row's sets carries none (`sets` False: the
    slots attend alone)."""

    P: int                   # tokens a row: the engine's tile (prompt_pad)
    W: int                   # a block table's columns
    B: int                   # slots
    states: bool = False     # the three state columns
    sets: bool = True        # the last row's shared prefixes

    # `flag`: no row; the row ends its prompt: it yields the first token and
    # its slot decodes from this dispatch on (a slot the host still marks
    # active for the request before is the new request's: it gets no decode
    # row in the pass); more of the prompt is to come: its K/V are written
    # and its slot stays out of the decode steps.
    NO_ROW, CLOSES, MORE = 0, 1, 2

    @classmethod
    def of(cls, prompt_pad: int, caches: PagedDecodeCaches,
           width: Optional[int] = None) -> "FusedUpload":
        """The layout of an upload for `caches`; `width`: of an upload that
        is there already (what it is too narrow for, it does not carry)."""
        B, W = caches.block_tables.shape
        up = cls(prompt_pad, W, B, bool(caches.state_pool or caches.ring_k))
        return up._replace(sets=width is None or width >= up._sets[3])

    tokens = property(lambda up: slice(0, up.P))
    suffix_len = property(lambda up: up.P)
    prefix_len = property(lambda up: up.P + 1)
    slot = property(lambda up: up.P + 2)
    flag = property(lambda up: up.P + 3)
    scalars = property(lambda up: slice(up.P, up.P + 4))    # the four
    table = property(lambda up: slice(up.P + 4, up.P + 4 + up.W))
    state_from = property(lambda up: up.P + 4 + up.W)
    state_to = property(lambda up: slice(up.P + 5 + up.W, up.P + 7 + up.W))
    active = property(lambda up: slice(0, up.B))

    @property
    def _sets(self) -> Tuple[int, int, int, int]:
        """Where the last row's members, leaders and shared lengths start,
        and where they end."""
        from ray_tpu.ops.paged_attention import SHARED_MEMBERS
        programs = self.B // 2
        leader = self.B + programs * SHARED_MEMBERS
        return self.B, leader, leader + programs, leader + 2 * programs

    @property
    def width(self) -> int:
        return max(self.P + 4 + self.W + (3 if self.states else 0),
                   self._sets[3] if self.sets else self.B)

    def empty(self, rows: int):
        """The upload of `rows` rows with nothing in it (numpy, to be
        written in place): no row, no active slot, no set."""
        return np.zeros((rows + 1, self.width), np.int32)

    def put_sets(self, packed, members, leader, shared_len) -> None:
        """SharedPrefixes' three arrays (numpy) into an upload's last row."""
        at, _, _, end = self._sets
        packed[-1, at:end] = np.concatenate(
            [members.reshape(-1) + 1, leader, shared_len])

    def shared_sets(self, packed):
        """SharedPrefixes out of an upload's last row; none where the
        upload carries no sets."""
        from ray_tpu.ops import paged_attention as _pa
        if not self.sets:
            return _pa.no_shared_prefixes(self.B)
        row, programs = packed[-1], self.B // 2
        at, leader, _, end = self._sets
        members, rest = row[at:leader], row[leader:end]
        return _pa.SharedPrefixes(members.reshape(programs, -1) - 1,
                                  rest[:programs], rest[programs:])


@functools.partial(jax.jit, static_argnames=("cfg", "num_steps",
                                             "prompt_pad", "attn_impl"),
                   donate_argnums=(1,))
def paged_prefill_decode_packed(params: Dict[str, Any],
                                caches: PagedDecodeCaches,
                                packed: jax.Array,
                                cfg: TransformerConfig, num_steps: int,
                                prompt_pad: int, attn_impl: str = "auto"
                                ) -> Tuple[PagedDecodeCaches, jax.Array, Any]:
    """Fused suffix-prefill + chunked decode with ALL host inputs in
    ONE int32 upload (`packed`: FusedUpload, rows of `prompt_pad` tokens):
    one host->device transfer per dispatch.
    -> (caches', tokens [num_steps, B], counts): unrolled layers' expert
    counts (afmoe.MOE_COUNTS), else None.

    The prefill pass is the dispatch's FIRST decode step: it carries the
    next position of every slot that was active before this call (and that
    no row of this call closes), and `num_steps - 1` decode steps of every
    active slot follow.  So the call walks the weights `num_steps` times,
    like `paged_decode_steps`, and every active slot gets `num_steps`
    tokens: tokens[0] is the pass's (for a slot a row closes, its prompt's
    first token), tokens[1:] the steps'.

    A row is a tile of one request's uncached tokens (a KV block or two:
    N x P positions are what the dense products see, N one of the host
    loop's ladder of widths); a request longer than P takes several rows,
    one after the other, in this call or over several (the host loop:
    serve/llm.py), and those of one call attend in groups of up to
    ATTENTION_ROW queries.  The caches keep the upload's sets for the
    decode-only dispatches that follow."""
    up = FusedUpload.of(prompt_pad, caches, packed.shape[1])
    B = up.B
    flag = packed[:-1, up.flag]
    closes = flag == up.CLOSES
    slots = packed[:-1, up.slot]
    was_active = packed[-1, up.active] > 0
    at = jnp.where(closes, slots, B)
    closed = jnp.zeros((B,), bool).at[at].set(True, mode="drop")
    states = None
    if up.states:
        states = (packed[:-1, up.state_from], packed[:-1, up.state_to])
    caches, first, counts, tok = _paged_prefill_core(
        params, caches, packed[:-1, up.tokens], packed[:-1, up.suffix_len],
        packed[:-1, up.prefix_len], slots, flag > up.NO_ROW, closes,
        packed[:-1, up.table], cfg, attn_impl,
        carried=was_active & ~closed, states=states)
    tok = tok.at[at].set(first, mode="drop")[None]
    active = was_active | closed
    if caches.shared is not None:
        # The rows changed tables: what the slots share from here on.
        caches = caches._replace(shared=up.shared_sets(packed))
    caches, toks, more = _decode_scan(params, caches, active, cfg,
                                      num_steps - 1, attn_impl)
    return (caches, jnp.concatenate([tok, toks]),
            None if counts is None else counts + more)


# ===========================================================================
# unrolled layers: the paged steps over a model module's one layer definition
# ===========================================================================
# The layers are unrolled (their kinds differ in shape) and each has state
# of its own, a pair of arrays: an attention layer's K and V pools, a conv
# layer's block tails and slot tails, a latent layer's one pool of rows and
# None, a linear layer's states and conv inputs by state id
# (PagedDecodeCaches; `_LAYER_STATE` names the fields by mixer).
# `paged_prefill_layer` and `paged_decode_layer` are what the engine's
# dispatches are made of, one layer at a time: a caller that cannot hold
# every layer's weights at once (the benchmark's comparison with the plain
# reference at published widths) runs these very functions layer by layer.


def paged_prefill_layer(cfg: TransformerConfig, kind, p, x, k_pool, v_pool,
                        rows: PrefillRows, attn_impl: str = "auto",
                        tap=None, step: Optional[DecodeRows] = None):
    """One layer over a chunk x [N, P, D].  An attention layer: its K/V go
    into the pool, its queries attend to the pool (prefix and chunk alike,
    under the layer's window).  A conv layer, whose `k_pool` is its block
    tails and `v_pool` its slot tails: the tails of the blocks the rows
    complete go into the pool FIRST, then every row starts from the tail of
    the block before it (so a row that follows its request's row in this
    call reads what that one just wrote, and never another request's), or
    from zeros at position 0; a row that ends its prompt leaves its slot
    the tail its decode starts from.

    With `step`, B slots' next decode position rides in the same pass: x
    is then the pass's tokens side by side [1, N * P + B, D]
    (`_pass_tokens`), one set of products for both kinds of row; a decode
    row is written, attended and (a conv layer) started from its slot's
    tail as `paged_decode_layer` does it.
    -> (x', k_pool', v_pool', afmoe.MOE_COUNTS)."""
    model = unrolled(cfg)
    state = []
    (N, P), D = rows.positions.shape, x.shape[-1]
    positions, valid, blocks, offsets = _pass_tokens(rows, step)

    def attend(q, k, v):
        kp = _write_rows(k_pool, blocks, offsets, k[0], (N, P))
        vp = _write_rows(v_pool, blocks, offsets, v[0], (N, P))
        state.extend((kp, vp))
        return _attend_pass(q, kp, vp, rows, step, impl=attn_impl,
                            window=model.window_of(cfg, kind))

    def attend_latent(q, row):
        kp = _write_latent(k_pool, blocks, offsets, row[0], (N, P))
        state.extend((kp, None))
        return _attend_pass(q, kp, None, rows, step, impl=attn_impl,
                            **model.latent_kw(cfg))

    def before(u):
        K1 = v_pool.shape[1]
        up = u[0, :N * P].reshape(N, P, D)
        tails = k_pool.at[rows.tail_blocks.reshape(-1)].set(
            up[:, rows.tail_at].reshape(-1, K1 * D))
        came = jnp.where((rows.prefix_lens > 0)[:, None, None],
                         tails[rows.before_block].reshape(N, K1, D), 0)
        slot = v_pool
        if rows.close_slots is not None:
            ext = jnp.concatenate([came, up], axis=1)
            last = rows.suffix_lens[:, None] + jnp.arange(K1)    # [N, K-1]
            slot = slot.at[rows.close_slots].set(
                jnp.take_along_axis(ext, last[..., None], axis=1),
                mode="drop")
        prev = [t.reshape(1, N * P, D) for t in model.taps(came, up)]
        if step is not None:
            ud = u[0, N * P:, None]                              # [B, 1, D]
            moved = jnp.concatenate([v_pool[:, 1:], ud], axis=1)
            tails = tails.at[step.tail_blocks].set(
                moved.reshape(moved.shape[0], -1))
            slot = jnp.where(step.active[:, None, None], moved, slot)
            prev = [jnp.concatenate([a, b.reshape(1, -1, D)], axis=1)
                    for a, b in zip(prev, model.taps(v_pool, ud))]
        state.extend((tails, slot))
        return prev

    def linear_before(u):
        """A linear layer, whose `k_pool` is its states and `v_pool` its
        conv inputs, both by state id: a row starts from what `state_from`
        names, or from the row before it (its own request's)."""
        conv, K1, C = v_pool, v_pool.shape[1], u.shape[-1]
        up = u[0, :N * P].reshape(N, P, C)
        src = rows.state_from
        came = jnp.where(
            (src < 0)[:, None, None], jnp.roll(up[:, P - K1:], 1, axis=0),
            jnp.where((src > 0)[:, None, None], conv[jnp.maximum(src, 0)],
                      0))
        ext = jnp.concatenate([came, up], axis=1)
        last = rows.suffix_lens[:, None] + jnp.arange(K1)        # [N, K-1]
        ends = jnp.take_along_axis(ext, last[..., None], axis=1)
        conv = conv.at[rows.state_to.reshape(-1)].set(
            jnp.repeat(ends, 2, axis=0))
        prev = [t.reshape(1, N * P, C) for t in model.taps(came, up)]
        if step is not None:
            ud = u[0, N * P:, None]                              # [B, 1, C]
            mine = v_pool[step.state_ids]
            conv = conv.at[step.state_ids].set(
                jnp.concatenate([mine[:, 1:], ud], axis=1))
            prev = [jnp.concatenate([a, b.reshape(1, -1, C)], axis=1)
                    for a, b in zip(prev, model.taps(mine, ud))]
        state.append(conv)
        return prev

    def linear_rule(q, k, v, log_a, beta):
        from ray_tpu.ops import gated_delta
        live = valid[0, :, None]
        log_a, beta = jnp.where(live, log_a[0], 0), jnp.where(live, beta[0],
                                                              0)

        def rows_of(a):
            return a[:N * P].reshape(N, P, *a.shape[1:])

        o, pool = gated_delta.gated_delta_chunk(
            k_pool, rows.state_from, rows.state_to,
            *(rows_of(a) for a in (q[0], k[0], v[0], log_a, beta)),
            impl=attn_impl)
        o = o.reshape(N * P, *o.shape[2:])
        if step is not None:
            od, pool = gated_delta.gated_delta_step(
                pool, step.state_ids,
                *(a[N * P:] for a in (q[0], k[0], v[0], log_a, beta)),
                impl=attn_impl)
            o = jnp.concatenate([o, od])
        state.insert(0, pool)
        return o[None]

    def ring_attend(q, k, v, sink):
        """A ring layer, whose `k_pool` and `v_pool` are its rings by state
        id: the rows as a linear layer's (`state_from`, `state_to`), a
        decode row through its slot's ring."""
        from ray_tpu.ops import window_ring

        def rows_of(a):
            return a[0, :N * P].reshape(N, P, *a.shape[2:])

        o, rk, rv = window_ring.window_ring_chunk(
            k_pool, v_pool, rows.state_from, rows.state_to, rows.prefix_lens,
            rows.suffix_lens, rows_of(q), rows_of(k), rows_of(v), sink,
            impl=attn_impl)
        o = o.reshape(N * P, *o.shape[2:])
        if step is not None:
            od, rk, rv = window_ring.window_ring_step(
                rk, rv, step.state_ids, step.positions[:, 0], q[0, N * P:],
                k[0, N * P:], v[0, N * P:], sink, impl=attn_impl)
            o = jnp.concatenate([o, od])
        state.extend((rk, rv))
        return o[None]

    mix = {"conv": before, "latent": attend_latent,
           "linear": (linear_before, linear_rule),
           "ring": ring_attend}.get(kind[0], attend)
    y, counts = model.layer(cfg, kind, p, x.reshape(1, -1, D), positions,
                            mix, valid=valid,
                            moe_name=scopes.MOE_EXPERTS_PREFILL, tap=tap)
    return y.reshape(x.shape), state[0], state[1], counts


def paged_decode_layer(cfg: TransformerConfig, kind, p, x, k_pool, v_pool,
                       rows: DecodeRows, attn_impl: str = "auto", tap=None):
    """One layer over one new position per slot, x [B, 1, D]; the layer's
    state as `paged_prefill_layer` has it.  A conv layer starts from its
    slot's tail, moves it on by the new position where the slot is active,
    and leaves it in the pool where that position completes a block."""
    from ray_tpu.ops import paged_attention as _pa
    model = unrolled(cfg)
    state = []

    def attend(q, k, v):
        kp = _write_rows(k_pool, rows.blocks, rows.offsets, k[:, 0])
        vp = _write_rows(v_pool, rows.blocks, rows.offsets, v[:, 0])
        state.extend((kp, vp))
        with jax.named_scope(scopes.ATTN):
            return _pa.paged_attention(
                q[:, 0], kp, vp, rows.tables, rows.context_lens,
                impl=attn_impl, window=model.window_of(cfg, kind),
                shared=rows.shared)[:, None]

    def attend_latent(q, row):
        kp = _write_latent(k_pool, rows.blocks, rows.offsets, row[:, 0])
        state.extend((kp, None))
        with jax.named_scope(scopes.ATTN):
            return _pa.mla_paged_attention(
                q[:, 0], kp, rows.tables, rows.context_lens, impl=attn_impl,
                shared=rows.shared, **model.latent_kw(cfg))[:, None]

    def before(u):
        moved = jnp.concatenate([v_pool[:, 1:], u], axis=1)
        state.extend((k_pool.at[rows.tail_blocks].set(
            moved.reshape(moved.shape[0], -1)),
            jnp.where(rows.active[:, None, None], moved, v_pool)))
        return model.taps(v_pool, u)

    def linear_before(u):
        mine = v_pool[rows.state_ids]
        state.append(v_pool.at[rows.state_ids].set(
            jnp.concatenate([mine[:, 1:], u], axis=1)))
        return model.taps(mine, u)

    def linear_rule(q, k, v, log_a, beta):
        from ray_tpu.ops import gated_delta
        live = rows.active[:, None]
        o, pool = gated_delta.gated_delta_step(
            k_pool, rows.state_ids, q[:, 0], k[:, 0], v[:, 0],
            jnp.where(live, log_a[:, 0], 0), jnp.where(live, beta[:, 0], 0),
            impl=attn_impl)
        state.insert(0, pool)
        return o[:, None]

    def ring_attend(q, k, v, sink):
        from ray_tpu.ops import window_ring
        o, rk, rv = window_ring.window_ring_step(
            k_pool, v_pool, rows.state_ids, rows.positions[:, 0], q[:, 0],
            k[:, 0], v[:, 0], sink, impl=attn_impl)
        state.extend((rk, rv))
        return o[:, None]

    mix = {"conv": before, "latent": attend_latent,
           "linear": (linear_before, linear_rule),
           "ring": ring_attend}.get(kind[0], attend)
    x, counts = model.layer(cfg, kind, p, x, rows.positions, mix,
                            valid=rows.active[:, None],
                            moe_name=scopes.MOE_EXPERTS_DECODE, tap=tap)
    return x, state[0], state[1], counts


# The pair of PagedDecodeCaches fields that holds a layer's state, by mixer
# (any other: its K and V pools).
_LAYER_STATE = {"conv": ("tail_pool", "slot_tail"),
                "linear": ("state_pool", "conv_pool"),
                "ring": ("ring_k", "ring_v")}


def _unrolled_layers(cfg, params, caches, x, rows, layer_fn, attn_impl):
    """-> (x', the caches' per-layer fields as the layers left them,
    counts)."""
    state = {f: list(getattr(caches, f))
             for pair in (("kp", "vp"), *_LAYER_STATE.values())
             for f in pair}
    counts = unrolled(cfg).no_counts()
    for i, (kind, p) in enumerate(zip(cfg.layer_kinds, params["layers"])):
        a, b = _LAYER_STATE.get(kind[0], ("kp", "vp"))
        x, state[a][i], state[b][i], c = layer_fn(
            cfg, kind, p, x, state[a][i], state[b][i], rows, attn_impl)
        counts = counts + c
    return x, {f: tuple(v) for f, v in state.items()}, counts


def _unrolled_decode_core(params, caches: PagedDecodeCaches, active, cfg,
                          attn_impl):
    """One decode step; -> (caches', next tokens [B], logits [B, V],
    counts).  Slots that are not active attend to nothing, write to the
    scratch block and are routed to no expert."""
    model = unrolled(cfg)
    rows = decode_rows(caches.block_tables, caches.lengths, active,
                       block_size_of(caches), caches.slot_state,
                       caches.shared)
    x = model.embed(cfg, params["tok_embed"], caches.last_token[:, None])
    x, state, counts = _unrolled_layers(cfg, params, caches, x, rows,
                                        paged_decode_layer, attn_impl)
    logits = model.logits(cfg, params, x[:, 0])
    with jax.named_scope(scopes.HEAD):
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return caches._replace(
        lengths=jnp.where(active, caches.lengths + 1, caches.lengths),
        last_token=jnp.where(active, next_tok, caches.last_token),
        **state), next_tok, logits, counts


def _unrolled_decode_scan(params, caches, active, cfg, num_steps, attn_impl):
    def body(carry, _):
        c, counts = carry
        c, tok, _, more = _unrolled_decode_core(params, c, active, cfg,
                                                attn_impl)
        return (c, counts + more), tok

    (caches, counts), toks = jax.lax.scan(
        body, (caches, unrolled(cfg).no_counts()), None, length=num_steps)
    return caches, toks, counts
