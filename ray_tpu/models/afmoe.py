"""arch "afmoe": sliding-window and full attention layers mixed, dense and
sparse-expert feed-forwards mixed (Arcee's Trinity family).

ONE layer definition, `layer()`, which `forward` (no cache), the paged
prefill and the paged decode step (models/decoding.py) all run: they
differ only in the `attend` they hand it, which takes the layer's q, k, v
and returns the attention output (writing a cache on its way, or not).
The plain float32 reference is the deliberate second copy
(benchmarks/kinds/afmoe.py).

A layer of kind (mixer, feed-forward), N() an RMSNorm with its own weight:

  a        = N_in(x)
  q, k, v  = Wq a, Wk a, Wv a;  q = N_q(q), k = N_k(k)   (over each head)
  g        = sigmoid(Wg a)                                 (one per q value)
  sliding:   q, k get the rotary embedding; key j is visible to query i
             iff j <= i and i - j < sliding_window
  full:      no positional encoding; key j visible iff j <= i
  x        = x + N_post_attn(Wo (g * attention))
  m        = N_pre_mlp(x)
  dense:     y = Wdown(silu(Wgate m) * Wup m)
  experts:   s = sigmoid(Wr m) in float32; S = top-k of (s + b);
             w_e = route_scale * s_e / sum_{S} s      (b selects only)
             y = sum_{e in S} w_e FFN_e(m) + FFN_shared(m)
  x        = x + N_post_mlp(y)

and x0 = Embed[token] * sqrt(d_model), logits = Whead N_final(x_L).

Parameters are a tuple of per-layer trees (layer kinds differ in shape, so
there is no stacked scan axis); layer l's weights come from a key folded
with l, so one layer can be made alone.  There is no training path:
`transformer.loss_fn` raises.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.transformer import (TransformerConfig, _norm, _rope,
                                        _w_out)
from ray_tpu.ops import scopes
from ray_tpu.ops.grouped_ffn import grouped_ffn, tile_rows

# What one expert layer counts per call (a decode step or a prefill chunk):
# calls, (token, pick) rows routed to an expert held here, the largest
# expert's rows, experts with at least one row, and the (token, pick) rows
# of valid tokens whose expert is not held here (0 where every expert is).
MOE_COUNTS = ("layer_steps", "routed_rows", "busiest_expert_rows",
              "experts_touched", "absent_rows")
# ... and, where a module asks `experts` for it (`count_padded`; its own
# MOE_COUNTS then ends with this name), the rows the grouped product
# computed, each expert's group padded to whole tiles (ops/grouped_ffn.py).
PADDED_ROWS = "padded_rows"


def no_counts() -> jax.Array:
    return jnp.zeros((len(MOE_COUNTS),), jnp.int32)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def init_layer(cfg: TransformerConfig, key: jax.Array,
               index: int) -> Dict[str, Any]:
    """Layer `index` alone.  Norm weights and the selection bias get a
    spread that is not zero, so a program that ignores one of them cannot
    agree with the reference.  The bias is small beside the scores' own
    spread (0.02 against ~0.2): a trained model's bias evens the experts'
    load, a random one of the scores' size would send most tokens to the
    same few experts (0.1 read 63 of 128 experts touched a decode step
    where even routing gives 111: PERF.md section 6, PR 27)."""
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    pd = cfg.param_dtype
    ks = iter(jax.random.split(jax.random.fold_in(key, index), 24))

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32) * scale
                ).astype(pd)

    def norm_weight(n):
        return (1.0 + 0.1 * jax.random.normal(next(ks), (n,), jnp.float32)
                ).astype(pd)

    s_in = 1.0 / math.sqrt(d)
    p = {
        "attn_norm": norm_weight(d), "post_attn_norm": norm_weight(d),
        "mlp_norm": norm_weight(d), "post_mlp_norm": norm_weight(d),
        "q_norm": norm_weight(dh), "k_norm": norm_weight(dh),
        "wq": normal((d, h, dh), s_in), "wk": normal((d, hkv, dh), s_in),
        "wv": normal((d, hkv, dh), s_in), "wg": normal((d, h, dh), s_in),
        "wo": normal((h, dh, d), 1.0 / math.sqrt(h * dh)),
    }
    if cfg.layer_kinds[index][1] == "dense":
        f = cfg.ff_dim
        p.update(w_gate=normal((d, f), s_in), w_up=normal((d, f), s_in),
                 w_down=normal((f, d), 1.0 / math.sqrt(f)))
        return p
    E, f = cfg.moe_experts, cfg.moe_d_ff
    p.update(w_router=normal((d, E), s_in),
             route_bias=normal((E,), 0.02),
             w_gate=normal((E, d, f), s_in), w_up=normal((E, d, f), s_in),
             w_down=normal((E, f, d), 1.0 / math.sqrt(f)))
    if cfg.moe_shared_experts:
        fs = f * cfg.moe_shared_experts
        p.update(ws_gate=normal((d, fs), s_in), ws_up=normal((d, fs), s_in),
                 ws_down=normal((fs, d), 1.0 / math.sqrt(fs)))
    return p


def init_embed(cfg: TransformerConfig, key: jax.Array) -> jax.Array:
    """The table, small: `embed` multiplies it by sqrt(d_model)."""
    d = cfg.d_model
    return (jax.random.normal(jax.random.split(key, 8)[1],
                              (cfg.vocab_size, d), jnp.float32)
            / math.sqrt(d)).astype(cfg.param_dtype)


def init_head(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    """The final norm and (untied) the output head."""
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    out = {"final_norm": (1.0 + 0.1 * jax.random.normal(
        ks[2], (d,), jnp.float32)).astype(cfg.param_dtype)}
    if not cfg.tie_embeddings:
        out["lm_head"] = (jax.random.normal(
            ks[3], (d, cfg.vocab_size), jnp.float32) / math.sqrt(d)
            ).astype(cfg.param_dtype)
    return out


def init_params(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    layer_key = jax.random.split(key, 8)[0]
    return {"tok_embed": init_embed(cfg, key),
            "layers": tuple(init_layer(cfg, layer_key, i)
                            for i in range(cfg.n_layers)),
            **init_head(cfg, key)}


def logical_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    def layer(kind):
        p = {"attn_norm": ("embed",), "post_attn_norm": ("embed",),
             "mlp_norm": ("embed",), "post_mlp_norm": ("embed",),
             "q_norm": (None,), "k_norm": (None,),
             "wq": ("embed", "heads", "head_dim"),
             "wk": ("embed", "kv_heads", "head_dim"),
             "wv": ("embed", "kv_heads", "head_dim"),
             "wg": ("embed", "heads", "head_dim"),
             "wo": ("heads", "head_dim", "embed")}
        if kind[1] == "dense":
            p.update(w_gate=("embed", "mlp"), w_up=("embed", "mlp"),
                     w_down=("mlp", "embed"))
            return p
        p.update(w_router=("embed", None), route_bias=(None,),
                 w_gate=("expert", "embed", "mlp"),
                 w_up=("expert", "embed", "mlp"),
                 w_down=("expert", "mlp", "embed"))
        if cfg.moe_shared_experts:
            p.update(ws_gate=("embed", "mlp"), ws_up=("embed", "mlp"),
                     ws_down=("mlp", "embed"))
        return p

    axes = {"tok_embed": ("vocab", "embed"),
            "layers": tuple(layer(k) for k in cfg.layer_kinds),
            "final_norm": ("embed",)}
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------
@jax.named_scope(scopes.NORM)
def _rms(x, w, cfg):
    return _norm(x, w, None, cfg.norm_eps, True)


def _ffn(m, w_gate, w_up, w_down,
         names=(scopes.FFN_GATE_UP, scopes.FFN_DOWN)):
    """A gated feed-forward, its two halves under `names` (ops/scopes.py:
    a dense layer's; the shared expert gives its own)."""
    with jax.named_scope(names[0]):
        gate = jnp.einsum("bsd,df->bsf", m, w_gate.astype(m.dtype))
        up = jnp.einsum("bsd,df->bsf", m, w_up.astype(m.dtype))
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(m.dtype) * up
    with jax.named_scope(names[1]):
        return jnp.einsum("bsf,fd->bsd", act, w_down.astype(m.dtype))


def route(cfg: TransformerConfig, p: Dict[str, Any], m: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """m [T, D] -> (expert ids [T, k] int32, weights [T, k] float32).
    Scores in float32 at full precision: each logit's sigmoid, or
    (`cfg.moe_score_fn` "softmax") the softmax over the router's whole
    width; the bias (where the layer has one) only selects; the weights are
    the picks' scores over their sum; ties as `jax.lax.top_k` breaks them."""
    score = {"sigmoid": jax.nn.sigmoid,
             "softmax": functools.partial(jax.nn.softmax, axis=-1)
             }[cfg.moe_score_fn]
    with jax.named_scope(scopes.MOE_ROUTE):
        s = score(jnp.einsum(
            "td,de->te", m.astype(jnp.float32),
            p["w_router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        if cfg.moe_score_dtype != jnp.float32:
            # a comparison's control (XLA drops a pair of casts)
            info = jnp.finfo(cfg.moe_score_dtype)
            s = jax.lax.reduce_precision(
                s, exponent_bits=info.nexp, mantissa_bits=info.nmant)
        biased = s
        if "route_bias" in p:
            biased = s + p["route_bias"].astype(jnp.float32)
        _, idx = jax.lax.top_k(biased, cfg.moe_top_k)
        picked = jnp.take_along_axis(s, idx, axis=1)
        total = jnp.sum(picked, axis=1, keepdims=True)
        if cfg.moe_route_eps:       # 0: not in the program at all
            total = total + cfg.moe_route_eps
        return idx.astype(jnp.int32), cfg.moe_route_scale * picked / total


def experts(cfg: TransformerConfig, p: Dict[str, Any], m: jax.Array,
            valid: Optional[jax.Array], name: str,
            tap: Optional[Callable] = None, count_padded: bool = False
            ) -> Tuple[jax.Array, jax.Array]:
    """m [B, S, D], valid [B, S] (None: every row) -> (y, MOE_COUNTS, and
    with `count_padded` PADDED_ROWS after them).
    Rows that are not valid are routed nowhere and are not counted.
    `tap`, if given, is shown the picks [B * S, k] (a comparison's way to
    see them; the serving path passes none).

    A layer that holds a share of its experts (`cfg.moe_router_width`: the
    weights here are experts `moe_experts_first` .. + `moe_experts` - 1 of
    that many) routes, picks and normalises over the router's whole width;
    a (token, pick) pair whose expert is not held is routed nowhere, as an
    invalid row is, and counted as absent.  What comes back is the held
    experts' part of the layer's output plus the shared expert's, which is
    computed for every token (`cfg.moe_shared_gate`: times sigmoid(w . m),
    a scalar a token, in float32)."""
    B, S, D = m.shape
    m2 = m.reshape(B * S, D)
    ok = (jnp.ones((B * S,), bool) if valid is None
          else valid.reshape(B * S))
    idx, w = route(cfg, p, m2)
    if tap is not None:
        tap(idx)
    absent = jnp.zeros((), jnp.int32)
    if cfg.moe_router_width:
        local = idx - cfg.moe_experts_first
        held = (local >= 0) & (local < cfg.moe_experts)
        absent = jnp.sum((ok[:, None] & ~held).astype(jnp.int32))
        idx, ok = jnp.clip(local, 0, cfg.moe_experts - 1), ok[:, None] & held
    y, sizes = grouped_ffn(m2, idx, w, ok, p["w_gate"], p["w_up"],
                           p["w_down"], name=name,
                           router_width=cfg.router_width)
    y = y.reshape(B, S, D)
    if cfg.moe_shared_experts:
        with jax.named_scope(scopes.MOE_SHARED):
            shared = _ffn(m, p["ws_gate"], p["ws_up"], p["ws_down"],
                          names=(scopes.MOE_SHARED,) * 2)
            if cfg.moe_shared_gate:
                gate = jax.nn.sigmoid(jnp.einsum(
                    "bsd,d->bs", m.astype(jnp.float32),
                    p["w_shared_gate"].astype(jnp.float32)))
                shared = (gate[..., None] * shared.astype(jnp.float32)
                          ).astype(y.dtype)
            y = y + shared
    counts = [jnp.ones((), jnp.int32), jnp.sum(sizes), jnp.max(sizes),
              jnp.sum((sizes > 0).astype(jnp.int32)), absent]
    if count_padded:
        tm = tile_rows(idx.size, cfg.router_width)
        counts.append(jnp.sum((sizes + (tm - 1)) // tm) * tm)
    return y, jnp.stack(counts)


def layer(cfg: TransformerConfig, kind: Tuple[str, str], p: Dict[str, Any],
          x: jax.Array, positions: jax.Array, attend: Callable,
          valid: Optional[jax.Array] = None,
          moe_name: str = scopes.MOE_EXPERTS_PREFILL,
          tap: Optional[Callable] = None
          ) -> Tuple[jax.Array, jax.Array]:
    """x [B, S, D] at `positions` [B, S] -> (x', MOE_COUNTS of this call).
    `attend(q, k, v)` with q [B, S, H, Dh], k, v [B, S, Hkv, Dh] returns
    the attention output [B, S, H, Dh] under this layer's visibility rule
    (the caller knows the mixer: it built `attend` for it)."""
    mixer, ffn = kind
    a = _rms(x, p["attn_norm"], cfg)
    with jax.named_scope(scopes.ATTN_QKV):
        q = jnp.einsum("bsd,dhk->bshk", a, p["wq"].astype(a.dtype))
        k = jnp.einsum("bsd,dhk->bshk", a, p["wk"].astype(a.dtype))
        v = jnp.einsum("bsd,dhk->bshk", a, p["wv"].astype(a.dtype))
        q, k = _rms(q, p["q_norm"], cfg), _rms(k, p["k_norm"], cfg)
        gate = jax.nn.sigmoid(jnp.einsum(
            "bsd,dhk->bshk", a, p["wg"].astype(a.dtype)).astype(jnp.float32))
        if mixer == "sliding":
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
    o = attend(q, k, v)
    with jax.named_scope(scopes.ATTN_OUT):
        o = (gate * o.astype(jnp.float32)).astype(x.dtype)
        attn = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    x = x + _rms(attn, p["post_attn_norm"], cfg)
    m = _rms(x, p["mlp_norm"], cfg)
    if ffn == "dense":
        y, counts = _ffn(m, p["w_gate"], p["w_up"], p["w_down"]), no_counts()
    else:
        y, counts = experts(cfg, p, m, valid, moe_name, tap)
    return x + _rms(y, p["post_mlp_norm"], cfg), counts


def window_of(cfg: TransformerConfig, kind: Tuple[str, str]
              ) -> Optional[int]:
    return cfg.sliding_window if kind[0] == "sliding" else None


@jax.named_scope(scopes.EMBED)
def embed(cfg: TransformerConfig, table: jax.Array,
          tokens: jax.Array) -> jax.Array:
    return (table[tokens].astype(jnp.float32) * math.sqrt(cfg.d_model)
            ).astype(cfg.dtype)


@jax.named_scope(scopes.HEAD)
def logits(cfg: TransformerConfig, params: Dict[str, Any],
           x: jax.Array) -> jax.Array:
    """x [..., D] -> float32 logits [..., V]; `params` holds final_norm and
    the head (or the tied table)."""
    x = _rms(x, params["final_norm"], cfg)
    return jnp.einsum("...d,dv->...v", x.astype(cfg.dtype),
                      _w_out(params, cfg).astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward without a cache
# ---------------------------------------------------------------------------
def _attend_plain(cfg: TransformerConfig, window: Optional[int]):
    """Causal (windowed) attention over the sequence itself: float32
    scores [B, H, S, S], for the sizes `forward` is used at."""
    def attend(q, k, v):
        B, S, H, D = q.shape
        g = H // cfg.kv_heads
        qg = q.reshape(B, S, cfg.kv_heads, g, D).astype(jnp.float32)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(D)
        i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", w, v.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
        return o.reshape(B, S, H, D)
    return attend


def forward_hidden(params: Dict[str, Any], tokens: jax.Array,
                   cfg: TransformerConfig) -> jax.Array:
    """tokens [B, S] -> final-norm hidden states [B, S, D]."""
    B, S = tokens.shape
    x = embed(cfg, params["tok_embed"], tokens)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    for kind, p in zip(cfg.layer_kinds, params["layers"]):
        x, _ = layer(cfg, kind, p, x, positions,
                     _attend_plain(cfg, window_of(cfg, kind)))
    return _rms(x, params["final_norm"], cfg)
