"""arch "lfm2": gated short convolutions and full attention layers mixed,
dense and sparse-expert feed-forwards mixed (Liquid AI's LFM2 MoE family).

ONE layer definition, `layer()`, which `forward` (no cache), the paged
prefill and the paged decode step (models/decoding.py) all run: they
differ only in the `mix` they hand it.  For an attention layer `mix(q, k,
v)` returns the attention output (writing a cache on its way, or not); for
a conv layer `mix(u)` returns, for every position of u, the
`conv_kernel - 1` positions before it in its own sequence (from a cache, or
from u itself).  The routing, the experts, the dense feed-forward and the
head are models/afmoe.py's.  The plain float32 reference is the deliberate
second copy (benchmarks/kinds/lfm2-moe.py).

A layer of kind (mixer, feed-forward), N() an RMSNorm with its own weight:

  a        = N_op(x)
  conv:      B, C, z = split3(W_in a);  u = B * z
             c_t = sum_j w[:, j] * u_{t - (K - 1) + j}    (K = conv_kernel,
                   per channel, u before the sequence's start is zero)
             x = x + W_out (C * c)
  full:      q, k, v = Wq a, Wk a, Wv a;  q = N_q(q), k = N_k(k) (per head)
             then the rotary embedding on q and k;  key j visible iff j <= i
             x = x + Wo attention
  m        = N_ffn(x)
  dense:     x = x + Wdown(silu(Wgate m) * Wup m)
  experts:   s = sigmoid(Wr m) in float32; S = top-k of (s + b);
             w_e = s_e / (sum_{S} s + moe_route_eps)       (b selects only)
             x = x + sum_{e in S} w_e FFN_e(m)

and x0 = Embed[token], logits = Embed^T N_final(x_L) (the head is tied).
Two norms a layer, both before a branch, none after one.

Parameters are a tuple of per-layer trees, layer l's from a key folded
with l, as in models/afmoe.py.  There is no training path.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import afmoe
from ray_tpu.models.afmoe import (_ffn, _rms, experts,  # noqa: F401
                                  init_head, logits, no_counts)
from ray_tpu.models.transformer import TransformerConfig, _rope
from ray_tpu.ops import scopes

MIXERS = ("conv", "full")


def _check(cfg: TransformerConfig) -> None:
    kinds = cfg.layer_kinds or ()
    if len(kinds) != cfg.n_layers or any(
            m not in MIXERS or f not in ("dense", "experts")
            for m, f in kinds):
        raise ValueError(
            f"lfm2 needs one (conv|full, dense|experts) pair per layer, got "
            f"{cfg.layer_kinds!r} for {cfg.n_layers} layers")


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def init_layer(cfg: TransformerConfig, key: jax.Array,
               index: int) -> Dict[str, Any]:
    """Layer `index` alone; spreads as models/afmoe.py init_layer has them
    (norm weights 1 + 0.1 N, a selection bias small beside the scores)."""
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    pd = cfg.param_dtype
    ks = iter(jax.random.split(jax.random.fold_in(key, index), 16))

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32) * scale
                ).astype(pd)

    def norm_weight(n):
        return (1.0 + 0.1 * jax.random.normal(next(ks), (n,), jnp.float32)
                ).astype(pd)

    s_in = 1.0 / math.sqrt(d)
    mixer, ffn = cfg.layer_kinds[index]
    p = {"op_norm": norm_weight(d), "ffn_norm": norm_weight(d)}
    if mixer == "conv":
        K = cfg.conv_kernel
        p.update(w_in=normal((d, 3, d), s_in),
                 w_conv=normal((K, d), 1.0 / math.sqrt(K)),
                 w_out=normal((d, d), s_in))
    else:
        p.update(q_norm=norm_weight(dh), k_norm=norm_weight(dh),
                 wq=normal((d, h, dh), s_in), wk=normal((d, hkv, dh), s_in),
                 wv=normal((d, hkv, dh), s_in),
                 wo=normal((h, dh, d), 1.0 / math.sqrt(h * dh)))
    if ffn == "dense":
        f = cfg.ff_dim
        p.update(w_gate=normal((d, f), s_in), w_up=normal((d, f), s_in),
                 w_down=normal((f, d), 1.0 / math.sqrt(f)))
        return p
    E, f = cfg.moe_experts, cfg.moe_d_ff
    p.update(w_router=normal((d, E), s_in),
             route_bias=normal((E,), 0.02),
             w_gate=normal((E, d, f), s_in), w_up=normal((E, d, f), s_in),
             w_down=normal((E, f, d), 1.0 / math.sqrt(f)))
    return p


def init_embed(cfg: TransformerConfig, key: jax.Array) -> jax.Array:
    """The table at unit spread: it is the residual stream's first term
    as it stands (no multiplier), and the head."""
    return jax.random.normal(jax.random.split(key, 8)[1],
                             (cfg.vocab_size, cfg.d_model), jnp.float32
                             ).astype(cfg.param_dtype)


def init_params(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    _check(cfg)
    layer_key = jax.random.split(key, 8)[0]
    return {"tok_embed": init_embed(cfg, key),
            "layers": tuple(init_layer(cfg, layer_key, i)
                            for i in range(cfg.n_layers)),
            **init_head(cfg, key)}


def logical_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    def layer(kind):
        p = {"op_norm": ("embed",), "ffn_norm": ("embed",)}
        if kind[0] == "conv":
            p.update(w_in=("embed", None, "mlp"), w_conv=(None, "mlp"),
                     w_out=("mlp", "embed"))
        else:
            p.update(q_norm=(None,), k_norm=(None,),
                     wq=("embed", "heads", "head_dim"),
                     wk=("embed", "kv_heads", "head_dim"),
                     wv=("embed", "kv_heads", "head_dim"),
                     wo=("heads", "head_dim", "embed"))
        if kind[1] == "dense":
            p.update(w_gate=("embed", "mlp"), w_up=("embed", "mlp"),
                     w_down=("mlp", "embed"))
        else:
            p.update(w_router=("embed", None), route_bias=(None,),
                     w_gate=("expert", "embed", "mlp"),
                     w_up=("expert", "embed", "mlp"),
                     w_down=("expert", "mlp", "embed"))
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
def taps(prev: jax.Array, u: jax.Array) -> list:
    """u [B, S, D] after `prev` [B, K - 1, D], the positions before each row
    (zeros at a sequence's start) -> u's K - 1 predecessors at every
    position, farthest first: K - 1 arrays like u."""
    ext = jnp.concatenate([prev.astype(u.dtype), u], axis=1)
    return [ext[:, j:j + u.shape[1]] for j in range(prev.shape[1])]


def short_conv(cfg: TransformerConfig, p: Dict[str, Any], a: jax.Array,
               before: Callable) -> jax.Array:
    """The conv operator on a [B, S, D]: `before(u)` gives u's K - 1
    predecessors at every position (`taps`: the caller knows what lies
    before each of its rows)."""
    with jax.named_scope(scopes.SHORT_CONV):
        bcz = jnp.einsum("bsd,dcf->bscf", a, p["w_in"].astype(a.dtype))
        u = bcz[:, :, 0] * bcz[:, :, 2]
        w = p["w_conv"].astype(jnp.float32)
        c = sum(w[j] * t.astype(jnp.float32)
                for j, t in enumerate(before(u) + [u]))
        y = (bcz[:, :, 1].astype(jnp.float32) * c).astype(a.dtype)
        return jnp.einsum("bsf,fd->bsd", y, p["w_out"].astype(a.dtype))


def layer(cfg: TransformerConfig, kind: Tuple[str, str], p: Dict[str, Any],
          x: jax.Array, positions: jax.Array, mix: Callable,
          valid: Optional[jax.Array] = None,
          moe_name: str = scopes.MOE_EXPERTS_PREFILL,
          tap: Optional[Callable] = None
          ) -> Tuple[jax.Array, jax.Array]:
    """x [B, S, D] at `positions` [B, S] -> (x', MOE_COUNTS of this call).
    `mix` is the caller's, built for this layer's mixer: `mix(q, k, v)` ->
    attention output [B, S, H, Dh] of a full layer, `mix(u)` -> u's
    conv_kernel - 1 predecessors at every position (`taps`), of a conv
    layer.  `tap`, if given, is shown an expert layer's input [B * S, D]
    and its picks [B * S, k] (a comparison's way to see them; the serving
    path passes none)."""
    mixer, ffn = kind
    a = _rms(x, p["op_norm"], cfg)
    if mixer == "conv":
        x = x + short_conv(cfg, p, a, mix)
    else:
        with jax.named_scope(scopes.ATTN_QKV):
            q = jnp.einsum("bsd,dhk->bshk", a, p["wq"].astype(a.dtype))
            k = jnp.einsum("bsd,dhk->bshk", a, p["wk"].astype(a.dtype))
            v = jnp.einsum("bsd,dhk->bshk", a, p["wv"].astype(a.dtype))
            q = _rope(_rms(q, p["q_norm"], cfg), positions, cfg.rope_theta)
            k = _rope(_rms(k, p["k_norm"], cfg), positions, cfg.rope_theta)
        o = mix(q, k, v).astype(x.dtype)
        with jax.named_scope(scopes.ATTN_OUT):
            x = x + jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    m = _rms(x, p["ffn_norm"], cfg)
    if ffn == "dense":
        return x + _ffn(m, p["w_gate"], p["w_up"], p["w_down"]), no_counts()
    y, counts = experts(
        cfg, p, m, valid, moe_name,
        tap and (lambda picks: tap(m.reshape(-1, m.shape[2]), picks)))
    return x + y, counts


def window_of(cfg: TransformerConfig, kind: Tuple[str, str]
              ) -> Optional[int]:
    return None


@jax.named_scope(scopes.EMBED)
def embed(cfg: TransformerConfig, table: jax.Array,
          tokens: jax.Array) -> jax.Array:
    return table[tokens].astype(cfg.dtype)


# ---------------------------------------------------------------------------
# forward without a cache
# ---------------------------------------------------------------------------
def forward_hidden(params: Dict[str, Any], tokens: jax.Array,
                   cfg: TransformerConfig) -> jax.Array:
    """tokens [B, S] -> final-norm hidden states [B, S, D]."""
    B, S = tokens.shape
    x = embed(cfg, params["tok_embed"], tokens)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    attend = afmoe._attend_plain(cfg, None)

    def nothing_before(u):
        return taps(jnp.zeros((B, cfg.conv_kernel - 1, u.shape[2]), u.dtype),
                    u)

    for kind, p in zip(cfg.layer_kinds, params["layers"]):
        x, _ = layer(cfg, kind, p, x, positions,
                     nothing_before if kind[0] == "conv" else attend)
    return _rms(x, params["final_norm"], cfg)
