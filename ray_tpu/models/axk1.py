"""arch "axk1": multi-head latent attention (MLA) in every layer, dense and
sparse-expert feed-forwards mixed, the expert layers holding a SHARE of the
experts their router scores (SK Telecom's A.X-K1; the DeepSeek-V2/V3 family's
equations, which `model_type` axk1 inherits key for key).

ONE layer definition, `layer()`, which `forward` (no cache), the paged
prefill and the paged decode step (models/decoding.py) all run: they differ
only in the `mix` they hand it.  `mix(q, row)` takes the absorbed queries
[B, S, H, c + r] and the one latent row a position leaves behind [B, S, 1,
c + r] (c = kv_lora_rank, r = qk_rope_dim) and returns, per head, the
softmax-weighted sum of the rows' first c values [B, S, H, c] (writing a
cache on its way, or not).  The routing, the experts, the dense
feed-forward and the head are models/afmoe.py's.  The plain float32
reference, in the PUBLISHED (expanded, per-head keys and values) form, is
the deliberate second copy (benchmarks/kinds/mla-moe.py).

A layer of kind ("latent", feed-forward), N() an RMSNorm with its own weight:

  a        = N_in(x)
  c_q      = N_q(W_dq a);  [q_nope | q_rope]_h = W_uq,h c_q
  [c | k_r] = W_dkv a;  c = N_kv(c);  q_rope, k_r get the rotary embedding
             (yarn's frequencies over the r dims; ONE k_r for every head)
  published: [k_nope | v]_h = W_ukv,h c
             score_h(t, j) = scale (q_nope,h(t) . k_nope,h(j)
                                    + q_rope,h(t) . k_r(j)),  j <= t
             o_h = sum_j softmax_j v_h(j)
  absorbed (exact; what this module computes on every path):
             q~_h = W_uk,h^T q_nope,h            (in R^c)
             score_h = scale [q~_h | q_rope,h] . [c | k_r]
             o~_h = sum_j softmax_j c(j);   o_h = W_uv,h o~_h
  x        = x + W_o [o_1 .. o_H]
  m        = N_ffn(x)
  dense:     x = x + Wdown(silu(Wgate m) * Wup m)
  experts:   s = sigmoid(Wr m) in float32 over ALL `router_width` experts;
             S = top-k of s;  w_e = route_scale * s_e / sum_{S} s
             x = x + sum_{e in S, e held here} w_e FFN_e(m) + FFN_shared(m)

and x0 = Embed[token], logits = Whead N_final(x_L).  `scale` =
(qk_nope_dim + qk_rope_dim)^-1/2 * m^2 with yarn's m = 0.1 mscale_all_dim
ln(factor) + 1 (`softmax_scale`): it is never derived from a row's width.

That is multi-query attention of H heads over ONE key "head" of c + r whose
value is the key's first c lanes: one pool a layer, read once
(ops/paged_attention.py `mla_paged_attention`, `mla_prefix_attention`).

Parameters are a tuple of per-layer trees, layer l's from a key folded
with l, as in models/afmoe.py; W_ukv is kept as its two halves per head,
`w_uk` [H, nope, c] and `w_uv` [H, c, v], so that the absorb is one einsum.
There is no training path.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.afmoe import (_ffn, _rms, experts,  # noqa: F401
                                  init_head, logits, no_counts)
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.ops import scopes

MIXERS = ("latent",)


def _check(cfg: TransformerConfig) -> None:
    kinds = cfg.layer_kinds or ()
    if len(kinds) != cfg.n_layers or any(
            m not in MIXERS or f not in ("dense", "experts")
            for m, f in kinds):
        raise ValueError(
            f"axk1 needs one (latent, dense|experts) pair per layer, got "
            f"{cfg.layer_kinds!r} for {cfg.n_layers} layers")
    if min(cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim,
           cfg.qk_rope_dim, cfg.v_head_dim) <= 0 or cfg.qk_rope_dim % 2:
        raise ValueError("axk1 needs q_lora_rank, kv_lora_rank, qk_nope_dim, "
                         "an even qk_rope_dim and v_head_dim")


# ---------------------------------------------------------------------------
# the rotary embedding (yarn) and the softmax scale
# ---------------------------------------------------------------------------
def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_range(cfg: TransformerConfig) -> Tuple[int, int]:
    """(low, high): the pairs of the rotary dims between which the
    frequencies go from kept (fast, below low) to divided by the factor
    (slow, above high)."""
    dim = cfg.qk_rope_dim

    def at(rotations: float) -> float:
        return dim * math.log(cfg.rope_original_max
                              / (rotations * 2.0 * math.pi)) \
            / (2.0 * math.log(cfg.rope_theta))

    return (max(math.floor(at(cfg.rope_beta_fast)), 0),
            min(math.ceil(at(cfg.rope_beta_slow)), dim - 1))


def yarn_inv_freq(cfg: TransformerConfig) -> np.ndarray:
    """The qk_rope_dim / 2 rotary frequencies, float32: theta^(-2i / dim),
    and with a `rope_factor` above 1 each divided by it as far as yarn's ramp
    says."""
    half = cfg.qk_rope_dim // 2
    i = np.arange(half, dtype=np.float64)
    inv = cfg.rope_theta ** (-i / half)
    if cfg.rope_factor != 1.0:
        low, high = yarn_range(cfg)
        ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
        inv = inv / cfg.rope_factor * ramp + inv * (1.0 - ramp)
    return inv.astype(np.float32)


def softmax_scale(cfg: TransformerConfig) -> float:
    m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5 * m * m


def rope(cfg: TransformerConfig, x: jax.Array,
         positions: jax.Array) -> jax.Array:
    """x [B, S, heads, qk_rope_dim] at positions [B, S]; the two halves of
    the dims are a pair's two parts (rotate-half)."""
    half = cfg.qk_rope_dim // 2
    angles = positions[..., None].astype(jnp.float32) * yarn_inv_freq(cfg)
    mult = (_yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
            / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    cos = (jnp.cos(angles) * mult)[:, :, None, :]
    sin = (jnp.sin(angles) * mult)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def latent_kw(cfg: TransformerConfig) -> Dict[str, Any]:
    """What the latent attention functions need beside the rows."""
    return {"scale": softmax_scale(cfg), "v_dim": cfg.kv_lora_rank}


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def init_layer(cfg: TransformerConfig, key: jax.Array,
               index: int) -> Dict[str, Any]:
    """Layer `index` alone; spreads as models/afmoe.py init_layer has them
    (norm weights 1 + 0.1 N, so that a program that ignores one cannot
    agree with the reference)."""
    d, h = cfg.d_model, cfg.n_heads
    rq, c, r = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_dim
    n, v = cfg.qk_nope_dim, cfg.v_head_dim
    pd = cfg.param_dtype
    ks = iter(jax.random.split(jax.random.fold_in(key, index), 24))

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32) * scale
                ).astype(pd)

    def norm_weight(n):
        return (1.0 + 0.1 * jax.random.normal(next(ks), (n,), jnp.float32)
                ).astype(pd)

    s_in = 1.0 / math.sqrt(d)
    p = {"attn_norm": norm_weight(d), "ffn_norm": norm_weight(d),
         "q_norm": norm_weight(rq), "kv_norm": norm_weight(c),
         "w_dq": normal((d, rq), s_in),
         "w_uq": normal((rq, h, n + r), 1.0 / math.sqrt(rq)),
         "w_dkv": normal((d, c + r), s_in),
         "w_uk": normal((h, n, c), 1.0 / math.sqrt(c)),
         "w_uv": normal((h, c, v), 1.0 / math.sqrt(c)),
         "w_o": normal((h, v, d), 1.0 / math.sqrt(h * v))}
    if cfg.layer_kinds[index][1] == "dense":
        f = cfg.ff_dim
        p.update(w_gate=normal((d, f), s_in), w_up=normal((d, f), s_in),
                 w_down=normal((f, d), 1.0 / math.sqrt(f)))
        return p
    E, f = cfg.moe_experts, cfg.moe_d_ff
    p.update(w_router=normal((d, cfg.router_width), s_in),
             w_gate=normal((E, d, f), s_in), w_up=normal((E, d, f), s_in),
             w_down=normal((E, f, d), 1.0 / math.sqrt(f)))
    if cfg.moe_shared_experts:
        fs = f * cfg.moe_shared_experts
        p.update(ws_gate=normal((d, fs), s_in), ws_up=normal((d, fs), s_in),
                 ws_down=normal((fs, d), 1.0 / math.sqrt(fs)))
    return p


def init_embed(cfg: TransformerConfig, key: jax.Array) -> jax.Array:
    """The table at unit spread: it is the residual stream's first term as
    it stands (no multiplier)."""
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
        p = {"attn_norm": ("embed",), "ffn_norm": ("embed",),
             "q_norm": (None,), "kv_norm": (None,),
             "w_dq": ("embed", None), "w_uq": (None, "heads", "head_dim"),
             "w_dkv": ("embed", None), "w_uk": ("heads", "head_dim", None),
             "w_uv": ("heads", None, "head_dim"),
             "w_o": ("heads", "head_dim", "embed")}
        if kind[1] == "dense":
            p.update(w_gate=("embed", "mlp"), w_up=("embed", "mlp"),
                     w_down=("mlp", "embed"))
            return p
        p.update(w_router=("embed", None),
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
def latent_queries(cfg: TransformerConfig, p: Dict[str, Any], a: jax.Array,
                   positions: jax.Array) -> jax.Array:
    """a [B, S, D] -> the absorbed queries [q~ | q_rope] [B, S, H, c + r]."""
    with jax.named_scope(scopes.MLA_Q):
        cq = _rms(jnp.einsum("bsd,dr->bsr", a, p["w_dq"].astype(a.dtype)),
                  p["q_norm"], cfg)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"].astype(a.dtype))
        q_nope, q_rope = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
        q_abs = jnp.einsum("bshn,hnc->bshc", q_nope,
                           p["w_uk"].astype(a.dtype))
        return jnp.concatenate([q_abs, rope(cfg, q_rope, positions)],
                               axis=-1)


def latent_row(cfg: TransformerConfig, p: Dict[str, Any], a: jax.Array,
               positions: jax.Array) -> jax.Array:
    """a [B, S, D] -> what each position leaves behind, [B, S, 1, c + r]:
    the latent after its norm, the shared key part after its rotation."""
    with jax.named_scope(scopes.MLA_KV):
        ckv = jnp.einsum("bsd,dk->bsk", a, p["w_dkv"].astype(a.dtype))
        c = _rms(ckv[..., :cfg.kv_lora_rank], p["kv_norm"], cfg)
        k_r = rope(cfg, ckv[:, :, None, cfg.kv_lora_rank:], positions)
        return jnp.concatenate([c[:, :, None], k_r], axis=-1)


def layer(cfg: TransformerConfig, kind: Tuple[str, str], p: Dict[str, Any],
          x: jax.Array, positions: jax.Array, mix: Callable,
          valid: Optional[jax.Array] = None,
          moe_name: str = scopes.MOE_EXPERTS_PREFILL,
          tap: Optional[Callable] = None
          ) -> Tuple[jax.Array, jax.Array]:
    """x [B, S, D] at `positions` [B, S] -> (x', MOE_COUNTS of this call).
    `mix(q, row)` is the caller's: absorbed queries [B, S, H, c + r] and the
    positions' latent rows [B, S, 1, c + r] -> [B, S, H, c].  `tap`, if
    given, is shown an expert layer's input [B * S, D] and its picks [B * S,
    k] over the router's whole width (a comparison's way to see them)."""
    a = _rms(x, p["attn_norm"], cfg)
    o = mix(latent_queries(cfg, p, a, positions),
            latent_row(cfg, p, a, positions)).astype(x.dtype)
    with jax.named_scope(scopes.MLA_OUT):
        ov = jnp.einsum("bshc,hcv->bshv", o, p["w_uv"].astype(o.dtype))
        x = x + jnp.einsum("bshv,hvd->bsd", ov, p["w_o"].astype(o.dtype))
    m = _rms(x, p["ffn_norm"], cfg)
    if kind[1] == "dense":
        return x + _ffn(m, p["w_gate"], p["w_up"], p["w_down"]), no_counts()
    y, counts = experts(
        cfg, p, m, valid, moe_name,
        tap and (lambda picks: tap(m.reshape(-1, m.shape[2]), picks)))
    return x + y, counts


@jax.named_scope(scopes.EMBED)
def embed(cfg: TransformerConfig, table: jax.Array,
          tokens: jax.Array) -> jax.Array:
    return table[tokens].astype(cfg.dtype)


# ---------------------------------------------------------------------------
# forward without a cache
# ---------------------------------------------------------------------------
def _attend_plain(cfg: TransformerConfig):
    """Causal attention of the absorbed queries over the sequence's own
    latent rows: float32 scores [B, H, S, S], for the sizes `forward` is used
    at."""
    scale, c = softmax_scale(cfg), cfg.kv_lora_rank

    def attend(q, row):
        S = q.shape[1]
        k = row[:, :, 0].astype(jnp.float32)
        s = jnp.einsum("bqhk,bjk->bhqj", q.astype(jnp.float32), k,
                       precision=jax.lax.Precision.HIGHEST) * scale
        seen = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqj,bjc->bqhc", w, k[..., :c],
                          precision=jax.lax.Precision.HIGHEST)
    return attend


def forward_hidden(params: Dict[str, Any], tokens: jax.Array,
                   cfg: TransformerConfig) -> jax.Array:
    """tokens [B, S] -> final-norm hidden states [B, S, D]."""
    B, S = tokens.shape
    x = embed(cfg, params["tok_embed"], tokens)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    for kind, p in zip(cfg.layer_kinds, params["layers"]):
        x, _ = layer(cfg, kind, p, x, positions, _attend_plain(cfg))
    return _rms(x, params["final_norm"], cfg)
