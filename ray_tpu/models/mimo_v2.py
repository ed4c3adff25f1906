"""arch "mimo_v2": sliding-window layers with a learned sink in their softmax
and full attention layers mixed, five to one, with kv head counts that
differ by layer kind, keys wider than values and a partial rotary embedding
whose base differs by layer kind; a dense feed-forward in the first layer and
sigmoid-routed experts (no shared one) in the others (Xiaomi's MiMo-V2
family).

ONE layer definition, `layer()`, which `forward` (no cache), the fused
prefill pass and the paged decode step (models/decoding.py) all run: they
differ only in the `mix` they hand it.  For a full layer `mix(q, k, v)`
returns the attention output over everything before the query; for a ring
layer `mix(q, k, v, sink)` returns it over the last `sliding_window`
positions with the sink in the softmax, from whatever the caller keeps of
them (a ring a sequence: ops/window_ring.py; or the sequence itself).  The
routing and the experts are models/afmoe.py's (sigmoid scores, a bias that
only selects, `moe_router_width` for a share of the experts).  The plain
float32 reference is the deliberate second copy
(benchmarks/kinds/sink-window-moe.py).

A layer of kind (mixer, feed-forward); N(x) = x rsqrt(mean(x^2) + eps) w in
float32 (a plain weight); x the residual stream, a = N_in(x); H query heads,
keys dk wide, values dv wide:

  q = Wq a (H x dk),  k = Wk a (Hkv x dk),  v = value_scale * Wv a (Hkv x dv)
      Hkv = `n_kv_heads` (full) | `sliding_kv_heads` (ring); no bias, no
      q / k norm
  rotary on dims 0 .. rotary_dim - 1 of each q and k head, rotate-half
      pairing (i, i + rotary_dim / 2), base `rope_theta` (full) |
      `sliding_rope_theta` (ring); the other dims untouched
  s_ij = q_i . k_j dk^-1/2;  query head h reads kv head h // (H / Hkv)
  full:  j <= i;                p_ij = exp(s_ij - m) / sum_j exp(s_ij - m)
  ring:  j <= i and i - j < W;  p_ij = exp(s_ij - m)
                                      / (exp(b_h - m) + sum_j exp(s_ij - m))
         m = max(b_h, max_j s_ij), b_h a learned scalar a query head: the
         sink takes probability and adds no value
  y = Wo [sum_j p_ij v_j]_h                                (H x dv -> D)
  x = x + y;  m = N_post(x)
  dense:    f = Wd (silu(Wg m) * Wu m)
  experts:  c = sigmoid(Wr m) in float32 over ALL `router_width` experts;
            S = top-k of c + bias (the bias selects only);
            w_e = c_e / (sum_S c + moe_route_eps)
            f = sum_{e in S, e held here} w_e FFN_e(m)     (no shared expert)
  x = x + f

and x0 = Embed[token], logits = lm_head^T N_final(x_L).

DEPARTURE RISKS (what the published `config.json` does not carry; the
writer's recollection of the published modelling code, with no network here
to re-read it; each is listed in the benchmark's configuration file under
`assumed`): no q / k norm and no output gate (no key for either; the
parameter count agrees with the published 309 B without them);
`attention_value_scale` multiplies the values (linear: the result is the
same wherever it is applied); the sink as one more softmax column whose mass
is dropped; the window as i - j < W (not <=); the rotated dims are the FIRST
int(partial_rotary_factor x dk) = 64, rotate-half; the selection bias does
not enter the weights; the 1e-20 added to the picks' sum; float32 scores and
softmax; `attention_chunk_size` read as unused; the three multi-token-
prediction layers left out (the engine yields one token a sequence a step).

Parameters are a tuple of per-layer trees, layer l's from a key folded with
l, as in models/afmoe.py.  There is no training path.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import afmoe
from ray_tpu.models.afmoe import (_ffn, _rms, experts,  # noqa: F401
                                  init_head, logits, route)
from ray_tpu.models.transformer import TransformerConfig, _rope
from ray_tpu.ops import scopes

MIXERS = ("ring", "full")
# every expert layer's counts: models/afmoe.py's and the rows the grouped
# product computed, padding included
MOE_COUNTS = afmoe.MOE_COUNTS + (afmoe.PADDED_ROWS,)


def no_counts() -> jax.Array:
    return jnp.zeros((len(MOE_COUNTS),), jnp.int32)


def _check(cfg: TransformerConfig) -> None:
    kinds = cfg.layer_kinds or ()
    if len(kinds) != cfg.n_layers or any(
            m not in MIXERS or f not in ("dense", "experts")
            for m, f in kinds):
        raise ValueError(
            f"mimo_v2 needs one (ring|full, dense|experts) pair per layer, "
            f"got {cfg.layer_kinds!r} for {cfg.n_layers} layers")
    if any(m == "ring" for m, _ in kinds) and cfg.sliding_window < 1:
        raise ValueError("ring layers need a sliding_window")
    if rotary_dim(cfg) % 2 or rotary_dim(cfg) > cfg.head_dim:
        raise ValueError("rotary_dim is even and at most the head size")
    if cfg.n_heads % cfg.kv_heads or cfg.n_heads % kv_heads_of(cfg, "ring"):
        raise ValueError("query heads are not a multiple of the kv heads")


def rotary_dim(cfg: TransformerConfig) -> int:
    return cfg.rotary_dim or cfg.head_dim


def value_dim(cfg: TransformerConfig) -> int:
    return cfg.v_head_dim or cfg.head_dim


def kv_heads_of(cfg: TransformerConfig, mixer: str) -> int:
    """A layer's kv heads: a ring layer's are `sliding_kv_heads`."""
    return (cfg.sliding_kv_heads if mixer == "ring" else 0) or cfg.kv_heads


def theta_of(cfg: TransformerConfig, mixer: str) -> float:
    if mixer == "ring" and cfg.sliding_rope_theta is not None:
        return cfg.sliding_rope_theta
    return cfg.rope_theta


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def init_layer(cfg: TransformerConfig, key: jax.Array, index: int,
               like: Optional[int] = None) -> Dict[str, Any]:
    """Layer `index` alone (`like`: a layer of the same kind, static, where
    `index` is traced: one compiled maker a KIND of layer).  Spreads chosen
    so that a program that drops a piece cannot agree with the reference:
    norm weights 1 + 0.1 N; the sinks N(0, 1) (a softmax without the column,
    or with it on a full layer, is far off); the selection bias small beside
    the scores' own spread, 0.02 against ~0.2, as models/afmoe.py has it and
    for its reason (a random bias of the scores' size sends most tokens to a
    few experts, which no trained bias does)."""
    d, h, dk, dv = cfg.d_model, cfg.n_heads, cfg.head_dim, value_dim(cfg)
    pd = cfg.param_dtype
    ks = iter(jax.random.split(jax.random.fold_in(key, index), 16))
    mixer, ffn = cfg.layer_kinds[index if like is None else like]
    hkv = kv_heads_of(cfg, mixer)

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32) * scale
                ).astype(pd)

    def norm_weight(n):
        return (1.0 + 0.1 * jax.random.normal(next(ks), (n,), jnp.float32)
                ).astype(pd)

    s_in = 1.0 / math.sqrt(d)
    p = {"attn_norm": norm_weight(d), "ffn_norm": norm_weight(d),
         "wq": normal((d, h, dk), s_in), "wk": normal((d, hkv, dk), s_in),
         "wv": normal((d, hkv, dv), s_in),
         "wo": normal((h, dv, d), 1.0 / math.sqrt(h * dv))}
    if mixer == "ring":
        p["sink"] = normal((h,), 1.0)
    if ffn == "dense":
        f = cfg.ff_dim
        p.update(w_gate=normal((d, f), s_in), w_up=normal((d, f), s_in),
                 w_down=normal((f, d), 1.0 / math.sqrt(f)))
        return p
    E, f = cfg.moe_experts, cfg.moe_d_ff
    p.update(w_router=normal((d, cfg.router_width), s_in),
             route_bias=normal((cfg.router_width,), 0.02),
             w_gate=normal((E, d, f), s_in), w_up=normal((E, d, f), s_in),
             w_down=normal((E, f, d), 1.0 / math.sqrt(f)))
    return p


def init_embed(cfg: TransformerConfig, key: jax.Array) -> jax.Array:
    """The table at unit spread: the residual stream's first term as it
    stands (no multiplier)."""
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
             "wq": ("embed", "heads", "head_dim"),
             "wk": ("embed", "kv_heads", "head_dim"),
             "wv": ("embed", "kv_heads", "head_dim"),
             "wo": ("heads", "head_dim", "embed")}
        if kind[0] == "ring":
            p["sink"] = (None,)
        if kind[1] == "dense":
            p.update(w_gate=("embed", "mlp"), w_up=("embed", "mlp"),
                     w_down=("mlp", "embed"))
            return p
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
def _partial_rope(cfg: TransformerConfig, x: jax.Array, positions: jax.Array,
                  theta: float) -> jax.Array:
    """The rotary embedding on the first `rotary_dim` dims of every head of
    x [B, S, heads, dk]; the others pass."""
    r = rotary_dim(cfg)
    if r == x.shape[-1]:
        return _rope(x, positions, theta)
    return jnp.concatenate([_rope(x[..., :r], positions, theta), x[..., r:]],
                           axis=-1)


def attention(cfg: TransformerConfig, mixer: str, p: Dict[str, Any],
              a: jax.Array, positions: jax.Array, mix: Callable) -> jax.Array:
    """A layer's attention branch on a [B, S, D]; `mix` is the caller's
    (see the module docstring)."""
    ring = mixer == "ring"
    with jax.named_scope(scopes.RING_ATTN_QKV if ring
                         else scopes.FULL_ATTN_QKV):
        q = jnp.einsum("bsd,dhk->bshk", a, p["wq"].astype(a.dtype))
        k = jnp.einsum("bsd,dhk->bshk", a, p["wk"].astype(a.dtype))
        v = jnp.einsum("bsd,dhk->bshk", a, p["wv"].astype(a.dtype))
        if cfg.attn_value_scale != 1.0:
            v = (v.astype(jnp.float32) * cfg.attn_value_scale
                 ).astype(a.dtype)
        theta = theta_of(cfg, mixer)
        q = _partial_rope(cfg, q, positions, theta)
        k = _partial_rope(cfg, k, positions, theta)
    if ring:
        with jax.named_scope(scopes.RING_ATTN):
            o = mix(q, k, v, p["sink"].astype(jnp.float32)).astype(a.dtype)
    else:
        o = mix(q, k, v).astype(a.dtype)
    with jax.named_scope(scopes.RING_OUT if ring else scopes.FULL_ATTN_OUT):
        return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))


def layer(cfg: TransformerConfig, kind: Tuple[str, str], p: Dict[str, Any],
          x: jax.Array, positions: jax.Array, mix,
          valid: Optional[jax.Array] = None,
          moe_name: str = scopes.MOE_EXPERTS_PREFILL,
          tap: Optional[Callable] = None) -> Tuple[jax.Array, jax.Array]:
    """x [B, S, D] at `positions` [B, S] -> (x', this module's MOE_COUNTS of
    this call).  `mix` is the caller's, built for this layer's mixer (the
    module docstring).  `tap`, if given, is shown ("route", the expert
    layer's input [B * S, D], its picks [B * S, k] over the router's whole
    width and their weights [B * S, k], `route`'s own on that input): a
    comparison's way to see them; the serving path passes none."""
    x = x + attention(cfg, kind[0], p, _rms(x, p["attn_norm"], cfg),
                      positions, mix)
    m = _rms(x, p["ffn_norm"], cfg)
    if kind[1] == "dense":
        return x + _ffn(m, p["w_gate"], p["w_up"], p["w_down"]), no_counts()
    m2 = m.reshape(-1, m.shape[2])
    y, counts = experts(
        cfg, p, m, valid, moe_name,
        tap and (lambda picks: tap("route", m2, picks,
                                   route(cfg, p, m2)[1])),
        count_padded=True)
    return x + y, counts


def window_of(cfg: TransformerConfig, kind: Tuple[str, str]
              ) -> Optional[int]:
    """A full layer's paged attention has no window (a ring layer keeps no
    pages: its window is its ring's size)."""
    return None


@jax.named_scope(scopes.EMBED)
def embed(cfg: TransformerConfig, table: jax.Array,
          tokens: jax.Array) -> jax.Array:
    return table[tokens].astype(cfg.dtype)


# ---------------------------------------------------------------------------
# forward without a cache
# ---------------------------------------------------------------------------
def _attend_plain(window: Optional[int]):
    """Causal (windowed) attention over the sequence itself, keys and values
    of their own widths, with the sink column where one is given: float32
    scores [B, Hkv, G, S, S], for the sizes `forward` is used at."""
    def attend(q, k, v, sink=None):
        B, S, H, D = q.shape
        hkv = k.shape[2]
        g = H // hkv
        qg = q.reshape(B, S, hkv, g, D).astype(jnp.float32)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(D)
        i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        s = jnp.where(seen, s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        extra = 0.0
        if sink is not None:
            b = sink.astype(jnp.float32).reshape(1, hkv, g, 1, 1)
            m = jnp.maximum(m, b)
            extra = jnp.exp(b - m)
        e = jnp.exp(s - m)
        w = e / (extra + jnp.sum(e, axis=-1, keepdims=True))
        o = jnp.einsum("bhgqk,bkhd->bqhgd", w, v.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
        return o.reshape(B, S, H, v.shape[-1])
    return attend


def forward_hidden(params: Dict[str, Any], tokens: jax.Array,
                   cfg: TransformerConfig) -> jax.Array:
    """tokens [B, S] -> final-norm hidden states [B, S, D]."""
    B, S = tokens.shape
    x = embed(cfg, params["tok_embed"], tokens)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    for kind, p in zip(cfg.layer_kinds, params["layers"]):
        x, _ = layer(cfg, kind, p, x, positions, _attend_plain(
            cfg.sliding_window if kind[0] == "ring" else None))
    return _rms(x, params["final_norm"], cfg)
