"""arch "qwen3_next": gated delta-rule layers whose key heads are shared by
twice as many value heads, gated full-attention layers at a head size of 256
with a partial rotary embedding, and an expert layer (softmax-routed, with a
gated shared expert) in EVERY layer (Qwen's Qwen3-Next family).

ONE layer definition, `layer()`, which `forward` (no cache), the fused
prefill pass and the paged decode step (models/decoding.py) all run: they
differ only in the `mix` they hand it, as models/olmo_hybrid.py has it.  For
a full layer `mix(q, k, v)` returns the attention output.  For a linear layer
`mix` is a pair `(before, rule)`: `before(u)` gives the `conv_kernel - 1`
positions before each position of the convolution's input, `rule(q, k, v,
log_a, beta)` runs the recurrence from whatever state the caller keeps.  The
linear mixer IS models/olmo_hybrid.py's `delta_mixer` (its key heads counted
by `linear_key_heads`), the routing and the experts are models/afmoe.py's
(`moe_score_fn` "softmax", `moe_shared_gate`).  The plain float32 reference
is the deliberate second copy (benchmarks/kinds/gated-delta-moe.py).

A layer of kind (mixer, "experts"); N(x) = x rsqrt(mean(x^2) + eps) (1 + w)
in float32, the weight ZERO-CENTRED (`norm_zero_centered`); x the residual
stream, a = N_in(x):

  linear:  u = [Wq a | Wk a | Wv a]        (Hk dk + Hk dk + Hv dv wide)
           c_t = silu(sum_j w[:, j] u_{t-(K-1)+j})   (K = conv_kernel taps a
                 channel, no bias, u before the sequence's start is zero)
           q', k', v = split(c);  per key head:
           q = q' / |q'| dk^-1/2,  k = k' / |k'|        (L2, eps 1e-6)
           value head h uses key head h // (Hv / Hk)
           beta = sigmoid(Wb a)   in (0, 1);  per value head, float32:
           ln alpha = -exp(A_log) softplus(Wa a + dt_bias)
           S' = alpha S;  u = beta (v - S'^T k);  S = S' + k u^T;  o = S^T q
           y = Wo [rms(o_h) w_o silu(z_h)]_h,  z = Wz a   (w_o NOT
               zero-centred; a head's dv values)
  full:    [q | g]_h = (Wq a)_h            (a head's Dh query dims, then its
                                            Dh gate dims)
           k, v = Wk a, Wv a;  q_h = N_q(q_h), k_h = N_k(k_h) over the head
           rotary (rotate-half inside the first `rotary_dim` dims of a head,
           pairs (i, i + rotary_dim / 2)); the other dims untouched
           o = causal softmax(q k^T Dh^-1/2) v  (H / Hkv query heads a kv head)
           y = Wo (o * sigmoid(g))
  x = x + y
  m = N_post(x)
           p = softmax(Wr m) in float32 over ALL `router_width` experts;
           S = top-k of p;  w_e = p_e / sum_{S} p
           f = sum_{e in S, e held here} w_e FFN_e(m)
               + sigmoid(w_sg . m) FFN_shared(m)
  x = x + f

and x0 = Embed[token], logits = lm_head^T N_final(x_L).

DEPARTURE RISKS (what the published `config.json` does not carry; the
writer's recollection of the published modelling code, with no network here
to re-read it; each is listed in the benchmark's configuration file under
`assumed`): the (1 + w) norms and the plain-weight gated head norm; the order
[query | gate] inside a head of W_q; norm before rotary; the rotate-half
pairing inside the first `rotary_dim` dims; W_q, W_k, W_v, W_z and W_b, W_a
kept apart here where the published code fuses them into two matrices whose
rows are grouped by key head (a permutation of rows: immaterial under seeded
weights); value head h on key head h // 2 (`repeat_interleave`); L2 eps 1e-6
and dk^-1/2 on q after it; softmax BEFORE top-k; a shared gate one wide;
float32 state, gates and routing scores; no multi-token-prediction module
(the engine yields one token a sequence a step).

Parameters are a tuple of per-layer trees, layer l's from a key folded with
l, as in models/afmoe.py.  There is no training path.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import afmoe
from ray_tpu.models.afmoe import experts
from ray_tpu.models.lfm2 import taps  # noqa: F401
from ray_tpu.models.olmo_hybrid import (conv_width, delta_mixer,  # noqa: F401
                                        key_heads)
from ray_tpu.models.transformer import (TransformerConfig, _norm, _rope,
                                        _w_out)
from ray_tpu.ops import scopes

MIXERS = ("linear", "full")
# every layer's counts: models/afmoe.py's and the rows the grouped product
# computed, padding included
MOE_COUNTS = afmoe.MOE_COUNTS + (afmoe.PADDED_ROWS,)


def no_counts() -> jax.Array:
    return jnp.zeros((len(MOE_COUNTS),), jnp.int32)


def _check(cfg: TransformerConfig) -> None:
    kinds = cfg.layer_kinds or ()
    if len(kinds) != cfg.n_layers or any(
            m not in MIXERS or f != "experts" for m, f in kinds):
        raise ValueError(
            f"qwen3_next needs one (linear|full, experts) pair per layer, "
            f"got {cfg.layer_kinds!r} for {cfg.n_layers} layers")
    if any(m == "linear" for m, _ in kinds) and not (
            cfg.linear_heads and cfg.linear_key_dim and cfg.linear_value_dim
            and cfg.linear_heads % key_heads(cfg) == 0):
        raise ValueError("linear layers need linear_heads (a multiple of "
                         "linear_key_heads), linear_key_dim and "
                         "linear_value_dim")
    if rotary_dim(cfg) % 2 or rotary_dim(cfg) > cfg.head_dim:
        raise ValueError("rotary_dim is even and at most the head size")


def rotary_dim(cfg: TransformerConfig) -> int:
    return cfg.rotary_dim or cfg.head_dim


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def init_layer(cfg: TransformerConfig, key: jax.Array, index: int,
               like: Optional[int] = None) -> Dict[str, Any]:
    """Layer `index` alone (`like`: a layer of the same kind, static, where
    `index` is traced: one compiled maker a KIND of layer).  Spreads as
    models/olmo_hybrid.py init_layer has them, so that a program that drops
    a piece cannot agree with the reference: a norm's effective weight
    1 + 0.1 N (a zero-centred one is kept as 0.1 N), taps N(0, 1/K), A_log ~
    ln U(1, 16), softplus(dt_bias) log-uniform in (1e-3, 0.1): alpha spans
    ~0.2-0.999."""
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    pd = cfg.param_dtype
    ks = iter(jax.random.split(jax.random.fold_in(key, index), 24))
    index = index if like is None else like

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32) * scale
                ).astype(pd)

    def norm_weight(n, centred=cfg.norm_zero_centered):
        return ((0.0 if centred else 1.0) + 0.1 * jax.random.normal(
            next(ks), (n,), jnp.float32)).astype(pd)

    s_in = 1.0 / math.sqrt(d)
    p = {"attn_norm": norm_weight(d), "ffn_norm": norm_weight(d)}
    if cfg.layer_kinds[index][0] == "linear":
        H, Hk = cfg.linear_heads, key_heads(cfg)
        dk, dv, K = cfg.linear_key_dim, cfg.linear_value_dim, cfg.conv_kernel
        step = jnp.exp(jax.random.uniform(
            next(ks), (H,), jnp.float32, math.log(1e-3), math.log(0.1)))
        p.update(
            wq=normal((d, Hk, dk), s_in), wk=normal((d, Hk, dk), s_in),
            wv=normal((d, H, dv), s_in), wg=normal((d, H, dv), s_in),
            wa=normal((d, H), s_in), wb=normal((d, H), s_in),
            w_conv=normal((K, conv_width(cfg)), 1.0 / math.sqrt(K)),
            A_log=jnp.log(jax.random.uniform(
                next(ks), (H,), jnp.float32, 1.0, 16.0)).astype(pd),
            # the inverse of softplus
            dt_bias=(step + jnp.log(-jnp.expm1(-step))).astype(pd),
            o_norm=norm_weight(dv, centred=False),
            wo=normal((H, dv, d), 1.0 / math.sqrt(H * dv)))
    else:
        wide = 2 if cfg.attn_output_gate else 1
        p.update(q_norm=norm_weight(dh), k_norm=norm_weight(dh),
                 wq=normal((d, h, wide * dh), s_in),
                 wk=normal((d, hkv, dh), s_in), wv=normal((d, hkv, dh), s_in),
                 wo=normal((h, dh, d), 1.0 / math.sqrt(h * dh)))
    E, f = cfg.moe_experts, cfg.moe_d_ff
    p.update(w_router=normal((d, cfg.router_width), s_in),
             w_gate=normal((E, d, f), s_in), w_up=normal((E, d, f), s_in),
             w_down=normal((E, f, d), 1.0 / math.sqrt(f)))
    if cfg.moe_shared_experts:
        fs = f * cfg.moe_shared_experts
        p.update(ws_gate=normal((d, fs), s_in), ws_up=normal((d, fs), s_in),
                 ws_down=normal((fs, d), 1.0 / math.sqrt(fs)))
    if cfg.moe_shared_gate:
        p["w_shared_gate"] = normal((d,), s_in)
    return p


def init_embed(cfg: TransformerConfig, key: jax.Array) -> jax.Array:
    """The table at unit spread: the residual stream's first term as it
    stands (no multiplier)."""
    return jax.random.normal(jax.random.split(key, 8)[1],
                             (cfg.vocab_size, cfg.d_model), jnp.float32
                             ).astype(cfg.param_dtype)


def init_head(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    """The final norm (zero-centred as the layers') and the output head."""
    out = afmoe.init_head(cfg, key)
    if cfg.norm_zero_centered:
        out["final_norm"] = (out["final_norm"].astype(jnp.float32) - 1.0
                             ).astype(cfg.param_dtype)
    return out


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
             "w_router": ("embed", None),
             "w_gate": ("expert", "embed", "mlp"),
             "w_up": ("expert", "embed", "mlp"),
             "w_down": ("expert", "mlp", "embed")}
        if cfg.moe_shared_experts:
            p.update(ws_gate=("embed", "mlp"), ws_up=("embed", "mlp"),
                     ws_down=("mlp", "embed"))
        if cfg.moe_shared_gate:
            p["w_shared_gate"] = ("embed",)
        if kind[0] == "linear":
            p.update(wq=("embed", "heads", "head_dim"),
                     wk=("embed", "heads", "head_dim"),
                     wv=("embed", "heads", "head_dim"),
                     wg=("embed", "heads", "head_dim"),
                     wa=("embed", "heads"), wb=("embed", "heads"),
                     w_conv=(None, "mlp"), A_log=(None,), dt_bias=(None,),
                     o_norm=(None,), wo=("heads", "head_dim", "embed"))
        else:
            p.update(q_norm=(None,), k_norm=(None,),
                     wq=("embed", "heads", "head_dim"),
                     wk=("embed", "kv_heads", "head_dim"),
                     wv=("embed", "kv_heads", "head_dim"),
                     wo=("heads", "head_dim", "embed"))
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
    if cfg.norm_zero_centered:
        w = 1.0 + w.astype(jnp.float32)
    return _norm(x, w, None, cfg.norm_eps, True)


def _partial_rope(cfg: TransformerConfig, x: jax.Array,
                  positions: jax.Array) -> jax.Array:
    """The rotary embedding on the first `rotary_dim` dims of every head of
    x [B, S, heads, Dh]; the others pass."""
    r = rotary_dim(cfg)
    if r == x.shape[-1]:
        return _rope(x, positions, cfg.rope_theta)
    return jnp.concatenate([_rope(x[..., :r], positions, cfg.rope_theta),
                            x[..., r:]], axis=-1)


def gated_attention(cfg: TransformerConfig, p: Dict[str, Any], a: jax.Array,
                    positions: jax.Array, mix: Callable) -> jax.Array:
    """The full layer's branch on a [B, S, D]; `mix(q, k, v)` is the
    caller's attention."""
    dh = cfg.head_dim
    with jax.named_scope(scopes.GATED_ATTN_Q):
        q = jnp.einsum("bsd,dhk->bshk", a, p["wq"].astype(a.dtype))
        k = jnp.einsum("bsd,dhk->bshk", a, p["wk"].astype(a.dtype))
        v = jnp.einsum("bsd,dhk->bshk", a, p["wv"].astype(a.dtype))
        gate = None
        if cfg.attn_output_gate:
            q, gate = q[..., :dh], q[..., dh:]
        q, k = _rms(q, p["q_norm"], cfg), _rms(k, p["k_norm"], cfg)
        if cfg.rope_theta is not None:
            q = _partial_rope(cfg, q, positions)
            k = _partial_rope(cfg, k, positions)
    o = mix(q, k, v).astype(a.dtype)
    with jax.named_scope(scopes.GATED_ATTN_OUT):
        if gate is not None:
            o = (o.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(a.dtype)
        return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))


def layer(cfg: TransformerConfig, kind: Tuple[str, str], p: Dict[str, Any],
          x: jax.Array, positions: jax.Array, mix,
          valid: Optional[jax.Array] = None,
          moe_name: str = scopes.MOE_EXPERTS_PREFILL,
          tap: Optional[Callable] = None) -> Tuple[jax.Array, jax.Array]:
    """x [B, S, D] at `positions` [B, S] -> (x', this module's MOE_COUNTS of
    this call).  `mix` is the caller's, built for this layer's mixer:
    `mix(q, k, v)` -> attention output [B, S, H, Dh] of a full layer; a pair
    (before, rule) of a linear layer (`delta_mixer`).  `tap`, if given, is
    shown ("rule", q, k, v, log_a, beta) of a linear layer (what the rule is
    handed, the key heads repeated) and ("route", the expert layer's input
    [B * S, D], its picks [B * S, k] over the router's whole width): a
    comparison's way to see them; the serving path passes none."""
    a = _rms(x, p["attn_norm"], cfg)
    if kind[0] == "linear":
        x = x + delta_mixer(
            cfg, p, a, *mix,
            tap=tap and (lambda *shown: tap("rule", *shown)))
    else:
        x = x + gated_attention(cfg, p, a, positions, mix)
    m = _rms(x, p["ffn_norm"], cfg)
    y, counts = experts(
        cfg, p, m, valid, moe_name,
        tap and (lambda picks: tap("route", m.reshape(-1, m.shape[2]),
                                   picks)),
        count_padded=True)
    return x + y, counts


def window_of(cfg: TransformerConfig, kind: Tuple[str, str]
              ) -> Optional[int]:
    return None


@jax.named_scope(scopes.EMBED)
def embed(cfg: TransformerConfig, table: jax.Array,
          tokens: jax.Array) -> jax.Array:
    return table[tokens].astype(cfg.dtype)


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
def forward_hidden(params: Dict[str, Any], tokens: jax.Array,
                   cfg: TransformerConfig, chunk: int = 64) -> jax.Array:
    """tokens [B, S] -> final-norm hidden states [B, S, D]; the linear
    layers in chunks of `chunk` positions from a zero state (1: the step
    recurrence)."""
    from ray_tpu.ops import gated_delta
    B, S = tokens.shape
    x = embed(cfg, params["tok_embed"], tokens)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    attend = afmoe._attend_plain(cfg, None)

    def nothing_before(u):
        return taps(jnp.zeros((B, cfg.conv_kernel - 1, u.shape[2]), u.dtype),
                    u)

    def from_zeros(q, k, v, log_a, beta):
        return gated_delta.delta_sequence(q, k, v, log_a, beta, chunk)[0]

    for kind, p in zip(cfg.layer_kinds, params["layers"]):
        x, _ = layer(cfg, kind, p, x, positions,
                     (nothing_before, from_zeros) if kind[0] == "linear"
                     else attend)
    return _rms(x, params["final_norm"], cfg)
