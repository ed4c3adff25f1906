"""arch "olmo_hybrid": gated delta-rule layers (a recurrence whose carry
is a matrix a head) and full attention layers mixed, dense feed-forwards
(AI2's Olmo-Hybrid family).

ONE layer definition, `layer()`, which `forward` (no cache), the fused
prefill pass and the paged decode step (models/decoding.py) all run: they
differ only in the `mix` they hand it.  For a full layer `mix(q, k, v)`
returns the attention output.  For a linear layer `mix` is a pair
`(before, rule)`: `before(u)` gives, for every position of the
convolution's input u, the `conv_kernel - 1` positions before it in its own
sequence (`lfm2.taps`: from a cache, or from u itself), and `rule(q, k, v,
log_a, beta)` runs the recurrence from whatever state the caller keeps (or
from zeros) and returns o for every position.  The head is
models/afmoe.py's.  The plain float32 reference is the deliberate second
copy (benchmarks/kinds/gated-delta.py).

A layer of kind (mixer, "dense"), N() an RMSNorm with its own weight, x the
residual stream, a = x (a branch reads the stream UN-normed and its OUTPUT
is normed: `norm_after_branch`; False norms the input instead):

  linear:  u = [Wq a | Wk a | Wv a]              (H dk + H dk + H dv wide)
           c_t = silu(sum_j w[:, j] u_{t-(K-1)+j})   (K = conv_kernel taps a
                 channel, no bias, u before the sequence's start is zero)
           q', k', v = split(c);  per head h:
           q = q' / |q'| dk^-1/2,  k = k' / |k'|        (L2, eps 1e-6)
           beta = (2 if linear_neg_eigval else 1) sigmoid(Wb a)
           ln alpha = -exp(A_log) softplus(Wa a + dt_bias)      (float32)
           S' = alpha S;  u = beta (v - S'^T k);  S = S' + k u^T;  o = S^T q
           y = Wo [N_o(o_h) silu(g_h)]_h,  g = Wg a   (N_o over a head's dv)
  full:    q, k, v = Wq a, Wk a, Wv a;  q = N_q(q), k = N_k(k) over the WHOLE
           width before the split into heads; rotary only where rope_theta
           is not None; causal softmax attention;  y = Wo o
  x = x + N_attn(y);   x = x + N_ffn(Wdown(silu(Wgate x) Wup x))

and x0 = Embed[token], logits = lm_head^T N_final(x_L).

Parameters are a tuple of per-layer trees, layer l's from a key folded with
l, as in models/afmoe.py.  There is no training path.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import afmoe
from ray_tpu.models.afmoe import (_ffn, _rms, init_head, logits,  # noqa: F401
                                  no_counts)
from ray_tpu.models.lfm2 import taps  # noqa: F401
from ray_tpu.models.transformer import TransformerConfig, _rope
from ray_tpu.ops import scopes

MIXERS = ("linear", "full")
L2_EPS = 1e-6


def _check(cfg: TransformerConfig) -> None:
    kinds = cfg.layer_kinds or ()
    if len(kinds) != cfg.n_layers or any(
            m not in MIXERS or f != "dense" for m, f in kinds):
        raise ValueError(
            f"olmo_hybrid needs one (linear|full, dense) pair per layer, "
            f"got {cfg.layer_kinds!r} for {cfg.n_layers} layers")
    if any(m == "linear" for m, _ in kinds) and not (
            cfg.linear_heads and cfg.linear_key_dim
            and cfg.linear_value_dim):
        raise ValueError("linear layers need linear_heads, linear_key_dim "
                         "and linear_value_dim")


def key_heads(cfg: TransformerConfig) -> int:
    """A linear layer's key (and query) heads: `linear_heads` unless
    `linear_key_heads` says that fewer are shared."""
    return cfg.linear_key_heads or cfg.linear_heads


def conv_width(cfg: TransformerConfig) -> int:
    """Channels of a linear layer's convolution: q, k and v side by side."""
    return (2 * key_heads(cfg) * cfg.linear_key_dim
            + cfg.linear_heads * cfg.linear_value_dim)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def init_layer(cfg: TransformerConfig, key: jax.Array,
               index: int) -> Dict[str, Any]:
    """Layer `index` alone.  Spreads chosen so that a program that drops a
    piece cannot agree with the reference: norm weights 1 + 0.1 N, taps
    N(0, 1/K), A_log ~ ln U(1, 16), dt_bias with softplus(dt_bias) log-
    uniform in (1e-3, 0.1): alpha spans ~0.2-0.999."""
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
    mixer, _ = cfg.layer_kinds[index]
    f = cfg.ff_dim
    p = {"attn_norm": norm_weight(d), "ffn_norm": norm_weight(d),
         "w_gate": normal((d, f), s_in), "w_up": normal((d, f), s_in),
         "w_down": normal((f, d), 1.0 / math.sqrt(f))}
    if mixer == "linear":
        H, dk, dv = (cfg.linear_heads, cfg.linear_key_dim,
                     cfg.linear_value_dim)
        K = cfg.conv_kernel
        step = jnp.exp(jax.random.uniform(
            next(ks), (H,), jnp.float32, math.log(1e-3), math.log(0.1)))
        p.update(
            wq=normal((d, H, dk), s_in), wk=normal((d, H, dk), s_in),
            wv=normal((d, H, dv), s_in), wg=normal((d, H, dv), s_in),
            wa=normal((d, H), s_in), wb=normal((d, H), s_in),
            w_conv=normal((K, conv_width(cfg)), 1.0 / math.sqrt(K)),
            A_log=jnp.log(jax.random.uniform(
                next(ks), (H,), jnp.float32, 1.0, 16.0)).astype(pd),
            # the inverse of softplus
            dt_bias=(step + jnp.log(-jnp.expm1(-step))).astype(pd),
            o_norm=norm_weight(dv),
            wo=normal((H, dv, d), 1.0 / math.sqrt(H * dv)))
    else:
        p.update(q_norm=norm_weight(h * dh), k_norm=norm_weight(hkv * dh),
                 wq=normal((d, h, dh), s_in), wk=normal((d, hkv, dh), s_in),
                 wv=normal((d, hkv, dh), s_in),
                 wo=normal((h, dh, d), 1.0 / math.sqrt(h * dh)))
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
             "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
             "w_down": ("mlp", "embed")}
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
def _l2(x: jax.Array) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def delta_mixer(cfg: TransformerConfig, p: Dict[str, Any], a: jax.Array,
                before: Callable, rule: Callable,
                tap: Optional[Callable] = None) -> jax.Array:
    """The linear layer's branch on a [B, S, D] (see the module docstring:
    `before`, `rule` are the caller's).  `tap`, if given, is shown what the
    rule is handed: q, k, v, log_a, beta (a comparison's way to see them; the
    serving path passes none).  Where the layer has fewer key heads than
    value heads (`key_heads`; wq, wk [D, Hk, dk]) value head h uses key
    head h // (H / Hk): q and k are repeated before the rule sees them."""
    B, S, _ = a.shape
    H, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    Hk = key_heads(cfg)
    with jax.named_scope(scopes.DELTA_PROJ):
        def proj(w):
            return jnp.einsum("bsd,dhk->bshk", a, w.astype(a.dtype)
                              ).reshape(B, S, -1)
        u = jnp.concatenate([proj(p["wq"]), proj(p["wk"]), proj(p["wv"])],
                            axis=-1)
        w = p["w_conv"].astype(jnp.float32)
        c = jax.nn.silu(sum(w[j] * t.astype(jnp.float32)
                            for j, t in enumerate(before(u) + [u])))
        q = _l2(c[..., :Hk * dk].reshape(B, S, Hk, dk)) * dk ** -0.5
        k = _l2(c[..., Hk * dk:2 * Hk * dk].reshape(B, S, Hk, dk))
        v = c[..., 2 * Hk * dk:].reshape(B, S, H, dv)
        if Hk != H:
            q, k = (jnp.repeat(x, H // Hk, axis=2) for x in (q, k))
        af = a.astype(jnp.float32)
        beta = jax.nn.sigmoid(af @ p["wb"].astype(jnp.float32)) * (
            2.0 if cfg.linear_neg_eigval else 1.0)
        log_a = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            af @ p["wa"].astype(jnp.float32)
            + p["dt_bias"].astype(jnp.float32))
        g = jnp.einsum("bsd,dhk->bshk", a, p["wg"].astype(a.dtype))
    if tap is not None:
        tap(q, k, v, log_a, beta)
    with jax.named_scope(scopes.DELTA_RULE):
        o = rule(q, k, v, log_a, beta)          # all float32
    with jax.named_scope(scopes.DELTA_OUT):
        y = _rms(o, p["o_norm"], cfg).astype(jnp.float32) * jax.nn.silu(
            g.astype(jnp.float32))
        return jnp.einsum("bshk,hkd->bsd", y.astype(a.dtype),
                          p["wo"].astype(a.dtype))


def layer(cfg: TransformerConfig, kind: Tuple[str, str], p: Dict[str, Any],
          x: jax.Array, positions: jax.Array, mix,
          valid: Optional[jax.Array] = None,
          moe_name: str = "", tap: Optional[Callable] = None
          ) -> Tuple[jax.Array, jax.Array]:
    """x [B, S, D] at `positions` [B, S] -> (x', no counts: there is no
    expert layer).  `mix` is the caller's, built for this layer's mixer:
    `mix(q, k, v)` -> attention output [B, S, H, Dh] of a full layer; a
    pair (before, rule) of a linear layer (`delta_mixer`, which shows `tap`
    the rule's inputs)."""
    after = cfg.norm_after_branch

    def branch(norm, fn):
        if after:
            return x + _rms(fn(x), p[norm], cfg)
        return x + fn(_rms(x, p[norm], cfg))

    def full(a):
        B, S, _ = a.shape
        with jax.named_scope(scopes.ATTN_QKV):
            q = jnp.einsum("bsd,dhk->bshk", a, p["wq"].astype(a.dtype))
            k = jnp.einsum("bsd,dhk->bshk", a, p["wk"].astype(a.dtype))
            v = jnp.einsum("bsd,dhk->bshk", a, p["wv"].astype(a.dtype))
            q = _rms(q.reshape(B, S, -1), p["q_norm"], cfg).reshape(q.shape)
            k = _rms(k.reshape(B, S, -1), p["k_norm"], cfg).reshape(k.shape)
            if cfg.rope_theta is not None:
                q = _rope(q, positions, cfg.rope_theta)
                k = _rope(k, positions, cfg.rope_theta)
        o = mix(q, k, v).astype(a.dtype)
        with jax.named_scope(scopes.ATTN_OUT):
            return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))

    if kind[0] == "linear":
        x = branch("attn_norm",
                   lambda a: delta_mixer(cfg, p, a, *mix, tap=tap))
    else:
        x = branch("attn_norm", full)
    x = branch("ffn_norm", lambda m: _ffn(m, p["w_gate"], p["w_up"],
                                          p["w_down"]))
    return x, no_counts()


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
