"""Model kind `mla-moe` (SK Telecom's A.X-K1, `model_type` axk1; the
DeepSeek-V2/V3 family's layer): multi-head latent attention in every layer, a
leading dense layer, then layers of many sigmoid-routed experts and a shared
one, of which THIS CHIP HOLDS A SHARE.  The same interface as kinds/afmoe.py
and kinds/lfm2-moe.py, found by the configuration's `"kind"`; serving only
(the program has no training path for it, so CHECKS has no "train").

THE PLAIN REFERENCE is here (`reference_*`): the forward pass in float32 at
`jax.default_matmul_precision("highest")`, no cache, no kernel, no batching
of experts (a loop over the held experts, weighted by whether the token
chose them), latent attention in its PUBLISHED, expanded form (per-head keys
and values rebuilt from the latent; the program computes the absorbed form,
so the absorb itself is under test), blocked over heads and over query rows
so that 16 k positions fit.  For layer l of kind (latent, feed-forward), N()
an RMSNorm (eps 1e-6) with its own weight:

    x0      = Embed[token]
    a       = N_in(x)
    c_q     = N_q(W_dq a);          [q_nope | q_rope]_h = W_uq,h c_q
    [c | k_r] = W_dkv a;            c = N_kv(c)
    q_rope, k_r get the rotary embedding (below; ONE k_r for all heads)
    [k_nope | v]_h = W_ukv,h c      (kept as its halves w_uk, w_uv per head)
    score_h(t, j) = scale (q_nope,h(t) . k_nope,h(j) + q_rope,h(t) . k_r(j))
                    for j <= t;     scale = (128 + 64)^-1/2 * m^2,
                    m = 0.1 mscale_all_dim ln(factor) + 1
    o_h     = sum_j softmax_j v_h(j);   x = x + W_o [o_1 .. o_H]
    m       = N_ffn(x)
    dense:    x = x + W2(silu(W1 m) * W3 m)
    experts:  s = sigmoid(Wr m) over all `router_width` experts;
              S = top-k of s;   w_e = routed_scaling_factor s_e / sum_{S} s
              x = x + sum_{e in S, e held here} w_e FFN_e(m) + FFN_shared(m)
    logits  = Whead N_final(x_L)

Rotary (yarn) on the 64 rope dims: inv_freq_i = theta^(-2i/64); low =
floor(64 ln(orig / (beta_fast 2 pi)) / (2 ln theta)), high = ceil(the same
with beta_slow); ramp_i = clip((i - low) / (high - low), 0, 1); inv_freq'_i
= inv_freq_i / factor * ramp_i + inv_freq_i (1 - ramp_i); cos and sin times
yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim) (1 here).

THE SHARE.  16 chips share each layer, expert-parallel, attention
data-parallel: the router keeps its 192 outputs and its top-8, normalised
over all 8; only the `n_routed_experts` (12) experts held here, experts
`experts_held_first` .. + 11, and the shared one are summed, in the
reference as in the program, and that partial result goes on to the next
layer (model-configs guide, section 4).  tests/test_axk1.py adds all 16
shares up to the uncut layer.

DEPARTURE RISKS.  What config.json does not settle (each listed in the
configuration file under `assumed`; no network here to re-read the
modelling code):
  (a) `topk_method` "none" is read literally: plain top-8 over all 192
      scores, no group limit (`n_group` 8 / `topk_group` 4 unused), no
      correction bias;
  (b) the norms' names, two pre-norms a layer and none after a branch, the
      RMSNorms of the q and kv latents before their up-projections;
  (c) the pairing of the 64 rotary dims (the two halves of the dims are a
      pair's two parts; with weights from a seed a permutation of columns);
  (d) m^2 in the softmax scale, from `mscale_all_dim`;
  (e) `seq_aux`, `ep_size`, the aux terms are training's and unused.
"""

from __future__ import annotations

import functools
import math
import time
from typing import Any, Callable, Dict, List

# How each limit was set: PERF.md section 2, "Limits of `correct`".
# Readings: my chip runs, PR 44 (chiprun_out/pr44/parity44.jsonl, and the
# cell's own line), one process at the cell's widths and sizes, 16,392
# positions of request A and 63 short requests beside it: the sound program
# on five seeds, the fp8 control on two.  The control is 2.1-3.6 x the
# program, as for the other two expert kinds: each limit stands at the
# geometric mean of its two readings, ~1.4-1.9 x from both.
TOLERANCES: Dict[str, float] = {
    # relative RMS error of the logits (bf16 activations against float32)
    # over A's last 256 prompt positions: program 0.00996-0.00999, control
    # 0.0209 / 0.0212
    "logits_prefill_err": 0.0145,
    # ... over the 8 decoded positions of all 64 slots: program
    # 0.01446-0.01451 (its worst slot 0.0150-0.0153), control 0.0516 / 0.0518
    "logits_decode_err": 0.027,
    # relative RMS error of every layer's latent rows (the pool's rows of
    # A's 16,392 positions: the normed latent and the rotated shared key
    # part) against the reference's, largest layer: program
    # 0.009715-0.009729, control 0.02365 / 0.02374
    "latent_row_err": 0.015,
    # the logits of a request's last rows answered after a prefix hit (its
    # first blocks shared through its table) against the same rows answered
    # cold, in the same compiled call: program 0.0 on every seed (bit-equal)
    "logits_after_hit_err": 1e-3,
    # share of A's (row, expert) picks, over all 192 scores, that differ from
    # the reference's own on the reference's own path: program
    # 0.00786-0.00815, control 0.01647 / 0.01659
    "route_mismatch_share": 0.0116,
    # ... on the PROGRAM's own input to each router (what the tap shows),
    # scored by the reference in float32: program 0.0 on every seed (not one
    # of 786,816 picks)
    "route_own_input_mismatch_share": 1e-3,
}

CHECKS: Dict[str, tuple] = {
    "serve": tuple(TOLERANCES),
}

# How far below the reference's own k-th score an expert of the program's
# choice may score and still be followed (`reference_route`), as
# kinds/lfm2-moe.py has it.
FOLLOW_MARGIN = 0.04


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------
def layer_kinds(cfg: Dict[str, Any]) -> List[List[str]]:
    """(mixer, feed-forward) per layer: every mixer latent, the first
    `first_k_dense_replace` feed-forwards dense."""
    return [["latent", "dense" if i < cfg["first_k_dense_replace"]
             else "experts"] for i in range(cfg["num_hidden_layers"])]


def router_width(cfg: Dict[str, Any]) -> int:
    return cfg.get("router_width", cfg["n_routed_experts"])


def check(cfg: Dict[str, Any]) -> None:
    if cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"]:
        raise ValueError("only normalised sigmoid scores are expressed")
    if cfg["topk_method"] != "none":
        raise ValueError("only plain top-k selection is expressed")
    if cfg["moe_layer_freq"] != 1:
        raise ValueError("only an expert layer at every layer after the "
                         "dense ones is expressed")
    if cfg["rope_scaling"]["type"] != "yarn":
        raise ValueError("only the yarn rotary embedding is expressed")
    if cfg["attention_bias"] or cfg["hidden_act"] != "silu":
        raise ValueError("attention biases / another activation are not "
                         "expressed")
    first = cfg.get("experts_held_first", 0)
    if first < 0 or first + cfg["n_routed_experts"] > router_width(cfg):
        raise ValueError("the experts held are not among the router's")


def transformer_kwargs(cfg: Dict[str, Any], *, max_seq: int,
                       param_dtype: str, **extra: Any) -> Dict[str, Any]:
    check(cfg)
    rs = cfg["rope_scaling"]
    kw = {
        "vocab_size": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "d_head": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        "d_ff": cfg["intermediate_size"],
        "max_seq": max_seq,
        "arch": "axk1",
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "tie_embeddings": bool(cfg["tie_word_embeddings"]),
        "dtype": cfg.get("torch_dtype", "bfloat16"),
        "param_dtype": param_dtype,
        "layer_kinds": layer_kinds(cfg),
        "q_lora_rank": cfg["q_lora_rank"],
        "kv_lora_rank": cfg["kv_lora_rank"],
        "qk_nope_dim": cfg["qk_nope_head_dim"],
        "qk_rope_dim": cfg["qk_rope_head_dim"],
        "v_head_dim": cfg["v_head_dim"],
        "rope_factor": float(rs["factor"]),
        "rope_original_max": rs["original_max_position_embeddings"],
        "rope_beta_fast": float(rs["beta_fast"]),
        "rope_beta_slow": float(rs["beta_slow"]),
        "rope_mscale": float(rs["mscale"]),
        "rope_mscale_all_dim": float(rs["mscale_all_dim"]),
        "moe_experts": cfg["n_routed_experts"],
        "moe_router_width": router_width(cfg),
        "moe_experts_first": cfg.get("experts_held_first", 0),
        "moe_top_k": cfg["num_experts_per_tok"],
        "moe_d_ff": cfg["moe_intermediate_size"],
        "moe_shared_experts": cfg["n_shared_experts"],
        "moe_route_scale": float(cfg["routed_scaling_factor"]),
        "remat": False,
    }
    kw.update(extra)
    return kw


def param_counts(cfg: Dict[str, Any]) -> Dict[str, int]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, c, r = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    n, v = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    f, fe, E = (cfg["intermediate_size"], cfg["moe_intermediate_size"],
                cfg["n_routed_experts"])
    attn = (d * rq + rq * h * (n + r) + d * (c + r)     # W_dq, W_uq, W_dkv
            + h * n * c + h * c * v + h * v * d         # W_uk, W_uv, W_o
            + rq + c)                                   # the latents' norms
    norms = 2 * d
    dense_ffn = 3 * d * f
    expert = 3 * d * fe
    expert_ffn = (d * router_width(cfg) + E * expert
                  + cfg["n_shared_experts"] * expert)
    total = cfg["vocab_size"] * d + d           # table, last norm
    if not cfg["tie_word_embeddings"]:
        total += d * cfg["vocab_size"]
    for _, ffn in layer_kinds(cfg):
        total += attn + norms + (dense_ffn if ffn == "dense" else expert_ffn)
    return {"total": total, "input_embedding": cfg["vocab_size"] * d,
            "attention": attn, "dense_ffn": dense_ffn, "expert": expert,
            "expert_ffn": expert_ffn}


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """What the MODEL leaves behind a position: the latent and the rotated
    shared key part, bf16, a layer (the pool lays a row in whole 128-lane
    rows: 640 lanes for 576 values, `pool_bytes_per_token`)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * 2 \
        * cfg["num_hidden_layers"]


def pool_bytes_per_token(cfg: Dict[str, Any]) -> int:
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return -(-width // 128) * 128 * 2 * cfg["num_hidden_layers"]


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    raise NotImplementedError("kind mla-moe has no training path")


# ---------------------------------------------------------------------------
# cost functions of the kernels this kind's cell reads: fn(config, shapes)
# ---------------------------------------------------------------------------
def _latent_flops_per_pair(cfg) -> float:
    """Operations of one (query, cached position) pair in the absorbed form:
    every head scores c + r values and sums c."""
    c, r = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return 2.0 * cfg["num_attention_heads"] * ((c + r) + c)


def mla_paged_decode(cfg, s):
    """One call = one layer, one decode step: every live sequence's one
    query of 64 heads over its cached rows.  Bytes are the MODEL's: 1,152 B a
    cached position (576 values), q in and o out; the pool lays a row in 640
    lanes, so the kernel moves a ninth more and reads lower for it, and the
    same work whatever implements it.  `live_context` is serve_cell's, which
    under-reads where replies are unary (PERF.md section 7), so this share
    reads LOW by as much."""
    c, r = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    h = cfg["num_attention_heads"]
    flops = _latent_flops_per_pair(cfg) * s["live_context"]
    bytes_ = 2.0 * ((c + r) * s["live_context"]
                    + s["slots"] * h * ((c + r) + c))
    return flops, bytes_


# Per fused dispatch of the cell (serve-axk1-agent-sessions,
# traffic/agent-sessions.json at 64 slots): requests admitted, and the
# uncached tokens each brings (a message, the previous reply, the
# block-rounding remainder).  ASSUMED, a floor, as kinds/lfm2-moe.py does:
# the cell's own counters read 24.6 requests a fused dispatch
# (prefill.chunks / the rung_dispatches) and 32.0 tokens a request once the
# priming's 37,344 are taken out of prefill.chunk_tokens (my chip run, PR 44;
# PERF.md section 5); the harness hands a cost function the configuration
# and `slots` / `live_context` only.
PREFIX_ROWS_PER_CALL = 24.0
PREFIX_TOKENS_PER_ROW = 32.0


def mla_prefix_attention(cfg, s):
    """One call = one layer, one prefill dispatch: each admitted request's
    uncached tokens attend, as one row of queries, to the request's context
    (the mean live context).  Bytes: the latent rows read once per request
    plus q and o."""
    c, r = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    h = cfg["num_attention_heads"]
    ctx = s["live_context"] / max(s["slots"], 1)
    q_tokens = PREFIX_ROWS_PER_CALL * PREFIX_TOKENS_PER_ROW
    flops = _latent_flops_per_pair(cfg) * q_tokens * ctx
    bytes_ = 2.0 * (PREFIX_ROWS_PER_CALL * ctx * (c + r)
                    + q_tokens * h * ((c + r) + c))
    return flops, bytes_


def experts_touched_even(cfg: Dict[str, Any], rows: float) -> float:
    """Held experts with at least one of `rows` tokens' picks, under even
    routing over the router's whole width: E_held (1 - (1 - k / width) ^
    rows)."""
    k, width = cfg["num_experts_per_tok"], router_width(cfg)
    return cfg["n_routed_experts"] * (1.0 - (1.0 - k / width) ** rows)


def moe_experts_decode(cfg, s):
    """One call = one expert layer, one decode step, over the HELD experts:
    the rows routed here (slots x k x held / width) through three products
    of hidden x expert width; bytes = the distinct held experts read x 88 MB
    + the rows in and out.  A FLOOR as in kinds/afmoe.py: the expectation
    under even routing with HALF the slots live, so that it cannot read over
    100 %."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = (s["slots"] * cfg["num_experts_per_tok"]
            * cfg["n_routed_experts"] / router_width(cfg))
    flops = 2.0 * rows * 3 * d * fe
    bytes_ = 2.0 * (experts_touched_even(cfg, s["slots"] / 2) * 3 * d * fe
                    + 2 * rows * d)
    return flops, bytes_


COST_FNS: Dict[str, Callable] = {
    "mla_paged_decode": mla_paged_decode,
    "mla_prefix_attention": mla_prefix_attention,
    "moe_experts_decode": moe_experts_decode,
}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def hyper(cfg) -> Dict[str, Any]:
    """The numbers the reference needs, from the program's
    TransformerConfig (the harness hands parity() nothing else)."""
    m = 1.0
    if cfg.rope_factor > 1.0:
        m = 0.1 * cfg.rope_mscale_all_dim * math.log(cfg.rope_factor) + 1.0
    return {"heads": cfg.n_heads, "nope": cfg.qk_nope_dim,
            "rope": cfg.qk_rope_dim, "v": cfg.v_head_dim,
            "latent": cfg.kv_lora_rank, "hidden": cfg.d_model,
            "eps": cfg.norm_eps, "theta": cfg.rope_theta,
            "factor": cfg.rope_factor, "original": cfg.rope_original_max,
            "beta_fast": cfg.rope_beta_fast, "beta_slow": cfg.rope_beta_slow,
            "mscale": cfg.rope_mscale,
            "mscale_all_dim": cfg.rope_mscale_all_dim,
            "scale": (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5 * m * m,
            "top_k": cfg.moe_top_k, "route_scale": cfg.moe_route_scale,
            "held_first": cfg.moe_experts_first, "held": cfg.moe_experts,
            "kinds": tuple(cfg.layer_kinds)}


def _f32(x):
    import jax.numpy as jnp
    return x.astype(jnp.float32)


def _fp8(x):
    import jax.numpy as jnp
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _rmsnorm(x, w, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def yarn_range(hp):
    """(low, high) of yarn's ramp over the rotary pairs: 10, 23 at the
    published numbers."""
    dim = hp["rope"]

    def at(rotations):
        return dim * math.log(hp["original"] / (rotations * 2 * math.pi)) \
            / (2 * math.log(hp["theta"]))

    return (max(math.floor(at(hp["beta_fast"])), 0),
            min(math.ceil(at(hp["beta_slow"])), dim - 1))


def yarn_inv_freq(hp):
    import numpy as np
    half = hp["rope"] // 2
    i = np.arange(half, dtype=np.float64)
    inv = hp["theta"] ** (-2.0 * i / hp["rope"])
    if hp["factor"] == 1.0:
        return inv.astype(np.float32)
    low, high = yarn_range(hp)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (inv / hp["factor"] * ramp + inv * (1.0 - ramp)).astype(np.float32)


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def _rotary(hp, x, positions):
    """x [S, heads, rope]; the two halves of the dims are a pair's parts."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(hp)[None, :]
    mult = (_yarn_mscale(hp["factor"], hp["mscale"])
            / _yarn_mscale(hp["factor"], hp["mscale_all_dim"]))
    cos, sin = (jnp.cos(ang) * mult)[:, None, :], (jnp.sin(ang) * mult)[
        :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _pieces(n: int, want: int) -> int:
    """The largest count of equal pieces of n, at most `want`."""
    return next(k for k in range(min(want, n), 0, -1) if n % k == 0)


def _swiglu(m, w_gate, w_up, w_down, pieces: int = 1):
    """W_down(silu(W_gate m) * W_up m), the width in `pieces` blocks so that
    the float32 copy of a wide layer's weights is a block's at a time."""
    import jax
    import jax.numpy as jnp
    d, f = w_gate.shape
    if pieces == 1:
        return (jax.nn.silu(m @ _f32(w_gate)) * (m @ _f32(w_up))) \
            @ _f32(w_down)
    fc = f // pieces

    def one(y, w):
        g, u, dn = w
        return y + (jax.nn.silu(m @ _f32(g)) * (m @ _f32(u))) @ _f32(dn), None

    blocks = (jnp.moveaxis(w_gate.reshape(d, pieces, fc), 1, 0),
              jnp.moveaxis(w_up.reshape(d, pieces, fc), 1, 0),
              w_down.reshape(pieces, fc, d))
    return jax.lax.scan(one, jnp.zeros_like(m), blocks)[0]


def reference_route(hp, p, m, follow=None):
    """m [S, hidden] float32 -> (picks [S, k] over the router's whole width,
    the weights [S, k] of the experts used, the experts used, shortfall
    [S]).  `follow` [S, k] (-1: nothing to follow in this row): the experts
    used are these and not the picks (weighed by this function's own
    scores), in every row where each of them scores within FOLLOW_MARGIN of
    this function's own k-th (kinds/lfm2-moe.py has the reasons)."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(m @ _f32(p["w_router"]))
    top, picks = jax.lax.top_k(s, hp["top_k"])
    used, shortfall = picks, jnp.zeros(m.shape[:1], jnp.float32)
    if follow is not None:
        theirs = jnp.take_along_axis(s, jnp.maximum(follow, 0), axis=1)
        shortfall = jnp.where((follow >= 0).all(axis=1), jnp.max(
            top[:, -1:] - theirs, axis=1), jnp.inf)
        used = jnp.where((shortfall <= FOLLOW_MARGIN)[:, None], follow,
                         picks)
        shortfall = jnp.where(jnp.isinf(shortfall), 0.0,
                              jnp.maximum(shortfall, 0.0))
    chosen = jnp.take_along_axis(s, used, axis=1)
    weights = hp["route_scale"] * chosen / jnp.sum(chosen, axis=1,
                                                   keepdims=True)
    return picks, weights, used, shortfall


def reference_latent(hp, p, a, control: bool = False, wrong: str = ""):
    """a [S, hidden] float32 -> what a position leaves behind, (c [S,
    latent] after its norm, k_r [S, rope] after its rotation)."""
    import jax.numpy as jnp
    S, c = a.shape[0], hp["latent"]
    pos = jnp.arange(S)
    ckv = a @ _f32(p["w_dkv"])
    lat, k_r = ckv[:, :c], ckv[:, c:]
    if wrong != "no_kv_norm":
        lat = _rmsnorm(lat, p["kv_norm"], hp["eps"])
    if wrong == "rope_on_latent":       # the rotation in the wrong place
        r = hp["rope"]
        lat = jnp.concatenate([_rotary(hp, lat[:, None, :r], pos)[:, 0],
                               lat[:, r:]], axis=-1)
    else:
        k_r = _rotary(hp, k_r[:, None], pos)[:, 0]
    if control:
        lat, k_r = _fp8(lat), _fp8(k_r)
    return lat, k_r


def reference_attention(hp, p, a, block: int = 256, control: bool = False,
                        wrong: str = ""):
    """Latent attention as published: per-head keys and values rebuilt from
    the latent.  Heads in groups of 8 (a scan over the groups' weights),
    query rows in blocks of `block`, each against every key.  -> (W_o o,
    the rows [S, latent + rope])."""
    import jax
    import jax.numpy as jnp
    S = a.shape[0]
    H, n, r, vd = hp["heads"], hp["nope"], hp["rope"], hp["v"]
    pos = jnp.arange(S)
    lat, k_r = reference_latent(hp, p, a, control, wrong)
    cq = _rmsnorm(a @ _f32(p["w_dq"]), p["q_norm"], hp["eps"])
    scale = hp["scale"] if wrong != "no_mscale" else (n + r) ** -0.5
    G = _pieces(H, max(1, H // 8))             # groups of heads
    hg = H // G
    n_blocks = -(-S // block)
    pad = n_blocks * block - S
    pos_blocks = jnp.pad(pos, (0, pad)).reshape(n_blocks, block)

    def one_group(y, w):
        w_uq, w_uk, w_uv, w_o = w
        q = jnp.einsum("sr,rhk->shk", cq, _f32(w_uq))
        q = jnp.concatenate([q[..., :n], _rotary(hp, q[..., n:], pos)],
                            axis=-1)
        if control:
            q = _fp8(q)
        k = jnp.concatenate([
            jnp.einsum("sc,hnc->shn", lat, _f32(w_uk)),
            jnp.broadcast_to(k_r[:, None], (S, hg, r))], axis=-1)
        v = jnp.einsum("sc,hcv->shv", lat, _f32(w_uv))
        q_blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            n_blocks, block, hg, n + r)

        def one_block(args):
            qb, qi = args
            s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
            seen = pos[None, :] <= qi[:, None]
            w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khv->qhv", w, v)

        o = jax.lax.map(one_block, (q_blocks, pos_blocks)).reshape(
            n_blocks * block, hg, vd)[:S]
        return y + jnp.einsum("shv,hvd->sd", o, _f32(w_o)), None

    groups = (jnp.moveaxis(p["w_uq"].reshape(-1, G, hg, n + r), 1, 0),
              p["w_uk"].reshape(G, hg, n, -1),
              p["w_uv"].reshape(G, hg, -1, vd),
              p["w_o"].reshape(G, hg, vd, -1))
    y, _ = jax.lax.scan(one_group, jnp.zeros_like(a), groups)
    return y, jnp.concatenate([lat, k_r], axis=-1)


def reference_layer(hp, kind, p, x, follow=None, block: int = 256,
                    control: bool = False, wrong: str = ""):
    """x [S, hidden] float32 (positions 0..S-1) -> (x' [S, hidden], picks
    [S, k] or None, the latent rows [S, latent + rope], shortfall [S] or
    None).  `follow`: the experts to use in the picks' place and the
    shortfall (`reference_route`).  `control`: q, the latent rows and the
    expert weights rounded to fp8 (e4m3), the precision below the
    configuration's bfloat16: what `correct` must refuse.  `wrong` names one
    deliberate fault, for the tests that show the limits refuse it:
    "no_mscale" (the softmax scale without m^2), "rope_on_latent" (the
    rotation applied to the latent's first dims, not to k_r), "no_kv_norm",
    "held_normalised" (the top-k weights normalised over the HELD picks
    only)."""
    import jax
    import jax.numpy as jnp
    S = x.shape[0]
    a = _rmsnorm(x, p["attn_norm"], hp["eps"])
    y, rows = reference_attention(hp, p, a, block, control, wrong)
    x = x + y
    m = _rmsnorm(x, p["ffn_norm"], hp["eps"])
    picks = shortfall = None
    if kind[1] == "dense":
        y = _swiglu(m, p["w_gate"], p["w_up"], p["w_down"],
                    _pieces(p["w_gate"].shape[1], 8))
    else:
        picks, weights, used, shortfall = reference_route(hp, p, m, follow)
        first, E = hp["held_first"], hp["held"]
        here = (used >= first) & (used < first + E)
        if wrong == "held_normalised":
            kept = jnp.where(here, weights, 0.0)
            weights = hp["route_scale"] * kept / jnp.maximum(
                jnp.sum(kept, axis=1, keepdims=True), 1e-20)
        # each token's weight for each HELD expert (0 where it did not use
        # it); a pick that lies on another chip adds nothing here
        dense_w = jnp.zeros((S, E + 1), jnp.float32).at[
            jnp.arange(S)[:, None],
            jnp.where(here, used - first, E)].add(weights)[:, :E]
        rnd = _fp8 if control else _f32

        def one_expert(y, e):
            out = _swiglu(m, rnd(p["w_gate"][e]), rnd(p["w_up"][e]),
                          rnd(p["w_down"][e]))
            return y + dense_w[:, e][:, None] * out, None

        y, _ = jax.lax.scan(one_expert, jnp.zeros_like(m), jnp.arange(E))
        if "ws_gate" in p:
            y = y + _swiglu(m, p["ws_gate"], p["ws_up"], p["ws_down"])
    return x + y, picks, rows, shortfall


def reference_embed(hp, table, tokens):
    return _f32(table[tokens])


def reference_head(hp, params, x):
    """x [R, hidden] -> logits [R, V]; `params` holds final_norm and the
    head."""
    return _rmsnorm(x, params["final_norm"], hp["eps"]) \
        @ _f32(params["lm_head"])


def reference_logits(hp, params, tokens, block: int = 256,
                     control: bool = False, wrong: str = ""):
    """The whole model: tokens [S] -> logits [S, V] float32."""
    import jax
    with jax.default_matmul_precision("highest"):
        x = reference_embed(hp, params["tok_embed"], tokens)
        for kind, p in zip(hp["kinds"], params["layers"]):
            x = reference_layer(hp, kind, p, x, None, block, control,
                                wrong)[0]
        return reference_head(hp, params, x)


def rel_rms(got, want) -> float:
    """|got - want| / |want| in the root-mean-square sense, over all
    entries."""
    import jax.numpy as jnp
    got, want = _f32(got), _f32(want)
    return float(jnp.sqrt(jnp.mean((got - want) ** 2)
                          / jnp.mean(want ** 2)))


def picks_agree(got, want):
    """got, want [R, k] picks of one layer -> [R, k] bool: which of the
    reference's picks the program made too (in any order)."""
    return (got[:, :, None] == want[:, None, :]).any(axis=1)


# ---------------------------------------------------------------------------
# parity: the program's own prefill and decode layers against the reference
# ---------------------------------------------------------------------------
PARITY_PROMPT = 16_384        # positions of the compared request's prompt
PARITY_DECODE_STEPS = 8
PARITY_COMPARED = 256         # the prompt's last positions whose logits are
#                               compared, and answered again after a hit


def parity_sizes(caches) -> Dict[str, int]:
    """From the engine's own shapes: rows of P tokens (the engine's tile, a
    block, or two of 8), `rows` of them a prefill call (the widest compiled
    program's at the cells' size), a prompt of whole blocks, decode steps."""
    from ray_tpu.models import decoding
    bs = decoding.block_size_of(caches)
    B = int(caches.lengths.shape[0])
    M = caches.block_tables.shape[1] * bs
    P = -(-16 // bs) * bs
    room = (M - PARITY_DECODE_STEPS - 1) // P * P
    prompt = min(PARITY_PROMPT, room)
    return {"P": P, "rows": max(1, min(2048, prompt) // P), "prompt": prompt,
            "compared": min(PARITY_COMPARED, prompt // 2 // P * P),
            "steps": PARITY_DECODE_STEPS, "block": bs, "slots": B}


def _weights(cfg, seed: int):
    """Makers of the program's own weights, a piece at a time (BenchLLM
    makes them as init_params(cfg, PRNGKey(seed % 2**31))).  The key is an
    ARGUMENT of each jitted maker: closed over, it would be a constant of
    the program and every seed would compile anew."""
    import jax
    from ray_tpu.models import axk1
    key = jax.random.PRNGKey(seed % (2 ** 31))
    layer_key = jax.random.split(key, 8)[0]
    return {
        "layer": lambda i: jax.jit(
            lambda k: axk1.init_layer(cfg, k, i))(layer_key),
        "embed": lambda: jax.jit(lambda k: axk1.init_embed(cfg, k))(key),
        "head": lambda: jax.jit(lambda k: axk1.init_head(cfg, k))(key)}


def short_lengths(sizes: Dict[str, int]) -> List[int]:
    """The prompts of the short requests in slots 1..: a whole row and a
    part of one, P + 1 .. 2 P - 1 tokens, neighbours never the same."""
    P = sizes["P"]
    return [P + 1 + (5 * j) % (P - 1) for j in range(1, sizes["slots"])]


def parity_tokens(cfg, seed: int, sizes: Dict[str, int]):
    """Request A's prompt and decoded positions, then 2 P + steps tokens of
    every short request."""
    import jax
    n = sizes["prompt"] + sizes["steps"] + (sizes["slots"] - 1) * (
        2 * sizes["P"] + sizes["steps"])
    return jax.random.randint(jax.random.PRNGKey((seed + 1) % (2 ** 31)),
                              (n,), 0, cfg.vocab_size)


def logits_both_ways(cfg, seed: int, sizes: Dict[str, int], tokens, *,
                     control: bool = False, attn_impl: str = "auto"):
    """The same tokens through the PROGRAM and through the REFERENCE, one
    layer's weights at a time (made once, used by both, dropped).

    The program: its paged prefill and decode LAYERS (the functions the
    engine's dispatches are made of: models/decoding.py paged_prefill_layer
    / paged_decode_layer), over `slots` requests with tables of their own.
    Request A (slot 0) brings a prompt of `prompt` positions in calls of
    `rows` rows of P tokens (the engine's tile), every row against the
    latent rows the rows before it wrote.  Every other slot holds a SHORT
    request of its own tokens and its own length (a whole row and a part of
    one), all of them prefilled as rows of ONE call.  Then `steps` decode
    steps of all slots together, each at its own length.  Then request B,
    A's prompt after a prefix hit: A's blocks but the last `compared`
    positions' shared through its table, those positions prefilled again
    into blocks of its own (in the same compiled call, so that what differs
    is the hit and not a program's rounding).  With `control` the reference
    one precision down stands in the program's place.

    The reference FOLLOWS the program's choice of experts, layer by layer,
    where that choice lies within FOLLOW_MARGIN of its own
    (`reference_route`; kinds/lfm2-moe.py has the reasons).

    -> (got, want, rows, after_hit, routing): got, want (logits of A's last
    `compared` prompt positions [compared, V], of every slot's decoded
    positions [steps, slots, V]); rows (per layer the pool's rows of A's
    prompt and decoded positions, and the reference's); after_hit (B's
    logits, A's, of the same positions); routing (per expert layer, of A's
    positions: the program's picks, the reference's own on its own path,
    the reference's own on the PROGRAM's input to the router, and the
    shortfall of what it followed)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import axk1, decoding

    hp = hyper(cfg)
    P, R, prompt, cmp_n, steps, bs, B = (sizes[k] for k in (
        "P", "rows", "prompt", "compared", "steps", "block", "slots"))
    top_k, width = cfg.moe_top_k, cfg.kv_lora_rank + cfg.qk_rope_dim
    make = _weights(cfg, seed)
    n_short, Ls = B - 1, 2 * P + steps
    assert 2 * n_short <= R, "the short requests' rows do not fit one call"
    short_len = jnp.asarray(short_lengths(sizes), jnp.int32).reshape(n_short)
    short_tokens = tokens[prompt + steps:].reshape(n_short, Ls)
    short_blocks = -(-Ls // bs)
    shared = prompt // bs                   # whole blocks of the prompt
    tail = -(-(steps + 1) // bs)            # blocks A's decode fills
    again = cmp_n // bs                     # blocks request B fills itself
    W = shared + tail
    NB = 1 + W + n_short * short_blocks + again
    table = jnp.zeros((B, W), jnp.int32).at[0].set(1 + jnp.arange(W))
    table = table.at[1:, :short_blocks].set(
        1 + W + jnp.arange(n_short * short_blocks).reshape(n_short, -1))
    table_b = table[0].at[shared - again:shared].set(
        1 + W + n_short * short_blocks + jnp.arange(again))
    lengths = jnp.concatenate([jnp.asarray([prompt], jnp.int32), short_len])
    decoded_at = short_len[:, None] + jnp.arange(steps)      # [n_short, steps]

    def prefill(kind, p, x, a, b, tabs, starts, lens, slots, ends):
        """Rows x [R, P, D] of several requests: row r holds `lens[r]`
        tokens (0: no row) from position `starts[r]` on of the request in
        slot `slots[r]` with table `tabs[r]`, whose prompt is `ends[r]`
        long (the row that reaches it closes the slot)."""
        seen = []
        live = lens > 0
        rows = decoding.prefill_rows(
            jnp.where(live[:, None], tabs, 0), starts, lens, live, P, bs,
            slots, B, closes=live & (starts + lens == ends))
        out = decoding.paged_prefill_layer(
            cfg, kind, p, x, a, b, rows, attn_impl,
            tap=lambda m, picks: seen.extend((m, picks)))
        return out[:3] + (tuple(seen),)

    def decode(kind, p, x, a, b, lens):
        seen = []
        rows = decoding.decode_rows(table, lens, jnp.ones((B,), bool), bs)
        out = decoding.paged_decode_layer(
            cfg, kind, p, x, a, b, rows, attn_impl,
            tap=lambda m, picks: seen.extend((m, picks)))
        return out[:3] + (tuple(seen),)

    # one program per layer KIND, not per layer: the kind is the static part
    prefill = jax.jit(prefill, static_argnums=(0,), donate_argnums=(3,))
    decode = jax.jit(decode, static_argnums=(0,), donate_argnums=(3,))
    kinds = set(hp["kinds"])

    def both(**kw):
        """The reference's layer over request A, and over the short
        requests side by side."""
        return {kind: (
            jax.jit(functools.partial(reference_layer, hp, kind, **kw)),
            jax.jit(jax.vmap(functools.partial(reference_layer, hp, kind,
                                               **kw), in_axes=(None, 0, 0))))
            for kind in kinds}

    plain, rounded = both(), both(control=True) if control else None
    own_input = jax.jit(lambda p, m: reference_route(hp, p, _f32(m))[0])

    def rows_of(toks, n_rows):
        """[n_rows * P] tokens -> embedded rows [R, P, D]."""
        toks = jnp.pad(toks, (0, (R - n_rows) * P))
        return axk1.embed(cfg, embed, toks.reshape(R, P))

    def call(tab, slot, start, n_rows, end):
        """`n_rows` whole rows of one request from `start` on."""
        live = jnp.arange(R) < n_rows
        return (jnp.broadcast_to(tab, (R, W)), start + jnp.arange(R) * P,
                jnp.where(live, P, 0), jnp.full((R,), slot, jnp.int32),
                jnp.full((R,), end, jnp.int32))

    with jax.default_matmul_precision("highest"):
        embed = make["embed"]()
        x_ref = reference_embed(hp, embed, tokens[:prompt + steps])
        xs_ref = reference_embed(hp, embed, short_tokens)
    x_ctl, xs_ctl = x_ref, xs_ref
    per_call = [min(R, (prompt - c * R * P) // P)
                for c in range(-(-prompt // (R * P)))]
    calls_a = [call(table[0], 0, c * R * P, n, prompt)
               for c, n in enumerate(per_call)]
    xs = [rows_of(tokens[c * R * P:c * R * P + n * P], n)
          for c, n in enumerate(per_call)]
    # the short requests: rows 2 i and 2 i + 1 are slot i + 1's
    two = jnp.arange(2 * n_short) // 2
    first = jnp.arange(2 * n_short) % 2 == 0
    pad = (0, R - 2 * n_short)
    call_short = (
        jnp.pad(table[1 + two], (pad, (0, 0))),
        jnp.pad(jnp.where(first, 0, P), pad),
        jnp.pad(jnp.where(first, P, short_len[two] - P), pad),
        jnp.pad(1 + two, pad), jnp.pad(short_len[two], pad))
    x_short = rows_of(short_tokens[:, :2 * P].reshape(-1), 2 * n_short)
    Rb = cmp_n // P                         # request B's rows
    call_b = call(table_b, 1, prompt - cmp_n, Rb, prompt)
    xb = rows_of(tokens[prompt - cmp_n:prompt], Rb)
    xd = [axk1.embed(cfg, embed, jnp.concatenate([
        tokens[prompt + t][None], jnp.take_along_axis(
            short_tokens, decoded_at[:, t:t + 1], axis=1)[:, 0]])[:, None])
        for t in range(steps)]
    del embed
    routing, latent_rows = [], []
    for i, kind in enumerate(cfg.layer_kinds):
        p = make["layer"](i)
        follow = follow_short = fed = rows_here = None
        if control:
            with jax.default_matmul_precision("highest"):
                x_ctl, follow, rows_here, _ = rounded[kind][0](p, x_ctl, None)
                xs_ctl, follow_short, _, _ = rounded[kind][1](
                    p, xs_ctl, None)
        else:
            a = jnp.zeros(decoding.unrolled_pool_shape(cfg, NB - 1, bs,
                                                       "latent"), cfg.dtype)
            seen = []               # (m, picks) of A's rows, in order
            for c, n in enumerate(per_call):
                xs[c], a, _, mp = prefill(kind, p, xs[c], a, None,
                                          *calls_a[c])
                seen.extend(v[:n * P] for v in mp)
            x_short, a, _, mp = prefill(kind, p, x_short, a, None,
                                        *call_short)
            seen_short = [v[:2 * n_short * P].reshape(n_short, 2 * P, -1)
                          for v in mp]
            for t in range(steps):
                xd[t], a, _, mp = decode(kind, p, xd[t], a, None,
                                         lengths + t)
                seen.extend(v[:1] for v in mp)
                seen_short.extend(v[1:, None] for v in mp)
            # what the pool holds of A: its prompt and decoded positions
            rows_here = a[1:1 + W, 0].reshape(W * bs, -1)[
                :prompt + steps, :width]
            if seen:
                fed = jnp.concatenate(seen[0::2])
                follow = jnp.concatenate(seen[1::2])
                # [n_short, 2 P + steps, k]: the prompt's rows, -1 past
                # its end, then the decoded positions where they belong
                at = jnp.arange(2 * P)[None, :, None]
                follow_short = jnp.concatenate([
                    jnp.where(at < short_len[:, None, None], seen_short[1],
                              -1),
                    jnp.full((n_short, steps, top_k), -1, jnp.int32)],
                    axis=1).at[jnp.arange(n_short)[:, None], decoded_at].set(
                        jnp.concatenate(seen_short[3::2], axis=1))
            # request B, after the hit: slot 1, its own table
            xb, a, _, _ = prefill(kind, p, xb, a, None, *call_b)
            del a
        with jax.default_matmul_precision("highest"):
            x_ref, own_picks, rows_ref, shortfall = plain[kind][0](
                p, x_ref, follow)
            xs_ref, _, _, _ = plain[kind][1](p, xs_ref, follow_short)
            if follow is not None:
                routing.append((follow, own_picks, None if fed is None
                                else own_input(p, fed), shortfall))
        latent_rows.append((rows_here, rows_ref))
        del p

    def decoded(x, xs):
        """[steps, slots, D] of the reference's rows."""
        return jnp.concatenate([x[prompt:, None], jnp.swapaxes(
            jnp.take_along_axis(xs, decoded_at[:, :, None], axis=1), 0, 1)],
            axis=1)

    # weights are ARGUMENTS of every jitted function here: one closed over
    # would be compiled in as a constant, on the host
    head = make["head"]()
    with jax.default_matmul_precision("highest"):
        ref_head = jax.jit(lambda head, x: reference_head(hp, head, x))
        want = (ref_head(head, x_ref[prompt - cmp_n:prompt]),
                ref_head(head, decoded(x_ref, xs_ref)))
        if control:
            got = (ref_head(head, x_ctl[prompt - cmp_n:prompt]),
                   ref_head(head, decoded(x_ctl, xs_ctl)))
    after_hit = None
    if not control:
        logits = jax.jit(lambda head, x: axk1.logits(cfg, head, x))
        last = jnp.concatenate(
            [x[:n].reshape(n * P, -1) for x, n in zip(xs, per_call)]
        )[-cmp_n:]
        got = (logits(head, last),
               logits(head, jnp.stack([x[:, 0] for x in xd])))
        after_hit = (logits(head, xb[:Rb].reshape(cmp_n, -1)), got[0])
    return got, want, latent_rows, after_hit, routing


def compare(cfg, seed: int, sizes: Dict[str, int], *, control=False,
            attn_impl: str = "auto") -> Dict[str, float]:
    """The program's logits and latent rows against the reference's, the
    reference following the program's choice of experts, and its own picks
    against the program's.  `control`: the reference one precision down in
    the program's place."""
    import jax.numpy as jnp
    got, want, rows, after_hit, routing = logits_both_ways(
        cfg, seed, sizes, parity_tokens(cfg, seed, sizes),
        control=bool(control), attn_impl=attn_impl)
    hits = jnp.stack([picks_agree(g, w) for g, w, _, _ in routing])
    out = {"route_mismatch_share": float(1.0 - jnp.mean(
        hits.astype(jnp.float32))),
        "rows_routed_alike_share": float(jnp.mean(
            hits.all(axis=(0, 2)).astype(jnp.float32))),
        "route_shortfall_max": max(float(jnp.max(r[3])) for r in routing),
        "rows_not_followed_share": float(jnp.mean(jnp.stack(
            [r[3] for r in routing]) > FOLLOW_MARGIN)),
        "latent_row_err": max(rel_rms(g, w) for g, w in rows),
        "logits_prefill_err": rel_rms(got[0], want[0]),
        "logits_decode_err": rel_rms(got[1], want[1]),
        "logits_decode_err_worst_slot": max(
            rel_rms(got[1][:, j], want[1][:, j])
            for j in range(got[1].shape[1]))}
    if after_hit is not None:
        out["logits_after_hit_err"] = rel_rms(*after_hit)
        out["route_own_input_mismatch_share"] = float(1.0 - jnp.mean(
            jnp.stack([picks_agree(g, o) for g, _, o, _ in routing]
                      ).astype(jnp.float32)))
    return out


def parity(where: str, cfg, seed: int, *, seq: int = 512,
           caches=None) -> Dict[str, Any]:
    """What `correct` compares in a serving cell, in the process that holds
    the chip: logits of the program's tiled paged prefill and paged decode,
    through the latent pools, of a long request and a short one in every
    other slot, against the reference's full forward pass in the published
    form; the pool's rows themselves; a request answered after a prefix hit
    against itself answered cold; the routing over the router's whole width
    on the reference's path and on the program's own.  At the engine's own
    widths, tile and table size, weights made again from the seed one layer
    at a time (two copies of them do not fit).  These are the functions the
    engine's dispatches are made of, driven by this check and not by the
    engine: admission, the radix hit and `_fused_dispatch`'s packing are
    covered by the CPU tests alone (tests/test_axk1.py; PERF.md section 7)."""
    if where != "serve":
        raise ValueError("kind mla-moe is compared in serving cells only")
    sizes = parity_sizes(caches)
    t0 = time.time()
    out: Dict[str, Any] = dict(compare(cfg, seed, sizes))
    out["parity_s"] = time.time() - t0
    out["parity_positions"] = sizes["prompt"] + sizes["steps"] + sum(
        n + sizes["steps"] for n in short_lengths(sizes))
    return out
