"""Model kind `lfm2-moe` (Liquid AI's LFM2 MoE family, `model_type`
lfm2_moe): gated short convolutions and full attention layers mixed,
leading dense layers, then layers of many small sigmoid-routed experts.
The same interface as kinds/afmoe.py, found by the configuration's
`"kind"`; serving only (the program has no training path for it, so CHECKS
has no "train").

THE PLAIN REFERENCE is here (`reference_*`): the forward pass in float32
at `jax.default_matmul_precision("highest")`, no cache, no kernel, no
batching of experts (a loop over every expert, weighted by whether the
token chose it), the convolution as K shifted multiplies, attention blocked
over query rows so that 17 k positions fit.  For layer l of kind (mixer,
feed-forward), N() an RMSNorm (eps 1e-5) with its own weight:

    x0      = Embed[token]
    a       = N_operator(x)
    conv:     B, C, z = split3(W_in a);   u = B * z          (elementwise)
              c_t = sum_{j=0..K-1} w[j] * u_{t-(K-1)+j}      (per channel,
                    K = conv_L_cache = 3, u before position 0 is zero)
              x = x + W_out (C * c)
    full:     q, k, v = Wq a, Wk a, Wv a;  q = N_q(q), k = N_k(k) per head
              rotary (theta 1e6, rotate-half, absolute position) on q, k
              o = softmax(q k^T / sqrt(head_dim) + causal mask) v
              x = x + Wo o
    m       = N_ffn(x)
    dense:    x = x + W2(silu(W1 m) * W3 m)
    experts:  s = sigmoid(Wg m);  S = top-k of (s + b);
              w_e = routed_scaling_factor * s_e / (sum_{e' in S} s_e' + 1e-6)
              x = x + sum_{e in S} w_e FFN_e(m)
    logits  = Embed^T N_embedding(x_L)

DEPARTURE RISKS.  The model's config.json carries conv_L_cache, conv_bias,
layer_types, num_dense_layers, norm_topk_prob, routed_scaling_factor,
use_expert_bias, norm_eps and rope_theta.  It does NOT carry what follows;
each is as ISSUE 34's writer recalls the public modelling code
(modeling_lfm2_moe.py), with no network here to re-read it, and each is
listed in the configuration file under `assumed`:
  1. head_dim = hidden_size / num_attention_heads = 64;
  2. two norms a layer (operator_norm, ffn_norm), both BEFORE a branch,
     none on a branch's output; a last embedding_norm before the head;
  3. RMSNorm of q and of k over each head's head_dim, before the rotary;
     rotary on every attention layer; no output gate;
  4. the order of the three parts of in_proj's output (B, C, z), u = B * z
     before the convolution and C * after it;
  5. selection by s + expert_bias, weights from s without it;
  6. the 1e-6 in the routing weights' denominator;
  7. the output head is tied to the embedding (the catalog row dropped the
     key); no embedding multiplier.
"""

from __future__ import annotations

import functools
import math
import time
from typing import Any, Callable, Dict, List

# How each limit was set: PERF.md section 2, "Limits of `correct`".  Readings:
# my chip runs, PR 34 (chiprun_out/parity34b.jsonl), one process at the
# cell's widths and sizes, 16,392 positions of request A and 31 short
# requests beside it: the sound program on six seeds, the fp8 control on
# two, the program with bfloat16 routing scores on three.
TOLERANCES: Dict[str, float] = {
    # relative RMS error of the logits (bf16 activations against float32)
    # over A's last 256 prompt positions: program 0.01853-0.01899, control
    # 0.0391-0.0396
    "logits_prefill_err": 0.027,
    # ... over the 8 decoded positions of all 32 slots: program
    # 0.01873-0.01898 (its worst slot 0.0194-0.0202), control 0.0426
    "logits_decode_err": 0.027,
    # relative RMS error of every conv layer's block tails (the pool's rows
    # of A's blocks) and every slot's tail against the reference's u at
    # those positions, largest layer: program 0.02362-0.02398, control
    # 0.0499
    "conv_tail_err": 0.034,
    # the logits of a request's last rows answered after a prefix hit (its
    # first blocks shared, tails restored from the pool) against the same
    # rows answered cold, in the same compiled call: program 0.0 on every
    # seed (bit-equal); rows that start from another block's tail than
    # the one before them read 0.52 at the toy's size (tests/test_lfm2.py)
    "logits_after_hit_err": 1e-3,
    # share of A's (row, expert) picks that differ from the reference's own
    # on the reference's own path: program 0.01247-0.01280, control
    # 0.0257-0.0258.  NOT what refuses bfloat16 scores: the program that
    # scores in bfloat16 reads 0.01512-0.01527, under this limit (the two
    # causes of a flip add nearer in quadrature than in sum)
    "route_mismatch_share": 0.0165,
    # ... on the PROGRAM's own input to each router (what the tap shows),
    # scored by the reference in float32: program 0.0 on every seed (not
    # one of 524,544 picks), the program with bfloat16 scores
    # 0.00819-0.00826
    "route_own_input_mismatch_share": 1e-3,
}

CHECKS: Dict[str, tuple] = {
    "serve": tuple(TOLERANCES),
}

MIXERS = {"conv": "conv", "full_attention": "full"}
ROUTE_EPS = 1e-6
# How far below the reference's own k-th score an expert of the program's
# choice may score and still be followed (`reference_route`); the scores
# are sigmoids, of the order of a half.
# Over six seeds the sound program's furthest followed pick lay 0.0134-0.0185
# below (`route_shortfall_max`), the fp8 control's 0.026 and 0.063.
FOLLOW_MARGIN = 0.04


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------
def layer_kinds(cfg: Dict[str, Any]) -> List[List[str]]:
    """(mixer, feed-forward) per layer: layer_types gives the mixers, the
    first num_dense_layers are dense."""
    return [[MIXERS[t], "dense" if i < cfg["num_dense_layers"] else "experts"]
            for i, t in enumerate(cfg["layer_types"])]


def head_dim(cfg: Dict[str, Any]) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def check(cfg: Dict[str, Any]) -> None:
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not list num_hidden_layers layers")
    if any(t not in MIXERS for t in cfg["layer_types"]):
        raise ValueError(f"layer_types other than {sorted(MIXERS)}")
    if cfg["num_attention_heads"] % cfg["num_key_value_heads"]:
        raise ValueError("query heads are not a multiple of KV heads")
    if cfg["hidden_size"] % cfg["num_attention_heads"]:
        raise ValueError("hidden_size is not a multiple of the heads")
    if not cfg["norm_topk_prob"] or not cfg["use_expert_bias"]:
        raise ValueError("only normalised sigmoid scores with a selection "
                         "bias are expressed")
    if cfg["conv_bias"]:
        raise ValueError("a convolution with a bias is not expressed")
    if cfg["rope_parameters"]["rope_type"] != "default":
        raise ValueError("only the default rotary embedding is expressed")


def transformer_kwargs(cfg: Dict[str, Any], *, max_seq: int,
                       param_dtype: str, **extra: Any) -> Dict[str, Any]:
    check(cfg)
    kw = {
        "vocab_size": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "d_head": head_dim(cfg),
        "d_ff": cfg["intermediate_size"],
        "max_seq": max_seq,
        "arch": "lfm2",
        "rope_theta": float(cfg["rope_parameters"]["rope_theta"]),
        "norm_eps": float(cfg["norm_eps"]),
        "tie_embeddings": True,
        "dtype": cfg.get("torch_dtype", "bfloat16"),
        "param_dtype": param_dtype,
        "layer_kinds": layer_kinds(cfg),
        "conv_kernel": cfg["conv_L_cache"],
        "moe_experts": cfg["num_experts"],
        "moe_top_k": cfg["num_experts_per_tok"],
        "moe_d_ff": cfg["moe_intermediate_size"],
        "moe_route_scale": float(cfg["routed_scaling_factor"]),
        "moe_route_eps": ROUTE_EPS,
        "remat": False,
    }
    kw.update(extra)
    return kw


def param_counts(cfg: Dict[str, Any]) -> Dict[str, int]:
    d, dh = cfg["hidden_size"], head_dim(cfg)
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, fe, E = (cfg["intermediate_size"], cfg["moe_intermediate_size"],
                cfg["num_experts"])
    attn = (d * h * dh + 2 * d * hkv * dh + h * dh * d      # q, k, v, o
            + 2 * dh)                                       # q and k norms
    conv = 3 * d * d + cfg["conv_L_cache"] * d + d * d      # in, taps, out
    norms = 2 * d
    dense_ffn = 3 * d * f
    expert_ffn = d * E + E + E * 3 * d * fe     # router, bias, experts
    total = cfg["vocab_size"] * d + d           # tied table, last norm
    for mixer, ffn in layer_kinds(cfg):
        total += ((conv if mixer == "conv" else attn) + norms
                  + (dense_ffn if ffn == "dense" else expert_ffn))
    return {"total": total, "input_embedding": cfg["vocab_size"] * d,
            "attention": attn, "conv": conv, "dense_ffn": dense_ffn,
            "expert_ffn": expert_ffn}


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """Keys and values of the attention layers alone."""
    attn = sum(1 for m, _ in layer_kinds(cfg) if m == "full")
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * 2 * attn


def tail_bytes_per_block(cfg: Dict[str, Any]) -> int:
    """The conv layers' state a completed block keeps: conv_L_cache - 1
    positions of hidden_size values a layer."""
    conv = sum(1 for m, _ in layer_kinds(cfg) if m == "conv")
    return (cfg["conv_L_cache"] - 1) * cfg["hidden_size"] * 2 * conv


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    raise NotImplementedError("kind lfm2-moe has no training path")


# ---------------------------------------------------------------------------
# cost functions of the kernels this kind's cell reads: fn(config, shapes)
# ---------------------------------------------------------------------------
def experts_touched_even(cfg: Dict[str, Any], rows: float) -> float:
    """Experts with at least one of `rows` tokens' picks, under even
    routing: E (1 - (1 - k / E) ^ rows)."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return E * (1.0 - (1.0 - k / E) ** rows)


def moe_experts_decode(cfg, s):
    """One call = one expert layer, one decode step: slots x k rows through
    three products of hidden x expert width; bytes = the distinct experts
    read x 3 x hidden x expert width x 2 B + the rows in and out.  How many
    experts a step reads follows the live rows and the routing, which a
    cost function is not shown, and a count above what the kernel moved
    reads over 100 %: so this is a FLOOR, the expectation under even
    routing with HALF the slots live (41 of 64 at 32 slots; all live: 56).
    What the engine counts is moe.experts_touched / moe.layer_steps
    (PERF.md section 5 has both)."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = s["slots"] * cfg["num_experts_per_tok"]
    flops = 2.0 * rows * 3 * d * fe
    bytes_ = 2.0 * (experts_touched_even(cfg, s["slots"] / 2) * 3 * d * fe
                    + 2 * rows * d)
    return flops, bytes_


# Per fused dispatch of the cell the kernel is measured in
# (serve-lfm2-agent-sessions, traffic/agent-sessions.json): requests
# admitted, and the uncached tokens each brings (a message, the previous
# reply, the block-rounding remainder).  ASSUMED, a floor: the cell's own
# counters read 13.95 requests a fused dispatch (prefill.chunks / the
# rung_dispatches) and 32.6 tokens a request once the priming's 37,312 are
# taken out of prefill.chunk_tokens (my chip run, PR 34; PERF.md section 5);
# the harness hands a cost function the configuration and `slots` /
# `live_context` only.
PREFIX_ROWS_PER_CALL = 13.0
PREFIX_TOKENS_PER_ROW = 32.0


def prefix_attention(cfg, s):
    """One call = one attention layer, one prefill dispatch.  Each admitted
    request's uncached tokens attend, as one row of queries, to the
    request's context (the mean live context: every attention layer is
    full).  Bytes: the K/V pages of all kv heads read once per request plus
    q and o.  Operations as the model's heads have them (two kv heads lie
    side by side in a row of 128 lanes, so the MXU contracts over 128 where
    head_dim is 64: that is not counted)."""
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    ctx = s["live_context"] / max(s["slots"], 1)
    q_tokens = PREFIX_ROWS_PER_CALL * PREFIX_TOKENS_PER_ROW
    flops = 2.0 * 2 * q_tokens * ctx * h * dh
    bytes_ = 2.0 * (PREFIX_ROWS_PER_CALL * 2 * ctx * hkv * dh
                    + 2 * q_tokens * h * dh)
    return flops, bytes_


COST_FNS: Dict[str, Callable] = {
    "moe_experts_decode": moe_experts_decode,
    "prefix_attention": prefix_attention,
}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def hyper(cfg) -> Dict[str, Any]:
    """The numbers the reference needs, from the program's
    TransformerConfig (the harness hands parity() nothing else)."""
    return {"heads": cfg.n_heads, "kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "hidden": cfg.d_model,
            "eps": cfg.norm_eps, "theta": cfg.rope_theta,
            "top_k": cfg.moe_top_k, "route_scale": cfg.moe_route_scale,
            "route_eps": cfg.moe_route_eps,
            "kinds": tuple(cfg.layer_kinds)}


def _f32(x):
    import jax.numpy as jnp
    return x.astype(jnp.float32)


def _fp8(x):
    import jax.numpy as jnp
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _bf16(x):
    """Rounded to bfloat16's 8 exponent and 7 mantissa bits (an operation
    of its own: XLA drops a float32 -> bfloat16 -> float32 pair of casts)."""
    import jax
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _rmsnorm(x, w, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def _rotary(x, positions, theta):
    """x [S, heads, D]; rotate-half, absolute positions."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _swiglu(m, w_gate, w_up, w_down):
    import jax
    return (jax.nn.silu(m @ _f32(w_gate)) * (m @ _f32(w_up))) @ _f32(w_down)


def reference_route(hp, p, m, scores=_f32, follow=None):
    """m [S, hidden] float32 -> (picks [S, k], the weights [S, k] of the
    experts used, shortfall [S]).  `scores` rounds the sigmoid scores (the
    control of the routing's precision).  `follow` [S, k] (-1: nothing to
    follow in this row): the experts used are these and not the picks
    (weighed by this function's own scores), in every row where each of
    them scores within FOLLOW_MARGIN of this function's own k-th: the
    comparison has the reference follow the program's choice, so that a
    score within rounding of the k-th counts as one mismatch and not as
    another function from there on; a choice that is further off is not
    followed, and shows in the logits.  shortfall: by how much the lowest
    of a row's followed experts scores below the k-th (0: none does)."""
    import jax
    import jax.numpy as jnp
    s = scores(jax.nn.sigmoid(m @ _f32(p["w_router"])))
    biased = s + _f32(p["route_bias"])
    top, picks = jax.lax.top_k(biased, hp["top_k"])
    used, shortfall = picks, jnp.zeros(m.shape[:1], jnp.float32)
    if follow is not None:
        theirs = jnp.take_along_axis(biased, jnp.maximum(follow, 0), axis=1)
        shortfall = jnp.where((follow >= 0).all(axis=1), jnp.max(
            top[:, -1:] - theirs, axis=1), jnp.inf)
        used = jnp.where((shortfall <= FOLLOW_MARGIN)[:, None], follow,
                         picks)
        shortfall = jnp.where(jnp.isinf(shortfall), 0.0,
                              jnp.maximum(shortfall, 0.0))
    chosen = jnp.take_along_axis(s, used, axis=1)
    weights = hp["route_scale"] * chosen / (
        jnp.sum(chosen, axis=1, keepdims=True) + hp["route_eps"])
    return picks, weights, used, shortfall


def reference_conv(p, a, control: bool = False, wrong: str = ""):
    """a [S, hidden] float32 -> (the conv operator's output, u [S, hidden]).
    Three shifted multiplies: tap j of K meets u shifted K - 1 - j
    positions into the past, zeros before position 0."""
    import jax.numpy as jnp
    S = a.shape[0]
    bcz = jnp.einsum("sd,dcf->scf", a, _f32(p["w_in"]))
    B, C, z = bcz[:, 0], bcz[:, 1], bcz[:, 2]
    u = B * z
    if control:
        u = _fp8(u)
    w = _f32(p["w_conv"])                       # [K, hidden]
    K = w.shape[0]
    c = jnp.zeros_like(u)
    for j in range(K):
        back = K - 1 - j + (1 if wrong == "taps_shifted" else 0)
        c = c + w[j] * jnp.pad(u, ((back, 0), (0, 0)))[:S]
    if wrong != "no_out_gate":
        c = C * c
    return c @ _f32(p["w_out"]), u


def reference_attention(hp, p, a, block: int = 256, control: bool = False,
                        wrong: str = ""):
    import jax
    import jax.numpy as jnp
    S = a.shape[0]
    H, Hkv, D = hp["heads"], hp["kv_heads"], hp["head_dim"]
    pos = jnp.arange(S)
    q = jnp.einsum("sd,dhk->shk", a, _f32(p["wq"]))
    k = jnp.einsum("sd,dhk->shk", a, _f32(p["wk"]))
    v = jnp.einsum("sd,dhk->shk", a, _f32(p["wv"]))
    q = _rmsnorm(q, p["q_norm"], hp["eps"])
    k = _rmsnorm(k, p["k_norm"], hp["eps"])
    if wrong != "no_rope":
        q, k = _rotary(q, pos, hp["theta"]), _rotary(k, pos, hp["theta"])
    if control:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    k_rep = jnp.repeat(k, H // Hkv, axis=1)          # [S, H, D]
    v_rep = jnp.repeat(v, H // Hkv, axis=1)
    # blocks of query rows, each against every key (a [H, block, S] score)
    n_blocks = -(-S // block)
    pad = n_blocks * block - S
    q_blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        n_blocks, block, H, D)
    pos_blocks = jnp.pad(pos, (0, pad)).reshape(n_blocks, block)

    def one_block(args):
        qb, qi = args
        s = jnp.einsum("qhd,khd->hqk", qb, k_rep) / math.sqrt(D)
        seen = pos[None, :] <= qi[:, None]
        w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v_rep)

    o = jax.lax.map(one_block, (q_blocks, pos_blocks)).reshape(
        n_blocks * block, H, D)[:S]
    return jnp.einsum("shk,hkd->sd", o, _f32(p["wo"]))


def reference_layer(hp, kind, p, x, follow=None, block: int = 256,
                    control: bool = False, wrong: str = ""):
    """x [S, hidden] float32 (positions 0..S-1) -> (x' [S, hidden], picks
    [S, k] or None, a conv layer's u [S, hidden] or None, shortfall [S] or
    None).  `follow`: the experts to use in the picks' place and the
    shortfall (`reference_route`).  `control`: q, k, v, u and the expert
    weights rounded to fp8 (e4m3), the precision below the configuration's
    bfloat16, and the routing scores to bfloat16, the precision below their
    float32: what `correct` must refuse.  `wrong` names one deliberate
    fault, for the tests that show the limits refuse it: "taps_shifted",
    "no_out_gate", "no_rope", "softmax_routing"."""
    import jax
    import jax.numpy as jnp
    mixer, ffn = kind
    S = x.shape[0]
    a = _rmsnorm(x, p["op_norm"], hp["eps"])
    u = None
    if mixer == "conv":
        y, u = reference_conv(p, a, control, wrong)
    else:
        y = reference_attention(hp, p, a, block, control, wrong)
    x = x + y
    m = _rmsnorm(x, p["ffn_norm"], hp["eps"])
    picks = shortfall = None
    if ffn == "dense":
        n_blocks = -(-S // block)
        rows = jnp.pad(m, ((0, n_blocks * block - S), (0, 0))).reshape(
            n_blocks, block, -1)
        y = jax.lax.map(lambda r: _swiglu(r, p["w_gate"], p["w_up"],
                                          p["w_down"]),
                        rows).reshape(n_blocks * block, -1)[:S]
    else:
        picks, weights, used, shortfall = reference_route(
            hp, p, m, _bf16 if control else _f32, follow)
        if wrong == "softmax_routing":
            logits = m @ _f32(p["w_router"])
            top, picks = jax.lax.top_k(logits, hp["top_k"])
            weights, used = jax.nn.softmax(top, axis=-1), picks
        E = p["w_gate"].shape[0]
        # each token's weight for each expert (0 where it did not use it)
        dense_w = jnp.zeros((S, E), jnp.float32).at[
            jnp.arange(S)[:, None], used].add(weights)
        rnd = _fp8 if control else _f32

        def one_expert(y, e):
            out = _swiglu(m, rnd(p["w_gate"][e]), rnd(p["w_up"][e]),
                          rnd(p["w_down"][e]))
            return y + dense_w[:, e][:, None] * out, None

        y, _ = jax.lax.scan(one_expert, jnp.zeros_like(m), jnp.arange(E))
    return x + y, picks, u, shortfall


def reference_embed(hp, table, tokens):
    return _f32(table[tokens])


def reference_head(hp, params, x):
    """x [R, hidden] -> logits [R, V]; `params` holds final_norm and the
    tied tok_embed."""
    return _rmsnorm(x, params["final_norm"], hp["eps"]) \
        @ _f32(params["tok_embed"]).T


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
    from ray_tpu.models import lfm2
    key = jax.random.PRNGKey(seed % (2 ** 31))
    layer_key = jax.random.split(key, 8)[0]
    return {
        "layer": lambda i: jax.jit(
            lambda k: lfm2.init_layer(cfg, k, i))(layer_key),
        "embed": lambda: jax.jit(lambda k: lfm2.init_embed(cfg, k))(key),
        "head": lambda: jax.jit(lambda k: lfm2.init_head(cfg, k))(key)}


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
    blocks and the block tails the rows before it wrote.  Every other slot
    holds a SHORT request of its own tokens and its own length (a whole row
    and a part of one), all of them prefilled as rows of ONE call.  Then
    `steps` decode steps of all slots together, each at its own length.
    Then request B, A's prompt after a prefix hit: A's blocks but the last
    `compared` positions' shared through its table, those positions
    prefilled again into blocks of its own (in the same compiled call, so
    that what differs is the hit and not a program's rounding), its conv
    layers starting from the tail A left in the last shared block.  With
    `control` the reference one precision down stands in the program's
    place.

    The reference FOLLOWS the program's choice of experts, layer by layer,
    where that choice lies within FOLLOW_MARGIN of its own
    (`reference_route`): its own picks are compared with the program's
    (`route_mismatch_share`), and its logits are those of the function the
    program computed.  Left to its own picks it computes another function
    from the first flipped score on, and here, unlike behind attention
    alone, a flipped NEIGHBOUR reaches a row through the next
    convolution's taps: with free routing the program read 0.044-0.048 on
    the logits and the fp8 control 0.064-0.073 (my chip run, PR 34), a
    limit between which would have judged the routing's luck.

    -> (got, want, tails, after_hit, routing): got, want (logits of A's
    last `compared` prompt positions [compared, V], of every slot's decoded
    positions [steps, slots, V]); tails (per conv layer the program's block
    tails of A's prompt and every slot's last tail, and the reference's u
    at those positions); after_hit (B's logits, A's, of the same
    positions); routing (per expert layer, of A's positions: the program's
    picks, the reference's own on its own path, the reference's own on the
    PROGRAM's input to the router, and the shortfall of what it followed)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import decoding, lfm2

    hp = hyper(cfg)
    P, R, prompt, cmp_n, steps, bs, B = (sizes[k] for k in (
        "P", "rows", "prompt", "compared", "steps", "block", "slots"))
    K1, D, top_k = cfg.conv_kernel - 1, cfg.d_model, cfg.moe_top_k
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
    # where the reference's u is compared: the last K - 1 positions of every
    # block of A's prompt, and of every slot's sequence
    block_ends = jnp.arange(shared)[:, None] * bs + bs - jnp.arange(K1, 0, -1)
    last_at = (lengths + steps)[:, None] - jnp.arange(K1, 0, -1)    # [B, K1]
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
            slots, B, closes=live & (starts + lens == ends),
            conv_kernel=cfg.conv_kernel)
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
    prefill = jax.jit(prefill, static_argnums=(0,), donate_argnums=(3, 4))
    decode = jax.jit(decode, static_argnums=(0,), donate_argnums=(3, 4))
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
        return lfm2.embed(cfg, embed, toks.reshape(R, P))

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
    xd = [lfm2.embed(cfg, embed, jnp.concatenate([
        tokens[prompt + t][None], jnp.take_along_axis(
            short_tokens, decoded_at[:, t:t + 1], axis=1)[:, 0]])[:, None])
        for t in range(steps)]
    del embed
    routing, tails = [], []
    for i, kind in enumerate(cfg.layer_kinds):
        p = make["layer"](i)
        follow = follow_short = fed = tails_here = None
        if control:
            with jax.default_matmul_precision("highest"):
                x_ctl, follow, u, _ = rounded[kind][0](p, x_ctl, None)
                xs_ctl, follow_short, us, _ = rounded[kind][1](
                    p, xs_ctl, None)
            if kind[0] == "conv":
                tails_here = jnp.concatenate([
                    u[block_ends], u[last_at[:1]], jnp.take_along_axis(
                        us, last_at[1:, :, None], axis=1)])
        else:
            if kind[0] == "conv":
                a = jnp.zeros((NB, K1 * D), cfg.dtype)
                b = jnp.zeros((B, K1, D), cfg.dtype)
            else:
                a = jnp.zeros(decoding.unrolled_pool_shape(cfg, NB - 1, bs),
                              cfg.dtype)
                b = jnp.zeros_like(a)
            seen = []               # (m, picks) of A's rows, in order
            for c, n in enumerate(per_call):
                xs[c], a, b, mp = prefill(kind, p, xs[c], a, b, *calls_a[c])
                seen.extend(v[:n * P] for v in mp)
            x_short, a, b, mp = prefill(kind, p, x_short, a, b, *call_short)
            seen_short = [v[:2 * n_short * P].reshape(n_short, 2 * P, -1)
                          for v in mp]
            if kind[0] == "conv":
                block_tails = a[1:1 + shared].reshape(shared, K1, D)
            for t in range(steps):
                xd[t], a, b, mp = decode(kind, p, xd[t], a, b, lengths + t)
                seen.extend(v[:1] for v in mp)
                seen_short.extend(v[1:, None] for v in mp)
            if kind[0] == "conv":
                tails_here = jnp.concatenate([block_tails, b])
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
            xb, a, b, _ = prefill(kind, p, xb, a, b, *call_b)
            del a, b
        with jax.default_matmul_precision("highest"):
            x_ref, own_picks, u, shortfall = plain[kind][0](p, x_ref, follow)
            xs_ref, _, us, _ = plain[kind][1](p, xs_ref, follow_short)
            if follow is not None:
                routing.append((follow, own_picks, None if fed is None
                                else own_input(p, fed), shortfall))
        if tails_here is not None:
            tails.append((tails_here, jnp.concatenate([
                u[block_ends], u[last_at[:1]], jnp.take_along_axis(
                    us, last_at[1:, :, None], axis=1)])))
        del p

    def decoded(x, xs):
        """[steps, slots, D] of the reference's rows."""
        return jnp.concatenate([x[prompt:, None], jnp.swapaxes(
            jnp.take_along_axis(xs, decoded_at[:, :, None], axis=1), 0, 1)],
            axis=1)

    # weights are ARGUMENTS of every jitted function here: one closed over
    # would be compiled in as a constant, on the host
    head = dict(make["head"](), tok_embed=make["embed"]())
    with jax.default_matmul_precision("highest"):
        ref_head = jax.jit(lambda head, x: reference_head(hp, head, x))
        want = (ref_head(head, x_ref[prompt - cmp_n:prompt]),
                ref_head(head, decoded(x_ref, xs_ref)))
        if control:
            got = (ref_head(head, x_ctl[prompt - cmp_n:prompt]),
                   ref_head(head, decoded(x_ctl, xs_ctl)))
    after_hit = None
    if not control:
        logits = jax.jit(lambda head, x: lfm2.logits(cfg, head, x))
        last = jnp.concatenate(
            [x[:n].reshape(n * P, -1) for x, n in zip(xs, per_call)]
        )[-cmp_n:]
        got = (logits(head, last),
               logits(head, jnp.stack([x[:, 0] for x in xd])))
        after_hit = (logits(head, xb[:Rb].reshape(cmp_n, -1)), got[0])
    return got, want, tails, after_hit, routing


def compare(cfg, seed: int, sizes: Dict[str, int], *, control=False,
            attn_impl: str = "auto") -> Dict[str, float]:
    """The program's logits and conv tails against the reference's, the
    reference following the program's choice of experts, and its own picks
    against the program's.  `control` True: the reference one precision
    down in the program's place; "scores": the PROGRAM with its routing
    scores rounded to bfloat16 (`moe_score_dtype`), everything else as it
    stands."""
    import dataclasses
    import jax.numpy as jnp
    if control == "scores":
        cfg = dataclasses.replace(cfg, moe_score_dtype=jnp.bfloat16)
    got, want, tails, after_hit, routing = logits_both_ways(
        cfg, seed, sizes, parity_tokens(cfg, seed, sizes),
        control=control is True, attn_impl=attn_impl)
    hits = jnp.stack([picks_agree(g, w) for g, w, _, _ in routing])
    out = {"route_mismatch_share": float(1.0 - jnp.mean(
        hits.astype(jnp.float32))),
        "rows_routed_alike_share": float(jnp.mean(
            hits.all(axis=(0, 2)).astype(jnp.float32))),
        "route_shortfall_max": max(float(jnp.max(r[3])) for r in routing),
        "rows_not_followed_share": float(jnp.mean(jnp.stack(
            [r[3] for r in routing]) > FOLLOW_MARGIN)),
        "conv_tail_err": max(rel_rms(g, w) for g, w in tails),
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
    through the K/V pools and the conv layers' tails, of a long request and
    a short one in every other slot, against the reference's full forward
    pass; the tails themselves; a request answered after a prefix hit
    against itself answered cold; the routing on the reference's path and
    on the program's own.  At the engine's own widths, tile and table
    size, weights made again from the seed one layer at a time (two copies
    of them do not fit).  These are the functions the engine's dispatches
    are made of, driven by this check and not by the engine: admission,
    the radix hit and `_fused_dispatch`'s packing are covered by the CPU
    tests alone (tests/test_lfm2.py; PERF.md section 7)."""
    if where != "serve":
        raise ValueError("kind lfm2-moe is compared in serving cells only")
    sizes = parity_sizes(caches)
    t0 = time.time()
    out: Dict[str, Any] = dict(compare(cfg, seed, sizes))
    out["parity_s"] = time.time() - t0
    out["parity_positions"] = sizes["prompt"] + sizes["steps"] + sum(
        n + sizes["steps"] for n in short_lengths(sizes))
    return out
