"""Model kind `sink-window-moe` (Xiaomi's MiMo-V2 family, `model_type`
mimo_v2_flash): sliding-window layers of 128 positions with a learned sink in
their softmax on 8 kv heads and full attention layers on 4 kv heads, five to
one, keys of 192 beside values of 128, a rotary on 64 of 192 dims with a base
a layer kind; a dense feed-forward in layer 0, then 256 sigmoid-routed experts
of width 2048 (top-8, no shared one), of which THIS CHIP HOLDS A SHARE.  The
same interface as the other kinds, found by the configuration's `"kind"`;
serving only (the program has no training path for it, so CHECKS has no
"train").

THE PLAIN REFERENCE is here (`reference_*`): the forward pass in float32 at
`jax.default_matmul_precision("highest")`, no cache, no kernel, no ring:
masked attention blocked over query rows so that 16 k positions fit, every
key against every query under the mask, the experts a loop over the held ones
(weighted by whether the token chose them).  With
N(x) = x rsqrt(mean(x^2) + 1e-5) w (a plain weight), x the residual stream
and a = N_in(x):

    x0      = Embed[token]
    H = 64 query heads, dk = 192, dv = 128; Hkv = 4 (full:
    num_key_value_heads) | 8 (sliding: swa_num_key_value_heads)
              q = Wq a (4096 -> 64 x 192),  k = Wk a (4096 -> Hkv x 192),
              v = 0.707 Wv a (4096 -> Hkv x 128)               no bias
              rotary on dims 0..63 of each q and k head (int(0.334 x 192)),
              rotate-half pairing (i, i + 32), theta 5e6 on full layers
              (rope_theta), 1e4 on sliding ones (swa_rope_theta); dims
              64..191 untouched
              s_ij = q_i . k_j 192^-1/2; query head h reads kv head
              h // (64 / Hkv)          (16 a kv head full, 8 sliding)
    full (hybrid_layer_pattern[i] == 0):     j <= i
              p_ij = exp(s_ij - m) / sum_j exp(s_ij - m)
    sliding (hybrid_layer_pattern[i] == 1):  j <= i and i - j < 128
              p_ij = exp(s_ij - m) / (exp(b_h - m) + sum_j exp(s_ij - m)),
              m = max(b_h, max_j s_ij); b_h a learned scalar a query head
              (add_swa_attention_sink_bias; full layers have none): the sink
              takes probability and adds no value
              y = Wo [sum_j p_ij v_j]_h                 (8192 -> 4096)
    x = x + y;  m = N_post(x)
    dense (moe_layer_freq[i] == 0: layer 0):
              f = Wd (silu(Wg m) * Wu m)                (4096 -> 16384 -> 4096)
    experts:  c = sigmoid(Wr m) in float32 (256); S = top-8 of c + bias (the
              bias selects only; noaux_tc with n_group = topk_group = 1 is
              plain top-k); w_e = c_e / (sum_S c + 1e-20) (norm_topk_prob;
              routed_scaling_factor null -> 1)
              f = sum_{e in S, e held here} w_e Wd,e (silu(Wg,e m) * Wu,e m)
              n_shared_experts null: no shared expert
    x = x + f
    logits  = lm_head^T N_final(x_L)                    (untied)

THE SHARE.  The sixteen chips of four hosts share each layer, expert-parallel,
the mixers data-parallel: the router keeps its 256 outputs and its top-8,
normalised over all eight; only the `n_routed_experts` (16) experts held here,
experts `experts_held_first` .. + 15, are summed, in the reference as in the
program, and that partial result goes on to the next layer (model-configs
guide, section 4).  tests/test_mimo_v2.py adds all sixteen shares up to the
uncut layer.

DEPARTURE RISKS.  What config.json does not settle (each listed in the
configuration file under `assumed`; no network here to re-read the modelling
code):
  (a) no q / k norm and no output gate: config has no key for either, and the
      parameter count agrees with the published 309 B without them (308.78);
  (b) `attention_value_scale` 0.707 multiplies the VALUES (linear: the result
      is the same wherever it is applied);
  (c) the sink is one more softmax column whose mass is dropped (a learned
      scalar a query head, sliding layers only);
  (d) the window is i - j < 128 (the query and the 127 positions before it),
      not <=;
  (e) the rotated dims are the FIRST int(0.334 x 192) = 64 of a head,
      rotate-half; the rest pass;
  (f) the selection bias does not enter the weights; the picks' scores are
      renormalised over their sum + 1e-20; no scaling factor;
  (g) float32 scores, softmax and routing; weights and activations bfloat16;
  (h) `attention_chunk_size` 128 is copied and unused;
  (i) the three multi-token-prediction layers `described_as` names are not
      built (no key for them; the engine yields one token a sequence a step);
  (j) weights from the seed (sinks N(0, 1) so that a program without the
      column, or with it on full layers, cannot agree; selection bias 0.02 N,
      small beside the scores' spread as models/afmoe.py has it, where the
      issue said 0.1: a random bias of the scores' size sends most tokens to
      a few experts, which no trained bias does; norm weights 1 + 0.1 N).
"""

from __future__ import annotations

import functools
import math
import time
from typing import Any, Callable, Dict, List

# How each limit was set: PERF.md section 2, "Limits of `correct`".  Readings:
# my chip runs, PR 54 (chiprun_out/pr54/parity54.jsonl, parity54b.jsonl and the
# cell's own lines), one process at the cell's widths and sizes beside 8.7 GiB
# held as the engine holds it, 16,384 positions of request A and 63 short
# requests beside it: the sound program (P; through prefill-only calls up to
# call c5, through the fused pass since: the same readings), the fp8 control
# (F: q, k, v, the projections' outputs and the feed-forward weights rounded
# to e4m3 in the reference, which then stands in the program's place) and the
# reference WITHOUT THE SINK (N) in the program's place.  Each limit that
# refuses a control stands near the geometric mean of its two readings; P's
# readings spread by 1-6 % over seeds.
TOLERANCES: Dict[str, float] = {
    # relative RMS error of the logits (bf16 activations against float32)
    # over A's last 256 prompt positions: P 0.00623-0.00628, F 0.01010 (1.6 x:
    # the geometric mean, 1.27 x from both).  N reads 0.0026, UNDER P: at
    # 16 k positions every window is full and the sink holds a small share of
    # a softmax over 128 scores; what refuses N is the next two
    "logits_prefill_err": 0.0079,
    # ... over the 8 decoded positions of all 64 slots (63 of them 17-39
    # positions long: the sink holds a large share of their softmax):
    # P 0.00662-0.00669 (its worst slot 0.0070), F 0.0234, N 0.106
    "logits_decode_err": 0.0125,
    # relative RMS error of every sliding layer's ring (keys and values, by
    # position, of the last 128 positions) at A's checkpoint and in every
    # slot after the decode steps, largest layer, against the reference's k
    # and v at those positions: P 0.00672-0.00678, F 0.0355, N 0.152 (the
    # residual stream the sink moved)
    "ring_err": 0.0155,
    # the logits of a request's last rows answered after a hit restored from
    # a checkpoint against the same rows answered cold, in the same compiled
    # call: a checkpoint is a copy, so P 0.0 on every seed; a restore from
    # the checkpoint one block earlier reads > 0.1 at the toy's size
    "logits_after_hit_err": 1e-3,
    # share of A's (row, expert) picks, over all 256 scores, that differ from
    # the reference's own on the reference's own path (786,816 picks: 16,392
    # positions x 8 x 6 layers): P 0.00561-0.00597, F 0.00849 (1.45 x)
    "route_mismatch_share": 0.0072,
    # ... on the PROGRAM's own input to each router (what the tap shows),
    # scored by the reference in float32: P 0.0 on every seed (not one of
    # 786,816 picks)
    "route_own_input_mismatch_share": 1e-3,
    # ... and the largest difference between the weight the program's router
    # gives a pick and the reference's, on that input, over the rows whose
    # picks agree (float32 at full precision on both sides): P 0.0 on every
    # seed (6e-8 on the CPU at the toy's size); the reference with the
    # selection bias in its weights (`wrong` "bias_in_weights", which moves
    # the logits of a 1/16 share by too little for their limits: 0.0023 and
    # 0.0020, UNDER P's) 0.0102 at the cell's size (call c6), 0.017 at the
    # toy's: the limit lies two decades from both
    "route_own_input_weight_err": 1e-4,
    # the paged kernel alone over the live pool of the first full layer, 64
    # query and 4 kv heads, keys of 192 in 256 lanes, values of 128, against
    # this file's own plain gather: P 0.0023-0.0027; lib/reference.py's limit
    "paged_err": 2e-2,
}

CHECKS: Dict[str, tuple] = {
    "serve": tuple(TOLERANCES),
}

NOT_COMPARED = float("nan")
# How far below the reference's own k-th sigmoid SCORE (bias included) an
# expert of the program's choice may lie and still be followed
# (`reference_route`; kinds/lfm2-moe.py has the reasons for following at all).
# The program's choices lie at most 0.0030-0.0039 below (my chip runs, PR 54,
# `route_shortfall_max` on three seeds; the fp8 control's 0.0065): every row
# is followed, and a choice further off than five times that shows in the
# logits (the reference without the sink chooses up to 0.149 below).
FOLLOW_MARGIN = 0.02
# the control names `compare` takes beside the reference's faults
CONTROLS = ("fp8", "no_sink")


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------
def layer_kinds(cfg: Dict[str, Any]) -> List[List[str]]:
    """(mixer, feed-forward) per layer: `hybrid_layer_pattern` 0 full, 1
    sliding ("ring": its window is kept as a ring a sequence);
    `moe_layer_freq` 0 dense, 1 experts."""
    n = cfg["num_hidden_layers"]
    return [["ring" if s else "full", "experts" if e else "dense"]
            for s, e in zip(cfg["hybrid_layer_pattern"][:n],
                            cfg["moe_layer_freq"][:n])]


def router_width(cfg: Dict[str, Any]) -> int:
    return cfg.get("router_width", cfg["n_routed_experts"])


def rotary_dim(cfg: Dict[str, Any]) -> int:
    return int(cfg["head_dim"] * cfg["partial_rotary_factor"])


def check(cfg: Dict[str, Any]) -> None:
    import importlib.util
    # in the driver, before a worker starts: a program without the
    # architecture (the parent of the PR that brought it) fails here, at once
    if importlib.util.find_spec("ray_tpu.models.mimo_v2") is None:
        raise ValueError("the program under test has no arch mimo_v2 "
                         "(ray_tpu/models/mimo_v2.py)")
    n = cfg["num_hidden_layers"]
    if len(cfg["hybrid_layer_pattern"]) < n or len(cfg["moe_layer_freq"]) < n:
        raise ValueError("the pattern lists are shorter than the layers")
    if cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"]:
        raise ValueError("only renormalised sigmoid routing is expressed")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("a group-limited top-k is not expressed")
    if cfg["n_shared_experts"] or cfg["routed_scaling_factor"]:
        raise ValueError("a shared expert / a scaling factor are not "
                         "expressed")
    if cfg["attention_bias"] or cfg["hidden_act"] != "silu":
        raise ValueError("attention biases / another activation are not "
                         "expressed")
    if not cfg["add_swa_attention_sink_bias"] \
            or cfg["add_full_attention_sink_bias"]:
        raise ValueError("the sink is expressed on the sliding layers only")
    if (cfg["swa_num_attention_heads"], cfg["swa_head_dim"],
            cfg["swa_v_head_dim"]) != (cfg["num_attention_heads"],
                                       cfg["head_dim"], cfg["v_head_dim"]):
        raise ValueError("sliding layers differ from full ones in their kv "
                         "heads only")
    if cfg["sliding_window"] != cfg["sliding_window_size"]:
        raise ValueError("two windows")
    for hkv in (cfg["num_key_value_heads"], cfg["swa_num_key_value_heads"]):
        if cfg["num_attention_heads"] % hkv:
            raise ValueError("query heads are not a multiple of KV heads")
    if rotary_dim(cfg) % 2:
        raise ValueError("the rotary dims are not pairs")
    first = cfg.get("experts_held_first", 0)
    if first < 0 or first + cfg["n_routed_experts"] > router_width(cfg):
        raise ValueError("the experts held are not among the router's")
    sv = cfg.get("serve") or {}
    if "num_states" in sv and sv["num_states"] < sv["num_slots"]:
        raise ValueError("serve.num_states is fewer than the slots")
    if sv and cfg["sliding_window"] % sv["kv_block_size"]:
        raise ValueError("the window is not whole blocks (a prefill row's "
                         "positions must be slots of the ring in a row)")


def transformer_kwargs(cfg: Dict[str, Any], *, max_seq: int,
                       param_dtype: str, **extra: Any) -> Dict[str, Any]:
    check(cfg)
    # `num_states` reaches the engine through the program's own registry of
    # settings, as kinds/gated-delta-moe.py hands it on (lib/serve_cell.py
    # forwards a fixed list of serve keys)
    states = (cfg.get("serve") or {}).get("num_states")
    if states:
        from ray_tpu._private.config import config
        config.set("kv_num_states", int(states))
    kw = {
        "vocab_size": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "sliding_kv_heads": cfg["swa_num_key_value_heads"],
        "d_head": cfg["head_dim"],
        "v_head_dim": cfg["v_head_dim"],
        "d_ff": cfg["intermediate_size"],
        "max_seq": max_seq,
        "arch": "mimo_v2",
        "rope_theta": float(cfg["rope_theta"]),
        "sliding_rope_theta": float(cfg["swa_rope_theta"]),
        "rotary_dim": rotary_dim(cfg),
        "sliding_window": cfg["sliding_window"],
        "attn_value_scale": float(cfg["attention_value_scale"]),
        "norm_eps": float(cfg["layernorm_epsilon"]),
        "tie_embeddings": bool(cfg["tie_word_embeddings"]),
        "dtype": cfg.get("torch_dtype", "bfloat16"),
        "param_dtype": param_dtype,
        "layer_kinds": layer_kinds(cfg),
        "moe_experts": cfg["n_routed_experts"],
        "moe_router_width": router_width(cfg),
        "moe_experts_first": cfg.get("experts_held_first", 0),
        "moe_top_k": cfg["num_experts_per_tok"],
        "moe_d_ff": cfg["moe_intermediate_size"],
        "moe_route_eps": 1e-20,
        "remat": False,
    }
    kw.update(extra)
    return kw


def param_counts(cfg: Dict[str, Any]) -> Dict[str, int]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dk, dv = cfg["head_dim"], cfg["v_head_dim"]

    def mixer(hkv):                                 # q, k, v, o
        return d * h * dk + d * hkv * dk + d * hkv * dv + h * dv * d

    full = mixer(cfg["num_key_value_heads"])
    sliding = mixer(cfg["swa_num_key_value_heads"]) + h         # the sinks
    dense = 3 * d * cfg["intermediate_size"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    expert_ffn = (d * router_width(cfg) + router_width(cfg)     # router, bias
                  + cfg["n_routed_experts"] * expert)
    norms = 2 * d
    embed = cfg["vocab_size"] * d
    total = embed + d + (0 if cfg["tie_word_embeddings"] else embed)
    for m, f in layer_kinds(cfg):
        total += ((sliding if m == "ring" else full)
                  + (dense if f == "dense" else expert_ffn) + norms)
    return {"total": total, "input_embedding": embed, "attention": full,
            "sliding": sliding, "dense_ffn": dense, "expert": expert,
            "expert_ffn": expert_ffn}


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """Keys and values of the FULL layers alone, the MODEL's widths: what a
    position costs (a sliding layer holds a window, not the context)."""
    full = sum(1 for m, _ in layer_kinds(cfg) if m == "full")
    return cfg["num_key_value_heads"] * (cfg["head_dim"]
                                         + cfg["v_head_dim"]) * 2 * full


def state_bytes_per_sequence(cfg: Dict[str, Any]) -> int:
    """What a sequence holds in the sliding layers whatever its length: the
    window's keys and values, the MODEL's widths."""
    sliding = sum(1 for m, _ in layer_kinds(cfg) if m == "ring")
    return sliding * cfg["sliding_window"] * cfg["swa_num_key_value_heads"] \
        * (cfg["swa_head_dim"] + cfg["swa_v_head_dim"]) * 2


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    raise NotImplementedError("kind sink-window-moe has no training path")


# ---------------------------------------------------------------------------
# cost functions of the kernels this kind's cell reads: fn(config, shapes)
# ---------------------------------------------------------------------------
def _position_bytes(cfg) -> int:
    """A sliding layer's keys and values of one position (the MODEL's)."""
    return cfg["swa_num_key_value_heads"] * (cfg["swa_head_dim"]
                                             + cfg["swa_v_head_dim"]) * 2


def window_ring_step(cfg, s):
    """One call = one sliding layer, one decode step, `slots` sequences: the
    window read (the MODEL's 128 x 8 x (192 + 128) x 2 = 655,360 B a
    sequence, the same count whatever implements it) and one position
    written, q in and o out; q . k and p . v over the window for 64 heads.
    Nothing here depends on the context."""
    h, W = cfg["num_attention_heads"], cfg["sliding_window"]
    dk, dv = cfg["swa_head_dim"], cfg["swa_v_head_dim"]
    flops = 2.0 * s["slots"] * h * W * (dk + dv)
    bytes_ = s["slots"] * ((W + 1.0) * _position_bytes(cfg)
                           + 2 * h * (dk + dv))
    return flops, bytes_


# Per fused dispatch of the cell the kernel is measured in
# (serve-mimo-agent-sessions, traffic/agent-sessions.json at 64 slots):
# requests admitted and the rows of 16 positions each brings (a ~33-token
# suffix is two rows, three where the block-rounding remainder spills).
# ASSUMED from the traffic's means, a floor, as kinds/gated-delta-moe.py does
# (24.6 requests a fused dispatch at 64 slots: PERF.md section 5, PR 44); the
# harness hands a cost function the configuration and `slots` /
# `live_context` only.
RING_REQUESTS_PER_CALL = 24.0
RING_ROWS_PER_REQUEST = 2.0
RING_ROW = 16


def window_ring_chunk(cfg, s):
    """One call = one sliding layer, one fused dispatch's prompt rows.  A
    request restores its ring once (a read), leaves it in its slot and in a
    checkpoint (two writes); a row of C positions scores its 64 heads against
    the window and itself."""
    h, W = cfg["num_attention_heads"], cfg["sliding_window"]
    dk, dv = cfg["swa_head_dim"], cfg["swa_v_head_dim"]
    C = RING_ROW
    rows = RING_REQUESTS_PER_CALL * RING_ROWS_PER_REQUEST
    flops = 2.0 * rows * C * h * (W + C) * (dk + dv)
    bytes_ = (RING_REQUESTS_PER_CALL * 3.0 * W * _position_bytes(cfg)
              + rows * C * (_position_bytes(cfg) + 2 * h * (dk + dv)))
    return flops, bytes_


def experts_touched_even(cfg: Dict[str, Any], rows: float) -> float:
    """Held experts with at least one of `rows` tokens' picks, under even
    routing over the router's whole width: E_held (1 - (1 - 1 / width) ^
    (k rows))."""
    k, width = cfg["num_experts_per_tok"], router_width(cfg)
    return cfg["n_routed_experts"] * (1.0 - (1.0 - 1.0 / width) ** (k * rows))


def moe_experts_decode(cfg, s):
    """One call = one expert layer, one decode step, over the HELD experts:
    the rows routed here (slots x k x held / width) through three products
    of hidden x expert width; bytes = the distinct held experts read x
    50,331,648 B + the rows in and out.  A FLOOR as in kinds/mla-moe.py: the
    expectation under even routing with HALF the slots live, so that it
    cannot read over 100 %."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = (s["slots"] * cfg["num_experts_per_tok"]
            * cfg["n_routed_experts"] / router_width(cfg))
    flops = 2.0 * rows * 3 * d * fe
    bytes_ = 2.0 * (experts_touched_even(cfg, s["slots"] / 2) * 3 * d * fe
                    + 2 * rows * d)
    return flops, bytes_


COST_FNS: Dict[str, Callable] = {
    "window_ring_step": window_ring_step,
    "window_ring_chunk": window_ring_chunk,
    "moe_experts_decode": moe_experts_decode,
}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def hyper(cfg) -> Dict[str, Any]:
    """The numbers the reference needs, from the program's
    TransformerConfig (the harness hands parity() nothing else)."""
    return {"heads": cfg.n_heads, "kv_full": cfg.kv_heads,
            "kv_sliding": cfg.sliding_kv_heads or cfg.kv_heads,
            "dk": cfg.head_dim, "dv": cfg.v_head_dim or cfg.head_dim,
            "hidden": cfg.d_model, "eps": cfg.norm_eps,
            "theta_full": cfg.rope_theta,
            "theta_sliding": cfg.sliding_rope_theta or cfg.rope_theta,
            "rotary": cfg.rotary_dim or cfg.head_dim,
            "window": cfg.sliding_window, "value_scale": cfg.attn_value_scale,
            "top_k": cfg.moe_top_k, "route_eps": cfg.moe_route_eps,
            "held_first": cfg.moe_experts_first, "held": cfg.moe_experts,
            "kinds": tuple(cfg.layer_kinds)}


def _f32(x):
    import jax.numpy as jnp
    return x.astype(jnp.float32)


def _fp8(x):
    import jax.numpy as jnp
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _same(x):
    return x


def _norm(x, w, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def _rotary(x, positions, theta, dims: int):
    """x [S, heads, D]: rotate-half inside the first `dims` dims (pairs
    (i, i + dims / 2)), absolute positions; the other dims pass."""
    import jax.numpy as jnp
    half = dims // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:dims]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., dims:]], axis=-1)


def _swiglu(m, w_gate, w_up, w_down):
    import jax
    return (jax.nn.silu(m @ _f32(w_gate)) * (m @ _f32(w_up))) @ _f32(w_down)


def _window_before(x, end, W: int):
    """x [S, ...] -> the W positions before `end` [W, ...], zeros where a
    position is before the sequence's start."""
    import jax
    import jax.numpy as jnp
    padded = jnp.concatenate([jnp.zeros((W,) + x.shape[1:], x.dtype), x])
    return jax.lax.dynamic_slice_in_dim(padded, end, W)


def reference_attention(hp, kind, p, a, length=None, split: int = 0,
                        block: int = 256, control: str = "",
                        wrong: str = ""):
    """a [S, hidden] float32 -> (W_o attention [S, hidden], a sliding layer's
    {"k", "v": its keys (rotated) and values (scaled) at the W positions
    before `length` (None: the end), "k_split", "v_split": before `split` (0:
    not asked)} or None).  Query rows in blocks, each against EVERY key under
    the mask; the query heads of a kv head score against it as it stands (no
    repeated copy of K and V).  Faults (`wrong`): "no_sink", "sink_on_full"
    (the sinks of the next sliding layer's shape: ones), "window_le" (i - j
    <= W), "rope_on_all" (every dim rotated), "one_theta" (the full layers'
    base on sliding layers), "kv_heads_as_full" (a sliding layer's query head
    h reads kv head h // 16 of its first four), "no_value_scale"."""
    import jax
    import jax.numpy as jnp
    S = a.shape[0]
    sliding = kind[0] == "ring"
    H, dk, dv, W = hp["heads"], hp["dk"], hp["dv"], hp["window"]
    Hkv = hp["kv_sliding"] if sliding else hp["kv_full"]
    rnd = _fp8 if control == "fp8" else _same
    pos = jnp.arange(S)
    q = rnd(jnp.einsum("sd,dhk->shk", a, _f32(p["wq"])))
    k = rnd(jnp.einsum("sd,dhk->shk", a, _f32(p["wk"])))
    v = rnd(jnp.einsum("sd,dhk->shk", a, _f32(p["wv"])))
    if wrong != "no_value_scale":
        v = v * hp["value_scale"]
    theta = hp["theta_sliding"] if sliding and wrong != "one_theta" \
        else hp["theta_full"]
    dims = dk if wrong == "rope_on_all" else hp["rotary"]
    q, k, v = (rnd(_rotary(q, pos, theta, dims)),
               rnd(_rotary(k, pos, theta, dims)), rnd(v))
    aux = None
    if sliding:
        end = S if length is None else length
        aux = {"k": _window_before(k, end, W), "v": _window_before(v, end, W)}
        if split:
            aux.update(k_split=_window_before(k, split, W),
                       v_split=_window_before(v, split, W))
    if sliding and wrong == "kv_heads_as_full":
        Hkv = hp["kv_full"]
        k, v = k[:, :Hkv], v[:, :Hkv]
    G = H // Hkv
    sink = None
    if sliding and wrong != "no_sink":
        sink = _f32(p["sink"]).reshape(Hkv, G, 1, 1)
    if not sliding and wrong == "sink_on_full":
        sink = jnp.ones((Hkv, G, 1, 1), jnp.float32)
    q = q.reshape(S, Hkv, G, dk)
    # blocks of query rows (a [Hkv, G, block, S] score: narrower blocks
    # where the sequence is long)
    block = max(16, min(block, (1 << 20) // max(S, 1)))
    n_blocks = -(-S // block)
    pad = n_blocks * block - S
    q_blocks = jnp.pad(q, ((0, pad),) + ((0, 0),) * 3).reshape(
        n_blocks, block, Hkv, G, dk)
    pos_blocks = jnp.pad(pos, (0, pad)).reshape(n_blocks, block)
    reach = W + 1 if wrong == "window_le" else W

    def one_block(args):
        qb, qi = args
        s = jnp.einsum("qhgd,khd->hgqk", qb, k) / math.sqrt(dk)
        seen = pos[None, :] <= qi[:, None]
        if sliding:
            seen &= qi[:, None] - pos[None, :] < reach
        s = jnp.where(seen[None, None], s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        extra = 0.0
        if sink is not None:
            m = jnp.maximum(m, sink)
            extra = jnp.exp(sink - m)
        e = jnp.exp(s - m)
        w = e / (extra + jnp.sum(e, axis=-1, keepdims=True))
        return jnp.einsum("hgqk,khd->qhgd", w, v)

    o = jax.lax.map(one_block, (q_blocks, pos_blocks)).reshape(
        n_blocks * block, H, dv)[:S]
    return jnp.einsum("shk,hkd->sd", o, _f32(p["wo"])), aux


def reference_route(hp, p, m, follow=None, wrong: str = ""):
    """m [S, hidden] float32 -> (picks [S, k] over the router's whole width,
    the weights [S, k] of the experts used, the experts used, shortfall
    [S]).  Each logit's sigmoid, top-k by score + bias, the weights the
    picks' scores (no bias) over their sum + eps.  `follow` [S, k] (-1:
    nothing to follow in this row): the experts used are these and not the
    picks (weighed by this function's own scores), in every row where each of
    them scores (bias included) within FOLLOW_MARGIN of this function's own
    k-th (kinds/lfm2-moe.py has the reasons).  Faults: "softmax_routing"
    (the softmax over the width in the sigmoids' place), "no_renorm" (the
    picks' scores as they stand), "bias_in_weights" (the weights from score
    + bias)."""
    import jax
    import jax.numpy as jnp
    z = m @ _f32(p["w_router"])
    s = jax.nn.softmax(z, axis=-1) if wrong == "softmax_routing" \
        else jax.nn.sigmoid(z)
    biased = s + _f32(p["route_bias"])
    _, picks = jax.lax.top_k(biased, hp["top_k"])
    used, shortfall = picks, jnp.zeros(m.shape[:1], jnp.float32)
    if follow is not None:
        kth = jnp.take_along_axis(biased, picks[:, -1:], axis=1)
        theirs = jnp.take_along_axis(biased, jnp.maximum(follow, 0), axis=1)
        shortfall = jnp.where((follow >= 0).all(axis=1),
                              jnp.max(kth - theirs, axis=1), jnp.inf)
        used = jnp.where((shortfall <= FOLLOW_MARGIN)[:, None], follow,
                         picks)
        shortfall = jnp.where(jnp.isinf(shortfall), 0.0,
                              jnp.maximum(shortfall, 0.0))
    chosen = jnp.take_along_axis(
        biased if wrong == "bias_in_weights" else s, used, axis=1)
    if wrong != "no_renorm":
        chosen = chosen / (jnp.sum(chosen, axis=1, keepdims=True)
                           + hp["route_eps"])
    return picks, chosen, used, shortfall


def reference_ffn(hp, kind, p, x, follow=None, control: str = "",
                  wrong: str = "", held=None):
    """The second half of a layer: x [S, hidden] -> (x + f, picks [S, k] (a
    dense layer: none, -1), shortfall [S]); f the dense feed-forward, or the
    HELD experts' part (`held` = (first, count); None: the program's own
    share).  A loop over the held experts, each over every row, weighted by
    whether the row used it.  Fault "shared_expert": the first held expert
    added for every row at weight 1."""
    import jax
    import jax.numpy as jnp
    S = x.shape[0]
    m = _norm(x, p["ffn_norm"], hp["eps"])
    rnd = _fp8 if control == "fp8" else _f32
    if kind[1] == "dense":
        f = _swiglu(m, rnd(p["w_gate"]), rnd(p["w_up"]), rnd(p["w_down"]))
        return (x + f, jnp.full((S, hp["top_k"]), -1, jnp.int32),
                jnp.zeros((S,), jnp.float32))
    picks, weights, used, shortfall = reference_route(hp, p, m, follow,
                                                      wrong)
    first, E = (hp["held_first"], hp["held"]) if held is None else held
    here = (used >= first) & (used < first + E)
    # each token's weight for each HELD expert (0 where it did not use it);
    # a pick that lies on another chip adds nothing here
    dense_w = jnp.zeros((S, E + 1), jnp.float32).at[
        jnp.arange(S)[:, None], jnp.where(here, used - first, E)
    ].add(weights)[:, :E]
    if wrong == "shared_expert":
        dense_w = dense_w.at[:, 0].add(1.0)

    def one_expert(y, e):
        out = _swiglu(m, rnd(p["w_gate"][e]), rnd(p["w_up"][e]),
                      rnd(p["w_down"][e]))
        return y + dense_w[:, e][:, None] * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(m), jnp.arange(E))
    return x + y, picks, shortfall


def reference_mixer(hp, kind, p, x, length=None, split: int = 0,
                    block: int = 256, control: str = "", wrong: str = ""):
    """The first half of a layer: x [S, hidden] -> (x + attention(N_in(x)),
    a sliding layer's windows (`reference_attention`) or None)."""
    y, aux = reference_attention(hp, kind, p,
                                 _norm(x, p["attn_norm"], hp["eps"]), length,
                                 split, block, control, wrong)
    return x + y, aux


def reference_layer(hp, kind, p, x, length=None, follow=None, split: int = 0,
                    block: int = 256, control: str = "", wrong: str = ""):
    """x [S, hidden] float32 (positions 0..S-1) -> (x' [S, hidden], a sliding
    layer's windows or None, picks [S, k], shortfall [S]): the two halves
    above, which the comparison at 16 k positions runs as two programs (the
    chip's memory beside a live engine).  `follow`: the experts to use in the
    picks' place (`reference_route`).  `control` "fp8": the projections'
    outputs, q, k, v and the feed-forward weights rounded to fp8 (e4m3), the
    precision below the configuration's bfloat16: what `correct` must refuse.
    `wrong` names one deliberate fault, for the tests that show the limits
    refuse it (`reference_attention`, `reference_route`, `reference_ffn`)."""
    x, aux = reference_mixer(hp, kind, p, x, length, split, block, control,
                             wrong)
    x, picks, shortfall = reference_ffn(hp, kind, p, x, follow, control,
                                        wrong)
    return x, aux, picks, shortfall


def reference_embed(hp, table, tokens):
    return _f32(table[tokens])


def reference_head(hp, params, x):
    """x [R, hidden] -> logits [R, V]; `params` holds final_norm and the
    untied lm_head."""
    return _norm(x, params["final_norm"], hp["eps"]) @ _f32(params["lm_head"])


def reference_logits(hp, params, tokens, block: int = 256,
                     control: str = "", wrong: str = ""):
    """The whole model: tokens [S] -> logits [S, V] float32."""
    import jax
    with jax.default_matmul_precision("highest"):
        x = reference_embed(hp, params["tok_embed"], tokens)
        for kind, p in zip(hp["kinds"], params["layers"]):
            x = reference_layer(hp, kind, p, x, None, None, 0, block,
                                control, wrong)[0]
        return reference_head(hp, params, x)


def reference_paged_attention(q, k_pool, v_pool, block_tables, context_lens):
    """One query a sequence over its cached positions, keys and values of
    their own widths: q [B, H, dk]; k_pool [NB, Hkv, bs, >= dk] (zeros past
    dk), v_pool [NB, Hkv, bs, dv] -> [B, H, dv] float32 (zeros where
    context_lens is 0).  A plain gather, as lib/reference.py's (which takes
    one width for both)."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        B, H, dk = q.shape
        hkv, bs = k_pool.shape[1], k_pool.shape[2]
        M = block_tables.shape[1] * bs

        def rows(pool, d):  # [B, W, Hkv, bs, .] -> [B, Hkv, M, d]
            x = _f32(pool[block_tables])[..., :d]
            return jnp.moveaxis(x, 2, 1).reshape(B, hkv, M, d)

        k, v = rows(k_pool, dk), rows(v_pool, v_pool.shape[3])
        qg = _f32(q).reshape(B, hkv, H // hkv, dk)
        s = jnp.einsum("bhgd,bhmd->bhgm", qg, k) / math.sqrt(dk)
        live = (jnp.arange(M)[None, :] < context_lens[:, None])[:, None, None]
        p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1) * live
        return jnp.einsum("bhgm,bhmd->bhgd", p, v).reshape(B, H, -1)


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


def weights_apart(got, got_weights, want, want_weights) -> float:
    """Two routes of the same rows, picks [R, k] and their weights [R, k] ->
    the largest difference between the two weights of one expert, over the
    rows in which both picked the same k experts (in any order)."""
    import jax.numpy as jnp

    def by_expert(picks, weights):
        return jnp.take_along_axis(weights, jnp.argsort(picks, axis=1),
                                   axis=1)

    alike = picks_agree(got, want).all(axis=1, keepdims=True)
    return float(jnp.max(jnp.where(alike, jnp.abs(
        by_expert(got, got_weights) - by_expert(want, want_weights)), 0.0)))


# ---------------------------------------------------------------------------
# parity: the program's own fused-pass and decode layers against the reference
# ---------------------------------------------------------------------------
PARITY_PROMPT = 16_384        # positions of the compared request's prompt
PARITY_DECODE_STEPS = 8
PARITY_COMPARED = 256         # the prompt's last positions whose logits are
#                               compared, and answered again after a hit
#                               restored from the checkpoint taken before them


def parity_sizes(caches) -> Dict[str, int]:
    """From the engine's own shapes: rows of P tokens (the engine's tile, a
    block), `rows` of them a prefill call (the widest compiled program's at
    the cell's size), a prompt of whole blocks, decode steps."""
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
    from ray_tpu.models import mimo_v2 as model
    key = jax.random.PRNGKey(seed % (2 ** 31))
    layer_key = jax.random.split(key, 8)[0]

    # one compiled maker a KIND of layer, the layer's index an argument
    def maker(like):
        return jax.jit(lambda k, i: model.init_layer(cfg, k, i, like))

    makers = {kind: maker(cfg.layer_kinds.index(kind))
              for kind in set(cfg.layer_kinds)}
    return {
        "layer": lambda i: makers[cfg.layer_kinds[i]](layer_key, i),
        "embed": lambda: jax.jit(lambda k: model.init_embed(cfg, k))(key),
        "head": lambda: jax.jit(lambda k: model.init_head(cfg, k))(key)}


def short_lengths(sizes: Dict[str, int]) -> List[int]:
    """The prompts of the short requests in slots 1..: a whole row and a
    part of one, P + 1 .. 2 P - 1 tokens, neighbours never the same (their
    rings are not yet full)."""
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


def ring_by_position(ring, end, width: int):
    """A ring [Hkv, W, lanes] as the W positions before `end`, [W, Hkv,
    width]: position p lies in slot p mod W; zeros where p < 0."""
    import jax.numpy as jnp
    W = ring.shape[1]
    pos = end - W + jnp.arange(W)
    rows = jnp.swapaxes(ring[:, pos % W, :width], 0, 1)
    return jnp.where((pos >= 0)[:, None, None], _f32(rows), 0.0)


def logits_both_ways(cfg, seed: int, sizes: Dict[str, int], tokens, *,
                     control: str = "", attn_impl: str = "auto"):
    """The same tokens through the PROGRAM and through the REFERENCE, one
    layer's weights at a time (made once, used by both, dropped).

    The program: its fused pass and decode LAYERS (the functions the
    engine's dispatches are made of: models/decoding.py paged_prefill_layer
    with the slots' decode rows riding it, as `_paged_prefill_core` calls
    it, and paged_decode_layer), over `slots` requests with tables and state
    ids of their own (slot s decodes from id s + 1).  Request A (slot 0)
    brings a prompt of `prompt` positions in passes of `rows` rows of P
    tokens, its full layers' K/V in the pools, its sliding layers' rings
    carried from pass to pass in its id, and a CHECKPOINT taken `compared`
    positions before the prompt's end (a flagged row in the middle of the
    last pass).  Every other slot holds a SHORT request of its own tokens
    and length (a whole row and a part of one: its ring is not yet full),
    all of them rows of ONE pass.  Then `steps` decode steps of all slots
    together (A's ring wraps), the last of them RIDING the pass of request
    B: A's prompt after a hit, A's blocks but the last `compared` positions'
    shared through its table, its rings restored from A's checkpoint, those
    positions prefilled again (in the same compiled program as A's own
    passes, so that what differs is the hit and not a program's rounding).
    With `control` the reference stands in the program's place, one
    precision down ("fp8") or with one of its faults (`reference_layer`'s
    `wrong`, "no_sink" the one the chip is shown).

    The reference FOLLOWS the program's choice of experts, layer by layer,
    where that choice lies within FOLLOW_MARGIN of its own
    (`reference_route`).

    -> (got, want, rings, after_hit, routing): got, want (logits of A's last
    `compared` prompt positions [compared, V], of every slot's decoded
    positions [steps, slots, V]); rings (per sliding layer: the relative RMS
    error of the program's keys and values by position, at A's checkpoint and
    of every slot after the steps, against the reference's, the larger of
    the two); after_hit (B's logits, A's, of the same positions); routing
    (per expert layer, of A's positions: the program's picks, the
    reference's own on its own path, the reference's own on the PROGRAM's
    input to the router, the shortfall of what it followed, and how far the
    program's weights of its picks lie from the reference's on that input,
    `weights_apart`; of a control, its own router's in the program's
    place)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import decoding
    from ray_tpu.models import mimo_v2 as model
    from ray_tpu.ops import window_ring

    hp = hyper(cfg)
    P, R, prompt, cmp_n, steps, bs, B = (sizes[k] for k in (
        "P", "rows", "prompt", "compared", "steps", "block", "slots"))
    W, dk, dv = hp["window"], hp["dk"], hp["dv"]
    top_k = cfg.moe_top_k
    make = _weights(cfg, seed)
    n_short, Ls = B - 1, 2 * P + steps
    assert 2 * n_short <= R, "the short requests' rows do not fit one call"
    short_len = jnp.asarray(short_lengths(sizes), jnp.int32).reshape(n_short)
    short_tokens = tokens[prompt + steps:].reshape(n_short, Ls)
    short_blocks = -(-Ls // bs)
    shared = prompt // bs                   # whole blocks of the prompt
    tail = -(-(steps + 1) // bs)            # blocks A's decode fills
    again = cmp_n // bs                     # blocks request B fills itself
    Wt = shared + tail
    NB = 1 + Wt + n_short * short_blocks + again
    table = jnp.zeros((B, Wt), jnp.int32).at[0].set(1 + jnp.arange(Wt))
    table = table.at[1:, :short_blocks].set(
        1 + Wt + jnp.arange(n_short * short_blocks).reshape(n_short, -1))
    table_b = table[0].at[shared - again:shared].set(
        1 + Wt + n_short * short_blocks + jnp.arange(again))
    lengths = jnp.concatenate([jnp.asarray([prompt], jnp.int32), short_len])
    # state ids: slot s decodes from s + 1; A's checkpoint; request B's own
    slot_ids = 1 + jnp.arange(B, dtype=jnp.int32)
    CKPT, OWN_B, NS = B + 1, B + 2, B + 2
    decoded_at = short_len[:, None] + jnp.arange(steps)      # [n_short, steps]

    def shown_by(seen):
        return lambda name, *arrays: seen.__setitem__(name, arrays)

    def fused(kind, p, x, a, b, tabs, starts, lens, slots, ends, src, dst,
              step_lens, carried):
        """One pass as the engine's fused program makes it
        (decoding.paged_prefill_layer with `step`): x [1, R * P + B, D], R
        rows of several requests and then the next position of every slot.
        Row r holds `lens[r]` tokens (0: no row) from position `starts[r]`
        on of the request in slot `slots[r]` with table `tabs[r]`, whose
        prompt is `ends[r]` long; `src`, `dst`: PrefillRows.state_from /
        state_to.  Slot s, where `carried[s]`, decodes the position after
        `step_lens[s]` in the same pass (elsewhere its row is dead, as in the
        engine's pass when a slot is not active).  -> (x', the layer's state
        pair, the router's input, picks and weights (a dense layer: None))."""
        seen = {}
        live = lens > 0
        rows = decoding.prefill_rows(
            jnp.where(live[:, None], tabs, 0), starts, lens, live, P, bs,
            slots, B, closes=live & (starts + lens == ends),
            states=(src, dst))
        step = decoding.decode_rows(table, step_lens, carried, bs, slot_ids)
        out = decoding.paged_prefill_layer(cfg, kind, p, x, a, b, rows,
                                           attn_impl, tap=shown_by(seen),
                                           step=step)
        return out[:3] + (seen.get("route"),)

    def decode(kind, p, x, a, b, lens):
        seen = {}
        rows = decoding.decode_rows(table, lens, jnp.ones((B,), bool), bs,
                                    slot_ids)
        out = decoding.paged_decode_layer(cfg, kind, p, x, a, b, rows,
                                          attn_impl, tap=shown_by(seen))
        return out[:3] + (seen.get("route"),)

    # one program per layer KIND, not per layer: the kind is the static part
    fused = jax.jit(fused, static_argnums=(0,), donate_argnums=(3, 4))
    decode = jax.jit(decode, static_argnums=(0,), donate_argnums=(3, 4))
    kinds = set(hp["kinds"])
    split = prompt - cmp_n

    def both(**kw):
        """The reference's layer over request A (windows also at the
        checkpoint), and over the short requests side by side, each as
        long as it is."""
        def long(kind):
            mixer = jax.jit(functools.partial(reference_mixer, hp, kind,
                                              split=split, **kw))
            ffn = jax.jit(functools.partial(reference_ffn, hp, kind, **kw))

            def layer(p, x, length, follow):  # two programs: see the docstring
                mid, aux = mixer(p, x, length)
                x, picks, shortfall = ffn(p, mid, follow)
                return x, aux, picks, shortfall, mid
            return layer

        def short(kind):
            fn = jax.jit(jax.vmap(
                functools.partial(reference_layer, hp, kind, **kw),
                in_axes=(None, 0, 0, 0)))
            return lambda p, x, length, follow: fn(p, x, length, follow)

        return {kind: (long(kind), short(kind)) for kind in kinds}

    plain = both()
    rounded = None
    if control:
        rounded = both(**({"control": control} if control == "fp8"
                          else {"wrong": control}))
    own_input = jax.jit(lambda p, m: reference_route(hp, p, _f32(m))[:2])
    fault = {"wrong": control} if control not in ("", "fp8") else {}

    @jax.jit
    def shown_by_control(p, mid):
        """What the program's tap shows, of a control: its router's input,
        picks and weights."""
        m = _norm(mid, p["ffn_norm"], hp["eps"])
        return (m,) + reference_route(hp, p, m, **fault)[:2]

    nothing = jnp.full((prompt + steps, top_k), -1, jnp.int32)
    nothing_short = jnp.full((n_short, Ls, top_k), -1, jnp.int32)

    nobody = (lengths, jnp.zeros((B,), bool))     # no decode step rides

    def pass_of(toks, n_rows, riding=None):
        """[n_rows * P] tokens -> a pass's embedded tokens [1, R * P + B, D]:
        the rows, then `riding` [B] (None: nobody rides, zeros)."""
        toks = jnp.pad(toks, (0, (R - n_rows) * P))
        if riding is None:
            riding = jnp.zeros((B,), toks.dtype)
        return model.embed(cfg, embed, jnp.concatenate([toks, riding]))[None]

    def call(tab, slot, start, n_rows, end, first, own, ckpt_row=-1,
             riding=None):
        """`n_rows` whole rows of one request from `start` on: its first
        row starts from `first` (an id, 0 an empty ring), its last leaves the
        rings in `own`, row `ckpt_row` also in CKPT; `riding` (the slots'
        lengths, which of them): the decode step that rides the pass."""
        live = jnp.arange(R) < n_rows
        riders = nobody if riding is None else riding
        src = jnp.full((R,), -1, jnp.int32).at[0].set(first)
        dst = jnp.zeros((R, 2), jnp.int32).at[n_rows - 1, 0].set(own)
        if ckpt_row >= 0:
            dst = dst.at[ckpt_row, 1].set(CKPT)
        return (jnp.broadcast_to(tab, (R, Wt)), start + jnp.arange(R) * P,
                jnp.where(live, P, 0), jnp.full((R,), slot, jnp.int32),
                jnp.full((R,), end, jnp.int32), src, dst) + riders

    with jax.default_matmul_precision("highest"):
        embed = make["embed"]()
        x_ref = reference_embed(hp, embed, tokens[:prompt + steps])
        xs_ref = reference_embed(hp, embed, short_tokens)
    x_ctl, xs_ctl = x_ref, xs_ref
    per_call = [min(R, (prompt - c * R * P) // P)
                for c in range(-(-prompt // (R * P)))]
    ckpt_at = split // P - 1                # the row after which it is taken
    calls_a = [call(table[0], 0, c * R * P, n, prompt,
                    0 if c == 0 else 1, 1,
                    ckpt_at - c * R if c * R <= ckpt_at < c * R + n else -1)
               for c, n in enumerate(per_call)]
    xs = [pass_of(tokens[c * R * P:c * R * P + n * P], n)
          for c, n in enumerate(per_call)]
    # the short requests: rows 2 i and 2 i + 1 are slot i + 1's
    two = jnp.arange(2 * n_short) // 2
    first = jnp.arange(2 * n_short) % 2 == 0
    pad = (0, R - 2 * n_short)
    call_short = (
        jnp.pad(table[1 + two], (pad, (0, 0))),
        jnp.pad(jnp.where(first, 0, P), pad),
        jnp.pad(jnp.where(first, P, short_len[two] - P), pad),
        jnp.pad(1 + two, pad), jnp.pad(short_len[two], pad),
        jnp.pad(jnp.where(first, 0, -1), pad, constant_values=-1),
        jnp.pad(jnp.stack([jnp.where(first, 0, 2 + two),
                           jnp.zeros_like(two)], axis=1), (pad, (0, 0)))
    ) + nobody
    x_short = pass_of(short_tokens[:, :2 * P].reshape(-1), 2 * n_short)
    decoded_tokens = [jnp.concatenate([
        tokens[prompt + t][None], jnp.take_along_axis(
            short_tokens, decoded_at[:, t:t + 1], axis=1)[:, 0]])
        for t in range(steps)]
    # request B's rows, and riding its pass the LAST decode step of every
    # slot (the engine's fused pass carries the active slots' next position)
    Rb = cmp_n // P
    call_b = call(table_b, 1, split, Rb, prompt, CKPT, OWN_B,
                  riding=(lengths + steps - 1, jnp.ones((B,), bool)))
    xb = pass_of(tokens[split:prompt], Rb, decoded_tokens[-1])
    xd = [model.embed(cfg, embed, toks[:, None])
          for toks in decoded_tokens[:-1]]
    del embed
    full_lengths = jnp.concatenate(
        [jnp.asarray([prompt + steps], jnp.int32), short_len + steps])
    ends = jnp.concatenate([jnp.asarray([split]), full_lengths])
    rings, routing = [], []

    def of_reference(aux, auxs):
        """A's windows at the checkpoint, then every slot's after the
        steps: ([1 + B, W, Hkv, dk], [1 + B, W, Hkv, dv])."""
        return tuple(jnp.concatenate(
            [aux[n + "_split"][None], aux[n][None], auxs[n]])
            for n in ("k", "v"))

    by_position = jax.jit(jax.vmap(ring_by_position, in_axes=(0, 0, None)),
                          static_argnums=(2,))

    for i, kind in enumerate(cfg.layer_kinds):
        p = make["layer"](i)
        here = None
        follow, follow_short = nothing, nothing_short
        sliding, routed = kind[0] == "ring", kind[1] == "experts"
        if control:
            with jax.default_matmul_precision("highest"):
                x_ctl, aux, follow, _, mid = rounded[kind][0](
                    p, x_ctl, full_lengths[0], nothing)
                xs_ctl, auxs, follow_short, _ = rounded[kind][1](
                    p, xs_ctl, full_lengths[1:], nothing_short)
                if routed:
                    fed, _, weights = shown_by_control(p, mid)
                del mid
            if sliding:
                here = of_reference(aux, auxs)
        else:
            if sliding:
                shk, shv = window_ring.ring_shapes(
                    NS, hp["kv_sliding"], W, dk, dv)
                a, b = jnp.zeros(shk, cfg.dtype), jnp.zeros(shv, cfg.dtype)
            else:
                a = jnp.zeros(decoding.unrolled_pool_shape(cfg, NB - 1, bs),
                              cfg.dtype)
                b = jnp.zeros(decoding.unrolled_pool_shape(
                    cfg, NB - 1, bs, values=True), cfg.dtype)
            # of A's positions: the router's input, picks and weights
            seen_a = []
            for c, n in enumerate(per_call):
                xs[c], a, b, route = fused(kind, p, xs[c], a, b,
                                           *calls_a[c])
                if routed:
                    seen_a.append([r[:n * P] for r in route])
            x_short, a, b, route = fused(kind, p, x_short, a, b,
                                         *call_short)
            if routed:
                picks_short = route[1][:2 * n_short * P].reshape(
                    n_short, 2 * P, -1)
            picks_decoded = []
            for t in range(steps - 1):
                xd[t], a, b, route = decode(kind, p, xd[t], a, b, lengths + t)
                if routed:
                    seen_a.append([r[:1] for r in route])
                    picks_decoded.append(route[1][1:, None])
            # request B, after the hit: slot 1's rows, its own table and
            # state id; the slots' last decode step rides its pass
            xb, a, b, route = fused(kind, p, xb, a, b, *call_b)
            if routed:
                seen_a.append([r[R * P:R * P + 1] for r in route])
                picks_decoded.append(route[1][R * P + 1:, None])
                fed, follow, weights = (jnp.concatenate(r)
                                        for r in zip(*seen_a))
                # [n_short, 2 P + steps, k]: the prompt's rows, -1 past its
                # end, then the decoded positions where they belong
                at = jnp.arange(2 * P)[None, :, None]
                follow_short = jnp.concatenate([
                    jnp.where(at < short_len[:, None, None], picks_short,
                              -1),
                    jnp.full((n_short, steps, top_k), -1, jnp.int32)],
                    axis=1).at[jnp.arange(n_short)[:, None], decoded_at].set(
                        jnp.concatenate(picks_decoded, axis=1))
            if sliding:
                ids = jnp.concatenate([jnp.asarray([CKPT]), slot_ids])
                here = (by_position(a[ids], ends, dk),
                        by_position(b[ids], ends, dv))
            del a, b
        with jax.default_matmul_precision("highest"):
            x_ref, aux, own_picks, shortfall, _ = plain[kind][0](
                p, x_ref, full_lengths[0], follow)
            xs_ref, auxs, _, _ = plain[kind][1](p, xs_ref, full_lengths[1:],
                                                follow_short)
            if routed:
                on_input, its_weights = own_input(p, fed)
                routing.append((follow, own_picks, on_input, shortfall,
                                weights_apart(follow, weights, on_input,
                                              its_weights)))
                del weights
        if here is not None:
            want_k, want_v = of_reference(aux, auxs)
            rings.append(max(rel_rms(here[0], want_k),
                             rel_rms(here[1], want_v)))
            del want_k, want_v
        del p, here, aux, auxs

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
        want = (ref_head(head, x_ref[split:prompt]),
                ref_head(head, decoded(x_ref, xs_ref)))
        if control:
            got = (ref_head(head, x_ctl[split:prompt]),
                   ref_head(head, decoded(x_ctl, xs_ctl)))
    after_hit = None
    if not control:
        logits = jax.jit(lambda head, x: model.logits(cfg, head, x))
        last = jnp.concatenate(
            [x[0, :n * P] for x, n in zip(xs, per_call)])[-cmp_n:]
        got = (logits(head, last),
               logits(head, jnp.stack([x[:, 0] for x in xd]
                                      + [xb[0, R * P:]])))
        after_hit = (logits(head, xb[0, :cmp_n]), got[0])
    return got, want, rings, after_hit, routing


def compare(cfg, seed: int, sizes: Dict[str, int], *, control: str = "",
            attn_impl: str = "auto") -> Dict[str, float]:
    """The program's logits and rings against the reference's, the reference
    following the program's choice of experts, and its own picks against the
    program's.  `control` "fp8": the reference one precision down in the
    program's place; any of the reference's faults ("no_sink", ...): the
    reference with that fault in the program's place."""
    import jax.numpy as jnp
    got, want, rings, after_hit, routing = logits_both_ways(
        cfg, seed, sizes, parity_tokens(cfg, seed, sizes),
        control=control, attn_impl=attn_impl)
    hits = jnp.stack([picks_agree(r[0], r[1]) for r in routing])
    out = {
        "ring_err": max(rings),
        "route_mismatch_share": float(1.0 - jnp.mean(
            hits.astype(jnp.float32))),
        # (row, pick) pairs of request A that the two shares above compare
        "route_picks_compared": int(hits.size),
        "rows_routed_alike_share": float(jnp.mean(
            hits.all(axis=(0, 2)).astype(jnp.float32))),
        "route_shortfall_max": max(float(jnp.max(r[3])) for r in routing),
        "rows_not_followed_share": float(jnp.mean(jnp.stack(
            [r[3] for r in routing]) > FOLLOW_MARGIN)),
        "logits_prefill_err": rel_rms(got[0], want[0]),
        "logits_decode_err": rel_rms(got[1], want[1]),
        "logits_decode_err_worst_slot": max(
            rel_rms(got[1][:, j], want[1][:, j])
            for j in range(got[1].shape[1])),
        "route_own_input_mismatch_share": float(1.0 - jnp.mean(jnp.stack(
            [picks_agree(r[0], r[2]) for r in routing]
        ).astype(jnp.float32))),
        "route_own_input_weight_err": max(r[4] for r in routing)}
    if after_hit is not None:
        out["logits_after_hit_err"] = rel_rms(*after_hit)
    return out


def paged_parity(caches, cfg, seed: int) -> Dict[str, Any]:
    """The program's paged kernel against `reference_paged_attention` over
    the LIVE pool of the first full layer (the tables and lengths the warm-up
    traffic left), at this configuration's heads: keys of dk in whole rows of
    lanes beside values of dv."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import paged_attention as prog
    full = next(i for i, k in enumerate(cfg.layer_kinds) if k[0] == "full")
    kp, vp = caches.kp[full], caches.vp[full]
    bs = kp.shape[2]
    cols = max(1, -(-(int(caches.lengths.max()) + 1) // bs))
    tables = caches.block_tables[:, :cols]
    lens = jnp.minimum(caches.lengths + 1, cols * bs)
    q = jax.random.normal(jax.random.PRNGKey(seed % (2 ** 31)),
                          (lens.shape[0], cfg.n_heads, cfg.head_dim),
                          cfg.dtype)
    auto = jax.jit(lambda *a: prog.paged_attention(*a, impl="auto"))
    args = (q, kp, vp, tables, lens)
    lowered = auto.lower(*args).as_text()
    err = float(jnp.max(jnp.abs(_f32(auto(*args))
                                - reference_paged_attention(*args))))
    return {"paged_err": err,
            "paged_is_kernel": "tpu_custom_call" in lowered,
            "paged_live_positions": int(jnp.sum(lens))}


def parity(where: str, cfg, seed: int, *, seq: int = 512,
           caches=None) -> Dict[str, Any]:
    """What `correct` compares in a serving cell, in the process that holds
    the chip: logits of the program's tiled paged prefill and paged decode,
    through the K/V pools of the full layers and the rings of the sliding
    ones, of a long request and a short one in every other slot, against the
    reference's full forward pass; the rings themselves, by position; a
    request answered after a hit restored from a checkpoint against itself
    answered cold; the routing over the router's whole width on the
    reference's path and on the program's own, picks and weights; and the
    paged kernel alone over the live pool of the first full layer.  At the
    engine's own widths, tile and table size, weights made again from the
    seed one layer at a time (the harness hands this check the engine's
    configuration and caches, not its weights, and a second copy of 6.86 GB
    does not fit beside it).  These are the functions the engine's two
    programs are made of, the fused pass with decode rows riding it among
    them, driven by this check and not by the engine: the two compiled
    programs themselves (`paged_prefill_decode_packed`,
    `paged_decode_steps`), admission, the radix match and its checkpoints
    and `_fused_dispatch`'s packing are covered by the CPU tests alone
    (tests/test_mimo_v2_engine.py); PERF.md section 7 says what a
    `benchmark` PR has to add to the harness for the chip to see them."""
    import jax
    if where != "serve":
        raise ValueError("kind sink-window-moe is compared in serving cells "
                         "only")
    sizes = parity_sizes(caches)
    t0 = time.time()
    out: Dict[str, Any] = dict(compare(cfg, seed, sizes))
    out["parity_s"] = time.time() - t0
    out.update(paged_parity(caches, cfg, seed))
    out["parity_positions"] = sizes["prompt"] + sizes["steps"] + sum(
        n + sizes["steps"] for n in short_lengths(sizes))
    if out["paged_live_positions"] <= 0:
        out["paged_err"] = NOT_COMPARED
    if jax.default_backend() == "tpu" and not out["paged_is_kernel"]:
        out["paged_err"] = NOT_COMPARED
    return out
