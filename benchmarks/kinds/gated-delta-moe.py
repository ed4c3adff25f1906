"""Model kind `gated-delta-moe` (Qwen's Qwen3-Next family, `model_type`
qwen3_next): gated delta-rule layers whose 16 key heads are shared by 32
value heads and gated full-attention layers at a head size of 256 with a
partial rotary embedding, three to one; in EVERY layer an expert layer of
many small softmax-routed experts and a gated shared one, of which THIS CHIP
HOLDS A SHARE.  The same interface as the other kinds, found by the
configuration's `"kind"`; serving only (the program has no training path for
it, so CHECKS has no "train").

THE PLAIN REFERENCE is here (`reference_*`): the forward pass in float32 at
`jax.default_matmul_precision("highest")`, no cache, no kernel, no chunk
form: a linear layer is the STEP recurrence under ONE `lax.scan` over
positions, the convolution K shifted multiplies, attention blocked over
query rows so that 16 k positions fit, the experts a loop over the held ones
(weighted by whether the token chose them).  With
N(x) = x rsqrt(mean(x^2) + 1e-6) (1 + w) (the weight ZERO-CENTRED), x the
residual stream and a = N_in(x):

    x0      = Embed[token]
    linear (layer i with (i + 1) % 4 != 0), Hk = 16, Hv = 32, dk = dv = 128:
              q~, k~, v~, z = Wq a, Wk a, Wv a, Wz a   (2048 -> 2048, 2048,
                                                         4096, 4096)
              b, al = Wb a, Wa a                        (2048 -> 32, 32)
              c_t = silu(sum_{j=0..3} w[:, j] * u_{t-3+j})  per channel of
                    u = [q~ | k~ | v~] (8192), no bias, zeros before 0
              per key head: q = q' / |q'| 128^-1/2,  k = k' / |k'| (eps 1e-6)
              value head h uses key head h // 2
              beta = sigmoid(b)   in (0, 1)
              alpha = exp(-exp(A_log) softplus(al + dt_bias))   per value head
              S' = alpha S;  u = beta (v - S'^T k);  S = S' + k u^T
              o = S^T q;  y = Wo [rms(o_h) w_o silu(z_h)]_h   (rms over the
                    head's 128 values, w_o NOT zero-centred)
    full (layer i with (i + 1) % 4 == 0), H = 16, Hkv = 2, Dh = 256:
              [q | g]_h = (Wq a)_h     (a head's 256 query dims, then its 256
                                        gate dims);  k, v = Wk a, Wv a
              q_h = N_q(q_h), k_h = N_k(k_h)  over the head's 256 dims
              rotary (theta 1e7, rotate-half, pairs (i, i + 32)) on dims
              0..63 of q_h and k_h; dims 64..255 untouched
              o = causal softmax(q k^T 256^-1/2) v, 8 query heads a kv head
              y = Wo (o * sigmoid(g))
    x = x + y
    m = N_post(x)
              p = softmax_512(Wr m);  S = top-10 by p;  w_e = p_e / sum_S p
              f = sum_{e in S, e held here} w_e Wd,e (silu(Wg,e m) * Wu,e m)
                  + sigmoid(w_sg . m) Wsd (silu(Wsg m) * Wsu m)
    x = x + f
    logits  = lm_head^T N_final(x_L)

THE SHARE.  The four chips of one host share each layer, expert-parallel,
the mixers data-parallel: the router keeps its 512 outputs and its top-10,
normalised over all ten; only the `num_experts` (128) experts held here,
experts `experts_held_first` .. + 127, and the shared one are summed, in the
reference as in the program, and that partial result goes on to the next
layer (model-configs guide, section 4).  tests/test_qwen3_next.py adds all
four shares up to the uncut layer.

DEPARTURE RISKS.  What config.json does not settle (each listed in the
configuration file under `assumed`; no network here to re-read the modelling
code):
  (a) the (1 + w) norms, and the plain-weight gated norm of a linear head;
  (b) the order [query | gate] inside a head of q_proj, norm before rotary,
      the rotate-half pairing inside the first 64 dims;
  (c) the published code's fused in_proj_qkvz / in_proj_ba, whose rows are
      grouped by key head: kept apart here (a permutation of rows, immaterial
      under seeded weights);
  (d) value head h on key head h // 2 (`repeat_interleave`), L2 eps 1e-6 and
      dk^-1/2 on q after it, ONE depthwise convolution over [q | k | v], silu
      after it;
  (e) softmax BEFORE top-k, renormalised over the picks (norm_topk_prob);
      the shared gate one wide;
  (f) float32 state, gates and routing scores; weights and activations
      bfloat16;
  (g) weights from the seed (A_log ~ ln U(1, 16), softplus(dt_bias)
      log-uniform in (1e-3, 0.1) so that alpha spans ~0.2-0.999, effective
      norm weights 1 + 0.1 N, taps N(0, 1/4));
  (h) the one multi-token-prediction module `described_as` names is not
      built (no key for it; the engine yields one token a sequence a step).
"""

from __future__ import annotations

import functools
import math
import time
import types
from typing import Any, Callable, Dict, List

# How each limit was set: PERF.md section 2, "Limits of `correct`".  Readings:
# my chip runs, PR 51 (chiprun_out/pr51/parity51.jsonl and the cell's own
# lines), one process at the cell's widths and sizes beside 11.2 GiB held as
# the engine holds it, 16,384 positions of request A and 63 short requests
# beside it: the sound program (P), the fp8 control (F: q, k, v, the
# projections' outputs and the expert weights rounded to e4m3 in the
# reference, which then stands in the program's place), the bfloat16-state
# control (S) and the program with its routing scores rounded to bfloat16
# (B).  Eight layers of many small experts average fp8's rounding out: F
# reads only 1.2-1.35 x P on the logits and the state, so the limits that
# refuse it stand at the geometric mean of their two readings, ~1.16 x from
# both; P's readings spread by ~1 % over its seeds.
TOLERANCES: Dict[str, float] = {
    # relative RMS error of the logits (bf16 activations against float32)
    # over A's last 256 prompt positions: P 0.01275-0.01292, F 0.01506 (1.17
    # x: too near to stand between); set from P alone, 1.5 x above it; F is
    # refused by the two below
    "logits_prefill_err": 0.019,
    # ... over the 8 decoded positions of all 64 slots: P 0.01259-0.01274
    # (its worst slot 0.0133-0.0135), F 0.01711
    "logits_decode_err": 0.0148,
    # relative RMS error of every linear layer's state S and conv inputs, at
    # A's checkpoint and in every slot after the decode steps, largest layer:
    # P 0.01685-0.01696, F 0.02289.  (S reads 0.0069, UNDER P: bfloat16
    # ACTIVATIONS move a float32 state more than rounding the state does.
    # What refuses a state kept below float32 is the next number.)
    "state_err": 0.0197,
    # ... of every short request's state against the reference's STEP
    # recurrence run in float32 over the PROGRAM's own inputs to the rule
    # (what the tap shows: q, k, v, ln alpha, beta): only the rule's own
    # arithmetic is left.  P 7.3e-6-7.4e-6; the same recurrence with its
    # state rounded to bfloat16 after every position reads 0.0045-0.0051
    # (`state_own_input_err_bf16`, beside it in every line)
    "state_own_input_err": 2e-4,
    # the logits of a request's last rows answered after a hit restored from
    # a checkpoint against the same rows answered cold, in the same compiled
    # call: a checkpoint is a copy, so P 0.0 on every seed; a restore from
    # the checkpoint one block earlier reads 0.3 at the toy's size
    "logits_after_hit_err": 1e-3,
    # share of A's (row, expert) picks, over all 512 scores, that differ from
    # the reference's own on the reference's own path (1,311,360 picks: 16,392
    # positions x 10 x 8 layers): P 0.01072-0.01083, F 0.01140 (1.05 x: the
    # tenth of 512 softmax scores is as near its neighbour for any small
    # error); set from P alone, 1.5 x above it
    "route_mismatch_share": 0.016,
    # ... on the PROGRAM's own input to each router (what the tap shows),
    # scored by the reference in float32: P 0.0 on every seed (not one of
    # 1,311,360 picks); B: see PERF.md section 2
    "route_own_input_mismatch_share": 1e-3,
    # the paged kernel alone over the live pool of the first full layer, 16
    # query and 2 kv heads of 256 (lib/reference.py's, as for the other kinds)
    "paged_err": 2e-2,
}

CHECKS: Dict[str, tuple] = {
    "serve": tuple(TOLERANCES),
}

NOT_COMPARED = float("nan")
L2_EPS = 1e-6
# How far below the reference's own k-th LOGIT an expert of the program's
# choice may score and still be followed (`reference_route`): under a softmax
# a difference of logits is the ratio of two probabilities whatever the
# other 510 read.  kinds/lfm2-moe.py has the reasons for following at all.
# The program's choices lie at most 0.058-0.066 below (my chip runs, PR 51,
# `route_shortfall_max` on four seeds; the fp8 control's 0.067): every row is
# followed, and a choice further off than twice that shows in the logits.
FOLLOW_MARGIN = 0.15


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------
def layer_kinds(cfg: Dict[str, Any]) -> List[List[str]]:
    """(mixer, feed-forward) per layer: every `full_attention_interval`-th
    layer full attention, the others linear; an expert layer in each."""
    n = cfg["full_attention_interval"]
    return [["full" if (i + 1) % n == 0 else "linear", "experts"]
            for i in range(cfg["num_hidden_layers"])]


def router_width(cfg: Dict[str, Any]) -> int:
    return cfg.get("router_width", cfg["num_experts"])


def rotary_dim(cfg: Dict[str, Any]) -> int:
    return int(cfg["head_dim"] * cfg["partial_rotary_factor"])


def check(cfg: Dict[str, Any]) -> None:
    import importlib.util
    # in the driver, before a worker starts: a program without the
    # architecture (the parent of the PR that brought it) fails here, at once
    if importlib.util.find_spec("ray_tpu.models.qwen3_next") is None:
        raise ValueError("the program under test has no arch qwen3_next "
                         "(ray_tpu/models/qwen3_next.py)")
    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"]:
        raise ValueError("only an expert layer in every layer is expressed")
    if not cfg["norm_topk_prob"]:
        raise ValueError("only renormalised top-k weights are expressed")
    if cfg["num_attention_heads"] % cfg["num_key_value_heads"]:
        raise ValueError("query heads are not a multiple of KV heads")
    if cfg["linear_num_value_heads"] % cfg["linear_num_key_heads"]:
        raise ValueError("value heads are not a multiple of key heads")
    if cfg["shared_expert_intermediate_size"] % cfg["moe_intermediate_size"]:
        raise ValueError("the shared expert is not whole experts wide")
    if cfg["hidden_act"] != "silu" or cfg.get("rope_scaling"):
        raise ValueError("another activation / a scaled rotary embedding "
                         "are not expressed")
    if cfg.get("use_sliding_window"):
        raise ValueError("a sliding window is not expressed")
    if rotary_dim(cfg) % 2:
        raise ValueError("the rotary dims are not pairs")
    first = cfg.get("experts_held_first", 0)
    if first < 0 or first + cfg["num_experts"] > router_width(cfg):
        raise ValueError("the experts held are not among the router's")
    sv = cfg.get("serve") or {}
    if "num_states" in sv and sv["num_states"] < sv["num_slots"]:
        raise ValueError("serve.num_states is fewer than the slots")


def transformer_kwargs(cfg: Dict[str, Any], *, max_seq: int,
                       param_dtype: str, **extra: Any) -> Dict[str, Any]:
    check(cfg)
    # `num_states` reaches the engine through the program's own registry of
    # settings, as kinds/gated-delta.py hands it on (lib/serve_cell.py
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
        "d_head": cfg["head_dim"],
        "d_ff": cfg["intermediate_size"],       # no layer here is dense
        "max_seq": max_seq,
        "arch": "qwen3_next",
        "rope_theta": float(cfg["rope_theta"]),
        "rotary_dim": rotary_dim(cfg),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "norm_zero_centered": True,
        "attn_output_gate": True,
        "tie_embeddings": bool(cfg["tie_word_embeddings"]),
        "dtype": cfg.get("torch_dtype", "bfloat16"),
        "param_dtype": param_dtype,
        "layer_kinds": layer_kinds(cfg),
        "conv_kernel": cfg["linear_conv_kernel_dim"],
        "linear_heads": cfg["linear_num_value_heads"],
        "linear_key_heads": cfg["linear_num_key_heads"],
        "linear_key_dim": cfg["linear_key_head_dim"],
        "linear_value_dim": cfg["linear_value_head_dim"],
        "moe_experts": cfg["num_experts"],
        "moe_router_width": router_width(cfg),
        "moe_experts_first": cfg.get("experts_held_first", 0),
        "moe_top_k": cfg["num_experts_per_tok"],
        "moe_d_ff": cfg["moe_intermediate_size"],
        "moe_shared_experts": (cfg["shared_expert_intermediate_size"]
                               // cfg["moe_intermediate_size"]),
        "moe_score_fn": "softmax",
        "moe_shared_gate": True,
        "remat": False,
    }
    kw.update(extra)
    return kw


def param_counts(cfg: Dict[str, Any]) -> Dict[str, int]:
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    K, fe = cfg["linear_conv_kernel_dim"], cfg["moe_intermediate_size"]
    linear = (d * (2 * Hk * dk + 2 * Hv * dv)       # q, k, v, z
              + 2 * d * Hv                          # the two gates
              + K * (2 * Hk * dk + Hv * dv)         # taps
              + 2 * Hv + dv                         # A_log, dt_bias, w_o
              + Hv * dv * d)                        # W_o
    full = (d * h * 2 * dh + 2 * d * hkv * dh + h * dh * d  # [q | g], k, v, o
            + 2 * dh)                                       # q and k norms
    expert = 3 * d * fe
    shared = 3 * d * cfg["shared_expert_intermediate_size"]
    expert_ffn = (d * router_width(cfg) + cfg["num_experts"] * expert
                  + shared + d)                     # router, held, shared, gate
    norms = 2 * d
    embed = cfg["vocab_size"] * d
    total = embed + d + (0 if cfg["tie_word_embeddings"] else embed)
    for mixer, _ in layer_kinds(cfg):
        total += (linear if mixer == "linear" else full) + expert_ffn + norms
    return {"total": total, "input_embedding": embed, "linear": linear,
            "attention": full, "expert": expert, "expert_ffn": expert_ffn}


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """Keys and values of the full layers alone: what a position costs."""
    full = sum(1 for m, _ in layer_kinds(cfg) if m == "full")
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2 * full


def state_bytes_per_sequence(cfg: Dict[str, Any]) -> int:
    """What a sequence leaves in the linear layers whatever its length: the
    float32 state S a value head and the bfloat16 inputs of the
    convolution's last K - 1 positions."""
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    linear = sum(1 for m, _ in layer_kinds(cfg) if m == "linear")
    return linear * (Hv * dk * dv * 4 + (cfg["linear_conv_kernel_dim"] - 1)
                     * (2 * Hk * dk + Hv * dv) * 2)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    raise NotImplementedError("kind gated-delta-moe has no training path")


# ---------------------------------------------------------------------------
# cost functions of the kernels this kind's cell reads: fn(config, shapes)
# ---------------------------------------------------------------------------
def gated_delta_step(cfg, s):
    """One call = one linear layer, one decode step, `slots` sequences: the
    state read and written (the MODEL's Hv x dk x dv float32), q, k of the
    key heads and v in, o out; 7 operations an entry of S (decay, S^T k, the
    rank-1 update, S^T q).  Nothing here depends on the context."""
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    flops = 7.0 * Hv * dk * dv * s["slots"]
    bytes_ = s["slots"] * (2.0 * Hv * dk * dv * 4
                           + 2 * (2 * Hk * dk + Hv * dv) + 4 * Hv * dv)
    return flops, bytes_


# Per fused dispatch of the cell the kernel is measured in
# (serve-qw3n-agent-sessions, traffic/agent-sessions.json at 64 slots):
# requests admitted and the rows of 16 positions each brings (a ~32-token
# suffix is two rows, three where the block-rounding remainder spills).
# ASSUMED from the traffic's means, a floor, as kinds/gated-delta.py and
# kinds/mla-moe.py do (24.6 requests a fused dispatch at 64 slots: PERF.md
# section 5, PR 44); the harness hands a cost function the configuration and
# `slots` / `live_context` only.
DELTA_REQUESTS_PER_CALL = 24.0
DELTA_ROWS_PER_REQUEST = 2.0
DELTA_ROW = 16


def gated_delta_chunk(cfg, s):
    """One call = one linear layer, one fused dispatch's prompt rows.  A
    request restores its state once (a read), leaves it in its slot and in a
    checkpoint (two writes); a row of C positions is the chunk form's four
    products with S a value head (W S, Q S, M V', K^T V') and its operands in
    float32."""
    Hv = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    C = DELTA_ROW
    rows = DELTA_REQUESTS_PER_CALL * DELTA_ROWS_PER_REQUEST
    flops = rows * Hv * (6.0 * C * dk * dv + 2.0 * C * C * dv)
    bytes_ = (DELTA_REQUESTS_PER_CALL * 3.0 * Hv * dk * dv * 4
              + rows * 4.0 * Hv * (3 * C * dk + C * C + 2 * C * dv))
    return flops, bytes_


def experts_touched_even(cfg: Dict[str, Any], rows: float) -> float:
    """Held experts with at least one of `rows` tokens' picks, under even
    routing over the router's whole width: E_held (1 - (1 - 1 / width) ^
    (k rows))."""
    k, width = cfg["num_experts_per_tok"], router_width(cfg)
    return cfg["num_experts"] * (1.0 - (1.0 - 1.0 / width) ** (k * rows))


def moe_experts_decode(cfg, s):
    """One call = one expert layer, one decode step, over the HELD experts:
    the rows routed here (slots x k x held / width) through three products
    of hidden x expert width; bytes = the distinct held experts read x
    6,291,456 B + the rows in and out.  A FLOOR as in kinds/mla-moe.py: the
    expectation under even routing with HALF the slots live, so that it
    cannot read over 100 %."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = (s["slots"] * cfg["num_experts_per_tok"]
            * cfg["num_experts"] / router_width(cfg))
    flops = 2.0 * rows * 3 * d * fe
    bytes_ = 2.0 * (experts_touched_even(cfg, s["slots"] / 2) * 3 * d * fe
                    + 2 * rows * d)
    return flops, bytes_


COST_FNS: Dict[str, Callable] = {
    "gated_delta_step": gated_delta_step,
    "gated_delta_chunk": gated_delta_chunk,
    "moe_experts_decode": moe_experts_decode,
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
            "rotary": cfg.rotary_dim or cfg.head_dim,
            "lin_heads": cfg.linear_heads,
            "key_heads": cfg.linear_key_heads or cfg.linear_heads,
            "dk": cfg.linear_key_dim, "dv": cfg.linear_value_dim,
            "taps": cfg.conv_kernel, "top_k": cfg.moe_top_k,
            "held_first": cfg.moe_experts_first, "held": cfg.moe_experts,
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


def _same(x):
    return x


def _rms(x, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _norm(x, w, eps, wrong: str = ""):
    """The layers' norm: the weight zero-centred (the fault "plain_norm":
    applied as it stands)."""
    return _rms(x, eps) * (_f32(w) if wrong == "plain_norm"
                           else 1.0 + _f32(w))


def _l2norm(x):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


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


def reference_conv_step(window, u_t, w, wrong: str = ""):
    """The causal depthwise convolution one position at a time.  `window`
    [K, C]: the K inputs before this one, oldest first (zeros before
    position 0); u_t [C]; w [K, C] -> (silu(sum_j w[j] u_{t-(K-1)+j}) [C],
    the window moved on)."""
    import jax
    import jax.numpy as jnp
    ext = jnp.concatenate([window, u_t[None]])               # [K + 1, C]
    seen = ext[:-1] if wrong == "taps_shifted" else ext[1:]
    return jax.nn.silu(jnp.sum(w * seen, axis=0)), ext[1:]


def reference_step(S, q, k, v, alpha, beta):
    """The gated delta rule, one position.  S [H, dk, dv]; q, k [H, dk];
    v [H, dv]; alpha, beta [H] -> (S', o [H, dv])."""
    import jax.numpy as jnp
    S = alpha[:, None, None] * S
    u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", S, k))
    S = S + k[:, :, None] * u[:, None, :]
    return S, jnp.einsum("hkv,hk->hv", S, q)


def reference_linear(hp, p, a, length=None, split: int = 0,
                     control: str = "", wrong: str = "", block: int = 256):
    """a [S, hidden] float32 -> (the linear mixer's output [S, hidden],
    {"S": the state after position `length` - 1 (None: the last), "tail":
    the convolution's input at the K - 1 positions before `length`,
    "S_split" / "tail_split": the same after `split` positions (0: not
    asked)}).  ONE scan over positions from a zero state and a window of
    zeros: a step takes the convolution's input u_t = [q~ | k~ | v~], keeps
    the K inputs before it (`reference_conv_step`), gives every value head
    its key head's q and k, and moves the state on (`reference_step`);
    positions at or beyond `length` leave the state as it is.  The gate, the
    head norm and W_o run over blocks of `block` rows."""
    import jax
    import jax.numpy as jnp
    S_len = a.shape[0]
    Hv, Hk, dk, dv, K = (hp["lin_heads"], hp["key_heads"], hp["dk"],
                         hp["dv"], hp["taps"])
    rnd = _fp8 if control == "fp8" else _same
    at = [Hk * dk, 2 * Hk * dk]
    u = [rnd(jnp.einsum("sd,dhk->shk", a, _f32(p[name])).reshape(S_len, -1))
         for name in ("wq", "wk", "wv")]
    w = _f32(p["w_conv"])
    end = S_len if length is None else length
    aux = {"tail": jnp.concatenate([jax.lax.dynamic_slice_in_dim(
        x, end - (K - 1), K - 1) for x in u], axis=-1)}
    if split:
        aux["tail_split"] = jnp.concatenate(
            [x[split - (K - 1):split] for x in u], axis=-1)
    beta = jax.nn.sigmoid(a @ _f32(p["wb"])) * (
        2.0 if wrong == "beta_x2" else 1.0)
    alpha = jnp.exp(-jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(
        a @ _f32(p["wa"]) + _f32(p["dt_bias"])))
    if wrong == "no_decay":
        alpha = jnp.ones_like(alpha)
    if length is not None:
        live = (jnp.arange(S_len) < length)[:, None]
        alpha, beta = jnp.where(live, alpha, 1.0), jnp.where(live, beta, 0.0)
    keep = _bf16 if control == "state_bf16" else _same

    def per_value_head(x):
        """[Hk, dk] -> [Hv, dk]: value head h gets key head h // (Hv / Hk)
        (the fault "key_head_mod": h % Hk)."""
        if wrong == "key_head_mod":
            return jnp.tile(x, (Hv // Hk, 1))
        return jnp.repeat(x, Hv // Hk, axis=0)

    def one(carry, x):
        S, kept, window = carry
        t, uq, uk, uv, alpha_t, beta_t = x
        c, window = reference_conv_step(
            window, jnp.concatenate([uq, uk, uv]), w, wrong)
        q = _l2norm(c[:at[0]].reshape(Hk, dk)) * dk ** -0.5
        k = _l2norm(c[at[0]:at[1]].reshape(Hk, dk))
        S, o = reference_step(S, per_value_head(rnd(q)),
                              per_value_head(rnd(k)),
                              rnd(c[at[1]:].reshape(Hv, dv)), alpha_t, beta_t)
        S = keep(S)
        return (S, jnp.where(t == split - 1, S, kept), window), o.reshape(-1)

    S0 = jnp.zeros((Hv, dk, dv), jnp.float32)
    (aux["S"], S_split, _), o = jax.lax.scan(
        one, (S0, S0, jnp.zeros((K, w.shape[1]), jnp.float32)),
        (jnp.arange(S_len), *u, alpha, beta))
    if split:
        aux["S_split"] = S_split

    def out(rows):
        a_rows, o_rows = rows
        z = jnp.einsum("sd,dhk->shk", a_rows, _f32(p["wg"]))
        # the head's norm: a PLAIN weight
        y = _rms(o_rows.reshape(-1, Hv, dv), hp["eps"]) * _f32(p["o_norm"]) \
            * jax.nn.silu(z)
        return jnp.einsum("shk,hkd->sd", y, _f32(p["wo"]))

    n_blocks = -(-S_len // block)
    pad = n_blocks * block - S_len
    y = jax.lax.map(out, (
        jnp.pad(a, ((0, pad), (0, 0))).reshape(n_blocks, block, -1),
        jnp.pad(o, ((0, pad), (0, 0))).reshape(n_blocks, block, -1)))
    return y.reshape(n_blocks * block, -1)[:S_len], aux


def reference_attention(hp, p, a, block: int = 256, control: str = "",
                        wrong: str = ""):
    """Gated attention: a [S, hidden] float32 -> W_o (o * sigmoid(g)).
    Query rows in blocks, each against every key; the 8 query heads of a kv
    head score against it as it stands (no repeated copy of K and V)."""
    import jax
    import jax.numpy as jnp
    S = a.shape[0]
    H, Hkv, D = hp["heads"], hp["kv_heads"], hp["head_dim"]
    G = H // Hkv
    rnd = _fp8 if control == "fp8" else _same
    pos = jnp.arange(S)
    qg = rnd(jnp.einsum("sd,dhk->shk", a, _f32(p["wq"])))       # [S, H, 2 D]
    q, gate = qg[..., :D], qg[..., D:]
    k = rnd(jnp.einsum("sd,dhk->shk", a, _f32(p["wk"])))
    v = rnd(jnp.einsum("sd,dhk->shk", a, _f32(p["wv"])))
    q = _norm(q, p["q_norm"], hp["eps"], wrong)
    k = _norm(k, p["k_norm"], hp["eps"], wrong)
    dims = D if wrong == "rope_on_all" else hp["rotary"]
    q, k = _rotary(q, pos, hp["theta"], dims), _rotary(k, pos, hp["theta"],
                                                       dims)
    q, k = rnd(q).reshape(S, Hkv, G, D), rnd(k)
    # blocks of query rows (a [Hkv, G, block, S] score: narrower blocks
    # where the sequence is long)
    block = max(16, min(block, (1 << 20) // max(S, 1)))
    n_blocks = -(-S // block)
    pad = n_blocks * block - S
    q_blocks = jnp.pad(q, ((0, pad),) + ((0, 0),) * 3).reshape(
        n_blocks, block, Hkv, G, D)
    pos_blocks = jnp.pad(pos, (0, pad)).reshape(n_blocks, block)

    def one_block(args):
        qb, qi = args
        s = jnp.einsum("qhgd,khd->hgqk", qb, k) / math.sqrt(D)
        seen = pos[None, :] <= qi[:, None]
        w = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", w, v)

    o = jax.lax.map(one_block, (q_blocks, pos_blocks)).reshape(
        n_blocks * block, H, D)[:S]
    if wrong != "no_output_gate":
        o = o * jax.nn.sigmoid(gate)
    return jnp.einsum("shk,hkd->sd", o, _f32(p["wo"]))


def reference_route(hp, p, m, follow=None, wrong: str = ""):
    """m [S, hidden] float32 -> (picks [S, k] over the router's whole width,
    the weights [S, k] of the experts used, the experts used, shortfall
    [S]).  Softmax over every expert, top-k by it, the weights the picks'
    probabilities over their sum.  `follow` [S, k] (-1: nothing to follow in
    this row): the experts used are these and not the picks (weighed by this
    function's own scores), in every row where each of them scores within
    FOLLOW_MARGIN (of logit) of this function's own k-th (kinds/lfm2-moe.py
    has the reasons).  Faults: "sigmoid_routing" (each logit's sigmoid in
    the softmax's place), "no_renorm" (the picks' probabilities as they
    stand)."""
    import jax
    import jax.numpy as jnp
    z = m @ _f32(p["w_router"])
    s = jax.nn.sigmoid(z) if wrong == "sigmoid_routing" \
        else jax.nn.softmax(z, axis=-1)
    _, picks = jax.lax.top_k(s, hp["top_k"])
    used, shortfall = picks, jnp.zeros(m.shape[:1], jnp.float32)
    if follow is not None:
        kth = jnp.take_along_axis(z, picks[:, -1:], axis=1)
        theirs = jnp.take_along_axis(z, jnp.maximum(follow, 0), axis=1)
        shortfall = jnp.where((follow >= 0).all(axis=1),
                              jnp.max(kth - theirs, axis=1), jnp.inf)
        used = jnp.where((shortfall <= FOLLOW_MARGIN)[:, None], follow,
                         picks)
        shortfall = jnp.where(jnp.isinf(shortfall), 0.0,
                              jnp.maximum(shortfall, 0.0))
    chosen = jnp.take_along_axis(s, used, axis=1)
    if wrong != "no_renorm":
        chosen = chosen / jnp.sum(chosen, axis=1, keepdims=True)
    return picks, chosen, used, shortfall


def reference_moe(hp, p, x, follow=None, control: str = "",
                  wrong: str = "", held=None):
    """The second half of a layer: x [S, hidden] -> (x + f, picks [S, k],
    shortfall [S]); f the HELD experts' part (`held` = (first, count); None:
    the program's own share) plus the gated shared expert's.  A loop over
    the held experts, each over every row, weighted by whether the row used
    it."""
    import jax
    import jax.numpy as jnp
    S = x.shape[0]
    m = _norm(x, p["ffn_norm"], hp["eps"], wrong)
    picks, weights, used, shortfall = reference_route(hp, p, m, follow,
                                                      wrong)
    first, E = (hp["held_first"], hp["held"]) if held is None else held
    here = (used >= first) & (used < first + E)
    # each token's weight for each HELD expert (0 where it did not use it);
    # a pick that lies on another chip adds nothing here
    dense_w = jnp.zeros((S, E + 1), jnp.float32).at[
        jnp.arange(S)[:, None], jnp.where(here, used - first, E)
    ].add(weights)[:, :E]
    rnd = _fp8 if control == "fp8" else _f32

    def one_expert(y, e):
        out = _swiglu(m, rnd(p["w_gate"][e]), rnd(p["w_up"][e]),
                      rnd(p["w_down"][e]))
        return y + dense_w[:, e][:, None] * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(m), jnp.arange(E))
    shared = _swiglu(m, rnd(p["ws_gate"]), rnd(p["ws_up"]), rnd(p["ws_down"]))
    if wrong != "no_shared_gate":
        shared = jax.nn.sigmoid(m @ _f32(p["w_shared_gate"]))[:, None] \
            * shared
    return x + y + shared, picks, shortfall


def reference_mixer(hp, kind, p, x, length=None, split: int = 0,
                    block: int = 256, control: str = "", wrong: str = ""):
    """The first half of a layer: x [S, hidden] -> (x + mixer(N_in(x)), a
    linear layer's states and conv inputs (`reference_linear`) or None)."""
    a = _norm(x, p["attn_norm"], hp["eps"], wrong)
    if kind[0] == "linear":
        y, aux = reference_linear(hp, p, a, length, split, control, wrong,
                                  block)
        return x + y, aux
    return x + reference_attention(hp, p, a, block, control, wrong), None


def reference_layer(hp, kind, p, x, length=None, follow=None, split: int = 0,
                    block: int = 256, control: str = "", wrong: str = ""):
    """x [S, hidden] float32 (positions 0..S-1) -> (x' [S, hidden], a linear
    layer's states and conv inputs or None, picks [S, k], shortfall [S]):
    the two halves above, which the comparison at 16 k positions runs as two
    programs (the chip's memory beside a live engine).
    `follow`: the experts to use in the picks' place (`reference_route`).
    `control`: "fp8": the projections' outputs, q, k, v of both layer kinds
    and the expert weights rounded to fp8 (e4m3), the precision below the
    configuration's bfloat16; "state_bf16": S rounded to bfloat16 after every
    position, the precision below its float32: what `correct` must refuse.
    `wrong` names one deliberate fault, for the tests that show the limits
    refuse it: "sigmoid_routing", "no_renorm", "no_shared_gate",
    "no_output_gate", "rope_on_all", "plain_norm", "beta_x2", "key_head_mod",
    "no_decay", "taps_shifted"."""
    x, aux = reference_mixer(hp, kind, p, x, length, split, block, control,
                             wrong)
    x, picks, shortfall = reference_moe(hp, p, x, follow, control, wrong)
    return x, aux, picks, shortfall


def reference_embed(hp, table, tokens):
    return _f32(table[tokens])


def reference_head(hp, params, x, wrong: str = ""):
    """x [R, hidden] -> logits [R, V]; `params` holds final_norm and the
    untied lm_head."""
    return _norm(x, params["final_norm"], hp["eps"], wrong) \
        @ _f32(params["lm_head"])


def reference_logits(hp, params, tokens, block: int = 256,
                     control: str = "", wrong: str = ""):
    """The whole model: tokens [S] -> logits [S, V] float32."""
    import jax
    with jax.default_matmul_precision("highest"):
        x = reference_embed(hp, params["tok_embed"], tokens)
        for kind, p in zip(hp["kinds"], params["layers"]):
            x = reference_layer(hp, kind, p, x, None, None, 0, block,
                                control, wrong)[0]
        return reference_head(hp, params, x, wrong)


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
    from ray_tpu.models import qwen3_next as model
    key = jax.random.PRNGKey(seed % (2 ** 31))
    layer_key = jax.random.split(key, 8)[0]
    # one compiled maker a KIND of layer, the layer's index an argument: a
    # maker a layer compiled ~22 s each on the chip's host, eight times
    # (my chip run, PR 51)
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
                     control: str = "", attn_impl: str = "auto"):
    """The same tokens through the PROGRAM and through the REFERENCE, one
    layer's weights at a time (made once, used by both, dropped).

    The program: its paged prefill and decode LAYERS (the functions the
    engine's dispatches are made of: models/decoding.py paged_prefill_layer
    / paged_decode_layer), over `slots` requests with tables and state ids
    of their own (slot s decodes from id s + 1).  Request A (slot 0) brings
    a prompt of `prompt` positions in calls of `rows` rows of P tokens, its
    K/V in the pools, its linear layers' state carried from call to call in
    its id, and a CHECKPOINT taken `compared` positions before the prompt's
    end (a flagged row in the middle of the last call).  Every other slot
    holds a SHORT request of its own tokens and length (a whole row and a
    part of one), all of them rows of ONE call.  Then `steps` decode steps
    of all slots together.  Then request B, A's prompt after a hit: A's
    blocks but the last `compared` positions' shared through its table,
    its state restored from A's checkpoint, those positions prefilled again
    (in the same compiled call, so that what differs is the hit and not a
    program's rounding).  With `control` the reference one precision down
    stands in the program's place.

    The reference FOLLOWS the program's choice of experts, layer by layer,
    where that choice lies within FOLLOW_MARGIN of its own
    (`reference_route`).

    -> (got, want, states, after_hit, own, routing): got, want (logits of
    A's last `compared` prompt positions [compared, V], of every slot's
    decoded positions [steps, slots, V]); states (per linear layer: the
    relative RMS error of the program's S and conv inputs, at A's checkpoint
    and of every slot after the steps, against the reference's, the larger
    of the two, and of S at the checkpoint alone); after_hit (B's logits,
    A's, of the same positions); own (per linear layer: the short requests'
    states against the reference's recurrence over the program's own inputs
    to the rule in float32, and that recurrence with its state in bfloat16
    against itself in float32); routing (per layer, of A's positions: the
    program's picks, the reference's own on its own path, the reference's
    own on the PROGRAM's input to the router, and the shortfall of what it
    followed)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import decoding
    from ray_tpu.models import qwen3_next as model
    from ray_tpu.ops import gated_delta

    hp = hyper(cfg)
    P, R, prompt, cmp_n, steps, bs, B = (sizes[k] for k in (
        "P", "rows", "prompt", "compared", "steps", "block", "slots"))
    H, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    K1, C = cfg.conv_kernel - 1, model.conv_width(cfg)
    g = gated_delta.heads_side_by_side(H, dv)
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
    W = shared + tail
    NB = 1 + W + n_short * short_blocks + again
    table = jnp.zeros((B, W), jnp.int32).at[0].set(1 + jnp.arange(W))
    table = table.at[1:, :short_blocks].set(
        1 + W + jnp.arange(n_short * short_blocks).reshape(n_short, -1))
    table_b = table[0].at[shared - again:shared].set(
        1 + W + n_short * short_blocks + jnp.arange(again))
    lengths = jnp.concatenate([jnp.asarray([prompt], jnp.int32), short_len])
    # state ids: slot s decodes from s + 1; A's checkpoint; request B's own
    slot_ids = 1 + jnp.arange(B, dtype=jnp.int32)
    CKPT, OWN_B, NS = B + 1, B + 2, B + 2
    decoded_at = short_len[:, None] + jnp.arange(steps)      # [n_short, steps]

    def shown_by(seen):
        return lambda name, *arrays: seen.__setitem__(name, arrays)

    def prefill(kind, p, x, a, b, tabs, starts, lens, slots, ends, src, dst):
        """Rows x [R, P, D] of several requests: row r holds `lens[r]`
        tokens (0: no row) from position `starts[r]` on of the request in
        slot `slots[r]` with table `tabs[r]`, whose prompt is `ends[r]`
        long; `src`, `dst`: PrefillRows.state_from / state_to.  -> (x', the
        layer's state pair, what the rule was handed (a full layer: ()), the
        router's input and picks)."""
        seen = {}
        live = lens > 0
        rows = decoding.prefill_rows(
            jnp.where(live[:, None], tabs, 0), starts, lens, live, P, bs,
            slots, B, closes=live & (starts + lens == ends),
            states=(src, dst))
        out = decoding.paged_prefill_layer(cfg, kind, p, x, a, b, rows,
                                           attn_impl, tap=shown_by(seen))
        return out[:3] + (seen.get("rule", ()), seen["route"])

    def decode(kind, p, x, a, b, lens):
        seen = {}
        rows = decoding.decode_rows(table, lens, jnp.ones((B,), bool), bs,
                                    slot_ids)
        out = decoding.paged_decode_layer(cfg, kind, p, x, a, b, rows,
                                          attn_impl, tap=shown_by(seen))
        return out[:3] + (seen.get("rule", ()), seen["route"])

    @jax.jit
    def own_input_states(shown):
        """The short requests' states by the reference's step recurrence
        over the program's OWN q, k, v, ln alpha, beta [n_short, 2 P + steps,
        H, ..] (dead prompt positions leave the state as it is), in float32
        and with the state rounded to bfloat16 after every position."""
        q, k, v, la, beta = (_f32(x) for x in shown)
        live = jnp.concatenate(
            [jnp.arange(2 * P)[None, :] < short_len[:, None],
             jnp.ones((n_short, steps), bool)], axis=1)[..., None]
        alpha, beta = jnp.where(live, jnp.exp(la), 1.0), jnp.where(live, beta,
                                                                   0.0)

        def final(keep):
            def one(q, k, v, alpha, beta):
                def step(S, x):
                    return keep(reference_step(S, *x)[0]), None
                return jax.lax.scan(
                    step, jnp.zeros((H, dk, dv), jnp.float32),
                    (q, k, v, alpha, beta))[0]
            return jax.vmap(one)(q, k, v, alpha, beta)

        return final(_same), final(_bf16)

    # one program per layer KIND, not per layer: the kind is the static part
    prefill = jax.jit(prefill, static_argnums=(0,), donate_argnums=(3, 4))
    decode = jax.jit(decode, static_argnums=(0,), donate_argnums=(3, 4))
    kinds = set(hp["kinds"])
    split = prompt - cmp_n

    def both(**kw):
        """The reference's layer over request A (states also at the
        checkpoint), and over the short requests side by side, each as
        long as it is."""
        moe = jax.jit(functools.partial(reference_moe, hp, **kw))

        def long(kind):
            mixer = jax.jit(functools.partial(reference_mixer, hp, kind,
                                              split=split, **kw))

            def layer(p, x, length, follow):  # two programs: see the docstring
                x, aux = mixer(p, x, length)
                x, picks, shortfall = moe(p, x, follow)
                return x, aux, picks, shortfall
            return layer

        def short(kind):
            fn = jax.jit(jax.vmap(
                functools.partial(reference_layer, hp, kind, **kw),
                in_axes=(None, 0, 0, 0)))
            return lambda p, x, length, follow: fn(p, x, length, follow)

        return {kind: (long(kind), short(kind)) for kind in kinds}

    plain = both()
    rounded = both(control=control) if control else None
    own_input = jax.jit(lambda p, m: reference_route(hp, p, _f32(m))[0])
    nothing = jnp.full((prompt + steps, top_k), -1, jnp.int32)
    nothing_short = jnp.full((n_short, Ls, top_k), -1, jnp.int32)

    def rows_of(toks, n_rows):
        """[n_rows * P] tokens -> embedded rows [R, P, D]."""
        toks = jnp.pad(toks, (0, (R - n_rows) * P))
        return model.embed(cfg, embed, toks.reshape(R, P))

    def call(tab, slot, start, n_rows, end, first, own, ckpt_row=-1):
        """`n_rows` whole rows of one request from `start` on: its first
        row starts from `first` (an id, 0 zeros), its last leaves the state
        in `own`, row `ckpt_row` also in CKPT."""
        live = jnp.arange(R) < n_rows
        src = jnp.full((R,), -1, jnp.int32).at[0].set(first)
        dst = jnp.zeros((R, 2), jnp.int32).at[n_rows - 1, 0].set(own)
        if ckpt_row >= 0:
            dst = dst.at[ckpt_row, 1].set(CKPT)
        return (jnp.broadcast_to(tab, (R, W)), start + jnp.arange(R) * P,
                jnp.where(live, P, 0), jnp.full((R,), slot, jnp.int32),
                jnp.full((R,), end, jnp.int32), src, dst)

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
        jnp.pad(1 + two, pad), jnp.pad(short_len[two], pad),
        jnp.pad(jnp.where(first, 0, -1), pad, constant_values=-1),
        jnp.pad(jnp.stack([jnp.where(first, 0, 2 + two),
                           jnp.zeros_like(two)], axis=1), (pad, (0, 0))))
    x_short = rows_of(short_tokens[:, :2 * P].reshape(-1), 2 * n_short)
    Rb = cmp_n // P                         # request B's rows
    call_b = call(table_b, 1, split, Rb, prompt, CKPT, OWN_B)
    xb = rows_of(tokens[split:prompt], Rb)
    xd = [model.embed(cfg, embed, jnp.concatenate([
        tokens[prompt + t][None], jnp.take_along_axis(
            short_tokens, decoded_at[:, t:t + 1], axis=1)[:, 0]])[:, None])
        for t in range(steps)]
    del embed
    full_lengths = jnp.concatenate(
        [jnp.asarray([prompt + steps], jnp.int32), short_len + steps])
    states, own, routing = [], [], []

    def of_reference(aux, auxs):
        """A's state and conv inputs at the checkpoint, then every slot's
        after the steps."""
        return (jnp.concatenate([aux["S_split"][None], aux["S"][None],
                                 auxs["S"]]),
                jnp.concatenate([aux["tail_split"][None], aux["tail"][None],
                                 auxs["tail"]]))

    for i, kind in enumerate(cfg.layer_kinds):
        p = make["layer"](i)
        here = fed = None
        follow, follow_short = nothing, nothing_short
        if control:
            with jax.default_matmul_precision("highest"):
                x_ctl, aux, follow, _ = rounded[kind][0](
                    p, x_ctl, full_lengths[0], nothing)
                xs_ctl, auxs, follow_short, _ = rounded[kind][1](
                    p, xs_ctl, full_lengths[1:], nothing_short)
            if kind[0] == "linear":
                here = of_reference(aux, auxs)
        else:
            if kind[0] == "linear":
                a = jnp.zeros(gated_delta.pool_shape(NS, H, dk, dv),
                              jnp.float32)
                b = jnp.zeros((NS + 1, K1, C), cfg.dtype)
            else:
                a = jnp.zeros(decoding.unrolled_pool_shape(cfg, NB - 1, bs),
                              cfg.dtype)
                b = jnp.zeros_like(a)
            fed_a, picks_a = [], []     # the router's input and picks of A
            for c, n in enumerate(per_call):
                xs[c], a, b, _, (m, picks) = prefill(kind, p, xs[c], a, b,
                                                     *calls_a[c])
                fed_a.append(m[:n * P])
                picks_a.append(picks[:n * P])
            x_short, a, b, shown, (_, picks) = prefill(kind, p, x_short, a,
                                                       b, *call_short)
            picks_short = picks[:2 * n_short * P].reshape(n_short, 2 * P, -1)
            shown = [[s[0, :2 * n_short * P].reshape(
                n_short, 2 * P, *s.shape[2:])] for s in shown]
            picks_decoded = []
            for t in range(steps):
                xd[t], a, b, more, (m, picks) = decode(kind, p, xd[t], a, b,
                                                       lengths + t)
                for kept, s in zip(shown, more):
                    kept.append(s[1:])
                fed_a.append(m[:1])
                picks_a.append(picks[:1])
                picks_decoded.append(picks[1:, None])
            fed, follow = jnp.concatenate(fed_a), jnp.concatenate(picks_a)
            # [n_short, 2 P + steps, k]: the prompt's rows, -1 past its
            # end, then the decoded positions where they belong
            at = jnp.arange(2 * P)[None, :, None]
            follow_short = jnp.concatenate([
                jnp.where(at < short_len[:, None, None], picks_short, -1),
                jnp.full((n_short, steps, top_k), -1, jnp.int32)],
                axis=1).at[jnp.arange(n_short)[:, None], decoded_at].set(
                    jnp.concatenate(picks_decoded, axis=1))
            if kind[0] == "linear":
                ids = jnp.concatenate([jnp.asarray([CKPT]), slot_ids])
                here = (gated_delta.from_pool(a[ids], g), b[ids])
                with jax.default_matmul_precision("highest"):
                    plain_own, bf16_own = own_input_states(
                        [jnp.concatenate(kept, axis=1) for kept in shown])
                # numbers, not arrays: six layers' states are gigabytes
                own.append((rel_rms(here[0][2:], plain_own),
                            rel_rms(bf16_own, plain_own)))
                del plain_own, bf16_own
            # request B, after the hit: slot 1, its own table and state id
            xb, a, b, _, _ = prefill(kind, p, xb, a, b, *call_b)
            del a, b, shown
        with jax.default_matmul_precision("highest"):
            x_ref, aux, own_picks, shortfall = plain[kind][0](
                p, x_ref, full_lengths[0], follow)
            xs_ref, auxs, _, _ = plain[kind][1](p, xs_ref, full_lengths[1:],
                                                follow_short)
            routing.append((follow, own_picks, None if fed is None
                            else own_input(p, fed), shortfall))
        if here is not None:
            want_S, want_tail = of_reference(aux, auxs)
            states.append((max(rel_rms(here[0], want_S),
                               rel_rms(here[1], want_tail)),
                           rel_rms(here[0][:1], want_S[:1])))
            del want_S, want_tail
        del p, here, aux, auxs, fed

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
            [x[:n].reshape(n * P, -1) for x, n in zip(xs, per_call)]
        )[-cmp_n:]
        got = (logits(head, last),
               logits(head, jnp.stack([x[:, 0] for x in xd])))
        after_hit = (logits(head, xb[:Rb].reshape(cmp_n, -1)), got[0])
    return got, want, states, after_hit, own, routing


def compare(cfg, seed: int, sizes: Dict[str, int], *, control: str = "",
            attn_impl: str = "auto") -> Dict[str, float]:
    """The program's logits, states and conv inputs against the
    reference's, the reference following the program's choice of experts,
    and its own picks against the program's.  `control` "fp8" /
    "state_bf16": the reference one precision down in the program's place
    (`reference_layer`)."""
    import jax.numpy as jnp
    got, want, states, after_hit, own, routing = logits_both_ways(
        cfg, seed, sizes, parity_tokens(cfg, seed, sizes),
        control=control, attn_impl=attn_impl)
    hits = jnp.stack([picks_agree(g, w) for g, w, _, _ in routing])
    out = {
        "state_err": max(both for both, _ in states),
        "state_err_checkpoint": max(ckpt for _, ckpt in states),
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
            for j in range(got[1].shape[1]))}
    if after_hit is not None:
        out["logits_after_hit_err"] = rel_rms(*after_hit)
        out["state_own_input_err"] = max(got for got, _ in own)
        # what that number reads for a state kept in bfloat16 (its control,
        # beside it in every line)
        out["state_own_input_err_bf16"] = min(ctl for _, ctl in own)
        out["route_own_input_mismatch_share"] = float(1.0 - jnp.mean(
            jnp.stack([picks_agree(g, o) for g, _, o, _ in routing]
                      ).astype(jnp.float32)))
    return out


def parity(where: str, cfg, seed: int, *, seq: int = 512,
           caches=None) -> Dict[str, Any]:
    """What `correct` compares in a serving cell, in the process that holds
    the chip: logits of the program's tiled paged prefill and paged decode,
    through the K/V pools of the full layers and the state pools of the
    linear ones, of a long request and a short one in every other slot,
    against the reference's full forward pass; the states themselves; a
    request answered after a hit restored from a checkpoint against itself
    answered cold; the routing over the router's whole width on the
    reference's path and on the program's own; and the paged kernel alone
    over the live pool of the first full layer.  At the engine's own widths,
    tile and table size, weights made again from the seed one layer at a
    time.  These are the functions the engine's dispatches are made of,
    driven by this check and not by the engine: admission, the radix match
    and its checkpoints and `_fused_dispatch`'s packing are covered by the
    CPU tests alone (tests/test_qwen3_next.py; PERF.md section 7)."""
    import jax
    from benchmarks.lib import reference
    if where != "serve":
        raise ValueError("kind gated-delta-moe is compared in serving cells "
                         "only")
    sizes = parity_sizes(caches)
    t0 = time.time()
    out: Dict[str, Any] = dict(compare(cfg, seed, sizes))
    out["parity_s"] = time.time() - t0
    # lib/reference.py reads a stacked [L, NB, ...] pool and gathers every
    # slot's whole table in float32: hand it the first full layer and the
    # table's columns that hold something
    full = next(i for i, k in enumerate(cfg.layer_kinds) if k[0] == "full")
    bs = caches.kp[full].shape[2]
    cols = max(1, -(-(int(caches.lengths.max()) + 1) // bs))
    layer = types.SimpleNamespace(
        kp=caches.kp[full][None], vp=caches.vp[full][None],
        block_tables=caches.block_tables[:, :cols], lengths=caches.lengths)
    out.update(reference.paged_parity(layer, cfg, seed))
    out["parity_positions"] = sizes["prompt"] + sizes["steps"] + sum(
        n + sizes["steps"] for n in short_lengths(sizes))
    if out["paged_live_positions"] <= 0:
        out["paged_err"] = NOT_COMPARED
    if jax.default_backend() == "tpu" and not out["paged_is_kernel"]:
        out["paged_err"] = NOT_COMPARED
    return out
