"""Model kind `afmoe` (Arcee's Trinity family, `model_type` afmoe): window
and full attention layers mixed, leading dense layers, then layers of many
small sigmoid-routed experts plus a shared one.  The same interface as
kinds/dense-llama.py, found by the configuration's `"kind"`; serving only
(the program has no training path for it, so CHECKS has no "train").

THE PLAIN REFERENCE is here (`reference_*`): the forward pass in float32
at `jax.default_matmul_precision("highest")`, no cache, no kernel, no
batching of experts (a loop over every expert, weighted by whether the
token chose it), blocked over query rows so that 17 k positions fit.  For
layer l of kind (mixer, feed-forward), N() an RMSNorm with its own weight:

    x0      = Embed[token] * sqrt(hidden_size)
    a       = N_in(x)
    q, k, v = Wq a, Wk a, Wv a;   q = N_q(q), k = N_k(k)   per head
    g       = sigmoid(Wg a)
    sliding:  rotary (theta, rotate-half, absolute position) on q and k;
              key j visible to query i  iff  j <= i and i - j < window
    full:     no positional encoding;   key j visible iff j <= i
    o       = softmax(q k^T / sqrt(head_dim) + mask) v
    x       = x + N_post_attn(Wo (g * o))
    m       = N_pre_mlp(x)
    dense:    y = Wdown(silu(Wgate m) * Wup m)
    experts:  s = sigmoid(Wr m);  S = top-k of (s + b);
              w_e = route_scale * s_e / sum_{e' in S} s_e'
              y = sum_{e in S} w_e FFN_e(m) + FFN_shared(m)
    x       = x + N_post_mlp(y)
    logits  = Whead N_final(x_L)

DEPARTURE RISKS.  The model's config.json carries score_func, route_norm,
route_scale, num_experts_per_tok, num_shared_experts, sliding_window,
layer_types, num_dense_layers, mup_enabled and head_dim.  It does NOT carry
what follows; each is as ISSUE 27's writer recalls the public modelling code
(modeling_afmoe.py), with no network here to re-read it, and each is listed
in the configuration file under `assumed`:
  1. RMSNorm of q and of k over each head's head_dim, before the rotary;
  2. the sigmoid output gate g = sigmoid(Wg a) on the attention output,
     one gate value per query-head value, before Wo;
  3. rotary on sliding layers only, none on full layers;
  4. four norms a layer, two of them on the BRANCH OUTPUT (post-attention
     and post-MLP norms applied before the residual add);
  5. the embedding multiplier sqrt(hidden_size) under mup_enabled;
  6. a selection bias b added to the scores for the top-k choice only;
  7. a key is visible while i - j < sliding_window (the query's own
     position counts as one of the window's).
"""

from __future__ import annotations

import functools
import math
import time
import types
from typing import Any, Callable, Dict, List

from benchmarks.lib import reference

# How each limit was set: PERF.md section 2, "Limits of `correct`".  Each lies
# between the sound program's largest reading and the fp8 control's smallest
# (my chip runs, PR 27: the cell's widths and sizes, 16,904 positions).
TOLERANCES: Dict[str, float] = {
    # relative RMS error of the logits over the compared rows that were
    # routed like the reference's: program 0.0099-0.0104 (bf16 activations
    # against float32), control 0.0427-0.0433
    "logits_prefill_err": 0.02,
    "logits_decode_err": 0.02,
    # the paged kernel alone over the live pool, as for dense-llama
    "paged_err": reference.TOLERANCE,
    # share of (row, expert) picks that differ from the reference's: the
    # program routes on bf16 activations, the reference on float32 ones,
    # and a pick whose score is within rounding of the k-th flips:
    # program 0.016-0.024, control 0.061-0.067
    "route_mismatch_share": 0.04,
}

CHECKS: Dict[str, tuple] = {
    "serve": ("logits_prefill_err", "logits_decode_err", "paged_err",
              "route_mismatch_share"),
}

NOT_COMPARED = 1e9      # over any limit, and finite: the line is strict JSON

MIXERS = {"sliding_attention": "sliding", "full_attention": "full"}


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------
def layer_kinds(cfg: Dict[str, Any]) -> List[List[str]]:
    """(mixer, feed-forward) per layer: layer_types gives the mixers, the
    first num_dense_layers are dense."""
    return [[MIXERS[t], "dense" if i < cfg["num_dense_layers"] else "experts"]
            for i, t in enumerate(cfg["layer_types"])]


def check(cfg: Dict[str, Any]) -> None:
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not list num_hidden_layers layers")
    if any(t not in MIXERS for t in cfg["layer_types"]):
        raise ValueError(f"layer_types other than {sorted(MIXERS)}")
    if cfg["num_attention_heads"] % cfg["num_key_value_heads"]:
        raise ValueError("query heads are not a multiple of KV heads")
    if cfg["score_func"] != "sigmoid" or not cfg["route_norm"]:
        raise ValueError("only sigmoid scores with route_norm are expressed")
    if not cfg["mup_enabled"]:
        raise ValueError("the program always scales the embedding "
                         "(mup_enabled)")
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("grouped routing (n_group > 1) is not expressed")
    if cfg.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not expressed")


def transformer_kwargs(cfg: Dict[str, Any], *, max_seq: int,
                       param_dtype: str, **extra: Any) -> Dict[str, Any]:
    check(cfg)
    kw = {
        "vocab_size": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "d_head": cfg["head_dim"],
        "d_ff": cfg["intermediate_size"],
        "max_seq": max_seq,
        "arch": "afmoe",
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "tie_embeddings": bool(cfg["tie_word_embeddings"]),
        "dtype": cfg.get("torch_dtype", "bfloat16"),
        "param_dtype": param_dtype,
        "layer_kinds": layer_kinds(cfg),
        "sliding_window": cfg["sliding_window"],
        "moe_experts": cfg["num_experts"],
        "moe_top_k": cfg["num_experts_per_tok"],
        "moe_d_ff": cfg["moe_intermediate_size"],
        "moe_shared_experts": cfg["num_shared_experts"],
        "moe_route_scale": float(cfg["route_scale"]),
        "remat": False,
    }
    kw.update(extra)
    return kw


def param_counts(cfg: Dict[str, Any]) -> Dict[str, int]:
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, fe, E = (cfg["intermediate_size"], cfg["moe_intermediate_size"],
                cfg["num_experts"])
    attn = (2 * d * h * dh          # q and the output gate
            + 2 * d * hkv * dh      # k, v
            + h * dh * d            # o
            + 2 * dh                # q and k norms
            + 4 * d)                # the layer's four norms
    dense = attn + 3 * d * f
    expert = (attn + d * E + E      # router and selection bias
              + E * 3 * d * fe
              + cfg["num_shared_experts"] * 3 * d * fe)
    n_dense = cfg["num_dense_layers"]
    embed = cfg["vocab_size"] * d
    head = 0 if cfg["tie_word_embeddings"] else d * cfg["vocab_size"]
    total = (n_dense * dense
             + (cfg["num_hidden_layers"] - n_dense) * expert
             + embed + head + d)
    return {"total": total, "input_embedding": embed, "dense_layer": dense,
            "expert_layer": expert}


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
            * cfg["num_hidden_layers"])


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    raise NotImplementedError("kind afmoe has no training path")


# ---------------------------------------------------------------------------
# cost functions of the kernels only this kind runs: fn(config, shapes)
# ---------------------------------------------------------------------------
def experts_touched_even(cfg: Dict[str, Any], rows: int) -> float:
    """Experts with at least one of `rows` tokens' picks, under even
    routing: E (1 - (1 - k / E) ^ rows)."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return E * (1.0 - (1.0 - k / E) ** rows)


def moe_experts_decode(cfg, s):
    """One call = one expert layer, one decode step: slots x k rows through
    three products of hidden x expert width; bytes = the weights of the
    experts touched + the rows in and out.  How many experts a step touches
    follows the live rows and the routing, which a cost function is not
    shown (PERF.md section 7), and a count above what the kernel moved
    reads over 100 %: so this is a FLOOR, the expectation under even
    routing with HALF the slots live (82 of 128 at 32 slots; all live:
    111).  What the engine counts is moe.experts_touched / moe.layer_steps
    (PERF.md section 5 has both)."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = s["slots"] * cfg["num_experts_per_tok"]
    flops = 2.0 * rows * 3 * d * fe
    bytes_ = 2.0 * (experts_touched_even(cfg, s["slots"] / 2) * 3 * d * fe
                    + 2 * rows * d)
    return flops, bytes_


# Per prefill dispatch of the cell the kernel is measured in
# (serve-agent-sessions, traffic/agent-sessions.json): requests admitted,
# and the uncached tokens each brings (a message, the previous reply, the
# block-rounding remainder).  ASSUMED from the traffic file's means, checked
# against prefill.chunk_tokens / prefill.chunks (PERF.md section 5): the
# harness hands a cost function the configuration and `slots` /
# `live_context` only (PERF.md section 7).
PREFIX_ROWS_PER_CALL = 15.0
PREFIX_TOKENS_PER_ROW = 40.0


def prefix_attention(cfg, s):
    """One call = one layer, one prefill dispatch.  Each admitted row's
    uncached tokens attend to the row's context: the mean live context on a
    full layer, at most the window on a sliding one; a call is the mean
    over the layer kinds.  Bytes: the K/V pages read once per row plus q
    and o."""
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    ctx = s["live_context"] / max(s["slots"], 1)
    kinds = layer_kinds(cfg)
    seen = sum(min(ctx, cfg["sliding_window"]) if m == "sliding" else ctx
               for m, _ in kinds) / len(kinds)
    q_tokens = PREFIX_ROWS_PER_CALL * PREFIX_TOKENS_PER_ROW
    flops = 2.0 * 2 * q_tokens * seen * h * dh
    bytes_ = 2.0 * (PREFIX_ROWS_PER_CALL * 2 * seen * hkv * dh
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
            "window": cfg.sliding_window, "top_k": cfg.moe_top_k,
            "route_scale": cfg.moe_route_scale,
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


def reference_route(hp, p, m):
    """m [S, hidden] float32 -> (picks [S, k], weights [S, k])."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(m @ _f32(p["w_router"]))
    _, picks = jax.lax.top_k(s + _f32(p["route_bias"]), hp["top_k"])
    chosen = jnp.take_along_axis(s, picks, axis=1)
    return picks, hp["route_scale"] * chosen / jnp.sum(chosen, axis=1,
                                                       keepdims=True)


def reference_layer(hp, kind, p, x, block: int = 256, control: bool = False,
                    wrong: str = ""):
    """x [S, hidden] float32 (positions 0..S-1) -> (x' [S, hidden], picks
    [S, k] or None).  `control`: q, k, v and the expert weights rounded to
    fp8 (e4m3), the precision below the configuration's bfloat16: what
    `correct` must refuse.  `wrong` names one deliberate fault, for the
    tests that show the limits refuse it: "window+1", "no_gate",
    "softmax_routing", "rope_on_full"."""
    import jax
    import jax.numpy as jnp
    mixer, ffn = kind
    S = x.shape[0]
    H, Hkv, D = hp["heads"], hp["kv_heads"], hp["head_dim"]
    pos = jnp.arange(S)
    a = _rmsnorm(x, p["attn_norm"], hp["eps"])
    q = jnp.einsum("sd,dhk->shk", a, _f32(p["wq"]))
    k = jnp.einsum("sd,dhk->shk", a, _f32(p["wk"]))
    v = jnp.einsum("sd,dhk->shk", a, _f32(p["wv"]))
    q = _rmsnorm(q, p["q_norm"], hp["eps"])
    k = _rmsnorm(k, p["k_norm"], hp["eps"])
    gate = jax.nn.sigmoid(jnp.einsum("sd,dhk->shk", a, _f32(p["wg"])))
    if mixer == "sliding" or wrong == "rope_on_full":
        q, k = _rotary(q, pos, hp["theta"]), _rotary(k, pos, hp["theta"])
    if control:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    window = hp["window"] + (1 if wrong == "window+1" else 0)
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
        if mixer == "sliding":
            seen &= qi[:, None] - pos[None, :] < window
        w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v_rep)

    o = jax.lax.map(one_block, (q_blocks, pos_blocks)).reshape(
        n_blocks * block, H, D)[:S]
    if wrong != "no_gate":
        o = gate * o
    x = x + _rmsnorm(jnp.einsum("shk,hkd->sd", o, _f32(p["wo"])),
                     p["post_attn_norm"], hp["eps"])
    m = _rmsnorm(x, p["mlp_norm"], hp["eps"])
    picks = None
    if ffn == "dense":
        y = _swiglu(m, p["w_gate"], p["w_up"], p["w_down"])
    else:
        picks, weights = reference_route(hp, p, m)
        if wrong == "softmax_routing":
            logits = m @ _f32(p["w_router"])
            top, picks = jax.lax.top_k(logits, hp["top_k"])
            weights = jax.nn.softmax(top, axis=-1)
        E = p["w_gate"].shape[0]
        # each token's weight for each expert (0 where it did not choose it)
        dense_w = jnp.zeros((S, E), jnp.float32).at[
            jnp.arange(S)[:, None], picks].add(weights)
        rnd = _fp8 if control else _f32

        def one_expert(y, e):
            out = _swiglu(m, rnd(p["w_gate"][e]), rnd(p["w_up"][e]),
                          rnd(p["w_down"][e]))
            return y + dense_w[:, e][:, None] * out, None

        y, _ = jax.lax.scan(one_expert, jnp.zeros_like(m), jnp.arange(E))
        if "ws_gate" in p:
            y = y + _swiglu(m, p["ws_gate"], p["ws_up"], p["ws_down"])
    return x + _rmsnorm(y, p["post_mlp_norm"], hp["eps"]), picks


def reference_embed(hp, table, tokens):
    return _f32(table[tokens]) * math.sqrt(hp["hidden"])


def reference_head(hp, params, x):
    """x [R, hidden] -> logits [R, V]; `params` holds final_norm and
    lm_head (or the tied tok_embed)."""
    w = params["lm_head"] if "lm_head" in params else params["tok_embed"].T
    return _rmsnorm(x, params["final_norm"], hp["eps"]) @ _f32(w)


def reference_logits(hp, params, tokens, block: int = 256,
                     control: bool = False, wrong: str = ""):
    """The whole model: tokens [S] -> logits [S, V] float32."""
    import jax
    with jax.default_matmul_precision("highest"):
        x = reference_embed(hp, params["tok_embed"], tokens)
        for kind, p in zip(hp["kinds"], params["layers"]):
            x, _ = reference_layer(hp, kind, p, x, block, control, wrong)
        return reference_head(hp, params, x)


def rel_rms(got, want) -> float:
    """|got - want| / |want| in the root-mean-square sense, over all
    entries: one row whose routing flipped moves it by its share, where a
    largest-entry error would read that one row."""
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
PARITY_PREFIX_CHUNKS = 32     # x 512 = a 16,384-token prefix, + 1 chunk
PARITY_DECODE_STEPS = 8


def parity_sizes(caches) -> Dict[str, int]:
    """From the engine's own shapes: chunk width P (512 at the cells' size,
    one block at a toy's), chunks of prompt, decode steps."""
    bs = caches.kp[0].shape[2]
    M = caches.block_tables.shape[1] * bs
    P = 512 if M >= 2048 else bs
    chunks = max(1, min(PARITY_PREFIX_CHUNKS + 1,
                        (M - PARITY_DECODE_STEPS - 1) // P))
    return {"P": P, "chunks": chunks, "steps": PARITY_DECODE_STEPS,
            "block": bs, "slots": int(caches.lengths.shape[0])}


def _weights(cfg, seed: int):
    """Makers of the program's own weights, a piece at a time (BenchLLM
    makes them as init_params(cfg, PRNGKey(seed % 2**31))).  The key is an
    ARGUMENT of each jitted maker: closed over, it would be a constant of
    the program and every seed would compile anew."""
    import jax
    from ray_tpu.models import afmoe
    key = jax.random.PRNGKey(seed % (2 ** 31))
    layer_key = jax.random.split(key, 8)[0]
    return {
        "layer": lambda i: jax.jit(
            lambda k: afmoe.init_layer(cfg, k, i))(layer_key),
        "embed": lambda: jax.jit(lambda k: afmoe.init_embed(cfg, k))(key),
        "head": lambda: jax.jit(lambda k: afmoe.init_head(cfg, k))(key)}


def logits_both_ways(cfg, seed: int, sizes: Dict[str, int], tokens, *,
                     control: bool = False, attn_impl: str = "auto"):
    """The same tokens through the PROGRAM and through the REFERENCE, one
    layer's weights at a time (made once, used by both, dropped).

    The program: its paged prefill and decode LAYERS (the functions the
    engine's dispatches are made of: models/decoding.py paged_prefill_layer
    / paged_decode_layer): `chunks` chunks of P tokens through a 4-row
    prefill (one live row) against the blocks the earlier chunks wrote,
    then `steps` decode steps of every slot, each slot a copy of the same
    sequence over shared prefix blocks and a tail block of its own.  With
    `control` the reference at fp8 stands in the program's place.

    -> (got, want), each (logits of the last chunk [P, V], of the decode
    positions [steps, V], picks [expert layers][P + steps, k])."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import afmoe, decoding

    hp = hyper(cfg)
    P, chunks, steps, bs, B = (sizes[k] for k in
                               ("P", "chunks", "steps", "block", "slots"))
    prompt = chunks * P
    make = _weights(cfg, seed)
    shared = prompt // bs                   # whole blocks of the prompt
    tail = -(-(steps + 1) // bs)            # blocks a slot's decode fills
    NB = 1 + shared + B * tail
    table = jnp.concatenate([
        jnp.broadcast_to(1 + jnp.arange(shared), (B, shared)),
        1 + shared + jnp.arange(B * tail).reshape(B, tail)],
        axis=1).astype(jnp.int32)
    N = min(4, B)
    valid = jnp.arange(N) == 0
    table_rows = jnp.where(valid[:, None], table[:N], 0)

    def prefill(kind, p, x, kp, vp, done):
        picks = []
        rows = decoding.prefill_rows(
            table_rows, jnp.full((N,), done, jnp.int32),
            jnp.full((N,), P, jnp.int32), valid, P, bs)
        out = decoding.paged_prefill_layer(
            cfg, kind, p, x, kp, vp, rows, attn_impl, tap=picks.append)
        return out[:3] + (picks[0][:P] if picks else None,)

    def decode(kind, p, x, kp, vp, length):
        picks = []
        rows = decoding.decode_rows(
            table, jnp.full((B,), length, jnp.int32), jnp.ones((B,), bool),
            bs)
        out = decoding.paged_decode_layer(
            cfg, kind, p, x, kp, vp, rows, attn_impl, tap=picks.append)
        return out[:3] + (picks[0][:1] if picks else None,)

    # one program per layer KIND, not per layer: the kind is the static part
    prefill = jax.jit(prefill, static_argnums=(0,), donate_argnums=(3, 4))
    decode = jax.jit(decode, static_argnums=(0,), donate_argnums=(3, 4))
    plain = {kind: jax.jit(functools.partial(reference_layer, hp, kind))
             for kind in set(hp["kinds"])}
    rounded = {kind: jax.jit(functools.partial(reference_layer, hp, kind,
                                               control=True))
               for kind in set(hp["kinds"])} if control else {}

    with jax.default_matmul_precision("highest"):
        embed = make["embed"]()
        x_ref = reference_embed(hp, embed, tokens[:prompt + steps])
    x_ctl = x_ref
    xs = [afmoe.embed(cfg, embed, jnp.where(
        valid[:, None], tokens[None, c * P:(c + 1) * P], 0))
        for c in range(chunks)]
    xd = [afmoe.embed(cfg, embed, jnp.broadcast_to(
        tokens[prompt + t], (B, 1))) for t in range(steps)]
    del embed
    got_picks, want_picks = [], []
    for i, kind in enumerate(cfg.layer_kinds):
        p = make["layer"](i)
        with jax.default_matmul_precision("highest"):
            x_ref, picks = plain[kind](p, x_ref)
            if picks is not None:
                want_picks.append(picks[prompt - P:])
            if control:
                x_ctl, picks = rounded[kind](p, x_ctl)
                if picks is not None:
                    got_picks.append(picks[prompt - P:])
        if not control:
            kp = jnp.zeros((NB, cfg.kv_heads, bs, cfg.head_dim), cfg.dtype)
            vp = jnp.zeros_like(kp)
            picks = []
            for c in range(chunks):
                xs[c], kp, vp, pk = prefill(kind, p, xs[c], kp, vp, c * P)
            if pk is not None:
                picks.append(pk)
            for t in range(steps):
                xd[t], kp, vp, pk = decode(kind, p, xd[t], kp, vp,
                                           prompt + t)
                if pk is not None:
                    picks.append(pk)
            if picks:
                got_picks.append(jnp.concatenate(picks, axis=0))
            del kp, vp
        del p
    # weights are ARGUMENTS of every jitted function here: one closed over
    # would be compiled in as a constant, on the host
    head = make["head"]()
    with jax.default_matmul_precision("highest"):
        ref_head = jax.jit(lambda head, x: reference_head(hp, head, x))
        want = ref_head(head, x_ref[prompt - P:])
        got = ref_head(head, x_ctl[prompt - P:]) if control else None
    if not control:
        logits = jax.jit(lambda head, x: afmoe.logits(cfg, head, x))
        got = jnp.concatenate([
            logits(head, xs[-1][0]),
            logits(head, jnp.stack([x[0, 0] for x in xd]))])
    return ((got[:P], got[P:], got_picks), (want[:P], want[P:], want_picks))


def parity_tokens(cfg, seed: int, sizes: Dict[str, int]):
    import jax
    n = sizes["chunks"] * sizes["P"] + sizes["steps"]
    return jax.random.randint(jax.random.PRNGKey((seed + 1) % (2 ** 31)),
                              (n,), 0, cfg.vocab_size)


def compare(cfg, seed: int, sizes: Dict[str, int], *, control: bool = False,
            attn_impl: str = "auto") -> Dict[str, float]:
    """The program's logits against the reference's (`control`: the
    reference at fp8 in the program's place).  A token whose routing
    differs in some layer (a score within rounding of the k-th) computes
    another function from there on: such rows are counted by
    `route_mismatch_share` and left out of the logits' error, which reads
    the rows that were routed alike."""
    import jax.numpy as jnp
    got, want = logits_both_ways(cfg, seed, sizes,
                                 parity_tokens(cfg, seed, sizes),
                                 control=control, attn_impl=attn_impl)
    P = sizes["P"]
    hits = jnp.stack([picks_agree(g, w) for g, w in zip(got[2], want[2])])
    alike = hits.all(axis=(0, 2))               # [P + steps] rows
    out = {"route_mismatch_share": float(1.0 - jnp.mean(
        hits.astype(jnp.float32))),
        "rows_routed_alike_share": float(jnp.mean(
            alike.astype(jnp.float32)))}
    for name, g, w, rows in (("logits_prefill_err", got[0], want[0],
                              alike[:P]),
                             ("logits_decode_err", got[1], want[1],
                              alike[P:])):
        # at least an eighth of the rows (and one) must have been routed
        # alike, or the error read nothing
        out[name] = (rel_rms(g[rows], w[rows])
                     if int(rows.sum()) >= max(1, rows.shape[0] // 8)
                     else NOT_COMPARED)
    return out


def parity(where: str, cfg, seed: int, *, seq: int = 512,
           caches=None) -> Dict[str, Any]:
    """What `correct` compares in a serving cell, in the process that holds
    the chip: logits of the program's chunked paged prefill and paged
    decode against the reference's full forward pass, at the engine's own
    widths and table size, weights made again from the seed one layer at a
    time (two copies of them do not fit); and the paged kernel alone over
    the live pool, as for dense-llama."""
    import jax
    if where != "serve":
        raise ValueError("kind afmoe is compared in serving cells only")
    sizes = parity_sizes(caches)
    t0 = time.time()
    out: Dict[str, Any] = dict(compare(cfg, seed, sizes))
    out["parity_s"] = time.time() - t0
    # lib/reference.py reads a stacked [L, NB, ...] pool and gathers every
    # slot's whole table in float32: hand it layer 0 (a sliding layer; the
    # live contexts are shorter than the window) and the table's columns
    # that hold something (at this cell's 1,072 columns the gather is 9 GB)
    bs = caches.kp[0].shape[2]
    cols = max(1, -(-(int(caches.lengths.max()) + 1) // bs))
    layer0 = types.SimpleNamespace(
        kp=caches.kp[0][None], vp=caches.vp[0][None],
        block_tables=caches.block_tables[:, :cols], lengths=caches.lengths)
    out.update(reference.paged_parity(layer0, cfg, seed))
    out["parity_positions"] = sizes["chunks"] * sizes["P"] + sizes["steps"]
    if out["paged_live_positions"] <= 0:
        out["paged_err"] = NOT_COMPARED
    if jax.default_backend() == "tpu" and not out["paged_is_kernel"]:
        out["paged_err"] = NOT_COMPARED
    return out
