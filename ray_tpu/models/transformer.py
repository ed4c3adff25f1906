"""Decoder-only transformer family (Llama-style and GPT-2-style), pure JAX.

TPU-first design decisions:
  * Parameters are a plain pytree with a parallel tree of *logical axis
    names* (parallel/sharding.py) — pjit shards params/activations from
    rule tables; model code never mentions devices.
  * Layers run under `lax.scan` over stacked per-layer params: one
    compiled layer body regardless of depth (fast compiles, XLA-friendly).
  * bf16 activations/matmuls with f32 softmax/norm/logits; params f32.
  * Attention dispatches to the pallas flash kernel on TPU, the reference
    path elsewhere; with an `sp` mesh axis it uses ring attention.
  * `jax.checkpoint` (remat) around each layer trades FLOPs for HBM.

Reference contrast: the reference has no model zoo of its own (RLlib
models aside); Train wraps torch models.  This transformer is the
flagship workload for the Train/bench path (BASELINE.json configs 1-2).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from ray_tpu.ops import scopes
from ray_tpu.ops.attention import attention_with_lse, uses_flash
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.parallel.sharding import (_current_mesh, _mesh_trivial,
                                       constrain, shard_count, spec_for)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None      # None => MHA
    d_ff: Optional[int] = None            # None => arch default
    max_seq: int = 2048
    arch: str = "llama"                   # "llama" | "gpt2" | a module of
    # unrolled layers under models/ ("afmoe", "lfm2", "axk1",
    # "olmo_hybrid", "qwen3_next", "mimo_v2": see `layer_kinds`)
    rope_theta: Optional[float] = 500_000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16             # activation/compute dtype
    param_dtype: Any = jnp.float32
    tie_embeddings: bool = False
    remat: bool = True
    remat_policy: str = "dots"            # dots | nothing
    attn_impl: str = "auto"               # auto | flash | reference
    attn_block_q: int = 512               # flash kernel tile sizes
    attn_block_k: int = 512
    # Fused cross-entropy chunk (tokens per logits block). None => dense
    # [B,S,V] logits path (only sensible for tiny vocab/testing).
    xent_chunk: Optional[int] = 1024
    # Mixture-of-Experts (expert-parallel over the `ep` mesh axis,
    # SURVEY §2.3 TPU-build obligation; reference analog: Mixtral-style
    # expert parallelism, BASELINE config #3).  0 => dense MLP.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # A head size of its own (None => d_model // n_heads).
    d_head: Optional[int] = None
    # arch "afmoe" (models/afmoe.py; serving and `forward` only): one
    # (mixer, feed-forward) pair per layer, mixer "sliding" | "full",
    # feed-forward "dense" | "experts".  Sliding layers see the last
    # `sliding_window` positions and carry the rotary embedding; expert
    # layers route every token to `moe_top_k` of `moe_experts` experts of
    # width `moe_d_ff` by sigmoid scores (no capacity, no dropped token)
    # and add `moe_shared_experts` shared ones; `d_ff` is the dense width.
    layer_kinds: Optional[Tuple[Tuple[str, str], ...]] = None
    sliding_window: int = 0
    moe_d_ff: Optional[int] = None
    moe_shared_experts: int = 0
    moe_route_scale: float = 1.0
    # added to the sum that normalises a token's chosen scores (0: the
    # program `afmoe.route` always was)
    moe_route_eps: float = 0.0
    # the precision `afmoe.route` keeps its scores in.  Anything below
    # float32 is the comparison's control (benchmarks/kinds/lfm2-moe.py: a
    # program that scores in bfloat16 must come out not correct), never a
    # deployment's; float32 leaves the program as it always was.
    moe_score_dtype: Any = jnp.float32
    # arch "lfm2" (models/lfm2.py; serving and `forward` only): mixer
    # "conv" | "full"; a conv layer is a gated depthwise causal convolution
    # over `conv_kernel` positions, whose state is its input at the last
    # conv_kernel - 1 of them.
    conv_kernel: int = 3
    # arch "axk1" (models/axk1.py; serving and `forward` only): mixer
    # "latent", multi-head latent attention.  Queries go down to
    # `q_lora_rank` and up to `n_heads` x (`qk_nope_dim` + `qk_rope_dim`);
    # keys and values come from ONE row per position of `kv_lora_rank`
    # latent values + `qk_rope_dim` rotated ones, shared by every head, which
    # is all a position leaves in the cache; a head's value is `v_head_dim`
    # wide.  The rotary embedding is yarn's over `qk_rope_dim`:
    # `rope_factor` 1 leaves the plain frequencies.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # An expert layer that holds a SHARE of its experts (expert parallelism
    # seen from one chip): the router scores `moe_router_width` experts (0:
    # `moe_experts`, every expert is here) and picks and normalises over all
    # of them; the `moe_experts` whose weights are here are experts
    # `moe_experts_first` .. + `moe_experts` - 1, and a pick outside them is
    # routed nowhere (ops/grouped_ffn.py, models/afmoe.py `experts`).
    moe_router_width: int = 0
    moe_experts_first: int = 0
    # arch "olmo_hybrid" (models/olmo_hybrid.py; serving and `forward`
    # only): mixer "linear" | "full".  A linear layer is a gated delta rule
    # (ops/gated_delta.py) over `linear_heads` heads with keys of
    # `linear_key_dim` and values of `linear_value_dim`, its q, k and v each
    # through a causal depthwise convolution of `conv_kernel` taps; its
    # write strength is in (0, 2) where `linear_neg_eigval` (else (0, 1)).
    # `rope_theta` None: a full layer carries no rotary embedding.
    # `norm_after_branch`: a branch reads the residual stream as it is and
    # its OUTPUT is normed before it is added (False: normed input).
    linear_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_neg_eigval: bool = False
    norm_after_branch: bool = False
    # arch "qwen3_next" (models/qwen3_next.py; serving and `forward` only):
    # mixer "linear" | "full", an expert layer in every layer.  What no key
    # above expresses, each with a default that leaves every other
    # architecture's tree and programs as they are:
    # `linear_key_heads` key (and query) heads of a linear layer, each shared
    # by linear_heads / linear_key_heads value heads in a row (0: as many as
    # `linear_heads`, which counts the VALUE heads, the state's);
    # `rotary_dim` leading dims of a head that carry the rotary embedding
    # (0: the whole head);
    # `moe_score_fn` what `afmoe.route` makes of the router's logits:
    # "sigmoid", or "softmax" over the router's whole width;
    # `moe_shared_gate` the shared expert scaled by sigmoid(w . m), a scalar
    # a token;
    # `attn_output_gate` a full layer's q projection twice as wide, a head's
    # query dims then its gate dims, the heads' output times sigmoid(gate)
    # before W_o;
    # `norm_zero_centered` an RMSNorm's weight w applied as (1 + w).
    linear_key_heads: int = 0
    rotary_dim: int = 0
    moe_score_fn: str = "sigmoid"
    moe_shared_gate: bool = False
    attn_output_gate: bool = False
    norm_zero_centered: bool = False
    # arch "mimo_v2" (models/mimo_v2.py; serving and `forward` only): mixer
    # "ring" | "full".  A ring layer is sliding-window attention over the
    # last `sliding_window` positions whose keys and values a sequence keeps
    # as a ring by state id (ops/window_ring.py), with a learned sink a query
    # head in its softmax; keys are `d_head` wide, values `v_head_dim` (0:
    # `d_head`), the rotary embedding on the first `rotary_dim` dims.  What no
    # key above expresses, each with a default that leaves every other
    # architecture's tree and programs as they are:
    # `sliding_kv_heads` kv heads of a ring layer (0: `n_kv_heads`, which
    # counts a full layer's);
    # `sliding_rope_theta` a ring layer's rotary base (None: `rope_theta`);
    # `attn_value_scale` multiplies every layer's values.
    sliding_kv_heads: int = 0
    sliding_rope_theta: Optional[float] = None
    attn_value_scale: float = 1.0

    def __post_init__(self):
        if self.layer_kinds is not None:
            kinds = tuple((str(m), str(f)) for m, f in self.layer_kinds)
            object.__setattr__(self, "layer_kinds", kinds)  # hashable
        if self.arch == "afmoe":
            kinds = self.layer_kinds or ()
            if len(kinds) != self.n_layers or any(
                    m not in ("sliding", "full")
                    or f not in ("dense", "experts") for m, f in kinds):
                raise ValueError(
                    f"afmoe needs one (sliding|full, dense|experts) pair "
                    f"per layer, got {self.layer_kinds!r} for "
                    f"{self.n_layers} layers")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def router_width(self) -> int:
        return self.moe_router_width or self.moe_experts

    @property
    def ff_dim(self) -> int:
        if self.d_ff is not None:
            return self.d_ff
        if self.arch == "llama":
            # 8/3 * d rounded up to a 128 multiple: MXU-tile friendly and
            # divisible by any power-of-two tp degree.
            return ((int(self.d_model * 8 / 3) + 127) // 128) * 128
        return 4 * self.d_model


# -- presets (flagship + test) ----------------------------------------------
PRESETS: Dict[str, TransformerConfig] = {
    "tiny": TransformerConfig(vocab_size=512, d_model=128, n_layers=2,
                              n_heads=4, max_seq=256, remat=False),
    "gpt2-small": TransformerConfig(vocab_size=50_304, d_model=768,
                                    n_layers=12, n_heads=12, arch="gpt2",
                                    max_seq=1024, rope_theta=0.0),
    "llama-1b": TransformerConfig(vocab_size=128_256, d_model=2048,
                                  n_layers=16, n_heads=32, n_kv_heads=8,
                                  d_ff=8192, max_seq=8192),
    "llama-8b": TransformerConfig(vocab_size=128_256, d_model=4096,
                                  n_layers=32, n_heads=32, n_kv_heads=8,
                                  d_ff=14_336, max_seq=8192),
    # BASELINE.json config #3 ("Mixtral 8x7B MoE expert-parallel"):
    # Mixtral-shaped MoE — 8 experts, top-2 routing, expert-parallel
    # over the `ep` mesh axis.  No path can serve it (decoding._mlp has no
    # expert branch for it) and its training path drops tokens over capacity.
    "mixtral-8x7b": TransformerConfig(vocab_size=32_000, d_model=4096,
                                      n_layers=32, n_heads=32,
                                      n_kv_heads=8, d_ff=14_336,
                                      max_seq=8192, moe_experts=8,
                                      moe_top_k=2),
}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def unrolled(cfg: TransformerConfig):
    """The module that defines `cfg`'s layers, where they are unrolled by
    kind (`layer_kinds`) and not one scanned stack: models/<arch>.py,
    imported when first asked for (a training job or a worker's start
    imports none).  None for the scanned architectures."""
    if cfg.layer_kinds is None:
        return None
    import importlib
    return importlib.import_module(f"ray_tpu.models.{cfg.arch}")


def init_params(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    """Returns the parameter pytree (per-layer params stacked on axis 0;
    unrolled layers: a tuple of per-layer trees, models/<arch>.py)."""
    if cfg.layer_kinds is not None:
        return unrolled(cfg).init_params(cfg, key)
    keys = jax.random.split(key, 8)
    d, h, hkv, dh, f = (cfg.d_model, cfg.n_heads, cfg.kv_heads,
                        cfg.head_dim, cfg.ff_dim)
    L = cfg.n_layers
    pd = cfg.param_dtype

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale
                ).astype(pd)

    scale_in = 1.0 / math.sqrt(d)
    scale_out = 1.0 / math.sqrt(d) / math.sqrt(2 * L)

    def layer_init(key):
        ks = jax.random.split(key, 8)
        p = {
            "attn_norm": jnp.ones((d,), pd),
            "wq": normal(ks[0], (d, h, dh), scale_in),
            "wk": normal(ks[1], (d, hkv, dh), scale_in),
            "wv": normal(ks[2], (d, hkv, dh), scale_in),
            "wo": normal(ks[3], (h, dh, d), scale_out),
            "mlp_norm": jnp.ones((d,), pd),
            "w_down": normal(ks[5], (f, d), scale_out),
        }
        if cfg.moe_experts > 0:
            E = cfg.moe_experts
            p["w_router"] = normal(ks[7], (d, E), scale_in)
            p["w_gate"] = normal(ks[4], (E, d, f), scale_in)
            p["w_up"] = normal(ks[6], (E, d, f), scale_in)
            p["w_down"] = normal(ks[5], (E, f, d), scale_out)
            if cfg.arch == "gpt2":
                p["attn_norm_b"] = jnp.zeros((d,), pd)
                p["mlp_norm_b"] = jnp.zeros((d,), pd)
            return p
        if cfg.arch == "llama":
            p["w_gate"] = normal(ks[4], (d, f), scale_in)
            p["w_up"] = normal(ks[6], (d, f), scale_in)
        else:
            p["w_up"] = normal(ks[6], (d, f), scale_in)
            p["b_up"] = jnp.zeros((f,), pd)
            p["b_down"] = jnp.zeros((d,), pd)
            p["attn_norm_b"] = jnp.zeros((d,), pd)
            p["mlp_norm_b"] = jnp.zeros((d,), pd)
        return p

    layer_keys = jax.random.split(keys[0], L)
    layers = jax.vmap(layer_init)(layer_keys)

    params: Dict[str, Any] = {
        "tok_embed": normal(keys[1], (cfg.vocab_size, d), 1.0),
        "layers": layers,
        "final_norm": jnp.ones((d,), pd),
    }
    if cfg.arch == "gpt2":
        params["pos_embed"] = normal(keys[2], (cfg.max_seq, d), 0.01)
        params["final_norm_b"] = jnp.zeros((d,), pd)
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(keys[3], (d, cfg.vocab_size), scale_in)
    return params


def logical_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    """Pytree (matching init_params) of logical axis-name tuples."""
    if cfg.layer_kinds is not None:
        return unrolled(cfg).logical_axes(cfg)
    layer = {
        "attn_norm": ("embed",),
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
        "mlp_norm": ("embed",),
        "w_down": ("mlp", "embed"),
    }
    if cfg.moe_experts > 0:
        layer["w_router"] = ("embed", None)
        layer["w_gate"] = ("expert", "embed", "mlp")
        layer["w_up"] = ("expert", "embed", "mlp")
        layer["w_down"] = ("expert", "mlp", "embed")
        if cfg.arch == "gpt2":
            layer["attn_norm_b"] = ("embed",)
            layer["mlp_norm_b"] = ("embed",)
    elif cfg.arch == "llama":
        layer["w_gate"] = ("embed", "mlp")
        layer["w_up"] = ("embed", "mlp")
    else:
        layer["w_up"] = ("embed", "mlp")
        layer["b_up"] = ("mlp",)
        layer["b_down"] = ("embed",)
        layer["attn_norm_b"] = ("embed",)
        layer["mlp_norm_b"] = ("embed",)
    # stacked layer axis is the scan ("layers") axis
    layer = {k: ("layers",) + v for k, v in layer.items()}
    axes: Dict[str, Any] = {
        "tok_embed": ("vocab", "embed"),
        "layers": layer,
        "final_norm": ("embed",),
    }
    if cfg.arch == "gpt2":
        axes["pos_embed"] = (None, "embed")
        axes["final_norm_b"] = ("embed",)
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _norm(x, w, b, eps, rms: bool):
    xf = x.astype(jnp.float32)
    if rms:
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    else:
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * w.astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(x.dtype)


def _rope(x, positions, theta):
    """x: [B, S, H, Dh]; rotary embedding over the head dim."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-math.log(theta) *
                    jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _moe_block(cfg: TransformerConfig, mesh, h, p):
    """Expert-parallel MoE FFN (GShard-style dense dispatch).

    h: [B, S, D] (already normed) -> ([B, S, D], aux_loss scalar).

    TPU-first formulation: routing is expressed as dense einsums with a
    fixed per-expert capacity; the expert dimension is sharded over the
    `ep` mesh axis (rules: "expert" -> ep), so XLA inserts the
    all-to-all between the token-sharded and expert-sharded layouts —
    the collective the reference would run through NCCL alltoall, here
    derived from sharding constraints and ridden over ICI.
    """
    B, S, D = h.shape
    E, K = cfg.moe_experts, cfg.moe_top_k
    T = B * S
    ht = h.reshape(T, D)
    logits = jnp.einsum("td,de->te", ht.astype(jnp.float32),
                        p["w_router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)               # [T, E]
    gate_vals, gate_idx = jax.lax.top_k(probs, K)         # [T, K]
    # Normalize the selected gates to sum 1 (Mixtral-style).
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    cap = int(math.ceil(T * K * cfg.moe_capacity_factor / E))
    combine = jnp.zeros((T, E, cap), jnp.float32)
    occupancy = jnp.zeros((T, E), jnp.float32)
    for j in range(K):
        onehot = jax.nn.one_hot(gate_idx[:, j], E)        # [T, E]
        pos = jnp.cumsum(onehot, axis=0) - onehot + \
            jnp.sum(occupancy, axis=0, keepdims=True)     # [T, E]
        pos_t = jnp.sum(pos * onehot, axis=-1)            # [T]
        keep = (pos_t < cap).astype(jnp.float32)
        slot = jax.nn.one_hot(pos_t.astype(jnp.int32), cap)
        combine = combine + (gate_vals[:, j] * keep)[:, None, None] \
            * onehot[:, :, None] * slot[:, None, :]
        occupancy = occupancy + onehot * keep[:, None]

    dispatch = (combine > 0).astype(cfg.dtype)            # [T, E, cap]
    xin = jnp.einsum("tec,td->ecd", dispatch, ht)         # [E, cap, D]
    xin = constrain(xin, ("expert", None, "embed"), mesh=mesh)
    wg = p["w_gate"].astype(cfg.dtype)
    wu = p["w_up"].astype(cfg.dtype)
    wd = p["w_down"].astype(cfg.dtype)
    gate = jnp.einsum("ecd,edf->ecf", xin, wg)
    up = jnp.einsum("ecd,edf->ecf", xin, wu)
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(cfg.dtype) * up
    act = constrain(act, ("expert", None, "mlp"), mesh=mesh)
    out_e = jnp.einsum("ecf,efd->ecd", act, wd)           # [E, cap, D]
    out = jnp.einsum("tec,ecd->td", combine.astype(cfg.dtype), out_e)
    out = out.reshape(B, S, D)

    # Load-balancing auxiliary loss (Switch/GShard): fraction of tokens
    # per expert x mean router prob per expert, scaled by E.
    top1 = jax.nn.one_hot(gate_idx[:, 0], E)
    frac_tokens = jnp.mean(top1, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return out, aux


def _attention(cfg: TransformerConfig, mesh, q, k, v):
    """Causal attention for [B, H, S, Dh] q / [B, Hkv, S, Dh] k, v with
    the sequence unsharded.

    Both dispatcher outputs arrive tagged remat-saveable ("attn_out" /
    "attn_lse") by the dispatcher/custom-vjp, so the remat policy never
    re-runs the forward kernel in the backward pass; lse is consumed
    only as a bwd residual.

    XLA cannot partition a Mosaic kernel, so on a sharded mesh the
    flash path runs per shard under `shard_map` over the batch and head
    axes (attention is independent along both); the einsum reference is
    left to the SPMD partitioner like the rest of the layer."""
    def attend(q, k, v):
        return attention_with_lse(q, k, v, causal=True,
                                  impl=cfg.attn_impl,
                                  block_q=cfg.attn_block_q,
                                  block_k=cfg.attn_block_k)[0]

    if (mesh is None or _mesh_trivial(mesh)
            or not uses_flash(cfg.attn_impl)):
        return attend(q, k, v)
    q_spec = spec_for(("batch", "heads", None, None), mesh=mesh)
    kv_spec = spec_for(("batch", "kv_heads", None, None), mesh=mesh)
    return jax.shard_map(attend, mesh=mesh,
                         in_specs=(q_spec, kv_spec, kv_spec),
                         out_specs=q_spec, check_vma=False)(q, k, v)


# The stacked layer weights that `_layer_body` and `_moe_block` cast WHOLE
# to the compute dtype before a product (dense and expert alike).  Norms,
# biases and the router are read in float32; the embedding and the head
# are not layer weights, and their gradients accumulate (a scatter-add of
# many tokens into a row, a sum over the loss's trips).
PRODUCT_WEIGHTS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def with_product_weights_cast(params: Dict[str, Any],
                              cfg: TransformerConfig) -> Dict[str, Any]:
    """`params` with the layers' `PRODUCT_WEIGHTS` cast to `cfg.dtype` ONCE,
    for a caller that differentiates (train/train_step.py): the `.astype`
    calls of `_layer_body` / `_moe_block` are then no-ops, and the gradient
    of such a leaf is the one the product's transpose computed, in
    `cfg.dtype`, written once into its layer's slot of the backward scan's
    stack.  With respect to a float32 parameter that same value comes back
    widened by the cast's transpose, INSIDE the scan: a float32 stack of
    compute-precision values, twice the bytes and no more information (no
    layer's slot is accumulated into), which the caller can widen where it
    reads them instead.  A leaf that already is `cfg.dtype` is passed
    through, so a run with parameters in the compute dtype traces what it
    always did; the unrolled architectures have no training path and are
    returned as they are."""
    if cfg.layer_kinds is not None:
        return params
    layers = {k: v.astype(cfg.dtype) if k in PRODUCT_WEIGHTS else v
              for k, v in params["layers"].items()}
    return {**params, "layers": layers}


def _layer_body(cfg: TransformerConfig, mesh, x, p, positions):
    """One decoder layer. x: [B, S, D]."""
    rms = cfg.arch == "llama"
    with jax.named_scope(scopes.NORM):
        h = _norm(x, p["attn_norm"], p.get("attn_norm_b"), cfg.norm_eps, rms)
    with jax.named_scope(scopes.ATTN_QKV):
        q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(h.dtype))
        k = jnp.einsum("bsd,dhk->bshk", h, p["wk"].astype(h.dtype))
        v = jnp.einsum("bsd,dhk->bshk", h, p["wv"].astype(h.dtype))
        if cfg.arch == "llama":
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
        q = q.transpose(0, 2, 1, 3)   # [B, H, S, Dh]
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
        q = constrain(q, ("batch", "heads", "seq", None), mesh=mesh)
        k = constrain(k, ("batch", "kv_heads", "seq", None), mesh=mesh)
        v = constrain(v, ("batch", "kv_heads", "seq", None), mesh=mesh)
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        o = ring_attention(q, k, v, mesh, axis_name="sp", causal=True)
        o = jax.ad_checkpoint.checkpoint_name(o, "attn_out")
    else:
        with jax.named_scope(scopes.ATTN):
            o = _attention(cfg, mesh, q, k, v)
    with jax.named_scope(scopes.ATTN_OUT):
        o = o.transpose(0, 2, 1, 3)   # [B, S, H, Dh]
        attn_out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
        x = x + constrain(attn_out, ("batch", "seq", "embed"), mesh=mesh)

    with jax.named_scope(scopes.NORM):
        h = _norm(x, p["mlp_norm"], p.get("mlp_norm_b"), cfg.norm_eps, rms)
    if cfg.moe_experts > 0:
        moe_out, aux = _moe_block(cfg, mesh, h, p)
        x = x + constrain(moe_out, ("batch", "seq", "embed"), mesh=mesh)
        return x, aux
    with jax.named_scope(scopes.FFN_GATE_UP):
        if cfg.arch == "llama":
            gate = jnp.einsum("bsd,df->bsf", h, p["w_gate"].astype(h.dtype))
            up = jnp.einsum("bsd,df->bsf", h, p["w_up"].astype(h.dtype))
            act = jax.nn.silu(gate.astype(jnp.float32)).astype(h.dtype) * up
        else:
            up = jnp.einsum("bsd,df->bsf", h, p["w_up"].astype(h.dtype))
            up = up + p["b_up"].astype(h.dtype)
            act = jax.nn.gelu(up.astype(jnp.float32)).astype(h.dtype)
        act = constrain(act, ("batch", "seq", "mlp"), mesh=mesh)
    with jax.named_scope(scopes.FFN_DOWN):
        down = jnp.einsum("bsf,fd->bsd", act, p["w_down"].astype(act.dtype))
        if cfg.arch == "gpt2":
            down = down + p["b_down"].astype(down.dtype)
        down = jax.ad_checkpoint.checkpoint_name(down, "ffn_out")
        x = x + constrain(down, ("batch", "seq", "embed"), mesh=mesh)
    return x, jnp.zeros((), jnp.float32)


def _remat_policy(cfg: TransformerConfig):
    if cfg.remat_policy == "nothing":
        return jax.checkpoint_policies.nothing_saveable
    if cfg.remat_policy == "names":
        # Save only the d_model-sized per-layer outputs; recompute the
        # d_ff-sized gate/up/act tensors (and qkv projections) in the
        # backward pass.  At d_ff=4*d this trades ~+12% step FLOPs for a
        # ~4x cut in saved-activation HBM vs "dots" — the policy that
        # lets ~1B-param configs train on a single 16 GB v5e chip.
        return jax.checkpoint_policies.save_only_these_names(
            "attn_out", "attn_lse", "ffn_out")
    # "dots": save matmul outputs (qkv/wo/mlp projections — no batch dims
    # in those dot_generals) plus the flash-attention output, so the bwd
    # pass recomputes only cheap elementwise/norm work.
    return jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        jax.checkpoint_policies.save_only_these_names(
            "attn_out", "attn_lse"))


def forward_hidden_aux(params: Dict[str, Any], tokens: jax.Array,
                       cfg: TransformerConfig, mesh=None
                       ) -> Tuple[jax.Array, jax.Array]:
    """tokens: [B, S] int32 -> (final-norm hidden [B, S, D],
    summed MoE aux loss — zero for dense models)."""
    if cfg.layer_kinds is not None:
        return (unrolled(cfg).forward_hidden(params, tokens, cfg),
                jnp.zeros((), jnp.float32))
    B, S = tokens.shape
    # Shard the indices BEFORE the lookup: a replicated-index gather from
    # the (vocab/embed)-sharded table comes out embed-sharded, and moving
    # that to the (batch, seq)-sharded activation layout forces XLA into
    # involuntary full rematerialization (spmd_partitioner.cc:652).  With
    # (batch, seq)-sharded indices the gather lands directly in
    # activation layout and the table's shards are all-gathered once —
    # the same all-gather ZeRO-3 pays anyway when a weight is used.
    with jax.named_scope(scopes.EMBED):
        tokens = constrain(tokens, ("batch", "seq"), mesh=mesh)
        emb = constrain(params["tok_embed"], (None, None), mesh=mesh)
        x = emb[tokens].astype(cfg.dtype)
        x = constrain(x, ("batch", "seq", "embed"), mesh=mesh)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        if cfg.arch == "gpt2":
            x = x + params["pos_embed"][:S][None].astype(cfg.dtype)
        x = constrain(x, ("batch", "seq", "embed"), mesh=mesh)

    body = functools.partial(_layer_body, cfg, mesh, positions=positions)
    if cfg.remat:
        body = jax.checkpoint(body, policy=_remat_policy(cfg))

    def scan_fn(carry, layer_params):
        x, aux = carry
        x, a = body(x, layer_params)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(
        scan_fn, (x, jnp.zeros((), jnp.float32)), params["layers"])

    rms = cfg.arch == "llama"
    with jax.named_scope(scopes.NORM):
        return _norm(x, params["final_norm"], params.get("final_norm_b"),
                     cfg.norm_eps, rms), aux


def forward_hidden(params: Dict[str, Any], tokens: jax.Array,
                   cfg: TransformerConfig, mesh=None) -> jax.Array:
    """tokens: [B, S] int32 -> final-norm hidden states [B, S, D]."""
    return forward_hidden_aux(params, tokens, cfg, mesh)[0]


def _w_out(params, cfg: TransformerConfig):
    return (params["tok_embed"].T if cfg.tie_embeddings
            else params["lm_head"])


def forward(params: Dict[str, Any], tokens: jax.Array,
            cfg: TransformerConfig, mesh=None) -> jax.Array:
    """tokens: [B, S] int32 -> logits [B, S, vocab] (f32)."""
    x = forward_hidden(params, tokens, cfg, mesh)
    # bf16 operands + f32 accumulation: full MXU rate, f32-exact softmax.
    logits = jnp.einsum("bsd,dv->bsv", x.astype(cfg.dtype),
                        _w_out(params, cfg).astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
    return constrain(logits, ("batch", "seq", "vocab"), mesh=mesh)


@jax.named_scope(scopes.XENT)
def fused_cross_entropy(x: jax.Array, w_out: jax.Array, targets: jax.Array,
                        cfg: TransformerConfig) -> jax.Array:
    """Chunked softmax cross-entropy that never materializes the full
    [B, S, V] logits (f32 logits for gpt2-small at B=32,S=1k are ~6 GB).

    A scan over BLOCKS; each trip computes one block's float32 logits and
    reduces them to per-token nll, so peak memory is one block.  Under
    differentiation the SAME trip also makes the block's gradients: the
    loss is a scalar mean, so for a cotangent of 1 a block's
    `d = (softmax(logits) - onehot(target)) * [target >= 0] / (B * S)` is
    known while its logits are there.  A trip then runs three head-size
    products (logits, `dx = d . wd^T`, `dW += xc^T . d`) and the backward
    pass none: it scales the stacked `dx` and the summed `dW` by the
    loss's cotangent.  (Up to PR 54 the trip was under `jax.checkpoint`
    and the backward trip made the logits product a second time: four.)
    An undifferentiated call runs the one logits product a trip.

    The `jax.custom_vjp` sits INSIDE everything this function does before
    the scan (the layout into blocks, the padding, the head's cast and its
    gather), so their transposes stay autodiff's and the head's gradient
    is reduced once, after the scan.  The arithmetic is what the
    checkpointed scan compiled to (read off the parent's step for v5e):
    softmax, `lse` and the picked logit float32; `d` float32 and
    autodiff's own (`jax.vjp` of the block's nll with respect to its
    logits, called in the trip with the cotangent `1 / (B * S)`: the mean
    is folded into `d`, as autodiff folded it); the two gradient products
    take `d` float32 beside a `cfg.dtype` operand and accumulate float32;
    `dx` is rounded to x's dtype a trip and the head's gradient is a
    `cfg.dtype` carry over the trips.  What a caller loses: the loss
    cannot be differentiated twice, nor in forward mode (`custom_vjp`);
    nothing in `ray_tpu` does (no `hessian`, `jacfwd` or `jvp(` anywhere
    in the package).

    On one device a block is `cfg.xent_chunk` consecutive tokens of the
    flattened batch.  Under a mesh that splits "batch" b ways (read off
    the ambient mesh through the rule table) EVERY device flattens the
    rows it holds and a block is the same `xent_chunk` tokens of each of
    them, [b, chunk, D] with b constrained to "batch": `xent_chunk` stays
    the bound on one device's logits block whatever the mesh, the scanned
    dimension is never a sharded one, and no token leaves its device.
    Where "seq" is split (`sp`) the sequence is gathered first, as the
    unchunked loss in `loss_fn` has it: the sp devices of one batch shard
    compute the same blocks.  Each device's tokens are padded to whole
    blocks; padded positions carry target -1 and add nothing.

    The head is cast and gathered ONCE, before the scan: its "embed"
    (contraction) dimension is sharded under `fsdp`, and a product with a
    shard of it leaves partial logits that must be all-reduced, one
    logits-sized collective a trip.  Gathered, the tokens stay where they
    are.  The scan sees the head as [b, D, V] with b constrained to
    "batch" (every device its own copy: no bytes move), so a trip's `dW`
    is [b, D, V] too: every device sums its own tokens' over the trips,
    and the broadcast's transpose reduces them once after the scan.
    Under `tp` ("vocab") only [tokens]-sized maxima, sums and picks and
    the [tokens, D] partial `dx` cross devices.
    Invariant (tests/test_tpu_aot.py, tests/test_xent_sharding.py): NO
    COLLECTIVE OF LOGITS SIZE OR OF THE HEAD'S SIZE INSIDE THE SCAN.
    """
    B, S, D = x.shape
    b = shard_count("batch")
    N = B * S // b                                # one device's tokens
    chunk = min(cfg.xent_chunk or N, N)
    n = -(-N // chunk)
    pad = ((0, 0), (0, n * chunk - N))
    xb = jnp.pad(x.reshape(b, N, D), pad + ((0, 0),))
    tb = jnp.pad(targets.reshape(b, N), pad, constant_values=-1)
    xb = constrain(jnp.moveaxis(xb.reshape(b, n, chunk, D), 1, 0),
                   (None, "batch", None, None))
    tb = constrain(jnp.moveaxis(tb.reshape(b, n, chunk), 1, 0),
                   (None, "batch", None))
    wd = constrain(w_out.astype(cfg.dtype), (None, "vocab"))
    wb = constrain(jnp.broadcast_to(wd, (b,) + wd.shape),
                   ("batch", None, "vocab"))
    # The rules below can be traced after this call has returned: they
    # take the mesh of the call.
    pin = functools.partial(constrain, mesh=_current_mesh())

    def block_logits(xc, wb):
        return pin(jnp.einsum("bcd,bdv->bcv", xc, wb,
                              preferred_element_type=jnp.float32),
                   ("batch", None, "vocab"))

    def nll_sum(logits, tc):
        # Rows flattened: the pick's transpose then compiles to a select
        # inside the products' fusions, not to a scatter.
        logits = pin(logits.reshape(b * chunk, -1), ("batch", "vocab"))
        tc = tc.reshape(b * chunk)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, jnp.maximum(tc, 0)[:, None], axis=1)[:, 0]
        return jnp.sum(jnp.where(tc >= 0, lse - tgt, 0.0))

    @jax.custom_vjp
    def mean_nll(xb, wb, tb):
        def body(total, inp):
            return total + nll_sum(block_logits(inp[0], wb), inp[1]), None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xb, tb))
        return total / (B * S)

    def mean_nll_fwd(xb, wb, tb):
        def body(carry, inp):
            total, dw = carry
            # The block as an array of its own: left to slice it from the
            # stack inside the products' fusions, the TPU compiler runs
            # `xc^T . d` through a slower emitter (4.1 ms a trip against
            # 3.2 at train-4k-1chip's size; PERF.md, PR 55).
            xc, tc = jax.lax.optimization_barrier(inp[0]), inp[1]
            nll, pull = jax.vjp(lambda logits: nll_sum(logits, tc),
                                block_logits(xc, wb))
            d, = pull(jnp.float32(1.0) / (B * S))
            dxc = jnp.einsum("bcv,bdv->bcd", d, wb,
                             preferred_element_type=jnp.float32)
            dwc = jnp.einsum("bcd,bcv->bdv", xc, d,
                             preferred_element_type=jnp.float32)
            return ((total + nll,
                     pin(dw + dwc.astype(dw.dtype), ("batch", None, "vocab"))),
                    pin(dxc.astype(xc.dtype), ("batch", None, None)))

        (total, dw), dxb = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros_like(wb)), (xb, tb))
        return total / (B * S), (dxb, dw)

    def mean_nll_bwd(grads, ct):
        return tuple((ct * g.astype(jnp.float32)).astype(g.dtype)
                     for g in grads) + (None,)

    mean_nll.defvjp(mean_nll_fwd, mean_nll_bwd)
    return mean_nll(xb, wb, tb)


def loss_fn(params, tokens, cfg: TransformerConfig, mesh=None
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross-entropy (+ MoE load-balance aux when MoE).
    tokens: [B, S]; predicts tokens[:,1:]."""
    if cfg.layer_kinds is not None:
        # No quiet fall-back to _moe_block's capacity routing: that drops
        # tokens, and these architectures' routing drops none.
        raise NotImplementedError(
            f"arch {cfg.arch!r} has no training path: its expert layer has "
            f"no backward pass yet (serving and `forward` only)")
    targets = tokens[:, 1:]
    if cfg.xent_chunk is None:
        x, aux = forward_hidden_aux(params, tokens[:, :-1], cfg, mesh)
        logits = jnp.einsum("bsd,dv->bsv", x.astype(cfg.dtype),
                            _w_out(params, cfg).astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        loss = jnp.mean(nll)
    else:
        x, aux = forward_hidden_aux(params, tokens[:, :-1], cfg, mesh)
        loss = fused_cross_entropy(x, _w_out(params, cfg), targets, cfg)
    metrics = {"loss": loss, "ppl": jnp.exp(loss)}
    if cfg.moe_experts > 0:
        metrics["moe_aux"] = aux
        loss = loss + cfg.moe_aux_weight * aux
        metrics["total_loss"] = loss
    return loss, metrics


def num_params(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))
