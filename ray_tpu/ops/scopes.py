"""The names the programs give their parts: every `jax.named_scope` the
models enter and every `pallas_call`'s `name`, in one table.

A name is metadata: it changes no instruction, no lowered text and no
compile-cache key.  It reaches the device trace as a component of each
instruction's name stack (`tf_op` in the trace's event metadata,
`jit(f)/while/body/closed_call/attn_qkv/dot_general`), and
`ray_tpu.util.profiling.device_time` sums a program's device seconds by
the innermost component that is one of SCOPES.  A part of a layer that
enters none is summed under its jax primitive's own path: give it a name
here, enter it there.  No jax in this module: the reader imports it.
"""

# -- every architecture ------------------------------------------------------
EMBED = "embed"                    # the token (and position) embedding
NORM = "norm"                      # a layer's two norms and the final one
HEAD = "head"                      # serving: logits of the yielding rows, argmax
XENT = "xent"                      # training: transformer.fused_cross_entropy
OPTIMIZER = "optimizer"            # training: the update, grad_norm
KV_WRITE = "kv_write"              # decoding._write_rows: new K/V into the pools

# -- the dense layer (arch llama / gpt2: transformer._layer_body,
# decoding._qkv / _mlp and the two layer scans) ------------------------------
ATTN_QKV = "attn_qkv"              # q, k, v products and RoPE
ATTN = "attn"                      # scores, softmax, values: in training the
#   three flash kernels (which have no name), in serving what the engine does
#   around the paged kernels (tables, groups, rows side by side again)
ATTN_OUT = "attn_out"              # the heads' output times W_o
FFN_GATE_UP = "ffn_gate_up"        # gate and up products, the activation
FFN_DOWN = "ffn_down"              # the down product

# -- expert layers (models/afmoe.py, ops/grouped_ffn.py) ---------------------
MOE_ROUTE = "moe_route"            # router, picks, the plan, rows in and out
MOE_SHARED = "moe_shared"          # the shared expert
MOE_EXPERTS_DECODE = "moe_experts_decode"      # the grouped product's kernel,
MOE_EXPERTS_PREFILL = "moe_experts_prefill"    # named by its caller

# -- conv mixer (models/lfm2.py) ---------------------------------------------
SHORT_CONV = "short_conv"

# -- latent attention (models/axk1.py, decoding._write_latent) ---------------
MLA_Q = "mla_q"
MLA_KV = "mla_kv"
MLA_OUT = "mla_out"

# -- delta mixer (models/olmo_hybrid.py) and gated attention
# (models/qwen3_next.py) -----------------------------------------------------
DELTA_PROJ = "delta_proj"
DELTA_RULE = "delta_rule"
DELTA_OUT = "delta_out"
GATED_ATTN_Q = "gated_attn_q"
GATED_ATTN_OUT = "gated_attn_out"

# -- window ring and full attention beside it (models/mimo_v2.py) ------------
RING_ATTN_QKV = "ring_attn_qkv"
FULL_ATTN_QKV = "full_attn_qkv"
RING_ATTN = "ring_attn"
RING_OUT = "ring_out"
FULL_ATTN_OUT = "full_attn_out"

# -- the kernels (`pallas_call(name=...)`) -----------------------------------
PAGED_ATTENTION = "paged_attention"            # ops/paged_attention.py
PREFIX_ATTENTION = "prefix_attention"
MLA_PAGED_ATTENTION = "mla_paged_attention"
MLA_PREFIX_ATTENTION = "mla_prefix_attention"
GATED_DELTA_STEP = "gated_delta_step"          # ops/gated_delta.py
GATED_DELTA_CHUNK = "gated_delta_chunk"
WINDOW_RING_STEP = "window_ring_step"          # ops/window_ring.py
WINDOW_RING_CHUNK = "window_ring_chunk"

SCOPES = frozenset(v for k, v in dict(globals()).items()
                   if k.isupper() and isinstance(v, str))
