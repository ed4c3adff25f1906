"""Weight-only int8 quantization (models/quantize.py).

Reference contrast: the reference has no quantization of its own — LLM
serving delegates to vLLM (doc/source/serve/doc_code/vllm_example.py).
Here the serving engine owns the weights, so int8 is a framework
feature; these tests pin (a) the per-channel error bound, (b) decode
parity between quantized and full-precision weights, (c) the memory
math that puts an 8B shape on a 16 GB chip, (d) the engine running
end-to-end on a quantized tree.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decoding, transformer as tfm
from ray_tpu.models.quantize import (QuantizedArray, init_quantized_params,
                                     kv_cache_bytes, param_bytes, quantize,
                                     quantize_params,
                                     serving_memory_report)

CFG = tfm.PRESETS["tiny"]


def test_quantize_error_bound():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 0.3
    qa = quantize(w, (0,))
    assert qa.q.dtype == jnp.int8
    assert qa.s.shape == (1, 32)
    err = jnp.abs(qa.astype(jnp.float32) - w)
    # Symmetric round-to-nearest: error <= s/2 per element, per channel.
    assert float(jnp.max(err - qa.s / 2)) <= 1e-6


def test_quantized_array_access_patterns():
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    qa = quantize(w, (1,))          # per-row scales [16, 1]
    # gather
    rows = qa[jnp.array([3, 5])]
    assert rows.shape == (2, 8)
    np.testing.assert_allclose(
        rows, np.asarray(qa.astype(jnp.float32))[[3, 5]], rtol=1e-6)
    # transpose carries scales
    qt = qa.T
    assert qt.q.shape == (8, 16) and qt.s.shape == (1, 16)
    np.testing.assert_allclose(qt.astype(jnp.float32),
                               qa.astype(jnp.float32).T, rtol=1e-6)
    # pytree round-trip (what jit tracing does)
    leaves, treedef = jax.tree.flatten(qa)
    assert len(leaves) == 2
    back = jax.tree.unflatten(treedef, leaves)
    assert isinstance(back, QuantizedArray)


def test_quantize_params_structure():
    p = tfm.init_params(CFG, jax.random.PRNGKey(0))
    qp = quantize_params(p, CFG)
    assert isinstance(qp["tok_embed"], QuantizedArray)
    assert isinstance(qp["layers"]["wq"], QuantizedArray)
    assert isinstance(qp["lm_head"], QuantizedArray)
    # norms stay full precision
    assert not isinstance(qp["layers"]["attn_norm"], QuantizedArray)
    # stacked layer axis preserved on q AND s (lax.scan slices both)
    L = CFG.n_layers
    assert qp["layers"]["wq"].q.shape[0] == L
    assert qp["layers"]["wq"].s.shape[0] == L
    assert qp["layers"]["wo"].s.shape == (L, 1, 1, CFG.d_model)
    # int8 tree is smaller
    assert param_bytes(qp) < 0.4 * param_bytes(p)


def _prefilled(params, prompts, block_size=8, max_len=32):
    """Paged caches holding `prompts`, one per slot, after the serving
    path's packed prefill, and each prompt's first token."""
    B, P = len(prompts), 8
    W = decoding.paged_table_width(max_len, block_size)
    caches = decoding.init_paged_caches(CFG, B, B * W, block_size, max_len)
    up = decoding.FusedUpload.of(P, caches)
    packed = up.empty(B)
    for row, prompt in enumerate(prompts):
        packed[row, :len(prompt)] = prompt
        packed[row, up.scalars] = (len(prompt), 0, row, up.CLOSES)
        packed[row, up.table] = 1 + row * W + np.arange(W)
    caches, toks, _ = decoding.paged_prefill_decode_packed(
        params, caches, jnp.asarray(packed), CFG, 1, P)
    return caches, toks[0]


def test_quantized_prefill_decode_close_to_fp():
    """Greedy decode over quantized weights tracks the fp32 model."""
    p = tfm.init_params(CFG, jax.random.PRNGKey(0))
    qp = quantize_params(p, CFG)
    toks = jnp.array([[5, 9, 2, 7]])
    logits = tfm.forward(p, toks, CFG)[0, -1]
    logits_q = tfm.forward(qp, toks, CFG)[0, -1]
    rel = float(jnp.max(jnp.abs(logits - logits_q))
                / (jnp.max(jnp.abs(logits)) + 1e-9))
    assert rel < 0.05, f"quantized prefill drifted {rel:.3f}"

    active = jnp.ones((2,), bool)
    prompts = [[5, 9, 2], [1, 2, 3, 4]]
    caches, t = _prefilled(p, prompts)
    caches_q, tq = _prefilled(qp, prompts)
    agree = int(jnp.sum(t == tq))
    for _ in range(7):
        caches, t, _ = decoding.paged_decode_steps(p, caches, active, CFG, 1)
        caches_q, tq, _ = decoding.paged_decode_steps(qp, caches_q, active,
                                                      CFG, 1)
        agree += int(jnp.sum(t == tq))
    # Random tiny model: near-argmax ties can flip, but the two decodes
    # must be substantially the same trajectory.
    assert agree >= 10, f"only {agree}/16 greedy tokens agree"


def test_init_quantized_params_no_f32_stage():
    qp = init_quantized_params(CFG, jax.random.PRNGKey(1))
    assert isinstance(qp["layers"]["w_up"], QuantizedArray)
    caches = decoding.init_paged_caches(CFG, 4, 16, 16, 64)
    active = jnp.ones((4,), bool)
    _, tok, _ = decoding.paged_decode_steps(qp, caches, active, CFG, 1)
    assert tok.shape == (1, 4) and tok.dtype == jnp.int32


def test_8b_memory_math_fits_v5e():
    """The north-star justification: int8 8B + KV fits 16 GB; bf16
    does not."""
    cfg = tfm.PRESETS["llama-8b"]
    q = serving_memory_report(cfg, 16, 1024, quantized=True)
    f = serving_memory_report(cfg, 16, 1024, quantized=False)
    assert q["total_gb"] < 12.0, q
    assert f["total_gb"] > 16.0, f
    assert kv_cache_bytes(cfg, 16, 1024) == q["kv_cache_gb"] * 2**30


def test_continuous_batcher_on_quantized_params():
    from ray_tpu.serve.llm import PagedBatcher
    qp = init_quantized_params(CFG, jax.random.PRNGKey(2))
    bat = PagedBatcher(qp, CFG, num_slots=2, max_len=48,
                       prompt_pad=16, decode_chunk=4,
                       pipeline_depth=2, kv_block_size=4)
    try:
        out = bat.generate([1, 2, 3], max_new=6, timeout=120)
        assert len(out["tokens"]) == 6
    finally:
        bat.stop()


def test_moe_quantized_serving_rejected():
    with pytest.raises(NotImplementedError):
        init_quantized_params(
            tfm.PRESETS["mixtral-8x7b"], jax.random.PRNGKey(0))
