"""ON-DEVICE kernel validation: the pallas kernels as REAL TPU kernels.

The main suite (tests/) deliberately forces a virtual CPU platform, so
every kernel-vs-oracle test there runs the pallas interpreter.  This
lane runs the same oracles against the compiled Mosaic kernels on an
attached chip:

    python -m pytest tests_tpu/ -q        # errors without a TPU

(kept outside testpaths so `pytest tests/` stays hermetic/CPU-only).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import (attention, attention_reference,
                                   attention_reference_with_lse,
                                   flash_attention,
                                   flash_attention_with_lse)
from ray_tpu.ops.paged_attention import (paged_attention,
                                         paged_attention_kernel,
                                         paged_attention_reference)


def _inputs(b=2, hq=4, hkv=4, sq=1024, sk=1024, d=64, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (b, hq, sq, d), jnp.bfloat16)
    k = jax.random.normal(k2, (b, hkv, sk, d), jnp.bfloat16)
    v = jax.random.normal(k3, (b, hkv, sk, d), jnp.bfloat16)
    return q, k, v


@pytest.mark.parametrize("bq,bk", [(128, 128), (512, 512), (512, 1024)])
def test_flash_fwd_matches_oracle_on_tpu(bq, bk):
    q, k, v = _inputs()
    o = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=bq, block_k=bk))(q, k, v)
    o_ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(o_ref, np.float32),
        atol=2e-2, rtol=2e-2)


def test_flash_gqa_on_tpu():
    q, k, v = _inputs(hq=8, hkv=2)
    o = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True)
                )(q, k, v)
    o_ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(o_ref, np.float32),
        atol=2e-2, rtol=2e-2)


def test_flash_grads_match_oracle_on_tpu():
    q, k, v = _inputs(b=1, hq=2, hkv=2, sq=512, sk=512)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) ** 2)

    g_flash = jax.jit(jax.grad(loss(
        lambda q, k, v: flash_attention(q, k, v, causal=True)),
        argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss(
        lambda q, k, v: attention_reference(q, k, v, causal=True)),
        argnums=(0, 1, 2)))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf, np.float32), np.asarray(gr, np.float32),
            atol=5e-2, rtol=5e-2, err_msg=f"grad d{name}")


def test_flash_lse_on_tpu():
    q, k, v = _inputs(b=1, hq=2, hkv=2, sq=512, sk=512)
    o_f, lse_f = jax.jit(lambda q, k, v: flash_attention_with_lse(
        q, k, v, causal=True))(q, k, v)
    o_r, lse_r = attention_reference_with_lse(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(lse_f), np.asarray(lse_r),
                               atol=2e-2, rtol=2e-2)


def test_cross_length_prefill_on_tpu():
    # decode-style: sq < sk (prefix cache)
    q, k, v = _inputs(b=1, hq=2, hkv=2, sq=128, sk=1024)
    o = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True)
                )(q, k, v)
    o_ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(o_ref, np.float32),
        atol=2e-2, rtol=2e-2)


def test_auto_is_the_kernel_or_an_error_on_tpu():
    """On a TPU backend impl="auto" never quietly yields the reference:
    a shape the kernel takes lowers to a Mosaic call, one it cannot
    take raises with the shape and the reason."""
    q, k, v = _inputs(b=1, hq=2, hkv=2, sq=256, sk=256)
    hlo = jax.jit(lambda q, k, v: attention(q, k, v)).lower(
        q, k, v).as_text()
    assert "tpu_custom_call" in hlo
    q16, k16, v16 = _inputs(b=1, hq=2, hkv=2, sq=256, sk=256, d=16)
    with pytest.raises(ValueError, match="head_dim 16"):
        attention(q16, k16, v16)
    o = attention(q16, k16, v16, impl="reference")  # for those who mean it
    assert o.shape == q16.shape


def _paged_inputs(h, hkv, d=64, bs=16, b=8, w=16, dtype=jnp.bfloat16,
                  seed=0):
    nb = 1 + b * w
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (b, h, d), dtype)
    kp = jax.random.normal(k2, (nb, hkv, bs, d), dtype)
    vp = jax.random.normal(k3, (nb, hkv, bs, d), dtype)
    # Every slot owns w scattered pool blocks (block 0 is the engine's
    # scratch block and stays out of the tables).
    bt = np.random.RandomState(seed).permutation(
        np.arange(1, nb, dtype=np.int32)).reshape(b, w)
    # Ragged: an empty slot, one token, a block boundary on either
    # side, mid-table, and a completely full table; beyond those eight,
    # the 128-position page group's boundary on either side, empty
    # slots between live ones, and lengths drawn over the whole table.
    lens = [0, 1, bs - 1, bs, bs + 1, 5 * bs + 3, w * bs - 1, w * bs,
            127, 128, 129, 0, 0, 2 * 128, 2 * 128 + 1, 0]
    lens += list(np.random.RandomState(seed + 1).randint(
        1, w * bs + 1, max(0, b - len(lens))))
    lens = np.asarray(lens[:b], np.int32)
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(lens)


@pytest.mark.parametrize("h,hkv,d,w,b", [(32, 8, 64, 16, 8),
                                         (12, 12, 64, 16, 8),
                                         (32, 8, 128, 48, 32)],
                         ids=["gqa-32-8", "mha-12-12", "cell-d128-w48-b32"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_paged_attention_matches_reference_on_tpu(h, hkv, d, w, b, dtype):
    """The compiled paged kernel at the llama-1b (GQA 32/8) and
    gpt2-small (MHA 12/12) head layouts, D 64, block 16, and at the
    benchmark's serving cell: Mistral-7B heads of 128, 32 slots with
    48-page tables and ragged lengths (the whole-page DMA path)."""
    args = _paged_inputs(h, hkv, d=d, w=w, b=b, dtype=dtype)
    out = jax.jit(paged_attention_kernel)(*args)
    ref = paged_attention_reference(*args)
    assert out.shape == ref.shape == (b, h, d)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(                  # zero-length slots
        out[np.asarray(args[-1]) == 0], 0.0)
    np.testing.assert_allclose(out, ref, atol=2e-2, rtol=2e-2)


def test_paged_auto_is_the_kernel_on_tpu():
    args = _paged_inputs(32, 8)
    hlo = jax.jit(lambda *a: paged_attention(*a, impl="auto")).lower(
        *args).as_text()
    assert "tpu_custom_call" in hlo
