"""Attention kernel tests: pallas flash (interpret mode on CPU) against
the reference oracle — forward and gradients, causal and GQA."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import (attention, attention_reference,
                                   flash_attention)


def _inputs(b=2, hq=4, hkv=4, sq=256, sk=256, d=64, dtype=jnp.float32,
            seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, hq, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, sk, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, sk, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_reference(causal):
    q, k, v = _inputs()
    out_ref = attention_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, out_ref, atol=2e-5, rtol=2e-5)


def test_flash_gqa():
    q, k, v = _inputs(hq=8, hkv=2)
    out_ref = attention_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, out_ref, atol=2e-5, rtol=2e-5)


def test_flash_multiblock():
    # More than one k block exercises the online-softmax accumulation.
    q, k, v = _inputs(sq=384, sk=384, d=64)
    out_ref = attention_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    np.testing.assert_allclose(out, out_ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match(causal):
    q, k, v = _inputs(b=1, hq=2, hkv=2, sq=256, sk=256, d=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            gf, gr, atol=5e-4, rtol=5e-4,
            err_msg=f"grad d{name} mismatch")


def test_flash_gradients_gqa():
    q, k, v = _inputs(b=1, hq=4, hkv=2, sq=256, sk=256, d=64)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(attention_reference), argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(gf, gr, atol=5e-4, rtol=5e-4,
                                   err_msg=f"grad d{name} mismatch")


# ---------------------------------------------------------------------------
# Review regressions: cross-length causal, shape validation, lse gradients
# ---------------------------------------------------------------------------
def test_flash_cross_length_causal():
    """Causal with sq < sk (kv-cache prefill shape): triangle must be
    bottom-right aligned, matching the reference oracle."""
    q, k, v = _inputs(sq=128, sk=256)
    out = flash_attention(q, k, v, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_cross_length_causal_grads():
    """dk/dv for key blocks beyond the last query block must be exact
    (regression: stale accumulator wrote garbage for sk > sq)."""
    q, k, v = _inputs(b=1, hq=2, hkv=2, sq=128, sk=384)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(attention_reference), argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(gf, gr, atol=5e-4, rtol=5e-4,
                                   err_msg=f"grad d{name} mismatch")


def test_flash_rejects_bad_shapes():
    import pytest
    q, k, v = _inputs(sq=192, sk=192)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v, causal=True)
    q2, k2, v2 = _inputs(sq=256, sk=128)
    with pytest.raises(ValueError, match="sq <= sk"):
        flash_attention(q2, k2, v2, causal=True)


def test_flash_with_lse_matches_and_differentiates():
    from ray_tpu.ops.attention import (attention_reference_with_lse,
                                       flash_attention_with_lse)

    q, k, v = _inputs(b=1, hq=2, hkv=2, sq=256, sk=256, d=64)
    o_f, lse_f = flash_attention_with_lse(q, k, v, causal=True)
    o_r, lse_r = attention_reference_with_lse(q, k, v, causal=True)
    np.testing.assert_allclose(o_f, o_r, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse_f, lse_r, atol=2e-5, rtol=2e-5)

    # Loss that uses BOTH outputs exercises the dlse path of the VJP.
    def loss(fn):
        def inner(q, k, v):
            o, lse = fn(q, k, v, causal=True)
            return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))
        return inner

    g_f = jax.grad(loss(flash_attention_with_lse),
                   argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss(attention_reference_with_lse),
                   argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_f, g_r, "qkv"):
        np.testing.assert_allclose(gf, gr, atol=5e-4, rtol=5e-4,
                                   err_msg=f"grad d{name} (lse path)")


def lower_for_tpu(fn, *args) -> str:
    """StableHLO of `fn` lowered for the TPU platform from this CPU
    host: the Pallas kernels take their compiled (Mosaic) branch, so a
    block shape the TPU lowering refuses fails here, without a chip."""
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("d", [64, 128])
def test_flash_kernels_lower_for_tpu(d):
    q = jax.ShapeDtypeStruct((1, 4, 256, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 2, 256, d), jnp.bfloat16)
    hlo = lower_for_tpu(
        jax.grad(lambda q, k, v: flash_attention(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)), q, kv, kv)
    # forward, dk/dv and dq: compiled kernels, not interpreter expansions
    assert hlo.count("tpu_custom_call") == 3


def test_auto_off_tpu_is_the_reference_and_bad_impl_raises():
    q, k, v = _inputs(sq=48, sk=48, d=16)    # a shape flash refuses
    np.testing.assert_allclose(
        attention(q, k, v), attention_reference(q, k, v),
        atol=1e-6, rtol=1e-6)
    with pytest.raises(ValueError, match="head_dim 16"):
        attention(q, k, v, impl="flash")     # asked for: loud
    with pytest.raises(ValueError, match="unknown attention impl"):
        attention(q, k, v, impl="pallas")
