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


# -- arch "afmoe": the window, the prefix attention, the expert product -----
# At Trinity-Mini's widths: 32 query / 4 KV heads of 128, blocks of 16,
# 128 experts of 2048 x 1024.
def _afmoe_pool(B, W, seed=0, hkv=4, bs=16, d=128):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    nb = B * W + 1
    kp = jax.random.normal(k1, (nb, hkv, bs, d), jnp.bfloat16)
    vp = jax.random.normal(k2, (nb, hkv, bs, d), jnp.bfloat16)
    tables = jax.random.permutation(k3, jnp.arange(1, nb)).reshape(
        B, W).astype(jnp.int32)
    return kp, vp, tables


@pytest.mark.parametrize("window", [2048, None])
def test_windowed_paged_kernel_at_afmoe_widths_on_tpu(window):
    """Contexts inside the window, at it, one past it, far past it."""
    ctx = jnp.asarray([100, 2048, 2049, 16500, 0, 5000, 17151, 1],
                      jnp.int32)
    kp, vp, tables = _afmoe_pool(8, 1072)
    q = jax.random.normal(jax.random.PRNGKey(9), (8, 32, 128), jnp.bfloat16)
    got = paged_attention(q, kp, vp, tables, ctx, impl="kernel",
                          window=window)
    with jax.default_matmul_precision("highest"):
        want = paged_attention_reference(q, kp, vp, tables, ctx,
                                         window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


def _check_prefix_attention(kp, vp, tables, q, prefix, suffix, window,
                            attend=None):
    """The kernel (or `attend`, which takes its arguments) against plain
    attention, computed row by row over each row's own keys."""
    from ray_tpu.ops.paged_attention import prefix_attention
    N, P, H, D = q.shape
    hkv, bs = kp.shape[1], kp.shape[2]
    W = tables.shape[1]
    got = (attend or prefix_attention)(q, kp, vp, tables, prefix, suffix,
                                       impl="kernel", window=window)

    @jax.jit
    def plain(kp, vp, qn, table, pre, live):    # the pools as arguments:
        def rows(pool):                         # closed over, constants
            return pool[table].transpose(0, 2, 1, 3).reshape(
                W * bs, hkv, D).astype(jnp.float32)
        k, v = (jnp.repeat(rows(p), H // hkv, axis=1) for p in (kp, vp))
        s = jnp.einsum("phd,mhd->hpm", qn.astype(jnp.float32), k) \
            / np.sqrt(D)
        qpos = pre + jnp.arange(P)[:, None]
        j = jnp.arange(W * bs)[None, :]
        seen = (j <= qpos) & (j < pre + live)
        if window is not None:
            seen &= qpos - j < window
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hpm,mhd->phd", p, v)

    with jax.default_matmul_precision("highest"):
        for n in range(N):
            live = int(suffix[n])
            if live:
                want = plain(kp, vp, q[n], tables[n], prefix[n], suffix[n])
                np.testing.assert_allclose(
                    np.asarray(got[n, :live], np.float32),
                    np.asarray(want[:live]), atol=2e-2, rtol=2e-2)
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))


@pytest.mark.parametrize("window", [2048, None])
def test_prefix_attention_at_afmoe_widths_on_tpu(window):
    """A 512 chunk over prefixes of 0, 448, 2,000 and 16,384 positions, a
    partly filled chunk and a row that is padding."""
    N, P, H, W = 6, 512, 32, 1072
    kp, vp, tables = _afmoe_pool(N, W, seed=1)
    q = jax.random.normal(jax.random.PRNGKey(2), (N, P, H, 128),
                          jnp.bfloat16)
    _check_prefix_attention(
        kp, vp, tables, q,
        jnp.asarray([0, 448, 2000, 16384, 9000, 64], jnp.int32),
        jnp.asarray([512, 512, 300, 512, 40, 0], jnp.int32), window)


@pytest.mark.parametrize("tile", [64, 128])
@pytest.mark.parametrize("hkv,W,window", [(8, 48, None), (4, 1072, 2048),
                                          (4, 1072, None)])
def test_prefix_attention_at_tile_rows_on_tpu(hkv, W, window, tile):
    """The engine's fused prefill as it packs it (serve/llm.py): 32 rows of
    one tile, at Mistral's 32 / 8 heads of 128 under 48-column tables and
    Trinity's 32 / 4 under 1,072.  Rows 0-2 are three tiles of ONE request
    (one table; each sees the tiles before it as prefix), then a whole
    prompt of one tile, session turns of 20-70 tokens behind cached
    prefixes, and rows that are padding."""
    N, H = 32, 32
    kp, vp, tables = _afmoe_pool(N, W, seed=3, hkv=hkv)
    tables = tables.at[1:3].set(tables[0])
    q = jax.random.normal(jax.random.PRNGKey(4), (N, tile, H, 128),
                          jnp.bfloat16)
    far = W * 16 - tile - 16                   # the table's last tile
    prefix = [256, 256 + tile, 256 + 2 * tile, 0, 448, 320, 16, far, 0]
    suffix = [tile, tile, 17, tile, 45, min(70, tile), 20, tile, 1]
    pad = N - len(prefix)
    _check_prefix_attention(
        kp, vp, tables, q, jnp.asarray(prefix + [0] * pad, jnp.int32),
        jnp.asarray(suffix + [0] * pad, jnp.int32), window)


@pytest.mark.parametrize("tile", [16, 32])
@pytest.mark.parametrize("hkv,W,window", [(8, 48, None), (4, 1072, 2048),
                                          (4, 1072, None)])
def test_rows_of_one_request_attend_in_groups_on_tpu(hkv, W, window, tile):
    """The fused prefill's rows of a block or two as the engine packs them
    (serve/llm.py PREFILL_TILE), regrouped into attention rows of up to 64
    queries (models/decoding.py QueryGroups), against plain attention row
    by row: session turns of 17-70 tokens behind cached prefixes (two to
    five rows of a slot, the last partly filled, starting at a block inside
    the slot's table), a whole prompt of 200 tokens, and padding up to a
    width of 768 positions."""
    from ray_tpu.models import decoding
    slots, H = 32, 32
    kp, vp, per_slot = _afmoe_pool(slots, W, seed=5, hkv=hkv)
    far = W * 16 - 64 - 16
    turns = [(256, 39), (448, 17), (far, 64), (0, 200), (320, 70), (16, 33)]
    slot, prefix, suffix = [], [], []
    for i, (pre, n) in enumerate(turns):
        for at in range(0, n, tile):
            slot.append(i)
            prefix.append(pre + at)
            suffix.append(min(tile, n - at))
    pad = 768 // tile - len(slot)
    slot, prefix, suffix = (jnp.asarray(a + [0] * pad, jnp.int32)
                            for a in (slot, prefix, suffix))
    rows = decoding.prefill_rows(per_slot[slot], prefix, suffix, suffix > 0,
                                 tile, 16, slot, slots)
    assert np.asarray(rows.groups.suffix_lens)[:9].tolist() == [
        39, 17, 64, 64, 64, 64, 8, 64, 6]
    q = jax.random.normal(jax.random.PRNGKey(6),
                          (len(slot), tile, H, 128), jnp.bfloat16)
    real = decoding._attend_rows
    _check_prefix_attention(
        kp, vp, per_slot[slot], q, prefix, suffix, window,
        attend=lambda q, kp, vp, *_, **kw: real(q, kp, vp, rows, **kw))


@pytest.mark.parametrize("rows", [32, 2048])
def test_grouped_ffn_at_afmoe_widths_on_tpu(rows):
    """32 tokens x top-8 = 256 rows (a decode step) and 2,048 x 8 = 16,384
    rows (a prefill's), against a loop over the 128 experts."""
    from ray_tpu.ops.grouped_ffn import grouped_ffn
    E, D, F, K = 128, 2048, 1024, 8
    ks = jax.random.split(jax.random.PRNGKey(rows), 6)
    x = jax.random.normal(ks[0], (rows, D), jnp.bfloat16)
    wg, wu = (jax.random.normal(k, (E, D, F), jnp.bfloat16) / np.sqrt(D)
              for k in ks[1:3])
    wd = jax.random.normal(ks[3], (E, F, D), jnp.bfloat16) / np.sqrt(F)
    idx = jnp.argsort(jax.random.uniform(ks[4], (rows, E)), axis=1)[:, :K]
    w = jax.random.uniform(ks[5], (rows, K), minval=0.1)
    valid = jnp.arange(rows) % 7 != 3
    y, sizes = grouped_ffn(x, idx.astype(jnp.int32), w, valid, wg, wu, wd,
                           name="moe_experts_decode", impl="kernel")

    @jax.jit
    def loop(x, idx, w, valid, wg, wu, wd):
        x32 = x.astype(jnp.float32)

        def one(acc, e):
            out = (jax.nn.silu(x32 @ wg[e].astype(jnp.float32))
                   * (x32 @ wu[e].astype(jnp.float32))) \
                @ wd[e].astype(jnp.float32)
            weight = jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)
            return acc + jnp.where(valid, weight, 0.0)[:, None] * out, None
        return jax.lax.scan(one, jnp.zeros_like(x32), jnp.arange(E))[0]

    with jax.default_matmul_precision("highest"):
        want = loop(x, idx, w, valid, wg, wu, wd)
    err = np.abs(np.asarray(y, np.float32) - np.asarray(want))
    assert float(err.max()) < 0.1 and float(err.mean()) < 0.01, \
        (float(err.max()), float(err.mean()), float(np.abs(want).mean()))
    assert int(jnp.sum(sizes)) == int(jnp.sum(valid)) * K
    assert float(jnp.max(jnp.abs(y[~valid].astype(jnp.float32)))) == 0.0


@pytest.mark.parametrize("tokens,live,name", [
    (32, 0.8, "moe_experts_decode")] + [
    (rung + 32, 0.56, "moe_experts_prefill")
    for rung in (256, 640, 896, 2048)])
@pytest.mark.parametrize("config", ["trinity-mini", "lfm2"])
def test_tile_sweep_at_afmoe_and_lfm2_shapes_on_tpu(config, tokens, live,
                                                    name):
    """Every tile (16 ... 256) at a decode step's and the four rungs' shapes
    of the two configurations that hold every expert of their router (128
    of 2048 x 1024 with 8 picks; 64 of 2048 x 1536 with 4): what
    `grouped_ffn.tile_rows` is fitted from (`expert_sweep.py`)."""
    import expert_sweep as es
    es.check_rule(*es.sweep(config, tokens, live, name))


# -- heads of 64, two kv heads side by side in a row of 128 lanes -------------
# At LFM2's widths: 32 query / 8 KV heads of 64, blocks of 16, tables of
# 1,072 columns; the pool as models/decoding.py unrolled_pool_shape lays it
# out, [NB, 4, 16, 128].
def _side_by_side(pool):
    nb, hkv, bs, d = pool.shape
    return pool.reshape(nb, hkv // 2, 2, bs, d).transpose(
        0, 1, 3, 2, 4).reshape(nb, hkv // 2, bs, 2 * d)


def test_paged_kernel_over_heads_side_by_side_on_tpu():
    ctx = jnp.asarray([100, 2048, 16500, 0, 5000, 17151, 1, 33], jnp.int32)
    kp, vp, tables = _afmoe_pool(8, 1072, seed=7, hkv=8, d=64)
    q = jax.random.normal(jax.random.PRNGKey(9), (8, 32, 64), jnp.bfloat16)
    got = paged_attention(q, _side_by_side(kp), _side_by_side(vp), tables,
                          ctx, impl="kernel")
    with jax.default_matmul_precision("highest"):
        want = paged_attention_reference(q, kp, vp, tables, ctx)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_prefix_attention_over_heads_side_by_side_on_tpu():
    """Attention rows of up to 64 queries as the engine groups them: turns
    of 17-64 tokens behind prefixes of 0 to 16 k positions, and padding."""
    from ray_tpu.ops.paged_attention import prefix_attention
    N, P, H, W = 16, 64, 32, 1072
    kp, vp, tables = _afmoe_pool(N, W, seed=8, hkv=8, d=64)
    q = jax.random.normal(jax.random.PRNGKey(2), (N, P, H, 64),
                          jnp.bfloat16)
    prefix = [0, 448, 8192, 16384, 12288, 16, W * 16 - 64]
    suffix = [64, 39, 17, 64, 33, 1, 64]
    pad = N - len(prefix)
    packed = (_side_by_side(kp), _side_by_side(vp))
    _check_prefix_attention(
        kp, vp, tables, q, jnp.asarray(prefix + [0] * pad, jnp.int32),
        jnp.asarray(suffix + [0] * pad, jnp.int32), None,
        attend=lambda q, kp, vp, *a, **kw: prefix_attention(
            q, *packed, *a, **kw))


# -- the serving cells' decode calls, as the engine makes them (PR 41) ------
# 32 slots.  "lfm2" / "trinity": 1,072-column tables over an [8192, 4, 16,
# 128] pool under the agents' mix: four tenants' system prompts of 448 /
# 8,192 / 12,288 / 16,384 positions, each primed in one allocation (its
# blocks lie in a row in the pool) and shared by 8 slots, and 40-400
# positions of history a slot whose blocks are scattered.  "mistral":
# 48-column tables over [1536, 8, 16, 128], contexts log-uniform 64-768,
# every block scattered.
_CELLS = {"lfm2": (64, None), "trinity-full": (128, None),
          "trinity-window": (128, 2048), "mistral": (128, None)}
# Olmo-Hybrid's full layers under the same mix: 30 / 30 heads of 128.
_HEADS = {"olmoh": (30, 30)}
_HBM_BYTES_PER_S = 819e9            # TPU v5e (benchmarks/lib/peaks.py)


def _cell_call(cell, seed=0):
    d, window = _CELLS.get(cell, (128, None))
    H, heads = _HEADS.get(cell, (32, 4))
    rng = np.random.RandomState(seed)
    B, bs = 32, 16
    if cell == "mistral":
        W, NB, hkv = 48, 1536, 8
        lens = np.exp(rng.uniform(np.log(64), np.log(768), B)).astype(
            np.int32)
        prompts = [np.zeros(0, np.int32)] * 4
        first_free = 1
    else:
        W, NB, hkv = 1072, 8192, heads
        sizes = [448, 8192, 12288, 16384]
        starts = np.cumsum([1] + [s // bs for s in sizes])
        prompts = [np.arange(a, a + s // bs, dtype=np.int32)
                   for a, s in zip(starts, sizes)]
        first_free = int(starts[-1])
        lens = np.asarray([sizes[b % 4] + rng.randint(40, 400)
                           for b in range(B)], np.int32)
    scattered = iter(rng.permutation(np.arange(first_free, NB,
                                               dtype=np.int32)))
    bt = np.zeros((B, W), np.int32)
    for b in range(B):
        shared = prompts[b % 4]
        own = -(-int(lens[b]) // bs) - len(shared)
        bt[b, :len(shared) + own] = np.concatenate(
            [shared, [next(scattered) for _ in range(own)]])
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (B, H, d), jnp.bfloat16)
    kp = jax.random.normal(k2, (NB, hkv, bs, 128), jnp.bfloat16)
    vp = jax.random.normal(k3, (NB, hkv, bs, 128), jnp.bfloat16)
    return (q, kp, vp, jnp.asarray(bt), jnp.asarray(lens)), window


@pytest.mark.parametrize("cell", list(_CELLS))
def test_paged_kernel_at_the_cells_shapes_on_tpu(cell):
    (q, kp, vp, bt, lens), window = _cell_call(cell)
    got = np.asarray(paged_attention(q, kp, vp, bt, lens, impl="kernel",
                                     window=window), np.float32)
    assert np.isfinite(got).all()
    with jax.default_matmul_precision("highest"):
        for i in range(0, q.shape[0], 4):       # the gather is 4 rows' wide
            rows = slice(i, i + 4)
            want = paged_attention_reference(q[rows], kp, vp, bt[rows],
                                             lens[rows], window=window)
            np.testing.assert_allclose(got[rows],
                                       np.asarray(want, np.float32),
                                       atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("cell", list(_CELLS))
def test_paged_kernel_call_time_on_tpu(cell):
    """Prints (`-s`) what a call takes beside what its pages' bytes take
    at the HBM peak: 200 calls in chains of 20 inside one program, each
    call's q depending on the call before, fenced."""
    import time
    args, window = _cell_call(cell)
    lens, bs = np.asarray(args[4]), 16
    first = 0 if window is None else np.maximum(lens - window, 0) // 128 * 8
    pages = int((-(-lens // bs) - first).sum())
    page_bytes = int(np.prod(args[1].shape[1:])) * args[1].dtype.itemsize
    floor = 2 * pages * page_bytes / _HBM_BYTES_PER_S

    @jax.jit
    def chain(q, *rest):
        def call(_, q):
            o = paged_attention(q, *rest, impl="kernel", window=window)
            return q + (o * 0).astype(q.dtype)
        return jax.lax.fori_loop(0, 20, call, q)

    chain(*args).block_until_ready()
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(10):
            out = chain(*args)
        out.block_until_ready()
        times.append((time.perf_counter() - t0) / 200)
    took = min(times)
    print(f"\npaged_attention {cell}: {took * 1e6:.1f} us a call, "
          f"{2 * pages * page_bytes / 1e6:.1f} MB of pages = "
          f"{floor * 1e6:.1f} us at 819 GB/s: {100 * floor / took:.1f} %")
    assert 0 < floor / took < 1.05


def _found_shared(bt, lens, bs=16):
    """The sets of a step's slots as the engine finds them -> SharedRows."""
    from ray_tpu.ops import paged_attention as pa
    from ray_tpu.serve.llm import find_shared_prefixes
    bt, lens = np.asarray(bt), np.asarray(lens)
    found = find_shared_prefixes(
        {b: bt[b, :-(-int(lens[b]) // bs)] for b in range(len(lens))
         if lens[b]}, bs, len(lens))
    return pa.shared_rows(
        pa.SharedPrefixes(*(jnp.asarray(a) for a in found)),
        jnp.asarray(bt), jnp.asarray(lens))


@pytest.mark.parametrize("cell", ["olmoh", "trinity-full", "lfm2", "mistral"])
def test_shared_prefixes_read_once_at_the_cells_shapes_on_tpu(cell):
    """The decode call with the slots' sets (the cells' parity runs the
    layers without them, so this is the chip's only word on the merge):
    against the gather in value, against the call without sets in time
    (printed with `-s`, kept in chiprun_out/pr49/kernels.jsonl).  Mistral's
    contexts share nothing: its call with the empty sets is the branch."""
    import json
    import os
    import time
    args, _ = _cell_call(cell)
    q, kp, vp, bt, lens = args
    shared = _found_shared(bt, lens)
    sets = [(int((np.asarray(shared.place) // 8 == p).sum()), int(n))
            for p, n in enumerate(np.asarray(shared.lens)) if n]
    assert bool(sets) == (cell != "mistral")
    got = np.asarray(paged_attention(*args, impl="kernel", shared=shared),
                     np.float32)
    assert np.isfinite(got).all()
    with jax.default_matmul_precision("highest"):
        for i in range(0, q.shape[0], 4):       # the gather is 4 rows' wide
            rows = slice(i, i + 4)
            want = paged_attention_reference(q[rows], kp, vp, bt[rows],
                                             lens[rows])
            np.testing.assert_allclose(got[rows],
                                       np.asarray(want, np.float32),
                                       atol=2e-2, rtol=2e-2)

    @jax.jit
    def chain(q, kp, vp, bt, lens, *sets):
        def call(_, q):
            o = paged_attention(q, kp, vp, bt, lens, impl="kernel",
                                shared=type(shared)(*sets) if sets else None)
            return q + (o * 0).astype(q.dtype)
        return jax.lax.fori_loop(0, 20, call, q)

    took = {}
    for name, extra in (("alone", ()), ("shared", tuple(shared))):
        chain(*args, *extra).block_until_ready()
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(5):
                out = chain(*args, *extra)
            out.block_until_ready()
            times.append((time.perf_counter() - t0) / 100)
        took[name] = min(times) * 1e6
    lens = np.asarray(lens)
    page_bytes = 2 * int(np.prod(kp.shape[1:])) * kp.dtype.itemsize
    every = int((-(-lens // 16)).sum())
    read = every - sum((n - 1) * blocks // 16 for n, blocks in sets)
    floor = read * page_bytes / _HBM_BYTES_PER_S * 1e6
    print(f"\npaged_attention {cell}: {took['alone']:.1f} us a call alone, "
          f"{took['shared']:.1f} us with sets {sets}; pages {every} -> "
          f"{read}, {floor:.1f} us at 819 GB/s: "
          f"{100 * floor / took['shared']:.1f} %")
    os.makedirs("chiprun_out/pr49", exist_ok=True)
    with open("chiprun_out/pr49/kernels.jsonl", "a") as f:
        f.write(json.dumps(dict(
            what="paged_attention", cell=cell, sets=sets, alone_us=took[
                "alone"], shared_us=took["shared"], pages=every,
            pages_read=read, floor_us=floor)) + "\n")
    assert 0 < floor / took["shared"] < 1.05


def test_fused_dispatch_against_decode_only_on_tpu():
    """Prints (`-s`) and keeps (chiprun_out/pr43/fused_vs_decode.json) what
    the 256-position fused program takes beside the decode-only one, at
    Mistral-7B's widths with 16 layers, 32 live slots at the batch cell's
    contexts and a chunk of 8: the difference is what a prefill of 256
    positions adds to a dispatch whose first decode step rides in its pass
    (the two programs walk the weights eight times each)."""
    import json
    import os
    import time
    from ray_tpu.models import decoding
    from ray_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=32000, d_model=4096, n_layers=16, n_heads=32,
        n_kv_heads=8, d_ff=14336, max_seq=768, arch="llama",
        rope_theta=10000.0, norm_eps=1e-5, tie_embeddings=False,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    B, bs, max_len, NB, T, chunk = 32, 16, 768, 1536, 16, 8
    params = jax.block_until_ready(jax.jit(
        lambda key: tfm.init_params(cfg, key))(jax.random.PRNGKey(0)))
    caches = decoding.init_paged_caches(cfg, B, NB, bs, max_len)
    W = caches.block_tables.shape[1]
    rng = np.random.RandomState(0)
    lens = np.exp(rng.uniform(np.log(64), np.log(600), B)).astype(np.int32)
    held = 40                                   # blocks a live slot holds
    tables = np.zeros((B, W), np.int32)
    tables[:, :held] = 1 + np.arange(B * held).reshape(B, held)
    N = 256 // T
    up = decoding.FusedUpload.of(T, caches)._replace(sets=False)
    packed = up.empty(N)
    packed[N, up.active] = 1
    idle = np.array(packed)                     # the same program, no row
    packed[:N, :T] = rng.randint(1, 32000, (N, T))
    # one request's 256 positions in blocks of its own, flag 2 throughout:
    # it closes no slot, so all 32 ride in the pass and no new slot joins
    # the steps (both programs decode the same 32)
    packed[:N, up.scalars] = [[T, r * T, 0, up.MORE] for r in range(N)]
    packed[:N, up.table][:, :N] = 1 + B * held + np.arange(N)
    assert B * held + N <= NB and lens.max() + 2 * chunk <= held * bs

    def fresh():
        return caches._replace(
            block_tables=jnp.asarray(tables), lengths=jnp.asarray(lens),
            last_token=jnp.asarray(rng.randint(1, 32000, B), jnp.int32))

    def timed(call, reps=5):
        c = fresh()
        c = call(c)[0]
        jax.block_until_ready(c)
        times = []
        for _ in range(3):
            c = c._replace(lengths=jnp.asarray(lens))
            t0 = time.perf_counter()
            for _ in range(reps):
                c = call(c)[0]
                c = c._replace(lengths=jnp.asarray(lens))
            jax.block_until_ready(c)
            times.append((time.perf_counter() - t0) / reps)
        return min(times) * 1e3, c

    active = jnp.ones((B,), bool)
    decode_ms, caches = timed(lambda c: decoding.paged_decode_steps(
        params, c, active, cfg, chunk, attn_impl="kernel"))
    fused_ms, caches = timed(lambda c: decoding.paged_prefill_decode_packed(
        params, c, jnp.asarray(packed), cfg, chunk, T, attn_impl="kernel"))
    empty_ms, caches = timed(lambda c: decoding.paged_prefill_decode_packed(
        params, c, jnp.asarray(idle), cfg, chunk, T, attn_impl="kernel"))
    out = {"decode_only_ms": decode_ms, "fused_256_ms": fused_ms,
           "fused_256_no_row_ms": empty_ms,
           "prefill_adds_ms": fused_ms - decode_ms, "live_slots": B,
           "decode_chunk": chunk, "mean_context": float(lens.mean()),
           "device": jax.devices()[0].device_kind}
    print(f"\nfused 256 {fused_ms:.2f} ms, decode-only {decode_ms:.2f} ms: "
          f"+{fused_ms - decode_ms:.2f} ms ({json.dumps(out)})")
    os.makedirs("chiprun_out/pr43", exist_ok=True)
    with open("chiprun_out/pr43/fused_vs_decode.json", "w") as f:
        json.dump(out, f)
    # eight walks each: the prefill may add its own products, never a step
    assert decode_ms < fused_ms < decode_ms + 3 * decode_ms / chunk
