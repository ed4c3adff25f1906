"""ON-DEVICE: the kernels of the cell serve-qw3n-agent-sessions at the shapes
arch "qwen3_next" brings, none of which had run before: `gated_delta_step`
and `gated_delta_chunk` at 32 heads of [128, 128] (a head a row of lanes: no
pair masks), the two paged kernels at 16 query / 2 kv heads of 256, and the
grouped expert product over 128 held experts of 2048 x 512 of a router 512
wide, for a decode step's 64 tokens, a fused pass's 650 and the pass the cell
mostly runs (2,048 positions + 64 slots' rows, 56 % of them live); each
against its plain-JAX form.  What a call takes beside what its bytes take at
the HBM peak is printed (`-s`) and kept in chiprun_out/pr51/kernels.jsonl
(the expert product's, and its tile sweep: chiprun_out/pr52/kernels.jsonl,
`expert_sweep.py`).

    python -m pytest tests_tpu/test_qw3n_kernels_on_device.py -q -s
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import expert_sweep as es
from ray_tpu.ops import gated_delta as gd
from ray_tpu.ops import grouped_ffn as gf
from ray_tpu.ops import paged_attention as pa

_HBM_BYTES_PER_S = 819e9            # TPU v5e (benchmarks/lib/peaks.py)
H, DK, DV, NS, C, SLOTS = 32, 128, 128, 256, 16, 64
STATE_BYTES = H * DK * DV * 4
OUT = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                   "chiprun_out", "pr51")


def _keep(name, record):
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "kernels.jsonl"), "a") as f:
        f.write(json.dumps(dict(record, what=name)) + "\n")


def _inputs(shape, seed):
    """q, k (L2-normed), v, ln alpha in (ln 0.2, 0), beta in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(key):
        x = jax.random.normal(key, shape + (H, DK), jnp.float32)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return (unit(ks[0]) * DK ** -0.5, unit(ks[1]),
            jax.random.normal(ks[2], shape + (H, DV), jnp.float32),
            jnp.log(jax.random.uniform(ks[3], shape + (H,), jnp.float32,
                                       0.2, 0.999)),
            jax.random.uniform(ks[4], shape + (H,), jnp.float32, 0.0, 1.0))


def _pool(seed=0):
    assert gd.pool_shape(NS, H, DK, DV) == (NS + 1, H, DK, DV)
    return jax.random.normal(jax.random.PRNGKey(seed),
                             gd.pool_shape(NS, H, DK, DV), jnp.float32)


def _timed(chain, *args, calls=20, repeats=3, launches=5):
    jax.block_until_ready(chain(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(launches):
            out = chain(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / (launches * calls))
    return min(times)


def test_gated_delta_step_at_whole_heads_on_tpu():
    pool = _pool()
    ids = jnp.arange(1, SLOTS + 1, dtype=jnp.int32).at[5].set(0).at[9].set(0)
    q, k, v, la, beta = _inputs((SLOTS,), 1)
    live = (ids > 0)[:, None]
    la, beta = jnp.where(live, la, 0), jnp.where(live, beta, 0)
    o, after = gd.gated_delta_step(pool, ids, q, k, v, la, beta,
                                   impl="kernel")
    want_o, want = gd.gated_delta_step(pool, ids, q, k, v, la, beta,
                                       impl="reference")
    rows = np.asarray(ids) > 0
    np.testing.assert_allclose(np.asarray(o)[rows], np.asarray(want_o)[rows],
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(after)[1:], np.asarray(want)[1:],
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(after)[SLOTS + 1:],
                                  np.asarray(pool)[SLOTS + 1:])

    @jax.jit
    def chain(pool, q):
        def call(_, c):
            pool, q = c
            o, pool = gd.gated_delta_step(pool, ids, q, k, v, la, beta,
                                          impl="kernel")
            return pool, q + (o[..., :DK] * 0)
        return jax.lax.fori_loop(0, 20, call, (pool, q))

    took = _timed(chain, pool, q)
    least = SLOTS * 2 * STATE_BYTES / _HBM_BYTES_PER_S
    print(f"\ngated_delta_step {SLOTS} slots of [32, 128, 128]: "
          f"{took * 1e6:.1f} us a call, the states' bytes {least * 1e6:.1f} "
          f"us ({100 * least / took:.1f} %)")
    _keep("gated_delta_step", dict(slots=SLOTS, us=took * 1e6,
                                   bytes_share=least / took))
    assert 0 < least / took < 1.05


def _twenty_four_requests():
    """48 rows of 24 requests (a window's fused dispatch at 64 slots: a
    ~32-token suffix is two rows, the last partial), restored from
    checkpoints 100.., left in slots' ids 1.. and in fresh checkpoints 140..
    at the row before the last; 8 rows of padding behind them."""
    src, dst, live = [], [], []
    for r in range(24):
        src += [100 + r, -1]
        dst += [[0, 140 + r], [1 + r, 0]]
        live += [C, 5 + r % 9]
    pad = 56 - len(src)
    return (jnp.asarray(src + [-1] * pad, jnp.int32),
            jnp.asarray(dst + [[0, 0]] * pad, jnp.int32),
            jnp.asarray(live + [0] * pad, jnp.int32))


def test_gated_delta_chunk_at_whole_heads_on_tpu():
    pool = _pool(3)
    src, dst, live = _twenty_four_requests()
    rows, requests = int(src.shape[0]), 24
    q, k, v, la, beta = _inputs((rows, C), 4)
    on = (jnp.arange(C)[None, :] < live[:, None])[..., None]
    la, beta = jnp.where(on, la, 0), jnp.where(on, beta, 0)
    o, after = gd.gated_delta_chunk(pool, src, dst, q, k, v, la, beta,
                                    impl="kernel")
    want_o, want = gd.gated_delta_chunk(pool, src, dst, q, k, v, la, beta,
                                        impl="reference")
    mask = np.asarray(on)[..., None]
    np.testing.assert_allclose(np.where(mask, np.asarray(o), 0),
                               np.where(mask, np.asarray(want_o), 0),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(after)[1:], np.asarray(want)[1:],
                               atol=1e-4, rtol=1e-4)

    @jax.jit
    def chain(pool, q):
        def call(_, c):
            pool, q = c
            o, pool = gd.gated_delta_chunk(pool, src, dst, q, k, v, la, beta,
                                           impl="kernel")
            return pool, q + (o[..., :DK] * 0)
        return jax.lax.fori_loop(0, 20, call, (pool, q))

    took = _timed(chain, pool, q)
    moved = requests * 3 * STATE_BYTES + rows * 4 * H * (
        3 * C * DK + C * C + 2 * C * DV)
    least = moved / _HBM_BYTES_PER_S
    print(f"\ngated_delta_chunk 24 requests: {rows} rows, {took * 1e6:.1f} us "
          f"a call (its batched jnp operands included), "
          f"{took / rows * 1e6:.2f} us a row; states and operands at the HBM "
          f"peak {least * 1e6:.1f} us ({100 * least / took:.1f} %)")
    _keep("gated_delta_chunk", dict(rows=rows, us=took * 1e6,
                                    bytes_share=least / took))
    assert 0 < least / took < 1.05


# -- the two paged kernels at 16 query / 2 kv heads of 256 --------------------
HEADS, KV, D, BS, W, NB = 16, 2, 256, 16, 1072, 8192


def _agents_tables(B, rng):
    """The agents' mix at B slots: four tenants' system prompts of 448 /
    8,192 / 12,288 / 16,384 positions, each primed in one allocation and
    shared by B / 4 slots, and 40-400 positions of history a slot whose
    blocks are scattered."""
    sizes = [448, 8192, 12288, 16384]
    starts = np.cumsum([1] + [s // BS for s in sizes])
    prompts = [np.arange(a, a + s // BS, dtype=np.int32)
               for a, s in zip(starts, sizes)]
    lens = np.asarray([sizes[b % 4] + rng.randint(40, 400)
                       for b in range(B)], np.int32)
    scattered = iter(rng.permutation(np.arange(int(starts[-1]), NB,
                                               dtype=np.int32)))
    bt = np.zeros((B, W), np.int32)
    for b in range(B):
        shared = prompts[b % 4]
        own = -(-int(lens[b]) // BS) - len(shared)
        bt[b, :len(shared) + own] = np.concatenate(
            [shared, [next(scattered) for _ in range(own)]])
    return jnp.asarray(bt), lens


def _kv_pools(seed=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return tuple(jax.random.normal(k, (NB + 1, KV, BS, D), jnp.bfloat16)
                 for k in ks)


def test_paged_attention_at_heads_of_256_on_tpu():
    rng = np.random.RandomState(0)
    bt, lens = _agents_tables(SLOTS, rng)
    lens[5], lens[9] = 0, 1
    positions = int(lens.sum())
    lens = jnp.asarray(lens)
    kp, vp = _kv_pools()
    q = jax.random.normal(jax.random.PRNGKey(6), (SLOTS, HEADS, D),
                          jnp.bfloat16)
    got = np.asarray(pa.paged_attention(q, kp, vp, bt, lens, impl="kernel"),
                     np.float32)
    with jax.default_matmul_precision("highest"):
        for i in range(0, SLOTS, 8):
            rows = slice(i, i + 8)
            want = pa.paged_attention_reference(q[rows], kp, vp, bt[rows],
                                                lens[rows])
            np.testing.assert_allclose(got[rows], np.asarray(want, np.float32),
                                       atol=2e-2, rtol=2e-2)

    @jax.jit
    def chain(q, kp, vp, bt, lens):
        def call(_, q):
            o = pa.paged_attention(q, kp, vp, bt, lens, impl="kernel")
            return q + (o * 0).astype(q.dtype)
        return jax.lax.fori_loop(0, 20, call, q)

    took = _timed(chain, q, kp, vp, bt, lens)
    least = positions * 2 * KV * D * 2 / _HBM_BYTES_PER_S
    print(f"\npaged_attention 16 / 2 heads of 256, {SLOTS} slots, no sets: "
          f"{took * 1e6:.1f} us a call, {positions} positions: K/V at the "
          f"HBM peak {least * 1e6:.1f} us ({100 * least / took:.1f} %); ring "
          f"{pa._ring_shape(W, KV, BS, D, 2)}")
    _keep("paged_attention_256", dict(us=took * 1e6, positions=positions,
                                      bytes_share=least / took))
    assert 0 < least / took < 1.05


def test_prefix_attention_at_heads_of_256_on_tpu():
    """24 requests' suffixes of ~33 tokens (attention rows of 64 queries)
    over their 9 k contexts."""
    rng = np.random.RandomState(1)
    N, P, live_n = 28, 64, 24
    bt, lens = _agents_tables(N, rng)
    pre = jnp.asarray(lens // BS * BS - 32, jnp.int32).at[live_n:].set(0)
    suf = jnp.asarray([33 + i % 9 for i in range(N)], jnp.int32
                      ).at[live_n:].set(0)
    kp, vp = _kv_pools(7)
    q = jax.random.normal(jax.random.PRNGKey(8), (N, P, HEADS, D),
                          jnp.bfloat16)
    got = np.asarray(pa.prefix_attention(q, kp, vp, bt, pre, suf,
                                         impl="kernel"), np.float32)
    live = (np.arange(P)[None, :] < np.asarray(suf)[:, None])[..., None, None]
    with jax.default_matmul_precision("highest"):
        for i in range(0, live_n, 4):
            rows = slice(i, i + 4)
            want = pa.prefix_attention_reference(q[rows], kp, vp, bt[rows],
                                                 pre[rows], suf[rows])
            np.testing.assert_allclose(
                np.where(live[rows], got[rows], 0),
                np.where(live[rows], np.asarray(want, np.float32), 0),
                atol=2e-2, rtol=2e-2)

    @jax.jit
    def chain(q, kp, vp, bt, pre, suf):
        def call(_, q):
            o = pa.prefix_attention(q, kp, vp, bt, pre, suf, impl="kernel")
            return q + (o * 0).astype(q.dtype)
        return jax.lax.fori_loop(0, 20, call, q)

    took = _timed(chain, q, kp, vp, bt, pre, suf)
    positions = int((pre + suf).sum())
    least = positions * 2 * KV * D * 2 / _HBM_BYTES_PER_S
    print(f"\nprefix_attention 16 / 2 heads of 256, 24 requests: "
          f"{took * 1e6:.1f} us a call, {positions} positions read once a "
          f"request: K/V at the HBM peak {least * 1e6:.1f} us "
          f"({100 * least / took:.1f} %)")
    _keep("prefix_attention_256", dict(us=took * 1e6, positions=positions,
                                       bytes_share=least / took))
    assert 0 < least / took < 1.05


# -- the grouped product over 128 held experts of a router 512 wide ----------
E, WIDTH, TOP_K, HID, F = 128, 512, 10, 2048, 512


@pytest.mark.parametrize("tokens,live,name", [
    (64, 1.0, "moe_experts_decode"), (650, 1.0, "moe_experts_prefill"),
    (2112, 0.56, "moe_experts_prefill")])
def test_grouped_product_over_many_small_experts_on_tpu(tokens, live, name):
    """Even routing over the router's 512: a quarter of the picks fall on
    the 128 held experts, the others are routed nowhere; of the widest
    pass's 2,112 tokens 56 % are live (the others padding, routed
    nowhere)."""
    ks = jax.random.split(jax.random.PRNGKey(11), 7)
    x = jax.random.normal(ks[0], (tokens, HID), jnp.bfloat16)
    picks = jnp.argsort(jax.random.uniform(ks[1], (tokens, WIDTH)),
                        axis=1)[:, :TOP_K].astype(jnp.int32)
    held = (picks < E) & (jax.random.uniform(ks[6], (tokens, 1)) < live)
    idx = jnp.clip(picks, 0, E - 1)
    w = jax.nn.softmax(jax.random.normal(ks[2], (tokens, TOP_K)), axis=-1)
    wg, wu = (jax.random.normal(k, (E, HID, F), jnp.bfloat16) * HID ** -0.5
              for k in ks[3:5])
    wd = jax.random.normal(ks[5], (E, F, HID), jnp.bfloat16) * F ** -0.5
    kw = dict(name=name, router_width=WIDTH)
    y, sizes = gf.grouped_ffn(x, idx, w, held, wg, wu, wd, impl="kernel",
                              **kw)

    @jax.jit
    def loop(x, idx, w, held, wg, wu, wd):      # one held expert at a time
        x32 = x.astype(jnp.float32)

        def one(acc, e):
            out = (jax.nn.silu(x32 @ wg[e].astype(jnp.float32))
                   * (x32 @ wu[e].astype(jnp.float32))) \
                @ wd[e].astype(jnp.float32)
            weight = jnp.sum(jnp.where((idx == e) & held, w, 0.0), axis=1)
            return acc + weight[:, None] * out, None
        return jax.lax.scan(one, jnp.zeros_like(x32), jnp.arange(E))[0]

    with jax.default_matmul_precision("highest"):
        want = loop(x, idx, w, held, wg, wu, wd)
    err = np.abs(np.asarray(y, np.float32) - np.asarray(want))
    assert float(err.max()) < 0.1 and float(err.mean()) < 0.01, \
        (float(err.max()), float(err.mean()), float(np.abs(want).mean()))
    sizes = np.asarray(sizes)
    tm = gf.tile_rows(tokens * TOP_K, WIDTH)
    rows, touched = int(sizes.sum()), int((sizes > 0).sum())
    padded = int((-(-sizes // tm) * tm).sum())
    assert rows == int(held.sum())

    @jax.jit
    def chain(x, wg, wu, wd):       # weights as arguments, not constants
        def call(_, x):
            y, _ = gf.grouped_ffn(x, idx, w, held, wg, wu, wd,
                                  impl="kernel", **kw)
            return x + (y * 0).astype(x.dtype)
        return jax.lax.fori_loop(0, 20, call, x)

    took = _timed(chain, x, wg, wu, wd)
    least = touched * 3 * HID * F * 2 / _HBM_BYTES_PER_S
    print(f"\n{name} {tokens} tokens: {rows} rows on {touched} of {E} held "
          f"experts, tiles of {tm}: {padded} rows computed (fill "
          f"{100 * rows / padded:.1f} %); {took * 1e6:.1f} us a call (the "
          f"plan and the gathers included), the touched experts' bytes "
          f"{least * 1e6:.1f} us ({100 * least / took:.1f} %)")
    es.keep(name, dict(tokens=tokens, rows=rows, touched=touched, tile=tm,
                       padded=padded, us=took * 1e6,
                       bytes_share=least / took))
    assert 0 < least / took < 1.05


@pytest.mark.parametrize("tokens,live,name", [
    (64, 0.8, "moe_experts_decode")] + [
    (rung + 64, 0.56, "moe_experts_prefill") for rung in es.RUNGS])
def test_tile_sweep_at_qw3n_shapes_on_tpu(tokens, live, name):
    """Every tile at a decode step's and the four rungs' shapes; the rule's
    own may not lose to the best by more than the runs' spread."""
    es.check_rule(*es.sweep("qw3n", tokens, live, name))
