"""ON-DEVICE: the kernels arch "olmo_hybrid" brings, as real TPU kernels at
the shapes of the cell serve-olmoh-agent-sessions: `gated_delta_step` (32
slots, a state of 30 x 96 x 192 float32 each), `gated_delta_chunk` (one
request's 2,048-position dispatch, and 40 rows of 14 requests as a window's
fused dispatch has them), each against its plain-JAX form; and the two
paged kernels at 30 heads of 128 with a group of 1 (a shape they had never
run), over the agents' contexts.  What a call takes beside what its bytes
take at the HBM peak is printed (`-s`) and kept in chiprun_out/pr47/.

    python -m pytest tests_tpu/test_delta_kernels_on_device.py -q -s
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gated_delta as gd
from ray_tpu.ops import paged_attention as pa

_HBM_BYTES_PER_S = 819e9            # TPU v5e (benchmarks/lib/peaks.py)
H, DK, DV, NS, C = 30, 96, 192, 160, 16
STATE_BYTES = H * DK * DV * 4
OUT = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                   "chiprun_out", "pr47")


def _keep(name, record):
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "kernels.jsonl"), "a") as f:
        f.write(json.dumps(dict(record, what=name)) + "\n")


def _inputs(shape, seed):
    """q, k (L2-normed), v, ln alpha in (ln 0.2, 0), beta in (0, 2)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(key):
        x = jax.random.normal(key, shape + (H, DK), jnp.float32)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return (unit(ks[0]) * DK ** -0.5, unit(ks[1]),
            jax.random.normal(ks[2], shape + (H, DV), jnp.float32),
            jnp.log(jax.random.uniform(ks[3], shape + (H,), jnp.float32,
                                       0.2, 0.999)),
            jax.random.uniform(ks[4], shape + (H,), jnp.float32, 0.0, 2.0))


def _pool(seed=0):
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


def test_gated_delta_step_at_the_cells_shape_on_tpu():
    pool = _pool()
    ids = jnp.arange(1, 33, dtype=jnp.int32).at[5].set(0).at[9].set(0)
    q, k, v, la, beta = _inputs((32,), 1)
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
    # the states of ids no slot named are as they were
    np.testing.assert_array_equal(np.asarray(after)[33:],
                                  np.asarray(pool)[33:])

    @jax.jit
    def chain(pool, q):
        def call(_, c):
            pool, q = c
            o, pool = gd.gated_delta_step(pool, ids, q, k, v, la, beta,
                                          impl="kernel")
            return pool, q + (o[..., :DK] * 0)
        return jax.lax.fori_loop(0, 20, call, (pool, q))

    took = _timed(chain, pool, q)
    least = 32 * 2 * STATE_BYTES / _HBM_BYTES_PER_S
    print(f"\ngated_delta_step 32 slots: {took * 1e6:.1f} us a call, the "
          f"states' bytes {least * 1e6:.1f} us ({100 * least / took:.1f} %)")
    _keep("gated_delta_step", dict(slots=32, us=took * 1e6,
                                   bytes_share=least / took))
    assert 0 < least / took < 1.05


def _one_request(rows):
    """A dispatch of one request: from id 7's state, the state left in id 7
    and (at row 100 of 128) in a checkpoint."""
    src = jnp.full((rows,), -1, jnp.int32).at[0].set(7)
    dst = jnp.zeros((rows, 2), jnp.int32).at[rows - 1, 0].set(7)
    return src, dst.at[min(100, rows - 2), 1].set(90)


def _fourteen_requests():
    """40 rows of 14 requests (a window's fused dispatch: a ~33-token suffix
    is three rows, the last partial), restored from checkpoints 40.., left
    in slots' ids 1.. and in fresh checkpoints 60.. at the row before the
    last; 8 rows of padding behind them."""
    src, dst, live = [], [], []
    for r in range(14):
        n = 3 if r < 12 else 2
        for i in range(n):
            src.append(40 + r if i == 0 else -1)
            dst.append([1 + r if i == n - 1 else 0,
                        60 + r if i == n - 2 else 0])
            live.append(C if i < n - 1 else 5 + r % 9)
    pad = 48 - len(src)
    return (jnp.asarray(src + [-1] * pad, jnp.int32),
            jnp.asarray(dst + [[0, 0]] * pad, jnp.int32),
            jnp.asarray(live + [0] * pad, jnp.int32))


@pytest.mark.parametrize("scene", ["one_request_2048", "fourteen_requests"])
def test_gated_delta_chunk_at_the_cells_shape_on_tpu(scene):
    pool = _pool(3)
    if scene == "one_request_2048":
        rows = 128
        src, dst = _one_request(rows)
        live = jnp.full((rows,), C, jnp.int32)
        requests = 1
    else:
        src, dst, live = _fourteen_requests()
        rows, requests = int(src.shape[0]), 14
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
    written = np.unique(np.asarray(dst))
    for i in range(1, NS + 1):
        if i not in written:
            np.testing.assert_array_equal(np.asarray(after)[i],
                                          np.asarray(pool)[i])

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
    print(f"\ngated_delta_chunk {scene}: {rows} rows, {took * 1e6:.1f} us a "
          f"call (its batched jnp operands included), {took / rows * 1e6:.2f}"
          f" us a row; states and operands at the HBM peak "
          f"{least * 1e6:.1f} us ({100 * least / took:.1f} %)")
    _keep("gated_delta_chunk", dict(scene=scene, rows=rows, us=took * 1e6,
                                    bytes_share=least / took))
    assert 0 < least / took < 1.05


# -- the two paged kernels at 30 heads of 128, a group of 1 -------------------
HEADS, D, BS, W, NB = 30, 128, 16, 1072, 4096


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
    return tuple(jax.random.normal(k, (NB + 1, HEADS, BS, D), jnp.bfloat16)
                 for k in ks)


def test_paged_attention_at_thirty_heads_on_tpu():
    rng = np.random.RandomState(0)
    bt, lens = _agents_tables(32, rng)
    lens[5], lens[9] = 0, 1
    positions = int(lens.sum())
    lens = jnp.asarray(lens)
    kp, vp = _kv_pools()
    q = jax.random.normal(jax.random.PRNGKey(6), (32, HEADS, D), jnp.bfloat16)
    got = np.asarray(pa.paged_attention(q, kp, vp, bt, lens, impl="kernel"),
                     np.float32)
    with jax.default_matmul_precision("highest"):
        for i in range(0, 32, 4):
            rows = slice(i, i + 4)
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
    least = positions * 2 * HEADS * D * 2 / _HBM_BYTES_PER_S
    print(f"\npaged_attention 30 heads, group 1: {took * 1e6:.1f} us a call, "
          f"{positions} positions: K/V at the HBM peak {least * 1e6:.1f} us "
          f"({100 * least / took:.1f} %); ring "
          f"{pa._ring_shape(W, HEADS, BS, D, 2)}")
    _keep("paged_attention_30", dict(us=took * 1e6, positions=positions,
                                     bytes_share=least / took))
    assert 0 < least / took < 1.05


def test_prefix_attention_at_thirty_heads_on_tpu():
    """14 requests' suffixes of ~33 tokens (attention rows of 48 queries)
    over their 9 k contexts."""
    rng = np.random.RandomState(1)
    bt, lens = _agents_tables(16, rng)
    N, P = 16, 64
    pre = jnp.asarray(lens // BS * BS - 32, jnp.int32).at[14:].set(0)
    suf = jnp.asarray([33 + i % 9 for i in range(N)], jnp.int32
                      ).at[14:].set(0)
    kp, vp = _kv_pools(7)
    q = jax.random.normal(jax.random.PRNGKey(8), (N, P, HEADS, D),
                          jnp.bfloat16)
    got = np.asarray(pa.prefix_attention(q, kp, vp, bt, pre, suf,
                                         impl="kernel"), np.float32)
    live = (np.arange(P)[None, :] < np.asarray(suf)[:, None])[..., None, None]
    with jax.default_matmul_precision("highest"):
        for i in range(0, 14, 2):
            rows = slice(i, i + 2)
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
    least = positions * 2 * HEADS * D * 2 / _HBM_BYTES_PER_S
    print(f"\nprefix_attention 30 heads, 14 requests: {took * 1e6:.1f} us a "
          f"call, {positions} positions read once a request: K/V at the HBM "
          f"peak {least * 1e6:.1f} us ({100 * least / took:.1f} %)")
    _keep("prefix_attention_30", dict(us=took * 1e6, positions=positions,
                                      bytes_share=least / took))
    assert 0 < least / took < 1.05
