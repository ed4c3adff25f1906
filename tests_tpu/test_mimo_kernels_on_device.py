"""ON-DEVICE: the kernels of the cell serve-mimo-agent-sessions at the shapes
arch "mimo_v2" brings, none of which had run before: `window_ring_step` and
`window_ring_chunk` at 64 query / 8 kv heads over rings of [8, 128, 256 | 128]
by state id, and the two paged kernels at 64 query / 4 kv heads with keys of
192 in pools of 256 lanes beside values of 128 (16 query heads a kv head, a
shared prefix read once a set); each against its plain-JAX form.  What a call
takes beside what its bytes take at the HBM peak is printed (`-s`) and kept in
chiprun_out/pr54/kernels.jsonl.

    python -m pytest tests_tpu/test_mimo_kernels_on_device.py -q -s
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops import window_ring as wr

_HBM_BYTES_PER_S = 819e9            # TPU v5e (benchmarks/lib/peaks.py)
H, HKV, W, DK, DV, NS, C, SLOTS = 64, 8, 128, 192, 128, 256, 16, 64
MODEL_RING_BYTES = W * HKV * (DK + DV) * 2          # 655,360
HELD_RING_BYTES = W * HKV * (256 + 128) * 2         # 786,432
OUT = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                   "chiprun_out", "pr54")
BF = jnp.bfloat16


def _keep(name, record):
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "kernels.jsonl"), "a") as f:
        f.write(json.dumps(dict(record, what=name)) + "\n")


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


def _rings(seed=0):
    shk, shv = wr.ring_shapes(NS, HKV, W, DK, DV)
    assert shk == (NS + 1, HKV, W, 256) and shv == (NS + 1, HKV, W, 128)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    rk = jax.random.normal(ks[0], shk, BF).at[..., DK:].set(0)
    return rk, jax.random.normal(ks[1], shv, BF)


def _qkv(shape, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], shape + (H, DK), BF),
            jax.random.normal(ks[1], shape + (HKV, DK), BF),
            jax.random.normal(ks[2], shape + (HKV, DV), BF),
            jax.random.normal(ks[3], (H,), jnp.float32))


def _close(got, want, tol=2e-2):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


def test_window_ring_step_on_tpu():
    rk, rv = _rings()
    ids = jnp.arange(1, SLOTS + 1, dtype=jnp.int32).at[5].set(0).at[9].set(0)
    # some rings not yet full, some wrapped many times
    pos = (jnp.arange(SLOTS, dtype=jnp.int32) * 137) % 9000 + 3
    q, k, v, sink = _qkv((SLOTS,), 1)
    o, ak, av = wr.window_ring_step(rk, rv, ids, pos, q, k, v, sink,
                                    impl="kernel")
    wo, wk, wv = wr.window_ring_step(rk, rv, ids, pos, q, k, v, sink,
                                     impl="reference")
    rows = np.asarray(ids) > 0
    _close(np.asarray(o, np.float32)[rows], np.asarray(wo, np.float32)[rows])
    np.testing.assert_array_equal(np.asarray(ak)[1:], np.asarray(wk)[1:])
    np.testing.assert_array_equal(np.asarray(av)[1:], np.asarray(wv)[1:])

    @jax.jit
    def chain(rk, rv, q):
        def call(_, c):
            rk, rv, q = c
            o, rk, rv = wr.window_ring_step(rk, rv, ids, pos, q, k, v, sink,
                                            impl="kernel")
            return rk, rv, q + jnp.pad(o, ((0, 0), (0, 0), (0, DK - DV))) * 0
        return jax.lax.fori_loop(0, 20, call, (rk, rv, q))

    took = _timed(chain, rk, rv, q)
    least = SLOTS * MODEL_RING_BYTES / _HBM_BYTES_PER_S
    held = SLOTS * HELD_RING_BYTES / _HBM_BYTES_PER_S
    print(f"\nwindow_ring_step {SLOTS} slots of [8, 128, 256 | 128]: "
          f"{took * 1e6:.1f} us a call, the model's rings' bytes "
          f"{least * 1e6:.1f} us ({100 * least / took:.1f} %), the held "
          f"{held * 1e6:.1f} us")
    _keep("window_ring_step", dict(slots=SLOTS, us=took * 1e6,
                                   bytes_share=least / took,
                                   held_bytes_share=held / took))
    assert 0 < least / took < 1.05


def _requests(n_req=24, pad=16):
    """Two rows of 16 a request (a ~33-token suffix, the last row partial),
    restored from checkpoints 100.., left in slots' ids 1.. and in fresh
    checkpoints 140.. at the row before the last; rows of padding behind."""
    src, dst, starts, lives = [], [], [], []
    for r in range(n_req):
        p0 = 16 * (500 + 37 * r)
        src += [100 + r, -1]
        dst += [[0, 140 + r], [1 + r, 0]]
        starts += [p0, p0 + C]
        lives += [C, 1 + (5 * r) % C]
    src += [-1] * pad
    dst += [[0, 0]] * pad
    starts += [0] * pad
    lives += [0] * pad
    return (jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
            jnp.asarray(starts, jnp.int32), jnp.asarray(lives, jnp.int32))


def test_window_ring_chunk_on_tpu():
    rk, rv = _rings(3)
    src, dst, starts, lives = _requests()
    N = int(src.shape[0])
    q, k, v, sink = _qkv((N, C), 2)
    o, ak, av = wr.window_ring_chunk(rk, rv, src, dst, starts, lives, q, k,
                                     v, sink, impl="kernel")
    wo, wk, wv = wr.window_ring_chunk(rk, rv, src, dst, starts, lives, q, k,
                                      v, sink, impl="reference")
    live = np.arange(C)[None, :] < np.asarray(lives)[:, None]
    _close(np.asarray(o, np.float32)[live], np.asarray(wo, np.float32)[live])
    np.testing.assert_array_equal(np.asarray(ak)[1:], np.asarray(wk)[1:])
    np.testing.assert_array_equal(np.asarray(av)[1:], np.asarray(wv)[1:])

    @jax.jit
    def chain(rk, rv, q):
        def call(_, c):
            rk, rv, q = c
            o, rk, rv = wr.window_ring_chunk(rk, rv, src, dst, starts, lives,
                                             q, k, v, sink, impl="kernel")
            return rk, rv, q + jnp.pad(
                o, ((0, 0),) * 3 + ((0, DK - DV),)) * 0
        return jax.lax.fori_loop(0, 20, call, (rk, rv, q))

    took = _timed(chain, rk, rv, q)
    least = 24 * 3 * MODEL_RING_BYTES / _HBM_BYTES_PER_S
    print(f"\nwindow_ring_chunk {N} rows of 24 requests: {took * 1e6:.1f} us "
          f"a call, the rings' copies' bytes {least * 1e6:.1f} us "
          f"({100 * least / took:.1f} %)")
    _keep("window_ring_chunk", dict(rows=N, us=took * 1e6,
                                    bytes_share=least / took))
    assert 0 < least / took < 1.05


def _pools(nb, seed=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    kp = jax.random.normal(ks[0], (nb, 4, 16, 256), BF).at[..., DK:].set(0)
    return kp, jax.random.normal(ks[1], (nb, 4, 16, 128), BF)


def _sessions():
    """64 slots in 8 sets of 8 that share a prefix of 8,192 positions and
    hold ~600 of their own -> (blocks, tables, context lengths, the sets)."""
    own, shared_blocks, Wt = 40, 512, 1072
    nb = 1 + 8 * shared_blocks + SLOTS * own
    tables = np.zeros((SLOTS, Wt), np.int32)
    for s in range(SLOTS):
        t = s // 8
        tables[s, :shared_blocks] = 1 + t * shared_blocks + np.arange(
            shared_blocks)
        tables[s, shared_blocks:shared_blocks + own] = (
            1 + 8 * shared_blocks + s * own + np.arange(own))
    ctx = jnp.asarray(8192 + 100 + (np.arange(SLOTS) * 7) % 500, jnp.int32)
    sets = pa.SharedPrefixes(
        jnp.asarray(np.pad(np.arange(SLOTS).reshape(8, 8),
                           ((0, SLOTS // 2 - 8), (0, 0)),
                           constant_values=-1), jnp.int32),
        jnp.asarray(np.pad(np.arange(0, SLOTS, 8), (0, SLOTS // 2 - 8)),
                    jnp.int32),
        jnp.asarray(np.pad(np.full(8, 8192), (0, SLOTS // 2 - 8)),
                    jnp.int32))
    return nb, jnp.asarray(tables), ctx, sets


def _time_paged(what, q, kp, vp, tables, ctx, rows, page, **kw):
    """Twenty calls a launch, alone and with the sets; printed and kept."""
    def chain_of(shared):
        @jax.jit
        def chain(q):
            def call(_, q):
                o = pa.paged_attention(q, kp, vp, tables, ctx, impl="kernel",
                                       shared=shared, **kw)
                return q + jnp.pad(o, ((0, 0), (0, 0), (
                    0, q.shape[-1] - o.shape[-1]))) * 0
            return jax.lax.fori_loop(0, 20, call, q)
        return chain

    for name, shared, positions in (
            ("alone", None, int(ctx.sum())),
            ("sets", rows, int(ctx.sum()) - 56 * 8192)):
        took = _timed(chain_of(shared), q)
        least = positions / 16 * page / _HBM_BYTES_PER_S
        print(f"\npaged_attention {what}, {name}: {took * 1e6:.1f} us a "
              f"call, its pages' bytes {least * 1e6:.1f} us "
              f"({100 * least / took:.1f} %)")
        _keep(f"paged_attention_{name}", dict(
            us=took * 1e6, positions=positions, bytes_share=least / took,
            layout=what))
        assert 0 < least / took < 1.05


def test_paged_attention_at_keys_of_192_on_tpu():
    """64 slots in 8 sets of 8 that share a prefix: alone (every slot
    streams its whole context) and with the sets (the prefix once a set),
    both the gather's result."""
    nb, tables, ctx, sets = _sessions()
    kp, vp = _pools(nb)
    q = jax.random.normal(jax.random.PRNGKey(7), (SLOTS, H, DK), BF)
    want = pa.paged_attention(q, kp, vp, tables, ctx, impl="reference")
    alone = pa.paged_attention(q, kp, vp, tables, ctx, impl="kernel")
    assert alone.shape == (SLOTS, H, DV)
    _close(alone, want)
    rows = pa.shared_rows(sets, tables, ctx)
    together = pa.paged_attention(q, kp, vp, tables, ctx, impl="kernel",
                                  shared=rows)
    _close(together, want)
    _time_paged("64 / 4 heads of 192 | 128 in 256 | 128 lanes", q, kp, vp,
                tables, ctx, rows, page=4 * 16 * (256 + 128) * 2)


def test_the_exact_bytes_key_layout_through_the_same_kernel():
    """ISSUE 54's other key layout, timed against the one the program holds:
    two kv heads side by side in 384 lanes, K [2, 16, 384] + V [2, 16, 256] a
    page (the MODEL's 2,560 B a token; the program's pages of K [4, 16, 256]
    + V [4, 16, 128] hold 3,072).  The SAME kernel reads it as one kv head of
    384 whose 32 query heads each carry zeros in the other head's 192 lanes
    (the lane mask) and keep their own 128 of the 256 output lanes: a sixth
    fewer bytes a page for half as much again q . k work and twice the
    p . v.  The same pools' content, the same result."""
    nb, tables, ctx, sets = _sessions()
    kp, vp = _pools(nb)
    q = jax.random.normal(jax.random.PRNGKey(7), (SLOTS, H, DK), BF)

    def paired(pool, d):    # [nb, 4, 16, .] -> [nb, 2, 16, 2 d]
        return pool[..., :d].reshape(nb, 2, 2, 16, d).transpose(
            0, 1, 3, 2, 4).reshape(nb, 2, 16, 2 * d)

    kp2, vp2 = paired(kp, DK), paired(vp, DV)
    assert kp2.shape[1:] == (2, 16, 384) and vp2.shape[1:] == (2, 16, 256)
    assert kp2.nbytes + vp2.nbytes == (nb * 16) * 2560
    odd = (jnp.arange(H) // (H // 4) % 2 == 1)[None, :, None]   # [1, H, 1]
    q2 = jnp.where(odd, jnp.pad(q, ((0, 0), (0, 0), (DK, 0))),
                   jnp.pad(q, ((0, 0), (0, 0), (0, DK))))
    kw = dict(scale=1.0 / DK ** 0.5)
    want = pa.paged_attention(q, kp, vp, tables, ctx, impl="kernel")
    rows = pa.shared_rows(sets, tables, ctx)
    for shared in (None, rows):
        got = pa.paged_attention(q2, kp2, vp2, tables, ctx, impl="kernel",
                                 shared=shared, **kw)
        assert got.shape == (SLOTS, H, 2 * DV)
        _close(jnp.where(odd, got[..., DV:], got[..., :DV]), want)
    _time_paged("64 / 2 pairs of heads of 192 | 128 in 384 | 256 lanes", q2,
                kp2, vp2, tables, ctx, rows, page=2 * 16 * (384 + 256) * 2,
                **kw)


def test_prefix_attention_at_keys_of_192_on_tpu():
    """12 attention rows of 64 queries (24 requests' two rows of 16, grouped)
    over prefixes of 8 k-16 k positions."""
    R, P, Wt = 12, 64, 1072
    nb = 1 + R * Wt
    kp, vp = _pools(nb, seed=11)
    tables = jnp.asarray(1 + np.arange(R * Wt).reshape(R, Wt), jnp.int32)
    prefix = jnp.asarray(16 * (512 + 40 * np.arange(R)), jnp.int32)
    suffix = jnp.asarray(17 + (np.arange(R) * 5) % 16, jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(13), (R, P, H, DK), BF)
    got = pa.prefix_attention(q, kp, vp, tables, prefix, suffix,
                              impl="kernel")
    assert got.shape == (R, P, H, DV)
    # the gather is [R, H, P, W * bs] float32: one row at a time
    for n in (0, R - 1):
        want = pa.prefix_attention(q[n:n + 1], kp, vp, tables[n:n + 1],
                                   prefix[n:n + 1], suffix[n:n + 1],
                                   impl="reference")
        live = int(suffix[n])
        _close(got[n, :live], want[0, :live])

    @jax.jit
    def chain(q):
        def call(_, q):
            o = pa.prefix_attention(q, kp, vp, tables, prefix, suffix,
                                    impl="kernel")
            return q + jnp.pad(o, ((0, 0),) * 3 + ((0, DK - DV),)) * 0
        return jax.lax.fori_loop(0, 20, call, q)

    took = _timed(chain, q)
    page = 4 * 16 * (256 + 128) * 2
    least = float((prefix + suffix).sum()) / 16 * page / _HBM_BYTES_PER_S
    print(f"\nprefix_attention {R} rows of 64 queries, 64 / 4 heads of 192 | "
          f"128: {took * 1e6:.1f} us a call, its pages' bytes "
          f"{least * 1e6:.1f} us ({100 * least / took:.1f} %)")
    _keep("prefix_attention", dict(rows=R, us=took * 1e6,
                                   bytes_share=least / took))
    assert 0 < least / took < 1.05
