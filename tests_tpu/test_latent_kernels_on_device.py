"""ON-DEVICE: the kernels arch "axk1" brings, as real TPU kernels at the
shapes of the cell serve-axk1-agent-sessions: `mla_paged_attention` (64
slots at the agents' contexts over a pool of 640-lane rows),
`mla_prefix_attention` (suffixes of a few tens of tokens over prefixes up to
16 k) and the expert product over 4 blocks of F at hidden 7168 x width 2048,
each against its plain-JAX form; and what a call takes beside what its bytes
take at the HBM peak (printed with `-s`, kept in chiprun_out/pr44/).

    python -m pytest tests_tpu/test_latent_kernels_on_device.py -q -s
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import grouped_ffn as gf
from ray_tpu.ops import paged_attention as pa

_HBM_BYTES_PER_S = 819e9            # TPU v5e (benchmarks/lib/peaks.py)
_PEAK_FLOPS = 197e12
KW = dict(scale=0.130861, v_dim=512)
H, WIDTH, LANES, BS, W, NB = 64, 576, 640, 16, 1072, 8192
OUT = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                   "chiprun_out", "pr44")


def _keep(name, record):
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "kernels.jsonl"), "a") as f:
        f.write(json.dumps(dict(record, what=name)) + "\n")


def _pool(seed=0, lanes=LANES):
    rows = jax.random.normal(jax.random.PRNGKey(seed),
                             (NB + 1, 1, BS, WIDTH), jnp.bfloat16)
    return jnp.pad(rows, ((0, 0), (0, 0), (0, 0), (0, lanes - WIDTH)))


def _agents_tables(B, rng):
    """The agents' mix at B slots: four tenants' system prompts of 448 /
    8,192 / 12,288 / 16,384 positions, each primed in one allocation (its
    blocks lie in a row in the pool) and shared by B / 4 slots, and 40-400
    positions of history a slot whose blocks are scattered."""
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


def _timed(chain, *args, calls=20, repeats=3, launches=5):
    chain(*args).block_until_ready()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(launches):
            out = chain(*args)
        out.block_until_ready()
        times.append((time.perf_counter() - t0) / (launches * calls))
    return min(times)


def test_mla_paged_kernel_at_the_cells_shape_on_tpu():
    rng = np.random.RandomState(0)
    bt, lens = _agents_tables(64, rng)
    lens[5], lens[9] = 0, 1                     # an empty slot, a new one
    lens = jnp.asarray(lens)
    pool = _pool()
    q = jax.random.normal(jax.random.PRNGKey(1), (64, H, WIDTH), jnp.bfloat16)
    got = np.asarray(pa.mla_paged_attention(q, pool, bt, lens, impl="kernel",
                                            **KW), np.float32)
    assert got.shape == (64, H, 512) and np.isfinite(got).all()
    with jax.default_matmul_precision("highest"):
        for i in range(0, 64, 8):
            rows = slice(i, i + 8)
            want = pa.mla_paged_attention_reference(q[rows], pool, bt[rows],
                                                    lens[rows], **KW)
            np.testing.assert_allclose(got[rows], np.asarray(want, np.float32),
                                       atol=2e-2, rtol=2e-2)
    assert float(np.abs(got[5]).max()) == 0.0


@pytest.mark.parametrize("slots,lanes", [(64, 640), (50, 640), (50, 768)])
def test_mla_paged_kernel_call_time_on_tpu(slots, lanes):
    """What a decode step's call of one layer takes beside its rows' bytes
    at the HBM peak (the model's 1,152 B a position and the pool's 1,280)
    and its arithmetic at the MXU peak: 20 calls in a chain inside one
    program, each call's q depending on the call before, fenced.  Rows of
    768 lanes (a fifth more bytes and score arithmetic, the same values)
    show what a lane of padding costs: the price of the 640-lane layout
    against one that would read the model's 576."""
    rng = np.random.RandomState(1)
    bt, lens = _agents_tables(64, rng)
    lens[slots:] = 0
    positions = int(lens.sum())
    pool = _pool(2, lanes)
    q = jax.random.normal(jax.random.PRNGKey(3), (64, H, WIDTH), jnp.bfloat16)
    lens = jnp.asarray(lens)

    @jax.jit
    def chain(q, pool, bt, lens):
        def call(_, q):
            o = pa.mla_paged_attention(q, pool, bt, lens, impl="kernel", **KW)
            return q + jnp.pad(o * 0, ((0, 0), (0, 0), (0, WIDTH - 512))
                               ).astype(q.dtype)
        return jax.lax.fori_loop(0, 20, call, q)

    took = _timed(chain, q, pool, bt, lens)
    model_s = positions * WIDTH * 2 / _HBM_BYTES_PER_S
    pool_s = -(-positions // BS) * BS * lanes * 2 / _HBM_BYTES_PER_S
    flops_s = 2.0 * H * (WIDTH + 512) * positions / _PEAK_FLOPS
    print(f"\nmla_paged_attention {slots} live, rows of {lanes} lanes: "
          f"{took * 1e6:.1f} us a call, "
          f"{positions} positions: the model's bytes {model_s * 1e6:.1f} us "
          f"({100 * model_s / took:.1f} %), the pool's {pool_s * 1e6:.1f} us "
          f"({100 * pool_s / took:.1f} %), arithmetic at peak "
          f"{flops_s * 1e6:.1f} us ({100 * flops_s / took:.1f} %)")
    _keep("mla_paged_attention", dict(
        live=slots, lanes=lanes, us=took * 1e6, positions=positions,
        model_bytes_share=model_s / took, pool_bytes_share=pool_s / took,
        flops_share=flops_s / took))
    assert 0 < pool_s / took < 1.05 and flops_s / took < 1.05


def test_latent_rows_that_slots_share_are_read_once_on_tpu():
    """`mla_paged_attention` at 64 live slots with the slots' sets (16 slots
    a tenant: two programs of 8 members x 64 heads = 512 rows a prompt)
    against the gather in value and against the call without sets in time
    (chiprun_out/pr49/kernels.jsonl; PR 44 read 1,550 us for the latter)."""
    from test_kernels_on_device import _found_shared
    rng = np.random.RandomState(1)
    bt, lens = _agents_tables(64, rng)
    shared = _found_shared(bt, lens)
    lens = jnp.asarray(lens)
    sets = [(int((np.asarray(shared.place) // 8 == p).sum()), int(n))
            for p, n in enumerate(np.asarray(shared.lens)) if n]
    assert len(sets) == 8
    pool = _pool(2)
    q = jax.random.normal(jax.random.PRNGKey(3), (64, H, WIDTH), jnp.bfloat16)
    got = np.asarray(pa.mla_paged_attention(
        q, pool, bt, lens, impl="kernel", shared=shared, **KW), np.float32)
    assert np.isfinite(got).all()
    with jax.default_matmul_precision("highest"):
        for i in range(0, 64, 8):
            rows = slice(i, i + 8)
            want = pa.mla_paged_attention_reference(q[rows], pool, bt[rows],
                                                    lens[rows], **KW)
            np.testing.assert_allclose(got[rows], np.asarray(want, np.float32),
                                       atol=2e-2, rtol=2e-2)

    @jax.jit
    def chain(q, pool, bt, lens, *sets):
        def call(_, q):
            o = pa.mla_paged_attention(
                q, pool, bt, lens, impl="kernel",
                shared=pa.SharedRows(*sets) if sets else None, **KW)
            return q + jnp.pad(o * 0, ((0, 0), (0, 0), (0, WIDTH - 512))
                               ).astype(q.dtype)
        return jax.lax.fori_loop(0, 20, call, q)

    alone = _timed(chain, q, pool, bt, lens) * 1e6
    grouped = _timed(chain, q, pool, bt, lens, *shared) * 1e6
    positions = int(lens.sum())
    read = positions - sum((n - 1) * blocks for n, blocks in sets)
    floor = read * LANES * 2 / _HBM_BYTES_PER_S * 1e6
    flops = 2.0 * H * (WIDTH + 512) * positions / _PEAK_FLOPS * 1e6
    print(f"\nmla_paged_attention 64 live: {alone:.1f} us a call alone, "
          f"{grouped:.1f} us with sets {sets}; positions {positions} -> "
          f"{read}, the pool's bytes {floor:.1f} us, arithmetic at peak "
          f"{flops:.1f} us ({100 * flops / grouped:.1f} %)")
    os.makedirs(os.path.join(os.path.dirname(OUT), "pr49"), exist_ok=True)
    with open(os.path.join(os.path.dirname(OUT), "pr49", "kernels.jsonl"),
              "a") as f:
        f.write(json.dumps(dict(
            what="mla_paged_attention", live=64, sets=sets, alone_us=alone,
            shared_us=grouped, positions=positions, positions_read=read,
            floor_us=floor, flops_us=flops)) + "\n")
    assert max(floor, flops) / grouped < 1.05


def _prefix_scene(seed=4):
    """13 admissions of a fused dispatch as the engine groups them: rows of
    64 queries, a turn's 17-40 uncached tokens behind the tenants' prompts
    and a conversation's history, and padding rows."""
    rng = np.random.RandomState(seed)
    N = 16
    bt, _ = _agents_tables(N, rng)
    sizes = [448, 8192, 12288, 16384]
    prefix = [sizes[b % 4] + 16 * rng.randint(2, 20) for b in range(13)]
    suffix = [int(rng.randint(17, 41)) for _ in range(13)]
    prefix[0], suffix[0] = 0, 64                # a cold row
    pad = N - 13
    return (bt, jnp.asarray(prefix + [0] * pad, jnp.int32),
            jnp.asarray(suffix + [0] * pad, jnp.int32))


def test_mla_prefix_kernel_at_16k_on_tpu():
    bt, pre, suf = _prefix_scene()
    pool = _pool(5)
    q = jax.random.normal(jax.random.PRNGKey(6), (16, 64, H, WIDTH),
                          jnp.bfloat16)
    got = np.asarray(pa.mla_prefix_attention(q, pool, bt, pre, suf,
                                             impl="kernel", **KW), np.float32)
    assert got.shape == (16, 64, H, 512)
    with jax.default_matmul_precision("highest"):
        for n in range(13):                     # the gather is a row's wide
            rows = slice(n, n + 1)
            want = np.asarray(pa.mla_prefix_attention_reference(
                q[rows], pool, bt[rows], pre[rows], suf[rows], **KW),
                np.float32)
            live = int(suf[n])
            np.testing.assert_allclose(got[n, :live], want[0, :live],
                                       atol=2e-2, rtol=2e-2)


def test_mla_prefix_kernel_call_time_on_tpu():
    bt, pre, suf = _prefix_scene()
    pool = _pool(7)
    q = jax.random.normal(jax.random.PRNGKey(8), (16, 64, H, WIDTH),
                          jnp.bfloat16)

    @jax.jit
    def chain(q, pool, bt, pre, suf):
        def call(_, q):
            o = pa.mla_prefix_attention(q, pool, bt, pre, suf, impl="kernel",
                                        **KW)
            return q + jnp.pad(o * 0, ((0, 0),) * 3 + ((0, WIDTH - 512),)
                               ).astype(q.dtype)
        return jax.lax.fori_loop(0, 10, call, q)

    took = _timed(chain, q, pool, bt, pre, suf, calls=10)
    pre_n, suf_n = np.asarray(pre), np.asarray(suf)
    pairs = float(sum(s * (p + (s + 1) / 2) for p, s in zip(pre_n, suf_n)))
    flops_s = 2.0 * H * (WIDTH + 512) * pairs / _PEAK_FLOPS
    rows_s = float(((pre_n + suf_n) * (suf_n > 0)).sum()) * WIDTH * 2 \
        / _HBM_BYTES_PER_S
    print(f"\nmla_prefix_attention 13 admissions: {took * 1e6:.1f} us a "
          f"call; arithmetic of the live (query, position) pairs at peak "
          f"{flops_s * 1e6:.1f} us ({100 * flops_s / took:.1f} %), the rows "
          f"read once a request {rows_s * 1e6:.1f} us "
          f"({100 * rows_s / took:.1f} %)")
    _keep("mla_prefix_attention", dict(
        us=took * 1e6, flops_share=flops_s / took, bytes_share=rows_s / took,
        tokens=int(suf_n.sum())))
    assert flops_s / took < 1.05


def _expert_call(tokens, seed=0):
    """`tokens` tokens' top-8 picks over a router 192 wide of which experts
    0-11 are held, at hidden 7168 x width 2048."""
    E, D, F, K, width = 12, 7168, 2048, 8, 192
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (tokens, D), jnp.bfloat16)
    wg, wu = ((jax.random.normal(k, (E, D, F), jnp.float32) / np.sqrt(D)
               ).astype(jnp.bfloat16) for k in ks[1:3])
    wd = (jax.random.normal(ks[3], (E, F, D), jnp.float32) / np.sqrt(F)
          ).astype(jnp.bfloat16)
    picks = jnp.argsort(jax.random.uniform(ks[4], (tokens, width)),
                        axis=1)[:, :K].astype(jnp.int32)
    w = jax.random.uniform(ks[5], (tokens, K), minval=0.1)
    held = picks < E
    return x, jnp.minimum(picks, E - 1), w, held, wg, wu, wd


@pytest.mark.parametrize("tokens", [64, 2112])
def test_blocked_expert_product_on_tpu(tokens):
    """A decode step's 64 tokens (tiles of 16) and a pass's 2,112 (tiles of
    256) through the product over 4 blocks of F, against a loop over the 12
    held experts; a pair whose expert is not held adds nothing."""
    assert gf._f_blocks(7168, 2048, 2) == 4
    x, idx, w, held, wg, wu, wd = _expert_call(tokens)
    y, sizes = gf.grouped_ffn(x, idx, w, held, wg, wu, wd,
                              name="moe_experts_decode", impl="kernel")

    @jax.jit
    def loop(x, idx, w, held, wg, wu, wd):
        x32 = x.astype(jnp.float32)

        def one(acc, e):
            out = (jax.nn.silu(x32 @ wg[e].astype(jnp.float32))
                   * (x32 @ wu[e].astype(jnp.float32))) \
                @ wd[e].astype(jnp.float32)
            weight = jnp.sum(jnp.where((idx == e) & held, w, 0.0), axis=1)
            return acc + weight[:, None] * out, None
        return jax.lax.scan(one, jnp.zeros_like(x32), jnp.arange(12))[0]

    with jax.default_matmul_precision("highest"):
        want = loop(x, idx, w, held, wg, wu, wd)
    err = np.abs(np.asarray(y, np.float32) - np.asarray(want))
    assert float(err.max()) < 0.15 and float(err.mean()) < 0.01, \
        (float(err.max()), float(err.mean()), float(np.abs(want).mean()))
    assert int(jnp.sum(sizes)) == int(jnp.sum(held))


@pytest.mark.parametrize("tokens", [50, 64])
def test_blocked_expert_product_call_time_on_tpu(tokens):
    """What a decode step's expert product takes beside the bytes of the
    held experts it touches at the HBM peak."""
    x, idx, w, held, wg, wu, wd = _expert_call(64, seed=tokens)
    held = held & (jnp.arange(64) < tokens)[:, None]
    touched = len(set(np.asarray(idx)[np.asarray(held)].tolist()))

    @jax.jit
    def chain(x, *rest):
        def call(_, x):
            y, _ = gf.grouped_ffn(x, *rest, name="moe_experts_decode",
                                  impl="kernel")
            return x + (y * 0).astype(x.dtype)
        return jax.lax.fori_loop(0, 20, call, x)

    took = _timed(chain, x, idx, w, held, wg, wu, wd)
    bytes_s = touched * 3 * 7168 * 2048 * 2 / _HBM_BYTES_PER_S
    print(f"\nmoe_experts_decode D 7168 / F 2048 in 4 blocks, {tokens} "
          f"tokens, {int(held.sum())} rows on {touched} of 12 experts: "
          f"{took * 1e6:.1f} us a call (with its plan and gathers), "
          f"{touched * 88.1:.0f} MB of weights = {bytes_s * 1e6:.1f} us at "
          f"819 GB/s: {100 * bytes_s / took:.1f} %")
    _keep("moe_experts_decode_blocked", dict(
        tokens=tokens, rows=int(held.sum()), touched=touched,
        us=took * 1e6, bytes_share=bytes_s / took))
    assert 0 < bytes_s / took < 1.05


@pytest.mark.parametrize("tokens,live,name", [
    (64, 0.8, "moe_experts_decode")] + [
    (rung + 64, 0.56, "moe_experts_prefill")
    for rung in (256, 640, 896, 2048)])
def test_tile_sweep_at_axk1_shapes_on_tpu(tokens, live, name):
    """Every tile (16 ... 256) through the blocked product (12 of 192
    experts held, rows of 7,168, 4 blocks of F: a tile reads its expert's
    88 MB whatever it holds): what `grouped_ffn.tile_rows` is fitted from
    (`expert_sweep.py`)."""
    import expert_sweep as es
    es.check_rule(*es.sweep("axk1", tokens, live, name))
