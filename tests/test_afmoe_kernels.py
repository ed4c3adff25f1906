"""The kernels arch "afmoe" brought, in the Pallas interpreter on the CPU:
the grouped expert FFN against a per-expert loop, the paged decode kernel
with a window and the prefix attention of a prefill chunk against plain
position-masked attention.  The same at published widths on the chip:
tests_tpu/test_kernels_on_device.py."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import grouped_ffn as gf
from ray_tpu.ops import paged_attention as pa


def _expert_loop(x, idx, w, valid, wg, wu, wd):
    """sum_k w[t, k] FFN_{idx[t, k]}(x[t]), one expert at a time."""
    x32 = x.astype(jnp.float32)
    y = jnp.zeros_like(x32)
    for e in range(wg.shape[0]):
        out = (jax.nn.silu(x32 @ wg[e].astype(jnp.float32))
               * (x32 @ wu[e].astype(jnp.float32))) \
            @ wd[e].astype(jnp.float32)
        weight = jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)
        y = y + jnp.where(valid, weight, 0.0)[:, None] * out
    return y


@pytest.mark.parametrize("T,K,E,impl", [(24, 2, 8, "kernel"),
                                        (24, 2, 8, "reference"),
                                        (2100, 2, 4, "kernel")])
def test_grouped_ffn_matches_a_per_expert_loop(T, K, E, impl):
    """Small tiles (a decode step's rows) and large ones (a prefill
    chunk's); rows that are not valid are routed nowhere: zeros out, not
    counted."""
    D, F = 32, 48
    ks = jax.random.split(jax.random.PRNGKey(T), 6)
    x = jax.random.normal(ks[0], (T, D), jnp.float32)
    wg, wu = (jax.random.normal(k, (E, D, F)) / math.sqrt(D) for k in ks[1:3])
    wd = jax.random.normal(ks[3], (E, F, D)) / math.sqrt(F)
    idx = jnp.argsort(jax.random.uniform(ks[4], (T, E)), axis=1)[:, :K]
    w = jax.random.uniform(ks[5], (T, K), minval=0.1)
    valid = jnp.arange(T) % 5 != 3
    with jax.default_matmul_precision("highest"):
        y, sizes = gf.grouped_ffn(x, idx.astype(jnp.int32), w, valid,
                                  wg, wu, wd, name="moe_experts_decode",
                                  impl=impl)
        want = _expert_loop(x, idx, w, valid, wg, wu, wd)
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-4)
    assert float(jnp.max(jnp.abs(y[~valid]))) == 0.0
    assert int(jnp.sum(sizes)) == int(jnp.sum(valid)) * K
    counted = np.bincount(np.asarray(idx[valid]).ravel(), minlength=E)
    np.testing.assert_array_equal(sizes, counted)


def test_grouped_ffn_with_every_row_on_one_expert():
    """The busiest case: one group of many tiles, the others empty."""
    T, K, E, D, F = 40, 1, 4, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (T, D))
    wg, wu = (jax.random.normal(k, (E, D, F)) for k in ks[1:3])
    wd = jax.random.normal(ks[3], (E, F, D))
    idx = jnp.full((T, K), 2, jnp.int32)
    w = jnp.ones((T, K))
    valid = jnp.ones((T,), bool)
    with jax.default_matmul_precision("highest"):
        y, sizes = gf.grouped_ffn(x, idx, w, valid, wg, wu, wd,
                                  impl="kernel")
        want = _expert_loop(x, idx, w, valid, wg, wu, wd)
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-3)
    assert sizes.tolist() == [0, 0, 40, 0]


# pairs = tokens x picks, the router's width -> the tile: the four expert
# cells' decode steps and fused rungs (slots + 256 / 640 / 896 / 2,048
# positions), as tests_tpu/expert_sweep.py timed them on the chip
@pytest.mark.parametrize("tokens,picks,width,tile", [
    (32, 8, 128, 16), (288, 8, 128, 16), (672, 8, 128, 32),     # Trinity-Mini
    (928, 8, 128, 64), (2080, 8, 128, 128),
    (32, 4, 64, 16), (672, 4, 64, 32), (2080, 4, 64, 128),      # LFM2
    (64, 8, 192, 16), (704, 8, 192, 32), (2112, 8, 192, 64),    # A.X-K1
    (64, 10, 512, 16), (704, 10, 512, 16), (960, 10, 512, 16),  # Qwen3-Next
    (2112, 10, 512, 32),
    (24, 2, 8, 16), (2100, 2, 4, 256), (0, 2, 4, 16)])
def test_the_tile_follows_the_rows_an_expert_of_the_router_gets(
        tokens, picks, width, tile):
    """`tile_rows`: the power of two from 16 to 256 nearest the mean rows
    an expert of the router gets, whatever share of them is held here."""
    assert gf.tile_rows(tokens * picks, width) == tile
    mean = tokens * picks / width
    assert tile in (16, 256) or tile / 2 ** 0.5 <= mean <= tile * 2 ** 0.5


def _plan_by_sort_and_scatters(idx, valid, E, tm):
    """The plan as it was before PR 52, in numpy: a stable sort of the
    pairs by expert, rows laid out group by group.  -> (row_token with -1
    where a row holds none, dest, tile_expert of the used tiles, sizes)."""
    T, K = idx.shape
    pairs = T * K
    n_tiles = -(-(pairs + E * (tm - 1)) // tm) + 1
    ok = valid if valid.ndim == 2 else valid[:, None]
    e = np.where(ok, idx, E).reshape(pairs)
    order = np.argsort(e, kind="stable")
    sizes = np.bincount(e, minlength=E + 1)[:E]
    tiles_per = -(-sizes // tm)
    pstart = (np.cumsum(tiles_per) - tiles_per) * tm
    start = np.cumsum(sizes) - sizes
    row_token = np.full(n_tiles * tm, -1)
    dest = np.full(pairs, n_tiles * tm - 1)
    for g in range(E):
        mine = order[start[g]:start[g] + sizes[g]]
        row_token[pstart[g]:pstart[g] + sizes[g]] = mine // K
        dest[mine] = pstart[g] + np.arange(sizes[g])
    return (row_token, dest.reshape(T, K), np.repeat(np.arange(E), tiles_per),
            sizes)


# tokens, picks, held experts, router width, tile, how the picks fall
@pytest.mark.parametrize("T,K,E,width,tm,picks", [
    (72, 8, 16, 16, 16, "valid-by-token"),      # Trinity-Mini: all held
    (72, 4, 8, 8, 32, "valid-by-token"),        # LFM2: all held, 4 picks
    (80, 10, 16, 64, 16, "valid-by-pair"),      # Qwen3-Next: a quarter
    (80, 10, 16, 64, 64, "valid-by-pair"),
    (80, 8, 4, 64, 256, "valid-by-pair"),       # A.X-K1: a sixteenth
    (40, 3, 8, 8, 16, "twice-the-same"),        # a token picks e twice
    (40, 1, 4, 4, 16, "all-on-one"),
    (40, 2, 4, 4, 16, "none-valid"),
    (1, 2, 4, 4, 16, "valid-by-token")])
def test_the_plan_gives_every_routed_pair_a_row_of_its_expert(
        T, K, E, width, tm, picks):
    """`_plan` at the four cells' shapes (toy widths): every valid pair on a
    held expert has a row of its own in a used tile of that expert, holding
    its token; a pair routed nowhere lands on the last tile, which is never
    used; `sizes` and `n_used` are the counts; rows, tiles and counts are
    those of a stable sort by expert (the plan before PR 52: same tile,
    same rows, bit for bit); and the product through it is the per-expert
    loop's."""
    rng = np.random.default_rng(T * K + tm)
    chosen = np.argsort(rng.random((T, width)), axis=1)[:, :K]
    if picks == "twice-the-same":
        chosen = rng.integers(0, E, (T, K))
    if picks == "all-on-one":
        chosen[:] = 2
    live = rng.random(T) < 0.56 if T > 1 else np.ones(1, bool)
    if picks == "all-on-one":
        live[:] = True
    if picks == "none-valid":
        live[:] = False
    idx = np.clip(chosen, 0, E - 1).astype(np.int32)
    valid = (chosen < E) & live[:, None] if picks == "valid-by-pair" \
        else live
    row_token, dest, tile_expert, n_used, sizes = (
        np.asarray(a) for a in gf._plan(jnp.asarray(idx), jnp.asarray(valid),
                                        E, tm))
    ok = np.broadcast_to(valid if valid.ndim == 2 else valid[:, None],
                         (T, K))
    n_used = int(n_used[0])
    np.testing.assert_array_equal(
        sizes, np.bincount(idx[ok], minlength=E))
    assert n_used == int((-(-sizes // tm)).sum())
    assert len(row_token) == len(tile_expert) * tm
    assert n_used < len(tile_expert)
    routed = dest[ok]
    assert len(set(routed.tolist())) == len(routed)     # a row of its own
    assert (routed // tm < n_used).all()
    np.testing.assert_array_equal(tile_expert[routed // tm], idx[ok])
    np.testing.assert_array_equal(row_token[routed],
                                  np.nonzero(ok)[0])    # its token's
    assert (dest[~ok] == len(row_token) - 1).all()
    assert (tile_expert[n_used:] == tile_expert[max(n_used - 1, 0)]).all()
    # ... and the very rows a stable sort by expert gives
    was_token, was_dest, was_expert, was_sizes = _plan_by_sort_and_scatters(
        idx, valid, E, tm)
    np.testing.assert_array_equal(dest, was_dest)
    np.testing.assert_array_equal(tile_expert[:n_used], was_expert)
    np.testing.assert_array_equal(sizes, was_sizes)
    np.testing.assert_array_equal(row_token,
                                  np.where(was_token < 0, 0, was_token))
    # the product through the plan
    D, F = 32, 48
    ks = jax.random.split(jax.random.PRNGKey(T), 5)
    x = jax.random.normal(ks[0], (T, D), jnp.float32)
    wg, wu = (jax.random.normal(k, (E, D, F)) / math.sqrt(D) for k in ks[1:3])
    wd = jax.random.normal(ks[3], (E, F, D)) / math.sqrt(F)
    w = jax.random.uniform(ks[4], (T, K), minval=0.1)
    with jax.default_matmul_precision("highest"):
        y, counted = gf.grouped_ffn(x, jnp.asarray(idx), w,
                                    jnp.asarray(valid), wg, wu, wd,
                                    impl="reference", router_width=width)
        want = sum(_expert_loop(x, jnp.asarray(idx[:, k:k + 1]), w[:, k:k + 1],
                                jnp.asarray(ok[:, k]), wg, wu, wd)
                   for k in range(K))
        # ... and the sum over the picks as it was taken before PR 52 (a
        # token's pairs side by side, [T, K, D]) from the same rows: the
        # same float32 terms, added up slab by slab now
        ys = gf._ffn_tiles_reference(
            x[row_token], jnp.asarray(tile_expert), None, wg, wu, wd, tm=tm)
        wk = jnp.where(jnp.asarray(ok), w, 0.0)
        was = jnp.sum(jnp.where((wk != 0.0)[..., None], ys[dest], 0.0)
                      * wk[..., None], axis=1)
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(counted, sizes)
    if gf.tile_rows(T * K, width) == tm:
        np.testing.assert_allclose(y, was, rtol=0, atol=5e-7)


def _plain_attention(q, k, v, qpos, total, window):
    """q [P, H, D] at positions qpos over keys k, v [M, Hkv, D] of which
    `total` exist: key j visible iff j <= i and i - j < window."""
    H, hkv = q.shape[1], k.shape[1]
    k, v = (jnp.repeat(a, H // hkv, axis=1) for a in (k, v))
    s = jnp.einsum("phd,mhd->hpm", q, k) / math.sqrt(q.shape[-1])
    j = jnp.arange(k.shape[0])[None, :]
    seen = (j <= qpos[:, None]) & (j < total)
    if window is not None:
        seen &= qpos[:, None] - j < window
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hpm,mhd->phd", jnp.where(seen[None], p, 0.0), v)


def _pool(key, NB, hkv, bs, D, B, W):
    k1, k2, k3 = jax.random.split(key, 3)
    kp = jax.random.normal(k1, (NB, hkv, bs, D), jnp.float32)
    vp = jax.random.normal(k2, (NB, hkv, bs, D), jnp.float32)
    tables = jax.random.permutation(k3, jnp.arange(1, NB))[:B * W].reshape(
        B, W).astype(jnp.int32)
    return kp, vp, tables


def _rows_of(pool, table):          # [W] -> [W * bs, Hkv, D]
    return pool[table].transpose(0, 2, 1, 3).reshape(
        -1, pool.shape[1], pool.shape[3])


@pytest.mark.parametrize("window", [None, 64, 130])
def test_paged_kernel_with_a_window(window):
    """Contexts inside the window, at it, one past it and far past it (so
    that the stream starts at a later page group), and an empty slot."""
    B, H, hkv, D, bs, W = 6, 4, 2, 32, 16, 24
    kp, vp, tables = _pool(jax.random.PRNGKey(0), B * W + 1, hkv, bs, D, B, W)
    q = jax.random.normal(jax.random.PRNGKey(1), (B, H, D), jnp.float32)
    ctx = jnp.asarray([40, 64, 65, 300, 0, 383], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = pa.paged_attention(q, kp, vp, tables, ctx, impl="kernel",
                                 window=window)
        ref = pa.paged_attention(q, kp, vp, tables, ctx, impl="reference",
                                 window=window)
        for b in range(B):
            n = int(ctx[b])
            want = _plain_attention(
                q[b][None], _rows_of(kp, tables[b]), _rows_of(vp, tables[b]),
                jnp.asarray([n - 1]), n, window)[0] if n else 0.0
            np.testing.assert_allclose(got[b], want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl", ["kernel", "reference"])
@pytest.mark.parametrize("window", [None, 24])
def test_prefix_attention_matches_plain_attention(impl, window):
    """A chunk of 16 queries per row over a paged prefix: no prefix, a
    prefix longer than the window with a partly filled chunk, one that
    spans several page groups, and a row that is padding."""
    N, P, H, hkv, D, bs, W = 4, 16, 4, 2, 32, 16, 24
    kp, vp, tables = _pool(jax.random.PRNGKey(2), N * W + 1, hkv, bs, D, N, W)
    q = jax.random.normal(jax.random.PRNGKey(3), (N, P, H, D), jnp.float32)
    prefix = jnp.asarray([0, 48, 320, 64], jnp.int32)
    suffix = jnp.asarray([16, 9, 16, 0], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = pa.prefix_attention(q, kp, vp, tables, prefix, suffix,
                                  impl=impl, window=window)
        for n in range(N):
            live = int(suffix[n])
            if not live:
                continue
            qpos = int(prefix[n]) + jnp.arange(P)
            want = _plain_attention(
                q[n], _rows_of(kp, tables[n]), _rows_of(vp, tables[n]),
                qpos, int(prefix[n]) + live, window)
            np.testing.assert_allclose(got[n, :live], want[:live],
                                       rtol=1e-4, atol=1e-4)
    assert bool(jnp.all(jnp.isfinite(got)))


@pytest.mark.parametrize("impl", ["kernel", "reference"])
@pytest.mark.parametrize("window", [None, 24])
def test_rows_of_one_request_attend_in_groups(impl, window):
    """Rows of 16 regrouped into attention rows of up to 64 queries
    (models/decoding.py QueryGroups) against every row alone: three rows of
    slot 0 that start inside its table (the last partly filled), six of
    slot 1 (a full group of four and one of two), a row of slot 2 that
    does NOT follow the row before it although it starts where that ended,
    a row of slot 0 again that follows nothing, and padding."""
    from ray_tpu.models import decoding
    P, H, hkv, D, bs, W, slots = 16, 4, 2, 32, 16, 24, 4
    kp, vp, per_slot = _pool(jax.random.PRNGKey(5), slots * W + 1, hkv, bs,
                             D, slots, W)
    slot = jnp.asarray([0, 0, 0, 1, 1, 1, 1, 1, 1, 2, 0, 0, 0], jnp.int32)
    prefix = jnp.asarray([48, 64, 80, 0, 16, 32, 48, 64, 80, 96, 200, 0, 0],
                         jnp.int32)
    suffix = jnp.asarray([16, 16, 7, 16, 16, 16, 16, 16, 3, 9, 16, 0, 0],
                         jnp.int32)
    valid = suffix > 0
    q = jax.random.normal(jax.random.PRNGKey(6), (len(slot), P, H, D),
                          jnp.float32)
    rows = decoding.prefill_rows(per_slot[slot], prefix, suffix, valid, P,
                                 bs, slot, slots)
    g = rows.groups
    assert g.take.shape == (min(13, (13 + slots * 3) // 4), 4)
    assert np.asarray(g.suffix_lens).tolist() == [39, 64, 19, 9, 16, 0]
    assert np.asarray(g.prefix_lens)[:5].tolist() == [48, 0, 64, 96, 200]
    with jax.default_matmul_precision("highest"):
        got = decoding._attend_rows(q, kp, vp, rows, impl=impl,
                                    window=window)
        want = decoding._attend_rows(q, kp, vp, rows._replace(groups=None),
                                     impl=impl, window=window)
    live = np.asarray(rows.live)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-4, atol=1e-4)
