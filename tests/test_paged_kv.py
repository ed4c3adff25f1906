"""Paged-KV serving tests: block allocator invariants, radix prefix
cache, LRU eviction, paged decode numerics against the full forward
pass, backpressure/finish-reason semantics, and multiplexed per-model
prefix-cache isolation (serve/llm.py PagedBatcher +
ops/paged_attention.py)."""

import random
import threading
import time

import numpy as np
import pytest

from ray_tpu.serve.llm import BlockAllocator, PagedBatcher, RadixCache


def _tiny_cfg(arch="llama"):
    import jax.numpy as jnp
    from ray_tpu.models.transformer import TransformerConfig
    return TransformerConfig(vocab_size=97, d_model=32, n_heads=4,
                             n_kv_heads=2, n_layers=2, d_ff=64,
                             max_seq=128, dtype=jnp.float32,
                             remat=False, arch=arch)


def _greedy(params, cfg, prompt, n):
    """The oracle: `n` greedy tokens by repeated full forward passes."""
    from ray_tpu.models import transformer
    seq = list(prompt)
    for _ in range(n):
        logits = transformer.forward(
            params, np.asarray([seq], np.int32), cfg)
        seq.append(int(np.argmax(np.asarray(logits[0, -1]))))
    return seq[len(prompt):]


def _tiny_params(seed=0):
    import jax
    from ray_tpu.models import transformer
    return transformer.init_params(_tiny_cfg(), jax.random.PRNGKey(seed))


def _paged(params, cfg, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("prompt_pad", 16)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("pipeline_depth", 2)
    kw.setdefault("kv_block_size", 4)
    return PagedBatcher(params, cfg, **kw)


# ===========================================================================
# BlockAllocator
# ===========================================================================
def test_allocator_alloc_free_refcount():
    a = BlockAllocator(8)
    assert a.available() == 8
    blocks = a.alloc(3)
    assert len(blocks) == 3 and len(set(blocks)) == 3
    assert 0 not in blocks                    # scratch block never issued
    assert a.available() == 5
    assert all(a.refcount(b) == 1 for b in blocks)
    # Share one block: refcount 2, one decref keeps it used.
    a.incref(blocks[0])
    assert a.refcount(blocks[0]) == 2
    a.decref(blocks[0])
    assert a.refcount(blocks[0]) == 1
    assert a.counts() == {"used": 3, "cached": 0, "free": 5}
    for b in blocks:
        a.decref(b)
    assert a.counts() == {"used": 0, "cached": 0, "free": 8}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_allocator_counts_match_a_recount(seed):
    """counts() is kept as blocks change state (it is read at every
    admission and retirement): after any sequence of allocations, shared
    holds, releases, caching and eviction it equals a walk of the pool."""
    import random
    rng = random.Random(seed)
    a = BlockAllocator(24)
    held, cached = [], set()
    for _ in range(400):
        op = rng.choice(["alloc", "share", "drop", "cache", "evict"])
        if op == "alloc":
            got = a.alloc(rng.randint(1, 4))
            held += got or []
        elif op == "share" and held:
            some = rng.sample(held, min(len(held), 3))
            a.incref_many(some)
            held += some
        elif op == "drop" and held:
            rng.shuffle(held)
            some, held = held[:3], held[3:]
            a.decref_many(some)
        elif op == "cache" and held:
            b = rng.choice(held)
            a.mark_cached(b)
            cached.add(b)
        elif op == "evict" and cached:
            b = rng.choice(sorted(cached))
            cached.discard(b)
            a.release_cached(b)
        used = sum(1 for r in a._ref.values() if r > 0)
        idle = sum(1 for b in a._cached if a._ref.get(b, 0) == 0)
        assert a.counts() == {"used": used, "cached": idle,
                              "free": a.available()}
        assert used + idle + a.available() == 24


def test_allocator_double_free_raises():
    a = BlockAllocator(2)
    (b,) = a.alloc(1)
    a.decref(b)
    with pytest.raises(RuntimeError, match="double-free"):
        a.decref(b)


def test_allocator_never_partial():
    a = BlockAllocator(4)
    held = a.alloc(3)
    assert a.alloc(2) is None                 # only 1 left: all-or-nothing
    assert a.available() == 1                 # nothing leaked by the miss
    assert a.alloc(1) is not None
    assert held is not None


def test_allocator_cached_state_transitions():
    a = BlockAllocator(4)
    (b,) = a.alloc(1)
    a.mark_cached(b)
    # Still referenced: used, not cached.
    assert a.counts() == {"used": 1, "cached": 0, "free": 3}
    a.decref(b)                               # refcount 0 + cached: retained
    assert a.counts() == {"used": 0, "cached": 1, "free": 3}
    a.incref(b)                               # prefix hit re-uses it
    assert a.counts() == {"used": 1, "cached": 0, "free": 3}
    a.decref(b)
    a.release_cached(b)                       # eviction returns it
    assert a.counts() == {"used": 0, "cached": 0, "free": 4}


def test_allocator_no_leak_random_lifecycles():
    """N random request lifecycles (alloc / share / cache / evict /
    free in random order) conserve blocks exactly: used + cached +
    free == num_blocks at every step, all free at the end."""
    rng = random.Random(7)
    a = BlockAllocator(32)
    live = []                                 # [(blocks, cached_flags)]
    cached_pool = []                          # refcount-0 cached blocks
    for step in range(400):
        c = a.counts()
        assert c["used"] + c["cached"] + c["free"] == 32, (step, c)
        op = rng.random()
        if op < 0.35:                         # admit: maybe share a cached
            share = [b for b in cached_pool if rng.random() < 0.5]
            fresh = a.alloc(rng.randint(1, 4))
            if fresh is None:
                continue
            for b in share:
                a.incref(b)
                cached_pool.remove(b)
            live.append((share + fresh, share[:]))
        elif op < 0.7 and live:               # retire: maybe cache blocks
            blocks, shared = live.pop(rng.randrange(len(live)))
            for b in blocks:
                if b not in shared and rng.random() < 0.3:
                    a.mark_cached(b)
                    shared.append(b)
            for b in blocks:
                a.decref(b)
            for b in shared:
                if a.refcount(b) == 0 and b not in cached_pool:
                    cached_pool.append(b)
        elif cached_pool:                     # evict a cached block
            b = cached_pool.pop(rng.randrange(len(cached_pool)))
            a.release_cached(b)
    for blocks, shared in live:
        for b in blocks:
            a.decref(b)
        for b in shared:
            if a.refcount(b) == 0:
                a.release_cached(b)
            cached_pool.append(b)
    for b in cached_pool:
        a.release_cached(b)
    assert a.counts() == {"used": 0, "cached": 0, "free": 32}


# ===========================================================================
# RadixCache
# ===========================================================================
def test_radix_hit_miss_partial():
    a = BlockAllocator(16)
    tree = RadixCache(block_size=4)
    toks = list(range(1, 13))                 # 3 full blocks
    blocks = a.alloc(3)
    assert tree.insert(toks, blocks, a) == 3
    # Full-prefix hit -- but capped at len-1 so a suffix always remains:
    assert tree.match(toks) == blocks[:2]
    assert tree.match(toks + [99]) == blocks  # one more token: all 3 hit
    # Partial prefix: first block shared, divergence stops the walk.
    assert tree.match(toks[:4] + [55, 56, 57, 58, 99]) == blocks[:1]
    # Miss from the first token.
    assert tree.match([70, 71, 72, 73, 74]) == []
    # Sub-block prompts can never hit (only FULL blocks shareable).
    assert tree.match(toks[:4]) == []


def test_radix_insert_collision_keeps_existing():
    a = BlockAllocator(16)
    tree = RadixCache(block_size=2)
    b1 = a.alloc(1)
    b2 = a.alloc(1)
    assert tree.insert([1, 2], b1, a) == 1
    assert tree.insert([1, 2], b2, a) == 0    # duplicate path: no new node
    assert tree.match([1, 2, 3]) == b1        # existing node wins
    assert a.refcount(b2[0]) == 1             # caller keeps its private copy


def test_radix_eviction_lru_leaf_only_respects_refcounts():
    """LRU eviction order over refcount-0 leaves; a block some request
    still references is NEVER evicted, and interior nodes are only
    evictable once their children are gone (prefix property)."""
    a = BlockAllocator(16)
    tree = RadixCache(block_size=2)
    blocks = a.alloc(3)
    tree.insert([1, 2, 3, 4, 5, 6], blocks, a)      # one chain of 3
    other = a.alloc(1)
    tree.insert([9, 9], other, a)                   # separate branch
    for b in blocks + other:
        a.decref(b)                                 # all cached now
    tree.match([9, 9, 0])                           # touch: most recent
    # Only leaves are candidates: the chain tail + the other branch.
    cands = sorted(tree.evictable())
    assert {n.block for _, n in cands} == {blocks[2], other[0]}
    # Oldest leaf first == the chain tail (match() touched `other`).
    assert cands[0][1].block == blocks[2]
    # A referenced leaf must survive any eviction sweep.
    a.incref(other[0])
    protected = [(t, n) for t, n in tree.evictable()
                 if a.refcount(n.block) == 0]
    assert {n.block for _, n in protected} == {blocks[2]}
    tree.remove_leaf(protected[0][1], a)
    assert blocks[2] in a._free and other[0] not in a._free
    # Its parent became a leaf -> now evictable; walk the chain down.
    assert {n.block for _, n in tree.evictable()
            if a.refcount(n.block) == 0} == {blocks[1]}
    with pytest.raises(RuntimeError):
        tree.remove_leaf(tree.root, a)


def test_radix_shared_clock_orders_lru_across_models():
    """Per-model trees share ONE LRU clock, so eviction recency is
    comparable across models: a high-traffic model's stale block must
    sort older than a low-traffic model's just-touched block (per-tree
    ticks would evict the low-traffic model's hot prefix first)."""
    import itertools
    a = BlockAllocator(8)
    counter = itertools.count(1)
    t1 = RadixCache(2, clock=lambda: next(counter))
    t2 = RadixCache(2, clock=lambda: next(counter))
    b1 = a.alloc(1)
    t1.insert([1, 2], b1, a)
    for _ in range(5):                  # heavy traffic on model 1
        t1.match([1, 2, 9])
    b2 = a.alloc(1)
    t2.insert([3, 4], b2, a)            # model 2: one FRESH block
    for b in b1 + b2:
        a.decref(b)
    cands = sorted((last, node) for tree in (t1, t2)
                   for last, node in tree.evictable())
    # Globally-oldest is model 1's block (touched before model 2's
    # insert) even though its per-tree tick count is far higher.
    assert cands[0][1].block == b1[0]


def test_eviction_pressure_never_clobbers_shared_blocks():
    """End-to-end pressure: a pool sized for ~1.5 requests forces the
    engine to LRU-evict the previous request's cached prefix while the
    current one still holds blocks; every request must still finish
    with exact greedy tokens (shared blocks never clobbered)."""
    import jax
    from ray_tpu.models import transformer
    cfg = _tiny_cfg()
    params = _tiny_params()
    bat = _paged(params, cfg, num_slots=2, max_len=32,
                 kv_block_size=4, kv_num_blocks=4)
    try:
        outs = {}
        for i in range(6):
            p = [10 * (i % 3) + 1, 2, 3, 4, 5]    # 3 distinct prompts
            outs.setdefault(i % 3, []).append(
                bat.generate(p, max_new=6, timeout=120)["tokens"])
        for runs in outs.values():
            assert all(r == runs[0] for r in runs), runs
        st = bat.kv_stats()
        assert st["prefix_cache"]["evictions"] > 0
        c = st["blocks"]
        assert c["used"] + c["cached"] + c["free"] == bat.num_blocks
    finally:
        bat.stop()


# ===========================================================================
# Numerics: paged == dense on the JAX reference path
# ===========================================================================
def test_paged_attention_reference_matches_dense_math():
    """Gather-based paged attention == dense attention over the same
    (contiguously laid out) KV, for ragged context lengths."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.paged_attention import paged_attention_reference
    B, H, HKV, D, BS, W = 3, 4, 2, 16, 4, 5
    NB = 1 + B * W
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (B, H, D), jnp.float32)
    kp = jax.random.normal(k2, (NB, HKV, BS, D), jnp.float32)
    vp = jax.random.normal(k3, (NB, HKV, BS, D), jnp.float32)
    bt = (1 + np.arange(B * W, dtype=np.int32)).reshape(B, W)
    lens = np.asarray([3, 11, 20], np.int32)
    out = paged_attention_reference(q, kp, vp, jnp.asarray(bt),
                                    jnp.asarray(lens))
    # Dense oracle: materialize each row's window ([B, W, Hkv, bs, D]
    # -> [B, M, Hkv, D]) and do plain attention.
    kd = np.asarray(kp)[bt].transpose(0, 1, 3, 2, 4).reshape(
        B, W * BS, HKV, D)
    vd = np.asarray(vp)[bt].transpose(0, 1, 3, 2, 4).reshape(
        B, W * BS, HKV, D)
    groups = H // HKV
    qg = np.asarray(q).reshape(B, HKV, groups, D)
    s = np.einsum("bhgk,bmhk->bhgm", qg, kd) / np.sqrt(D)
    mask = np.arange(W * BS)[None, :] < lens[:, None]
    s = np.where(mask[:, None, None, :], s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    want = np.einsum("bhgm,bmhk->bhgk", w, vd).reshape(B, H, D)
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-5)


def _ragged_lengths(B, W, bs):
    """Context lengths that walk the edges of the kernel's loops, the
    sharpest first: an empty row, one token, exactly one page, one page
    group (128 positions, or the whole table where that is shorter)
    and one position either side of it, mid-table, ...; then a full
    table whose first W - 1 pages the last row shares."""
    full = W * bs
    group = min(full, max(bs, 128 // bs * bs))
    edges = [0, 1, bs, group - 1, min(group + 1, full), group,
             full // 2 + 3, bs - 1, bs + 1, min(2 * group, full),
             full - 1]
    return np.asarray([edges[i % len(edges)] for i in range(B - 2)]
                      + [full, (W - 1) * bs + 1], np.int32)


def _stream_case(case, B, W, bs, bt, depth):
    """(lengths, window) of a named walk of the decode kernel's page
    stream, and `bt` edited in place where the case is about the table.
    `depth` is the ring's depth in groups of 128 positions."""
    full, lap = W * bs, depth * 128
    if case == "ragged":
        bt[B - 1, :W - 1] = bt[B - 2, :W - 1]     # a shared prefix
        return _ragged_lengths(B, W, bs), None
    if case == "ring-edges":
        # 0, 1, a group and one either side, then N x 128 +- 1 around the
        # ring's depth and in its second lap
        edges = [0, 1, 127, 128, 129, lap - 129, lap - 1, lap, lap + 1,
                 lap + 127, lap + 129, 2 * lap - 1, 2 * lap + 1, full]
    elif case == "empty-rows":
        # the first, the last and every other sequence empty: the stream
        # hands its head from program to program over them
        edges = [0, lap + 200, 0, 3, 0, 2 * lap + 77, 0, 0, 129, 0]
    elif case == "window-second-lap":
        # the first attended position mid-group in the ring's first and
        # second lap, at a group's edge, and contexts inside the window
        edges = [lap + 128 + 50 + 300, 300, 299, 301, 0, 128 + 300,
                 2 * lap + 77 + 300, 1, lap + 300 - 1, full]
        return np.asarray(edges[:B], np.int32), 300
    else:
        assert case == "runs"
        # consecutive and scattered physical ids in one table: a row all
        # in a row in the pool, one broken mid-group, one descending, one
        # that shares the first's pages and goes on scattered
        free = 1 + bt.max()
        bt[0] = np.arange(free, free + W)
        bt[1] = np.arange(free + W, free + 2 * W)
        bt[1, 11], bt[1, 12] = bt[1, 12], bt[1, 11]
        bt[2] = np.arange(free + 3 * W - 1, free + 2 * W - 1, -1)
        bt[3, :W // 2] = bt[0, :W // 2]
        edges = [full, full - 5, lap + 300, full - 1, lap - 1, 777]
    assert B <= len(edges) and max(edges) <= full
    return np.asarray(edges[:B], np.int32), None


@pytest.mark.parametrize("kernel,most", [("decode", 170_000),
                                         ("prefix", 110_000)])
def test_tracing_a_paged_kernel_stays_cheap(kernel, most):
    """A Pallas kernel's body is traced in Python once for every shape it
    meets, for either platform, in every process: an engine's warm start
    traces `prefix_attention` eight times and more, and a body written
    with operators on traced scalars (each a jitted `jnp` wrapper) cost
    the agents cell +29 % of its warm `setup_s` (PERF.md, PR 41).  The
    count of Python calls one trace makes is what that time follows and
    is the same on every host: 157 k (decode) and 100 k (prefix) as
    shipped, 195 k and 130 k with the operators."""
    import cProfile
    import pstats
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import paged_attention as pa
    S, dt = jax.ShapeDtypeStruct, jnp.bfloat16
    pool = S((64, 4, 16, 128), dt)

    def trace(rows):
        if kernel == "decode":
            fn, args = pa._paged_fwd, (S((rows, 8, 128), dt), pool, pool,
                                       S((rows, 40), jnp.int32),
                                       S((rows,), jnp.int32))
        else:
            fn, args = pa._prefix_fwd, (
                S((rows, 16, 8, 128), dt), pool, pool,
                S((rows, 40), jnp.int32), S((rows,), jnp.int32),
                S((rows,), jnp.int32))
        jax.make_jaxpr(lambda *a: fn(*a, scale=0.1, window=24,
                                     interpret=False))(*args)

    trace(3)                        # imports and jnp's own caches
    prof = cProfile.Profile()
    prof.enable()
    trace(5)
    prof.disable()
    calls = pstats.Stats(prof).total_calls
    assert calls < most, calls


@pytest.mark.parametrize("kernel", ["decode", "prefix"])
def test_a_tpu_host_traces_a_paged_kernel_once(kernel, monkeypatch):
    """`platform_dependent` traces every branch: in a process whose backend
    is a TPU the branch for the other platforms is the gather, so a
    kernel's body is traced once a shape and not twice; lowered for a TPU
    the program still holds the one Mosaic call, and run here (no TPU)
    it is the gather's output, bit for bit."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import paged_attention as pa
    from test_attention import lower_for_tpu
    bodies = []
    name = "_paged_kernel" if kernel == "decode" else "_prefix_kernel"
    body = getattr(pa, name)
    monkeypatch.setattr(pa, name, lambda *a, **k: (bodies.append(1),
                                                   body(*a, **k))[1])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, H, HKV, D, BS, W = 3, 8, 2, 128, 16, 20
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    kp = jax.random.normal(keys[1], (1 + B * W, HKV, BS, D), jnp.bfloat16)
    vp = jax.random.normal(keys[2], kp.shape, jnp.bfloat16)
    bt = jnp.asarray(1 + np.arange(B * W, dtype=np.int32).reshape(B, W))
    lens = jnp.asarray([200, 0, 37], jnp.int32)
    if kernel == "decode":
        q = jax.random.normal(keys[0], (B, H, D), jnp.bfloat16)
        fn, ref = pa.paged_attention_kernel, pa.paged_attention_reference
        args = (q, kp, vp, bt, lens)
    else:
        q = jax.random.normal(keys[0], (B, 16, H, D), jnp.bfloat16)
        fn, ref = pa.prefix_attention_kernel, pa.prefix_attention_reference
        args = (q, kp, vp, bt, lens, jnp.asarray([16, 9, 0], jnp.int32))
    fn = fn.__wrapped__             # past the jit: a trace of its own
    hlo = lower_for_tpu(fn, *args)
    assert hlo.count("tpu_custom_call") == 1
    assert len(bodies) == 1
    np.testing.assert_array_equal(
        np.asarray(jax.jit(fn)(*args), np.float32),
        np.asarray(ref(*args), np.float32))
    assert len(bodies) == 1


def test_ring_shape_follows_the_static_shapes():
    """(pages a group, groups in the ring, groups a softmax update) of
    the decode kernel's page stream, for the cells' pools and the edges
    of the rule: the kernel engages on every call, so what it owes the
    tests is what it derives."""
    from ray_tpu.ops.paged_attention import _ring_shape
    for (W, hkv, bs, D, itemsize), want in {
            (1072, 4, 16, 128, 2): (8, 16, 4),   # Trinity-Mini, LFM2 (x2)
            (48, 8, 16, 128, 2): (8, 8, 2),      # Mistral-7B
            (48, 8, 16, 128, 4): (8, 4, 1),      # ... an f32 pool
            (200, 4, 16, 128, 4): (8, 8, 2),     # the stream tests, f32
            (288, 2, 16, 128, 2): (8, 16, 4),    # ... bf16
            (5, 4, 8, 64, 4): (5, 16, 4),        # a table under a group
            (16, 8, 16, 64, 2): (8, 16, 4),      # llama-1b (narrow: pages)
            (64, 32, 32, 256, 4): (1, 2, 1),     # two groups are the budget
    }.items():
        assert _ring_shape(W, hkv, bs, D, itemsize) == want, (W, hkv, bs, D)


# (H, Hkv, D, bs, W, B, case): the benchmark's serving cell (Mistral-7B
# heads; D 128 takes the kernel's whole-page DMA path), llama-1b and
# gpt2-small heads (D 64: the block-table BlockSpec path), a small MHA
# table that is one short group, and tables that are not a whole number
# of 8-page groups on either path; then the page stream's own edges
# (`_stream_case`) at a small head count, f32 (a ring of 8 groups, 2 a
# softmax update) and bf16 (16 and 4), and heads of 64 side by side.
@pytest.mark.parametrize("H,HKV,D,BS,W,B,case,dtype", [
    (32, 8, 128, 16, 48, 32, "ragged", "float32"),
    (32, 8, 64, 16, 16, 8, "ragged", "float32"),
    (12, 12, 64, 16, 16, 8, "ragged", "float32"),
    (4, 4, 64, 8, 5, 3, "ragged", "float32"),
    (8, 2, 128, 16, 11, 12, "ragged", "float32"),
    (4, 2, 64, 16, 11, 12, "ragged", "float32"),
    (4, 4, 128, 16, 200, 14, "ring-edges", "float32"),
    (4, 2, 128, 16, 288, 14, "ring-edges", "bfloat16"),
    (4, 4, 128, 16, 200, 10, "empty-rows", "float32"),
    (4, 2, 128, 16, 288, 10, "empty-rows", "bfloat16"),
    (4, 4, 128, 16, 200, 10, "window-second-lap", "float32"),
    (4, 2, 128, 16, 288, 10, "window-second-lap", "bfloat16"),
    (4, 4, 128, 16, 200, 6, "runs", "float32"),
    (4, 2, 128, 16, 288, 6, "runs", "bfloat16"),
    (8, 8, 64, 16, 200, 6, "runs", "float32"),
    (8, 4, 64, 16, 288, 10, "window-second-lap", "bfloat16"),
    (8, 8, 64, 16, 200, 14, "ring-edges", "float32"),
], ids=str)
def test_paged_attention_kernel_matches_reference(H, HKV, D, BS, W, B, case,
                                                  dtype):
    """Pallas kernel (interpret mode off-TPU) == gather reference, for
    ragged lengths and with two rows sharing physical pages (a common
    prefix) while a third row's table is scattered differently.  The
    stream cases with D 64 hold two heads side by side in a pool row of
    128 lanes (LFM2's pool), so they too take the whole-page DMA path."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.paged_attention import (_ring_shape,
                                             paged_attention_kernel,
                                             paged_attention_reference)
    NB = 1 + B * W
    dtype = jnp.dtype(dtype)
    f = 2 if (D == 64 and case != "ragged") else 1      # heads a pool row
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (B, H, D), dtype)
    pool = (NB + 3 * W, HKV // f, BS, f * D)    # ("runs" adds three rows)
    kp = jax.random.normal(k2, pool, dtype)
    vp = jax.random.normal(k3, pool, dtype)
    rng = np.random.RandomState(0)
    bt = rng.permutation(np.arange(1, NB, dtype=np.int32)).reshape(B, W)
    depth = _ring_shape(W, HKV // f, BS, f * D, dtype.itemsize)[1]
    lens, window = _stream_case(case, B, W, BS, bt, depth)
    args = (q, kp, vp, jnp.asarray(bt), jnp.asarray(lens))
    ref = paged_attention_reference(*args, window=window)
    out = paged_attention_kernel(*args, window=window)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out[lens == 0], 0.0)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out, ref, atol=tol, rtol=tol)


def shared_scene(case, B, W, bs):
    """(tables, lengths, SharedRows) of a decode step in which sets of slots
    share prefixes, the sets found as the engine finds them.  "sets": B = 40
    slots in sets of 17 (three programs), 9 (two, the second of one
    member), 8 and 2, shared lengths that are and are not whole groups of
    128 positions, a member that is not active, and slots in no set, active
    and not.  "laps": a prefix longer than the ring is deep, shared by
    three.  "none": nothing shared."""
    import jax.numpy as jnp
    from ray_tpu.ops import paged_attention as pa
    from ray_tpu.serve.llm import find_shared_prefixes
    rng = np.random.RandomState(3)
    bt = rng.permutation(np.arange(1, 1 + B * W, dtype=np.int32)
                         ).reshape(B, W)
    sets = {"sets": [(range(0, 17), 9), (range(17, 26), 16),
                     (range(26, 34), 13), (range(34, 36), 8)],
            "laps": [(range(0, 3), W - 44), (range(4, 6), 9)],
            "none": []}[case]
    lens = rng.randint(1, W * bs + 1, size=B)
    for slots, blocks in sets:
        for s in slots:
            bt[s, :blocks] = bt[slots[0], :blocks]
            lens[s] = rng.randint(blocks * bs + 1, W * bs + 1)
        lens[slots[0]] = blocks * bs + 1        # one position of its own
    if case == "sets":
        lens[[3, 37]] = 0
    found = find_shared_prefixes(
        {s: bt[s] for s in range(B) if lens[s]}, bs, B)
    want = sorted((len([s for s in slots if lens[s]]), blocks * bs)
                  for slots, blocks in sets)
    got = {}
    for row, leader, positions in zip(*found):
        if positions:
            assert leader == row[0] and lens[leader]
            key = (int(bt[leader, 0]), int(positions))
            got[key] = got.get(key, 0) + int((row >= 0).sum())
    assert sorted((n, key[1]) for key, n in got.items()) == want
    tables, lens = jnp.asarray(bt), jnp.asarray(lens, jnp.int32)
    shared = pa.SharedPrefixes(*(jnp.asarray(a) for a in found))
    return tables, lens, pa.shared_rows(shared, tables, lens)


# Olmo-Hybrid's 30 multi-head kv heads, Mistral's groups of 4, LFM2's heads
# of 64 side by side, a prefix that laps the ring, and a step with no set
# (the branch not taken).
@pytest.mark.parametrize("H,HKV,D,W,B,case,dtype", [
    (30, 30, 128, 24, 40, "sets", "bfloat16"),
    (8, 2, 128, 24, 40, "sets", "float32"),
    (8, 4, 64, 24, 40, "sets", "float32"),
    (4, 2, 128, 200, 6, "laps", "bfloat16"),
    (4, 4, 128, 200, 6, "laps", "float32"),
    (8, 2, 128, 24, 40, "none", "float32"),
], ids=str)
def test_shared_prefixes_are_read_once_and_change_nothing(H, HKV, D, W, B,
                                                          case, dtype):
    """The kernel with `shared` (every sequence over what is its own, every
    program over what its members share, merged by the softmax statistics)
    == the gather reference, which knows of no sets."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.paged_attention import (paged_attention_kernel,
                                             paged_attention_reference)
    BS, dtype = 16, jnp.dtype(dtype)
    f = 128 // D
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(k1, (B, H, D), dtype)
    kp = jax.random.normal(k2, (1 + B * W, HKV // f, BS, f * D), dtype)
    vp = jax.random.normal(k3, kp.shape, dtype)
    tables, lens, shared = shared_scene(case, B, W, BS)
    assert bool(shared.some) == (case != "none")
    ref = paged_attention_reference(q, kp, vp, tables, lens)
    out = paged_attention_kernel(q, kp, vp, tables, lens, shared=shared)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    assert np.isfinite(out).all()
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(out[live], ref[live], atol=tol, rtol=tol)


def test_paged_attention_kernel_bf16_pool_and_dead_table_entries():
    """The serving dtype on the whole-page DMA path: bf16 q and pools,
    table entries beyond each row's length pointing at scratch block 0
    (as the engine pads them) and a batch that is mostly empty rows."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.paged_attention import (paged_attention_kernel,
                                             paged_attention_reference)
    B, H, HKV, D, BS, W = 8, 8, 2, 128, 16, 20
    NB = 1 + B * W
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(k1, (B, H, D), jnp.bfloat16)
    kp = jax.random.normal(k2, (NB, HKV, BS, D), jnp.bfloat16)
    vp = jax.random.normal(k3, (NB, HKV, BS, D), jnp.bfloat16)
    lens = np.asarray([0, 0, 200, 0, 0, 129, 0, 17], np.int32)
    bt = (1 + np.arange(B * W, dtype=np.int32)).reshape(B, W)
    bt[np.arange(W)[None, :] * BS >= lens[:, None]] = 0
    args = (q, kp, vp, jnp.asarray(bt), jnp.asarray(lens))
    out = np.asarray(paged_attention_kernel(*args), np.float32)
    ref = np.asarray(paged_attention_reference(*args), np.float32)
    np.testing.assert_array_equal(out[lens == 0], 0.0)
    np.testing.assert_allclose(out, ref, atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("h,hkv,d,w,b,f", [(32, 8, 64, 16, 8, 1),
                                           (12, 12, 64, 16, 8, 1),
                                           (32, 8, 128, 48, 32, 1),
                                           (32, 4, 128, 1072, 32, 1),
                                           (32, 8, 64, 1072, 32, 2)])
def test_paged_kernel_lowers_for_tpu(h, hkv, d, w, b, f):
    """The compiled kernel at the llama-1b (GQA 32/8) and gpt2-small
    (MHA 12/12) head layouts and at the benchmark's serving cells
    (Mistral-7B heads of 128, 32 slots, 48-page tables; Trinity-Mini's 4
    kv heads of 128 under 1,072-column tables; LFM2's 8 of 64, `f` = 2 side
    by side in a pool row), lowered for
    the TPU platform from this CPU host (tests/test_attention.py
    lower_for_tpu): ONE Mosaic call whatever the path, so a trace
    counts one `paged_attention` operation per layer and step.  (What
    Mosaic itself refuses shows in tests/test_tpu_aot.py.)"""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.paged_attention import paged_attention_kernel
    from test_attention import lower_for_tpu
    BS = 16
    S = jax.ShapeDtypeStruct
    pool = S((8193 if w > 48 else 1 + b * w, hkv // f, BS, f * d),
             jnp.bfloat16)
    hlo = lower_for_tpu(paged_attention_kernel,
                        S((b, h, d), jnp.bfloat16), pool, pool,
                        S((b, w), jnp.int32), S((b,), jnp.int32))
    assert hlo.count("tpu_custom_call") == 1
    with pytest.raises(ValueError, match="block size 4"):
        paged_attention_kernel(
            jnp.zeros((b, h, d)), jnp.zeros((9, hkv, 4, d)),
            jnp.zeros((9, hkv, 4, d)), jnp.zeros((b, 2), jnp.int32),
            jnp.zeros((b,), jnp.int32))


@pytest.mark.parametrize("impl", ["reference", "kernel"])
def test_paged_decode_inactive_slots_attend_to_nothing(impl):
    """The zero-context gate: slots the engine marks inactive (retired,
    their last length and table still on the device) take no part in a
    decode step.  The active slots' tokens, lengths and pool writes are
    those of an engine in which the inactive slots never existed, and
    an inactive slot's `lengths` / `last_token` stay as they were."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import decoding
    cfg, params = _tiny_cfg(), _tiny_params()
    B, BS, MAXLEN, STEPS = 4, 8, 40, 3
    W = decoding.paged_table_width(MAXLEN, BS)
    empty = decoding.init_paged_caches(cfg, B, B * W, BS, MAXLEN)
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    tables = (1 + np.arange(B * W, dtype=np.int32)).reshape(B, W)
    caches = empty._replace(
        kp=jax.random.normal(ks[0], empty.kp.shape, cfg.dtype),
        vp=jax.random.normal(ks[1], empty.vp.shape, cfg.dtype),
        block_tables=jnp.asarray(tables),
        lengths=jnp.asarray([5, 17, 8, 30], jnp.int32),
        last_token=jnp.asarray([3, 9, 27, 81], jnp.int32))
    live = np.asarray([0, 2])
    active = jnp.asarray([True, False, True, False])

    def steps(c, act):
        toks = []
        for _ in range(STEPS):
            c, t = decoding._paged_decode_core(params, c, act, cfg, impl)
            toks.append(np.asarray(t))
        return c, np.stack(toks)

    got, got_toks = steps(caches, active)
    alone = caches._replace(block_tables=caches.block_tables[live],
                            lengths=caches.lengths[live],
                            last_token=caches.last_token[live])
    want, want_toks = steps(alone, jnp.asarray([True, True]))
    np.testing.assert_array_equal(got_toks[:, live], want_toks)
    np.testing.assert_array_equal(np.asarray(got.lengths),
                                  [5 + STEPS, 17, 8 + STEPS, 30])
    np.testing.assert_array_equal(np.asarray(got.last_token)[live],
                                  np.asarray(want.last_token))
    np.testing.assert_array_equal(np.asarray(got.last_token)[[1, 3]],
                                  [9, 81])
    # Every block but the scratch block (0, where gated writes land).
    for pool, pool_alone, before in ((got.kp, want.kp, caches.kp),
                                     (got.vp, want.vp, caches.vp)):
        np.testing.assert_array_equal(np.asarray(pool)[:, 1:],
                                      np.asarray(pool_alone)[:, 1:])
        inactive = tables[[1, 3]].ravel()
        np.testing.assert_array_equal(np.asarray(pool)[:, inactive],
                                      np.asarray(before)[:, inactive])


def test_warmup_failure_is_loud_not_a_healthy_looking_engine():
    """A decode step that cannot be built (here: an attention impl that
    does not exist) fails the request queued behind warm-up AND every
    later submit with the cause, instead of an engine that stays up and
    answers nothing."""
    bat = _paged(_tiny_params(), _tiny_cfg(), attn_impl="no-such-impl")
    try:
        bat._thread.join(timeout=120)
        assert not bat._thread.is_alive() and not bat._warmed
        for _ in range(2):
            with pytest.raises(RuntimeError, match="warm-up") as ei:
                bat.submit([1, 2, 3], max_new=2)
            assert "no-such-impl" in repr(ei.value.__cause__)
    finally:
        bat.stop()


def test_paged_decode_matches_full_forward():
    """One packed prefill + 5 decode steps, six tokens a slot
    (paged_prefill_decode_packed, the engine's fused program) == greedy
    transformer.forward, token for token (the tier-1 CPU reference-path
    parity check)."""
    import jax.numpy as jnp
    from ray_tpu.models import decoding
    cfg = _tiny_cfg()
    params = _tiny_params(seed=3)
    num_slots, max_len, bs = 2, 32, 4
    prompts = [[5, 9, 11, 2], [60, 2, 8]]
    W = max_len // bs
    paged = decoding.init_paged_caches(cfg, num_slots,
                                       num_slots * W, bs, max_len)
    P = 8
    up = decoding.FusedUpload.of(P, paged)
    packed_p = up.empty(num_slots)
    for row, p in enumerate(prompts):
        packed_p[row, :len(p)] = p
        # suffix == whole prompt, no cached prefix
        packed_p[row, up.scalars] = (len(p), 0, row, up.CLOSES)
        packed_p[row, up.table] = np.arange(1 + row * W, 1 + (row + 1) * W)
    steps = 6
    paged, toks, _ = decoding.paged_prefill_decode_packed(
        params, paged, jnp.asarray(packed_p), cfg, steps, P,
        attn_impl="reference")
    toks = np.asarray(toks)
    for row, p in enumerate(prompts):
        # The prefill's first token, then one per decode step after it.
        assert toks[:, row].tolist() == _greedy(params, cfg, p, steps), p
    np.testing.assert_array_equal(np.asarray(paged.lengths),
                                  [len(p) + steps - 1 for p in prompts])


@pytest.mark.parametrize("arch", ["llama", "gpt2"])
def test_paged_engine_matches_oracle(arch, lm_params):
    """End-to-end: PagedBatcher greedy tokens == the full-forward
    oracle, including a prefix-cache-hit re-run."""
    cfg = _tiny_cfg(arch)
    params = lm_params(cfg, 0)
    prompts = [[5, 9, 11], [3], [60, 2, 8, 40, 7]]
    paged = _paged(params, cfg)
    try:
        outs = [paged.generate(p, max_new=8, timeout=120)
                for p in prompts]
        # Re-run: the 5-token prompt now hits its cached first block.
        hit = paged.generate(prompts[2], max_new=8, timeout=120)
        assert hit["cache_hit"] and hit["cached_tokens"] == 4
    finally:
        paged.stop()
    for p, out in zip(prompts, outs):
        assert out["tokens"] == _greedy(params, cfg, p, 8), p
    assert hit["tokens"] == outs[2]["tokens"]


# ===========================================================================
# Backpressure + finish-reason "cache" semantics
# ===========================================================================
def test_kv_exhaustion_queues_then_completes():
    """Transient pool exhaustion QUEUES requests for blocks instead of
    killing them: with a pool fitting ~one request, N concurrent
    requests all finish with reason length, never "cache"."""
    cfg = _tiny_cfg()
    params = _tiny_params()
    # 5 usable blocks of 4 = 20 positions; each request needs
    # ceil((5 + 8)/4) = 4 blocks, so two can never run concurrently.
    bat = _paged(params, cfg, num_slots=2, max_len=32,
                 kv_block_size=4, kv_num_blocks=5, prefix_cache=False)
    try:
        reqs = [bat.submit([i, 2, 3, 4, 5], max_new=8)
                for i in range(4)]
        for r in reqs:
            assert r.done.wait(120)
            assert r.error is None
            assert r.finish_reason == "length", r.finish_reason
            assert len(r.tokens) == 8
        c = bat.kv_stats()["blocks"]
        assert c == {"used": 0, "cached": 0, "free": 5}
    finally:
        bat.stop()


def test_oversized_request_reports_cache():
    """finish-reason "cache" is reserved for a single request that can
    NEVER fit (exceeds the whole pool or its block table)."""
    cfg = _tiny_cfg()
    params = _tiny_params()
    bat = _paged(params, cfg, num_slots=2, max_len=32,
                 kv_block_size=4, kv_num_blocks=3)
    try:
        # Needs ceil((5 + 24)/4) = 8 > 3 total blocks -> rejected, but
        # pool pressure alone never reports "cache" (prior test).
        req = bat.submit([1, 2, 3, 4, 5], max_new=24)
        assert req.done.wait(120)
        assert req.finish_reason == "cache"
        assert req.tokens == []
        # The pool is untouched and the engine still serves.
        out = bat.generate([1, 2, 3], max_new=4, timeout=120)
        assert out["finish_reason"] == "length"
    finally:
        bat.stop()


def test_request_capped_by_table_width_truncates_with_cache():
    """A request whose allocation is clamped to its table width decodes
    to the cap and reports "cache" (the one case where the reason still
    means a truncated reply)."""
    cfg = _tiny_cfg()
    params = _tiny_params()
    bat = _paged(params, cfg, num_slots=2, max_len=16, kv_block_size=4,
                 kv_num_blocks=16, prompt_pad=8)
    try:
        req = bat.submit([1, 2, 3, 4, 5], max_new=64)
        assert req.done.wait(120)
        assert req.finish_reason == "cache"
        # Decoded to the table cap: 16 positions - 5 prompt = 11.
        assert len(req.tokens) == 11
    finally:
        bat.stop()


def _is_greedy(params, cfg, req):
    """One forward pass over prompt + reply: every token of the reply is
    the argmax at the position before it."""
    from ray_tpu.models import transformer
    seq = np.asarray([req.prompt + req.tokens], np.int32)
    want = np.argmax(np.asarray(transformer.forward(params, seq, cfg)[0]),
                     axis=-1)[len(req.prompt) - 1:-1]
    assert req.tokens == want.tolist()


def test_a_clamped_request_rides_the_chunks_beside_a_live_slot():
    """A request that max_len clamps decodes in ordinary chunks, the last
    of which runs past its cap, beside a slot that lives on: its reply is
    the first cap - len(prompt) tokens its prompt yields, cut there with
    reason "cache", and the other slot's reply is what it yields alone."""
    cfg, params = _tiny_cfg(), _tiny_params()
    bat = _paged(params, cfg)               # max_len 48, chunks of 4
    try:
        other = bat.submit([3, 1, 4], max_new=42)
        clamped = bat.submit(list(range(20, 34)), max_new=64)
        for r in (other, clamped):
            assert r.done.wait(120) and r.error is None
    finally:
        bat.stop()
    assert clamped.finish_reason == "cache" and len(clamped.tokens) == 48 - 14
    assert other.finish_reason == "length" and len(other.tokens) == 42
    _is_greedy(params, cfg, clamped)
    _is_greedy(params, cfg, other)


def test_a_lone_clamped_request_takes_whole_chunks_to_its_cap():
    """Two programs, no single step: 34 tokens to the cap at 4 a dispatch
    are 9 dispatches (the engine with a one-token tail launched 8 chunks
    and 3 single steps); past the cap nothing is live and nothing more is
    launched."""
    cfg, params = _tiny_cfg(), _tiny_params()
    bat = _paged(params, cfg)
    try:
        req = bat.submit(list(range(20, 34)), max_new=64)
        assert req.done.wait(120) and req.error is None
        time.sleep(0.3)
        assert bat.host_stats()["dispatches"] == 9
    finally:
        bat.stop()
    assert req.finish_reason == "cache" and len(req.tokens) == 34
    _is_greedy(params, cfg, req)


def test_unaligned_max_len_caps_at_max_len_not_table():
    """max_len that is NOT a block multiple: the per-request cap stays
    at max_len (regression: it was table_width*block_size, letting
    requests decode into the rounding slack past max_len and
    potentially past cfg.max_seq)."""
    cfg = _tiny_cfg()
    params = _tiny_params()
    bat = _paged(params, cfg, num_slots=2, max_len=10, kv_block_size=4,
                 kv_num_blocks=16, prompt_pad=8)
    try:
        req = bat.submit([1, 2, 3, 4, 5], max_new=64)
        assert req.done.wait(120)
        assert req.finish_reason == "cache"
        # 10 positions - 5 prompt = 5, NOT table cap 12 - 5 = 7.
        assert len(req.tokens) == 5
    finally:
        bat.stop()


# ===========================================================================
# Multiplexing
# ===========================================================================
def test_multiplex_adapter_swap_isolates_prefix_caches():
    """Two adapters through one engine: per-model radix trees never
    cross (same prompt, different model -> different tokens, no
    cross-model cache_hit on first use), and swaps are LRU-resident."""
    import jax
    import jax.numpy as jnp
    cfg = _tiny_cfg()
    params = _tiny_params()
    # A large delta on the output head changes greedy argmax.
    d = np.zeros((cfg.d_model, cfg.vocab_size), np.float32)
    rng = np.random.RandomState(5)
    d[:, :] = rng.randn(cfg.d_model, cfg.vocab_size) * 0.5
    adapters = {"m1": {"delta": {"tok_embed": np.zeros(
        (cfg.vocab_size, cfg.d_model), np.float32)}},
        "m2": {"delta": {"tok_embed": rng.randn(
            cfg.vocab_size, cfg.d_model).astype(np.float32) * 0.5}}}
    bat = _paged(params, cfg, adapters=adapters)
    try:
        prompt = [7, 8, 9, 10, 11]
        base = bat.generate(prompt, max_new=6, timeout=120)
        m1 = bat.generate(prompt, max_new=6, timeout=120,
                          model_id="m1")
        m2 = bat.generate(prompt, max_new=6, timeout=120,
                          model_id="m2")
        # m1's adapter is a zero delta == base numerics; m2 differs.
        assert m1["tokens"] == base["tokens"]
        assert m2["tokens"] != base["tokens"]
        # First use per model never cache-hits across models even
        # though the BASE model already cached this exact prompt.
        assert base["cache_hit"] is False
        assert m1["cache_hit"] is False and m2["cache_hit"] is False
        # Second pass per model: each hits ITS OWN tree, tokens stable.
        m2b = bat.generate(prompt, max_new=6, timeout=120,
                           model_id="m2")
        assert m2b["cache_hit"] and m2b["tokens"] == m2["tokens"]
        baseb = bat.generate(prompt, max_new=6, timeout=120)
        assert baseb["cache_hit"] and baseb["tokens"] == base["tokens"]
        assert set(bat.resident_models()) == {"m1", "m2"}
        st = bat.kv_stats()
        assert st["model_id"] == ""            # base was last active
    finally:
        bat.stop()


def test_multiplex_unknown_model_fails_request_not_engine():
    cfg = _tiny_cfg()
    params = _tiny_params()
    bat = _paged(params, cfg, adapters={})
    try:
        with pytest.raises(KeyError):
            bat.generate([1, 2, 3], max_new=4, timeout=120,
                         model_id="nope")
        out = bat.generate([1, 2, 3], max_new=4, timeout=120)
        assert out["finish_reason"] == "length"
    finally:
        bat.stop()


def test_kv_metrics_recorded(monkeypatch):
    """Engine activity lands in the registered metric cells: the
    block-state gauges (the series state.memory_summary() folds into
    kv_blocks) sum to the pool size and the query/hit counters move.
    Cells are read directly, and only what THIS engine wrote: its own
    gauge series (by its tag), and counter cells that nothing drains
    meanwhile — a runtime client that an earlier test of this process left
    connected would have the flusher thread push and zero them between
    the two readings (the suite's one failure, PR 33's run), so for this
    test there is none."""
    from ray_tpu.serve.llm import _get_kv_metrics
    from ray_tpu.util import metrics as _metrics
    monkeypatch.setattr(_metrics, "get_global_client", lambda: None)
    cfg = _tiny_cfg()
    params = _tiny_params()
    km = _get_kv_metrics()
    assert km is not None
    before_q = sum(c["delta"] for c in km["queries"]._cells.values())
    before_h = sum(c["delta"] for c in km["hits"]._cells.values())
    bat = _paged(params, cfg, kv_num_blocks=16)
    try:
        bat.generate([1, 2, 3, 4, 5], max_new=4, timeout=120)
        hit = bat.generate([1, 2, 3, 4, 5], max_new=4, timeout=120)
        assert hit["cache_hit"]
        # Series are tagged per engine (so co-located engines don't
        # clobber each other); THIS engine's states sum to its pool.
        gauges = {dict(ts)["state"]: cell["value"]
                  for ts, cell in km["blocks"]._cells.items()
                  if dict(ts).get("engine") == bat._engine_tag}
    finally:
        bat.stop()
    assert set(gauges) >= {"used", "cached", "free"}
    assert gauges["used"] + gauges["cached"] + gauges["free"] == 16
    # A cleanly-stopped engine REMOVES its per-engine series (no dead
    # cells accumulating across construct/stop cycles), queueing one
    # final zero sample per state for the node-side aggregate.
    stopped = {dict(ts)["state"]: cell["value"]
               for ts, cell in km["blocks"]._cells.items()
               if dict(ts).get("engine") == bat._engine_tag}
    assert stopped == {}
    zeros = [s for s in _metrics._pending
             if s["name"] == _metrics.KV_BLOCKS_METRIC
             and s["tags"].get("engine") == bat._engine_tag]
    assert len(zeros) == 3 and all(s["value"] == 0.0 for s in zeros)
    d_q = sum(c["delta"] for c in km["queries"]._cells.values()) \
        - before_q
    d_h = sum(c["delta"] for c in km["hits"]._cells.values()) \
        - before_h
    assert d_h >= 1
    assert d_q >= d_h
    own = bat.kv_stats()["prefix_cache"]
    assert (own["queries"], own["hits"]) == (2, 1)


def test_engine_failure_flushes_prefix_cache():
    """An engine failure drops the whole prefix cache (regression:
    _post_admit inserts blocks at launch, so a dispatch that fails
    device-side left cached blocks holding never-written KV — a later
    prefix hit decoded garbage).  After the flush the same prompt must
    MISS, re-prefill, and still produce the exact pre-failure tokens;
    the pool must conserve."""
    cfg = _tiny_cfg()
    params = _tiny_params()
    bat = _paged(params, cfg, kv_num_blocks=16)
    try:
        prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9]
        before = bat.generate(prompt, max_new=6, timeout=120)
        hit = bat.generate(prompt, max_new=6, timeout=120)
        assert hit["cache_hit"] is True
        # Processor-thread-style engine failure.
        bat._fail_all(RuntimeError("injected device failure"))
        time.sleep(0.3)            # dispatcher consumes parked error
        assert bat.kv_stats()["blocks"]["cached"] == 0
        after = bat.generate(prompt, max_new=6, timeout=120)
        assert after["cache_hit"] is False       # cache was flushed
        assert after["tokens"] == before["tokens"]
        c = bat.kv_stats()["blocks"]
        assert c["used"] + c["cached"] + c["free"] == bat.num_blocks
    finally:
        bat.stop()


def test_multiplex_single_resident_model_swaps():
    """max_resident_models=1: the eviction sweep must never evict the
    adapter being swapped IN (regression: it deleted the just-loaded
    entry and the activation KeyError'd, permanently failing every
    multiplexed request)."""
    cfg = _tiny_cfg()
    params = _tiny_params()
    rng = np.random.RandomState(5)
    adapters = {"m1": {"delta": {"tok_embed": np.zeros(
        (cfg.vocab_size, cfg.d_model), np.float32)}},
        "m2": {"delta": {"tok_embed": rng.randn(
            cfg.vocab_size, cfg.d_model).astype(np.float32) * 0.5}}}
    bat = _paged(params, cfg, adapters=adapters, max_resident_models=1)
    try:
        prompt = [7, 8, 9, 10, 11]
        base = bat.generate(prompt, max_new=6, timeout=120)
        m1 = bat.generate(prompt, max_new=6, timeout=120,
                          model_id="m1")
        m2 = bat.generate(prompt, max_new=6, timeout=120,
                          model_id="m2")
        assert m1["tokens"] == base["tokens"]   # zero delta == base
        assert m2["tokens"] != base["tokens"]
        # Cap of 1 holds: base is pinned, only the active adapter stays.
        assert set(bat.resident_models()) == {"m2"}
        # Swap back: m1 reloads from its spec and still decodes right.
        m1b = bat.generate(prompt, max_new=6, timeout=120,
                           model_id="m1")
        assert m1b["tokens"] == m1["tokens"]
    finally:
        bat.stop()


def test_try_admit_undoes_prefix_holds_on_exception():
    """A raising eviction sweep between the prefix incref and the
    block handoff must undo the holds — they are not yet in
    req._blocks, so _retire could never free them (RT013
    self-finding; regression for the exception-edge leak)."""
    import pytest as _pytest
    from ray_tpu.serve.llm import _Request

    cfg = _tiny_cfg()
    params = _tiny_params()
    bat = _paged(params, cfg, num_slots=2, max_len=32,
                 kv_block_size=4, kv_num_blocks=8)
    try:
        # Populate the radix: one full shared block for this prompt.
        prompt = [5, 6, 7, 8, 9, 10, 11, 12]
        bat.generate(prompt, max_new=2, timeout=120)
        with bat._kv_lock:
            cached_before = bat._alloc.counts()["cached"]
        assert cached_before >= 1
        # Drain the free list so admission needs the eviction sweep,
        # then make the sweep raise.
        with bat._kv_lock:
            hold = bat._alloc.alloc(bat._alloc.available())
        orig = bat._evict_locked
        bat._evict_locked = lambda n: (_ for _ in ()).throw(
            RuntimeError("sweep boom"))
        req = _Request(prompt=list(prompt), max_new=4)
        with _pytest.raises(RuntimeError, match="sweep boom"):
            bat._try_admit(req)
        bat._evict_locked = orig
        # The prefix holds were undone: cached blocks are back to
        # refcount 0 (evictable), nothing leaked into "used".
        with bat._kv_lock:
            counts = bat._alloc.counts()
            assert counts["cached"] == cached_before
            assert counts["used"] == len(hold)
            for b in hold:
                bat._alloc.decref(b)
    finally:
        bat.stop()


def test_engine_reads_a_shared_prompt_once_and_says_the_same(monkeypatch):
    """Conversations on two shared system prompts through the engine with the
    Pallas kernels (interpreter; heads of 128, the whole-page path): token
    for token what the same engine gives with the sets emptied, in the decode
    steps and in a pass's carried step; a slot admitted in a dispatch is in
    its set in that dispatch; and the counters
    say what was read (`decode.streamed_positions` under
    `decode.context_positions` with sets, equal to it without)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import transformer
    from ray_tpu.models.transformer import TransformerConfig
    from ray_tpu.serve import llm
    cfg = TransformerConfig(vocab_size=97, d_model=256, n_heads=2,
                            n_kv_heads=1, n_layers=2, d_ff=64, max_seq=256,
                            dtype=jnp.float32, remat=False)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    systems = [rng.randint(1, 97, size=n).tolist() for n in (144, 160)]
    turns = [system + rng.randint(1, 97, size=5 + 3 * i).tolist()
             for i in range(3) for system in systems]
    found = llm.find_shared_prefixes

    def serve(find):
        calls = []

        def spy(tables, *a):
            calls.append((set(tables), find(tables, *a)))
            return calls[-1][1]

        monkeypatch.setattr(llm, "find_shared_prefixes", spy)
        bat = PagedBatcher(params, cfg, num_slots=6, max_len=208,
                           prompt_pad=192, decode_chunk=4, pipeline_depth=2,
                           kv_block_size=16, attn_impl="kernel")
        try:
            for system in systems:          # the prompts reach the cache
                bat.generate(system + [1, 2, 3], max_new=2, timeout=240)
            admitted = len(calls)
            # four turns, and two more once those decode: the dispatch that
            # admits the two carries the four's next step in its pass
            reqs = [bat.submit(p, max_new=24) for p in turns[:4]]
            while not all(r.tokens or r.done.is_set() for r in reqs):
                time.sleep(0.01)
            reqs += [bat.submit(p, max_new=10) for p in turns[4:]]
            for r in reqs:
                assert r.done.wait(240) and r.error is None
            return ([r.tokens for r in reqs], bat.kv_stats()["decode"],
                    calls[admitted:], [r.slot for r in reqs])
        finally:
            bat.stop()

    got, reads, calls, slots = serve(found)
    assert 0 < reads["streamed_positions"] < reads["context_positions"]
    # the dispatch that admitted the last turns found all six as two sets
    # of three over their system prompts, whole blocks of it
    sets = [{(frozenset(row[row >= 0].tolist()), int(n))
             for row, n in zip(members, lens) if n}
            for _, (members, _, lens) in calls]
    want = {(frozenset(slots[i::2]), len(systems[i]) // 16 * 16)
            for i in range(2)}
    assert want in sets
    first = sets.index(want)
    before = calls[first - 1][0] if first else set()
    assert set(slots) <= calls[first][0] and set(slots) - before

    def none(tables, block_size, num_slots):
        return found({}, block_size, num_slots)

    alone, reads, _, _ = serve(none)
    assert reads["streamed_positions"] == reads["context_positions"] > 0
    assert got == alone
    assert [len(t) for t in got] == [24] * 4 + [10] * 2
