"""A fused dispatch's prefill pass is its first decode step
(models/decoding.py paged_prefill_decode_packed, serve/llm.py _dispatch /
_hand_out): the live slots' next position rides beside the prompt rows, the
scan that follows is one step shorter, and every slot gets `decode_chunk`
tokens a dispatch.  Toy twins of the four architectures (arch "llama",
"afmoe", "lfm2", "axk1") in float32 on the reference attention path: (a) the
program against the prefill core, one decode step and the decode scan run
one after the other, (b) the engine's bookkeeping token for token against
the plain forward pass, (c) the program's shape read off its jaxpr."""

import contextlib
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import decoding
from ray_tpu.models import transformer as tfm
from ray_tpu.serve import llm

BLOCK, SLOTS, MAX_LEN, P = 4, 6, 64, 8      # P: a row, two blocks
W = MAX_LEN // BLOCK
CHUNK = 4                                   # the engine's decode_chunk

MOE = dict(moe_experts=8, moe_top_k=2, moe_d_ff=16, remat=False,
           dtype=jnp.float32, param_dtype=jnp.float32, max_seq=128,
           vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2, d_head=16)
CONFIGS = {
    "llama": dict(vocab_size=97, d_model=32, n_heads=4, n_kv_heads=2,
                  n_layers=2, d_ff=64, max_seq=128, dtype=jnp.float32,
                  remat=False),
    "afmoe": dict(MOE, n_layers=3, d_ff=32, arch="afmoe", rope_theta=1e4,
                  sliding_window=8, moe_shared_experts=1,
                  moe_route_scale=2.826,
                  layer_kinds=(("sliding", "dense"), ("sliding", "experts"),
                               ("full", "experts"))),
    "lfm2": dict(MOE, n_layers=4, d_ff=48, arch="lfm2", rope_theta=1e6,
                 tie_embeddings=True, conv_kernel=3, moe_route_scale=1.0,
                 moe_route_eps=1e-6,
                 layer_kinds=(("conv", "dense"), ("full", "experts"),
                              ("conv", "experts"), ("conv", "experts"))),
    # latent rows in one pool a layer; 4 of the router's 8 experts held
    "axk1": dict(MOE, n_layers=3, d_ff=32, arch="axk1", rope_theta=1e4,
                 q_lora_rank=24, kv_lora_rank=20, qk_nope_dim=8,
                 qk_rope_dim=4, v_head_dim=8, rope_factor=32.0,
                 rope_mscale_all_dim=1.0, moe_shared_experts=1,
                 moe_route_scale=2.5, moe_experts=4, moe_router_width=8,
                 moe_experts_first=2,
                 layer_kinds=(("latent", "dense"), ("latent", "experts"),
                              ("latent", "experts"))),
}


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def model(request):
    cfg = tfm.TransformerConfig(**CONFIGS[request.param])
    return cfg, tfm.init_params(cfg, jax.random.PRNGKey(0))


def prompt(n, seed):
    return np.random.RandomState(seed).randint(1, 97, n).tolist()


# -- (a) the device function --------------------------------------------------
UP = decoding.FusedUpload(P, W, SLOTS, sets=False)


def _pack(rows, active, N):
    """rows [(tokens, start, slot, flag, table)] -> the program's upload."""
    packed = UP.empty(N)
    for r, (toks, start, slot, flag, table) in enumerate(rows):
        packed[r, :len(toks)] = toks
        packed[r, UP.scalars] = (len(toks), start, slot, flag)
        packed[r, UP.table] = table
    packed[N, UP.active] = active
    return jnp.asarray(packed)


def _table(i):
    return np.arange(1 + i * W, 1 + (i + 1) * W)


def _scene(cfg, params):
    """Caches in which slots 0, 1 and 5 are decoding (slot 1's next position
    completes a block), and the call under test: slot 2 is admitted (two
    rows, the second closes), slot 3 gets the first row of a longer prompt
    (flag 2), slot 4 stays empty, and slot 5, which the host still marks
    active for the request before, is closed again by a new request with a
    table of its own.  -> (caches, rows, the host's active mask)."""
    caches = decoding.init_paged_caches(cfg, SLOTS, 8 * W, BLOCK, MAX_LEN)
    before = [(prompt(5, 1), 0, 0, UP.CLOSES, _table(0)),
              (prompt(6, 2), 0, 1, UP.CLOSES, _table(1)),
              (prompt(7, 3), 0, 5, UP.CLOSES, _table(5))]
    # two tokens each: lengths 6, 7 (offset 3 of a block of 4) and 8
    caches = decoding.paged_prefill_decode_packed(
        params, caches, _pack(before, np.zeros(SLOTS), 4), cfg, 2, P,
        attn_impl="reference")[0]
    assert np.asarray(caches.lengths).tolist() == [6, 7, 0, 0, 0, 8]
    new, long, again = prompt(11, 4), prompt(20, 5), prompt(3, 6)
    rows = [(new[:8], 0, 2, UP.MORE, _table(2)),
            (new[8:], 8, 2, UP.CLOSES, _table(2)),
            (long[:8], 0, 3, UP.MORE, _table(3)),
            (again, 0, 5, UP.CLOSES, _table(6))]
    return caches, rows, np.array([1, 1, 0, 0, 0, 1])


def _one_after_the_other(cfg, params, caches, rows, active, N, steps):
    """The same call as the prefill core alone, then ONE decode step of
    the slots that were active and are not closed, then `steps - 1` of
    every active slot: what the pass and its scan must equal."""
    packed = _pack(rows, active, N)
    flag, slots = packed[:-1, UP.flag], packed[:-1, UP.slot]
    closes = flag == UP.CLOSES
    caches, first, *_ = decoding._paged_prefill_core(
        params, caches, packed[:-1, UP.tokens], packed[:-1, UP.suffix_len],
        packed[:-1, UP.prefix_len], slots, flag > UP.NO_ROW, closes,
        packed[:-1, UP.table], cfg, "reference")
    closed = np.zeros(SLOTS, bool)
    closed[np.asarray(slots)[np.asarray(closes)]] = True
    was = np.asarray(active) > 0
    caches, tok, _ = decoding.paged_decode_steps(
        params, caches, jnp.asarray(was & ~closed), cfg, 1, "reference")
    tok = np.asarray(tok).copy()
    for r in np.flatnonzero(np.asarray(closes)):
        tok[0, int(slots[r])] = int(first[r])
    caches, toks, _ = decoding.paged_decode_steps(
        params, caches, jnp.asarray(was | closed), cfg, steps - 1,
        "reference")
    return caches, np.concatenate([tok, np.asarray(toks)])


def _same_state(a, b):
    """Every block but the scratch block, and every slot's state."""
    for name in ("kp", "vp", "tail_pool"):
        for x, y in zip(jax.tree.leaves(getattr(a, name)),
                        jax.tree.leaves(getattr(b, name))):
            blocks = 1 if x.ndim == 5 else 0        # the stacked pool
            np.testing.assert_allclose(
                np.delete(np.asarray(x), 0, axis=blocks),
                np.delete(np.asarray(y), 0, axis=blocks),
                rtol=1e-4, atol=1e-5, err_msg=name)
    for x, y in zip(jax.tree.leaves(a.slot_tail),
                    jax.tree.leaves(b.slot_tail)):
        np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-5)
    for name in ("block_tables", "lengths", "last_token"):
        np.testing.assert_array_equal(getattr(a, name), getattr(b, name))


def test_the_pass_is_the_first_decode_step(model):
    """Live, closing, flag-2, empty and drained-and-re-admitted slots in one
    call: the pool, the tails, the tables, lengths, last tokens and every
    active slot's tokens are those of prefill, step and scan run one after
    the other."""
    cfg, params = model
    steps, N = 3, 7
    caches, rows, active = _scene(cfg, params)
    want_c, want = _one_after_the_other(cfg, params, caches, rows, active,
                                        N, steps)
    caches, rows, active = _scene(cfg, params)      # the first were donated
    got_c, got, counts = decoding.paged_prefill_decode_packed(
        params, caches, _pack(rows, active, N), cfg, steps, P,
        attn_impl="reference")
    assert got.shape == (steps, SLOTS)
    live = [0, 1, 2, 5]
    np.testing.assert_array_equal(np.asarray(got)[:, live], want[:, live])
    _same_state(got_c, want_c)
    # 0 and 1 rode in the pass (+ 3); 2 and 5 closed (prompt + 2); 3 waits
    assert np.asarray(got_c.lengths).tolist() == [9, 10, 13, 0, 0, 5]
    np.testing.assert_array_equal(got_c.block_tables[5], _table(6))
    if counts is not None:  # expert layers: the pass is ONE call a layer
        n_moe = sum(f == "experts" for _, f in cfg.layer_kinds)
        assert int(counts[0]) == n_moe * steps


def test_both_programs_return_caches_tokens_and_counts(model):
    """One result shape: (caches', tokens [num_steps, B], counts), the
    counts an expert model's (afmoe.MOE_COUNTS), None where the layers are
    stacked: the host reads either program's result alike."""
    from ray_tpu.models import afmoe

    cfg, params = model
    caches, rows, active = _scene(cfg, params)
    fused = decoding.paged_prefill_decode_packed(
        params, caches, _pack(rows, active, 7), cfg, 3, P,
        attn_impl="reference")
    steps = decoding.paged_decode_steps(
        params, fused[0], jnp.asarray(active > 0), cfg, 2, "reference")
    for (caches, toks, counts), n in ((fused, 3), (steps, 2)):
        assert isinstance(caches, decoding.PagedDecodeCaches)
        assert toks.shape == (n, SLOTS) and toks.dtype == jnp.int32
        if cfg.layer_kinds is None:
            assert counts is None
        else:
            assert counts.shape == (len(afmoe.MOE_COUNTS),)


# What a [2 + 1, width] upload of P 2, W 3, B 4 holds once the rows below
# are written through the layout, byte for byte: the columns as every engine
# and program since PR 49 has had them.
_ROWS = [[11, 12, 2, 8, 3, 2, 5, 6, 7], [13, 0, 1, 10, 3, 1, 5, 6, 7]]
_STATES = [[9, 0, 0], [-1, 4, 2]]
_LAST = [1, 0, 1, 1] + [1, 3] + 14 * [0] + [0, 0] + [64, 0]
GOLDEN = {
    "plain": [r + 15 * [0] for r in _ROWS] + [_LAST],
    "states": [r + s + 12 * [0] for r, s in zip(_ROWS, _STATES)] + [_LAST],
    "no-sets": _ROWS + [_LAST[:4] + 5 * [0]],
}


@pytest.mark.parametrize("case", sorted(GOLDEN))
def test_the_upload_is_read_as_it_was_written(case):
    """decoding.FusedUpload is the one description of the fused dispatch's
    upload: what the host writes through it in numpy, the program's side
    reads back through it in jnp, field for field, with and without the
    three state columns; an upload too narrow for the last row's sets
    carries none."""
    from ray_tpu.ops import paged_attention as pa

    B, width, tile = 4, 3, 2
    zeros = jnp.zeros((B,), jnp.int32)
    caches = decoding.PagedDecodeCaches(
        None, None, jnp.zeros((B, width), jnp.int32), zeros, zeros,
        state_pool=(zeros,) if case == "states" else ())
    up = decoding.FusedUpload.of(tile, caches)
    assert up.sets and up.states == (case == "states")
    if case == "no-sets":
        up = up._replace(sets=False)
    fields = dict(tokens=[[11, 12], [13, 0]], suffix_len=[2, 1],
                  prefix_len=[8, 10], slot=[3, 3],
                  flag=[up.MORE, up.CLOSES], table=[[5, 6, 7]] * 2)
    if up.states:
        fields.update(state_from=[9, -1], state_to=[[0, 0], [4, 2]])
    members = np.full((B // 2, pa.SHARED_MEMBERS), -1, np.int32)
    members[0, :2] = (0, 2)
    sets = (members, np.array([0, 0], np.int32), np.array([64, 0], np.int32))
    packed = up.empty(2)
    for name, value in fields.items():
        packed[:-1, getattr(up, name)] = value
    packed[-1, up.active] = (1, 0, 1, 1)
    if up.sets:
        up.put_sets(packed, *sets)
    assert packed.dtype == np.int32 and packed.tolist() == GOLDEN[case]
    np.testing.assert_array_equal(packed[:-1, up.scalars],
                                  [[2, 8, 3, 2], [1, 10, 3, 1]])

    dev = jnp.asarray(packed)       # as the program finds its layout
    read = decoding.FusedUpload.of(tile, caches, dev.shape[1])
    assert read == up
    for name, value in fields.items():
        np.testing.assert_array_equal(dev[:-1, getattr(read, name)], value)
    np.testing.assert_array_equal(dev[-1, read.active], (1, 0, 1, 1))
    got = read.shared_sets(dev)
    for a, b in zip(got, sets if up.sets else pa.no_shared_prefixes(B)):
        np.testing.assert_array_equal(a, b)


# -- (b) the engine -----------------------------------------------------------
def _engine(model):
    cfg, params = model
    return llm.PagedBatcher(params, cfg, num_slots=4, max_len=MAX_LEN,
                            prompt_pad=32, decode_chunk=CHUNK,
                            kv_block_size=BLOCK, kv_num_blocks=96,
                            attn_impl="reference")


@pytest.fixture(scope="module")
def engine(model):
    eng = _engine(model)
    yield eng
    eng.stop()


@contextlib.contextmanager
def held(eng):
    """No dispatch while the body runs: what it submits is admitted
    together, beside what is live."""
    for _ in range(eng.pipeline_depth):
        assert eng._slots_sem.acquire(timeout=120)
    try:
        yield
    finally:
        for _ in range(eng.pipeline_depth):
            eng._slots_sem.release()


def _greedy_all_the_way(cfg, params, req):
    """One forward pass over prompt + reply: every token of the reply is
    the argmax at the position before it.  (Padded to MAX_LEN, which a
    causal model does not see: one compiled shape a model, not one a
    length.)"""
    n = len(req.prompt) + len(req.tokens)
    seq = jnp.asarray(req.prompt + req.tokens + [0] * (MAX_LEN - n))
    logits = tfm.forward(params, seq[None], cfg)[0]
    want = np.asarray(jnp.argmax(logits, axis=-1))[len(req.prompt) - 1:n - 1]
    assert req.tokens == want.tolist(), (len(req.prompt), req.max_new)


@pytest.mark.parametrize("max_new", [1, CHUNK - 1, CHUNK, CHUNK + 1, "cap"])
def test_admitted_beside_live_slots_token_for_token(model, engine, max_new):
    """Requests admitted while another is mid-reply (more of them than free
    slots, so some take a drained slot in the tick that frees it): each
    reply is its own greedy continuation, no token lost or doubled at the
    admitting dispatch, whatever `max_new` is against the chunk; "cap": a
    reply cut by its allocation at max_len."""
    cfg, params = model
    seed = 100 * (max_new if max_new != "cap" else 9)
    carried = engine.kv_stats()["prefill"]["carried_rows"]
    long = engine.submit(prompt(9, seed), max_new=MAX_LEN - 10)
    t0 = time.time()
    while not long.tokens and time.time() - t0 < 200:
        time.sleep(0.002)
    with held(engine):          # no dispatch while the others are submitted
        with engine._state_lock:
            assert engine._owner[long.slot] is long \
                and not engine._drained(long.slot, long)
        new = 200 if max_new == "cap" else max_new
        reqs = [engine.submit(prompt(n, seed + n), max_new=new)
                for n in (5, 12, 3, 17, 8)]
    for r in reqs + [long]:
        assert r.done.wait(300) and r.error is None
    for r in reqs:
        if max_new == "cap":
            assert r.finish_reason == "cache"
            assert len(r.prompt) + len(r.tokens) == MAX_LEN
        else:
            assert r.finish_reason == "length" and len(r.tokens) == max_new
        _greedy_all_the_way(cfg, params, r)
    assert len(long.tokens) == long.max_new
    _greedy_all_the_way(cfg, params, long)
    # `long` was live when the others were admitted: its step rode along
    assert engine.kv_stats()["prefill"]["carried_rows"] > carried


def test_a_clamped_request_rides_whole_chunks_past_its_cap(model, engine):
    """No single step for the tail of an allocation that max_len clamped:
    the request takes ordinary chunks, the last of which runs 2 steps past
    its cap (through a conv layer's tails and a latent pool like through
    K/V: into blocks of its own, or the scratch block), and its reply is
    cut at the cap.  Alone in an engine of its own (the module's shapes):
    34 tokens at 4 a dispatch are 9 dispatches, where the one-token tail
    made them 8 chunks and 3 steps.  Then in the module's engine beside a
    slot that lives on: both replies are their own greedy continuations."""
    cfg, params = model
    alone = _engine(model)
    try:
        req = alone.submit(prompt(30, 7), max_new=100)
        assert req.done.wait(300) and req.error is None
        time.sleep(0.3)         # nothing is live: nothing more is launched
        assert alone.host_stats()["dispatches"] == 9
    finally:
        alone.stop()
    assert req.finish_reason == "cache" and len(req.tokens) == MAX_LEN - 30
    _greedy_all_the_way(cfg, params, req)

    with held(engine):
        other = engine.submit(prompt(9, 8), max_new=MAX_LEN - 10)
        beside = engine.submit(prompt(30, 7), max_new=100)
    for r in (other, beside):
        assert r.done.wait(300) and r.error is None
    assert beside.finish_reason == "cache" and beside.tokens == req.tokens
    assert other.finish_reason == "length"
    _greedy_all_the_way(cfg, params, other)


# -- (c) the program's shape --------------------------------------------------
def _subjaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(j, "jaxpr"):
                j = j.jaxpr
            if hasattr(j, "eqns"):
                yield j


def _walk(jaxpr, steps_scan, inside, scans, gates, gate_shape):
    for eqn in jaxpr.eqns:
        here = inside
        if eqn.primitive.name == "scan":
            scans.append(eqn.params["length"])
            here = inside or eqn.params["length"] == steps_scan
        if eqn.primitive.name == "dot_general" \
                and eqn.invars[1].aval.shape == gate_shape:
            gates.append((here, eqn.invars[0].aval.shape))
        for sub in _subjaxprs(eqn):
            _walk(sub, steps_scan, here, scans, gates, gate_shape)


def test_a_fused_dispatch_walks_the_weights_num_steps_times(model):
    """Read off the jaxpr: the scan after the pass has num_steps - 1 trips
    (none has num_steps), and the dense gate product of the pass sees
    N * P + B rows, a decode step's B."""
    cfg, params = model
    steps, N = 7, 4                 # no layer count or width is 6 or 7
    caches = decoding.init_paged_caches(cfg, SLOTS, 8 * W, BLOCK, MAX_LEN)
    jaxpr = jax.make_jaxpr(
        lambda p, c, u: decoding.paged_prefill_decode_packed(
            p, c, u, cfg, steps, P, attn_impl="reference"))(
        params, caches, _pack([], np.zeros(SLOTS), N))
    scans, gates = [], []
    _walk(jaxpr.jaxpr, steps - 1, False, scans, gates,
          (cfg.d_model, cfg.d_ff))
    assert scans.count(steps - 1) == 1 and steps not in scans
    in_pass = {shape for inside, shape in gates if not inside}
    in_steps = {shape for inside, shape in gates if inside}
    assert in_pass == {(1, N * P + SLOTS, cfg.d_model)}
    assert in_steps == {(SLOTS, 1, cfg.d_model)}
