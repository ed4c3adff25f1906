"""arch "olmo_hybrid" through PagedBatcher, on the toy twin of
tests/olmo_hybrid_twin.py: the engine end to end with the checkpoints of the
recurrent state that its radix cache owns (tests/test_olmo_hybrid.py has the
rule, the kernels, the paged layers and the allocator alone).  Tokens are
compared with the reference's greedy continuation; a small model on the
CPU."""

import threading

import numpy as np
import pytest

import jax.numpy as jnp

from olmo_hybrid_twin import BS, KIND, T, model, tokens  # noqa: F401
from ray_tpu.models import decoding
from ray_tpu.serve import llm


def _is_greedy(cfg, params, prompt, got):
    n = len(prompt) + len(got)     # padded: one compiled shape for many
    seq = jnp.asarray(list(prompt) + list(got) + [0] * (-n % 64))
    lg = KIND.reference_logits(KIND.hyper(cfg), params, seq)[
        len(prompt) - 1:n - 1]
    top2 = jnp.sort(lg, axis=-1)[:, -2:]
    assert float(jnp.min(top2[:, 1] - top2[:, 0])) > 1e-4, "a tie"
    return jnp.argmax(lg, axis=-1).tolist() == list(got)


def _engine(model, **kw):
    cfg, params = model
    kw = {"num_slots": 2, "max_len": 160, "prompt_pad": 128,
          "decode_chunk": 4, "kv_block_size": BS, "kv_num_blocks": 80,
          "attn_impl": "reference", "num_states": 10, **kw}
    return llm.PagedBatcher(params, cfg, **kw)


def _run(eng, prompt, max_new=6):
    req = eng.submit(prompt, max_new=max_new)
    assert req.done.wait(300) and req.error is None, req.error
    return req


def test_engine_hits_are_cut_back_and_leave_a_checkpoint(model):
    """PagedBatcher end to end: a 5-block prompt cold leaves ONE checkpoint
    (its last whole block).  A request that shares n < 5 blocks matches n
    and can use none or the branch point an earlier one left: it is cut back
    to the deepest checkpoint, yields the reference's greedy tokens, and
    leaves a checkpoint at its branch point, which the NEXT request to
    branch there uses in full."""
    cfg, params = model
    eng = _engine(model)
    try:
        assert sum(p is not None for p in eng.caches.state_pool) == 6
        base = tokens(5 * BS + 3, seed=11)
        cold = _run(eng, base, max_new=20)
        assert not cold.cache_hit and _is_greedy(cfg, params, base,
                                                 cold.tokens)
        st = eng.kv_stats()["state"]
        assert st["snapshots"] == 1 and st["ids_used"] == 1 == \
            st["checkpoints"]
        used = []
        for n in (3, 3, 5, 2):
            prompt = base[:n * BS] + tokens(9, seed=20 + len(used))
            hit = _run(eng, prompt)
            used.append(hit.cached_tokens // BS)
            assert _is_greedy(cfg, params, prompt, hit.tokens)
        # 3 blocks matched, no checkpoint there: cold, a checkpoint left at
        # 3; the next to branch at 3 uses it; 5 was the cold prompt's own;
        # 2 has none and is cut back to nothing
        assert used == [0, 3, 5, 0]
        st = eng.kv_stats()
        assert st["prefix_cache"]["hit_tokens"] == 8 * BS
        assert st["state"]["matched_tokens"] == 13 * BS
        assert st["state"]["unbacked_tokens"] == 5 * BS
        assert st["state"]["restores"] == 2
        assert st["state"]["ids_used"] == st["state"]["checkpoints"]
        assert eng.host_stats()["state"] > 0
    finally:
        eng.stop()


def test_a_clamped_request_steps_its_own_state_past_its_cap(model):
    """A request that max_len clamps takes whole chunks (34 tokens at 4 a
    dispatch: 9 dispatches, the last 2 steps past the cap): those steps
    move the state id that is its own until it retires, and the reply, cut
    at the cap, is the reference's; the request that then takes the id and
    the slot is the reference's too, and so is one that hits the checkpoint
    the clamped prompt left."""
    cfg, params = model
    eng = _engine(model)
    try:
        long_ = tokens(126, seed=60)
        req = _run(eng, long_, max_new=100)
        assert req.finish_reason == "cache" and len(req.tokens) == 160 - 126
        assert eng.host_stats()["dispatches"] == 9
        assert _is_greedy(cfg, params, long_, req.tokens)
        fresh = tokens(20, seed=61)
        assert _is_greedy(cfg, params, fresh, _run(eng, fresh).tokens)
        hit = long_[:7 * BS] + tokens(5, seed=62)
        again = _run(eng, hit)
        assert again.cached_tokens == 7 * BS
        assert _is_greedy(cfg, params, hit, again.tokens)
    finally:
        eng.stop()


def test_engine_cuts_a_long_prompt_by_the_token_budget(model, monkeypatch):
    """Prompts longer than one dispatch's budget (cut to 32 here) carry
    their state in their slot's id between dispatches, beside a short
    request that decodes on meanwhile: both the reference's; the long
    one's checkpoint is taken in the dispatch that reaches it."""
    monkeypatch.setattr(llm, "PREFILL_CHUNK", 32)
    cfg, params = model
    eng = _engine(model, decode_chunk=2, kv_num_blocks=40)
    try:
        short, long_ = tokens(9, seed=8), tokens(100, seed=9)
        a = eng.submit(short, max_new=20)
        b = eng.submit(long_, max_new=6)
        assert a.done.wait(300) and b.done.wait(300)
        assert _is_greedy(cfg, params, short, a.tokens)
        assert _is_greedy(cfg, params, long_, b.tokens)
        st = eng.kv_stats()
        assert st["prefill"]["multi_chunk_requests"] == 1
        assert st["state"]["snapshots"] == 1        # at block 6 of `long_`
        again = _run(eng, long_[:96] + tokens(7, seed=10))
        assert again.cached_tokens == 96
        assert _is_greedy(cfg, params, again.prompt, again.tokens)
    finally:
        eng.stop()


def test_a_checkpoint_evicted_alone_leaves_its_block_cached(model):
    """Three state ids for two slots: the second request's checkpoint can
    only be had at the price of the first's, whose blocks stay cached (and
    matched, and unbacked)."""
    cfg, params = model
    eng = _engine(model, num_slots=1, num_states=2)
    try:
        p1, p2 = tokens(40, seed=50), tokens(40, seed=51)
        _run(eng, p1)
        assert eng.kv_stats()["state"]["checkpoints"] == 1
        _run(eng, p2)
        st = eng.kv_stats()
        assert st["state"]["snapshot_evictions"] == 1
        assert st["state"]["checkpoints"] == 1
        assert st["prefix_cache"]["cached_blocks"] == 4
        again = _run(eng, p1[:32] + tokens(5, seed=52))
        assert again.cached_tokens == 0
        assert _is_greedy(cfg, params, again.prompt, again.tokens)
        st = eng.kv_stats()["state"]
        assert st["unbacked_tokens"] == 32 and st["restores"] == 0
    finally:
        eng.stop()


def test_a_restore_source_survives_the_requests_own_id(model):
    """Two state ids, both taken (a live request's, a checkpoint): the
    request that matched the checkpoint holds it from the match on, so
    taking its OWN id cannot evict it (found on the chip, PR 47: the engine
    died of a KeyError and every cell after it thrashed); it waits for the
    live request's id and then restores."""
    cfg, params = model
    eng = _engine(model, num_slots=2, num_states=2)
    try:
        p1 = tokens(40, seed=55)
        _run(eng, p1)
        assert eng.kv_stats()["state"]["checkpoints"] == 1
        live = eng.submit(tokens(15, seed=56), max_new=40)  # no whole block
        hit = eng.submit(p1[:32] + tokens(5, seed=57), max_new=4)
        assert live.done.wait(300) and hit.done.wait(300)
        assert live.error is None and hit.error is None, hit.error
        assert hit.cached_tokens == 32
        assert _is_greedy(cfg, params, hit.prompt, hit.tokens)
        st = eng.kv_stats()["state"]
        assert st["restores"] == 1 and st["snapshot_evictions"] == 0
    finally:
        eng.stop()


def test_no_checkpoint_id_means_no_checkpoint_not_a_wait(model):
    """Every id a live request's: an admission proceeds without its
    checkpoint and counts it."""
    cfg, params = model
    eng = _engine(model, num_slots=2, num_states=2)
    try:
        a = eng.submit(tokens(40, seed=60), max_new=12)
        b = eng.submit(tokens(40, seed=61), max_new=12)
        assert a.done.wait(300) and b.done.wait(300)
        assert a.error is None and b.error is None
        st = eng.kv_stats()["state"]
        assert st["snapshots_skipped"] >= 1
        assert _is_greedy(cfg, params, b.prompt, b.tokens)
    finally:
        eng.stop()


def test_a_source_is_never_a_destination_of_its_own_dispatch(model,
                                                             monkeypatch):
    """Every fused dispatch's upload: the ids read as state_from (held
    until the launch) are no other row's state_to."""
    cfg, params = model
    seen = []
    real = decoding.paged_prefill_decode_packed

    def spy(params, caches, packed, *a, **kw):
        seen.append(np.asarray(packed))
        return real(params, caches, packed, *a, **kw)

    monkeypatch.setattr(decoding, "paged_prefill_decode_packed", spy)
    eng = _engine(model, num_states=4, kv_num_blocks=60)
    try:
        base = tokens(3 * BS + 2, seed=70)
        _run(eng, base)
        reqs = [eng.submit(base[:3 * BS] + tokens(4 + i, seed=71 + i),
                           max_new=4) for i in range(6)]
        assert all(r.done.wait(300) and r.error is None for r in reqs)
        assert eng.kv_stats()["state"]["restores"] >= 4
        up = decoding.FusedUpload.of(T, eng.caches)
        checked = 0
        for packed in seen:
            rows = packed[:-1][packed[:-1, up.flag] > up.NO_ROW]
            src = {int(s) for s in rows[:, up.state_from] if s > 0}
            own = {int(s) for s in rows[:, up.state_to][:, 0] if s > 0}
            ckpt = {int(s) for s in rows[:, up.state_to][:, 1] if s > 0}
            # a request that goes on from its own id is not a restore
            assert not (src - own) & (own | ckpt), (src, own, ckpt)
            assert not own & ckpt
            checked += bool(src)
        assert checked >= 2
    finally:
        eng.stop()


def test_no_state_id_leaks_over_two_hundred_admissions(model):
    """Conversations of three turns on two slots with six state ids:
    checkpoints come and go by LRU; when everything has retired the ids in
    use are the checkpoints held, and a stopped engine holds none."""
    cfg, params = model
    eng = _engine(model, num_states=6, kv_num_blocks=120, max_len=192,
                  prompt_pad=160)
    try:
        sent = 0
        system = tokens(2 * BS, seed=80)
        _run(eng, system + tokens(3, seed=81), max_new=2)
        for conv in range(50):
            history = system + tokens(5, seed=100 + conv)
            pending = []
            for turn in range(4):
                pending.append(eng.submit(history, max_new=3))
                sent += 1
                if turn % 2:
                    for r in pending:
                        assert r.done.wait(300) and r.error is None
                    pending = []
                history = history + tokens(6, seed=1000 + sent)
        st = eng.kv_stats()["state"]
        assert sent == 200
        assert st["ids_used"] == st["checkpoints"] <= 6
        assert st["restores"] >= 150 and st["snapshot_evictions"] > 20
        last = _run(eng, history)
        assert _is_greedy(cfg, params, last.prompt, last.tokens)
    finally:
        eng.stop()
    with eng._kv_lock:
        assert eng._states.used() == 0


def test_a_branch_point_keeps_its_checkpoint(model):
    """A checkpoint at block m where m > h is left only where the tree
    branches there.  B leaves A's path after 3 blocks: the node at 3 gets a
    second child and a checkpoint, and a prompt that goes on from A's 3
    blocks, one from B's 5 and one from A's own 5 all hit in full the second
    time.  C only lengthens a path whose checkpoint went: none at the old
    end, where C's own would supersede it at once."""
    cfg, params = model
    eng = _engine(model, max_len=192, prompt_pad=160)
    try:
        a = tokens(5 * BS + 3, seed=90)
        b = a[:3 * BS] + tokens(2 * BS + 3, seed=91)
        _run(eng, a)
        assert _run(eng, b).cached_tokens == 0
        st = eng.kv_stats()["state"]
        assert st["snapshots"] == 3 and st["unbacked_tokens"] == 3 * BS
        again = [a[:3 * BS] + tokens(7, seed=92), b[:5 * BS] + tokens(7, 93),
                 a[:5 * BS] + tokens(7, seed=94)]
        for prompt, blocks in zip(again, (3, 5, 5)):
            hit = _run(eng, prompt)
            assert hit.cached_tokens == blocks * BS
            assert _is_greedy(cfg, params, prompt, hit.tokens)
        st = eng.kv_stats()["state"]
        assert st["full_restores"] == 3 and st["unbacked_tokens"] == 3 * BS
        assert st["snapshots"] == 3         # each at a node that has one
        with eng._kv_lock:
            eng._states.drop_all()
        c = a[:5 * BS] + tokens(2 * BS + 3, seed=95)
        assert _run(eng, c).cached_tokens == 0
        st = eng.kv_stats()["state"]
        assert st["snapshots"] == 4 and st["checkpoints"] == 1
        assert st["full_restores"] == 3     # matched 5, used none: short
        assert _run(eng, c[:7 * BS] + tokens(5, seed=96)).cached_tokens \
            == 7 * BS
    finally:
        eng.stop()


@pytest.mark.parametrize("order", ["superseded-first", "plain-lru"])
def test_conversations_restore_in_full_from_their_newest_checkpoint(
        model, monkeypatch, order):
    """The cell's traffic scaled down: 8 slots, 16 callers, 4 tenants
    primed first (three of them dear), conversations of 5 / 4 / 4 / 3 turns,
    replies of 8-24 at 8 tokens a dispatch; turn k + 1's prompt is turn k's
    + its reply + a message.  A turn needs its conversation's newest
    checkpoint: with the superseded ones going first, 36 ids hold them all
    (16 + 4 tenants, the slots' 8, and as many again for the checkpoints a
    dispatch's admissions are about to leave and the ids of requests whose
    slot was admitted anew before they retired) and a hit is used whole
    (0-5 short ones of 128 as the threads fall); in plain LRU order (the
    parent's, PR 47) the turns' old checkpoints, and the branch checkpoints
    a short restore leaves, push live ones out: 40-51 of 128 are short.
    (At 32 ids, the cell's three checkpoints a slot, which 4 tenants and 8
    slots do not scale down to: 17-21 against 64; at 40: 0-1 against
    21-25.)"""
    monkeypatch.setattr(llm, "PREFILL_CHUNK", 256)  # one program; dear: 16
    if order == "plain-lru":
        monkeypatch.setattr(llm.StateAllocator, "superseded",
                            lambda self, sid: False)
        monkeypatch.setattr(llm.RadixCache, "branches_at",
                            lambda self, tokens, depth: True)
    slots = 8
    eng = _engine(model, num_slots=slots, num_states=36, max_len=704,
                  prompt_pad=640, decode_chunk=8, kv_num_blocks=1200)
    systems = [tokens(n * BS, seed=200 + n) for n in (4, 18, 20, 22)]
    turns = (5, 4, 4, 3)
    errors = []

    def caller(i):
        try:
            for conv in range(2):
                tenant = (i + conv) % 4
                history = list(systems[tenant])
                for turn in range(turns[tenant]):
                    seed = 10_000 + 100 * i + 10 * conv + turn
                    message, reply = np.random.default_rng(seed).integers(
                        (8, 8), (33, 25))
                    history += tokens(int(message), seed=seed)
                    history += _run(eng, history, max_new=int(reply)).tokens
        except Exception as e:      # shown by the test's own thread, below
            errors.append(e)

    try:
        for system in systems:
            _run(eng, system + tokens(8, seed=199), max_new=2)
        assert eng.kv_stats()["state"]["checkpoints"] == 4
        callers = [threading.Thread(target=caller, args=(i,))
                   for i in range(2 * slots)]
        for t in callers:
            t.start()
        for t in callers:
            t.join(600)
        assert not errors, errors
        st = eng.kv_stats()
        hits = st["prefix_cache"]["queries"] - 4
        assert hits == 2 * 4 * sum(turns)
        short = hits - st["state"]["full_restores"]
        if order == "plain-lru":
            assert short > hits // 5
            assert st["state"]["superseded_evictions"] == 0
        else:
            assert short <= hits // 8
            assert (st["state"]["unbacked_tokens"] > 0) == (short > 0)
            assert st["state"]["superseded_evictions"] > hits // 2
            assert st["state"]["snapshots_skipped"] == 0
        assert st["state"]["ids_used"] == st["state"]["checkpoints"] <= 36
    finally:
        eng.stop()
    with eng._kv_lock:
        assert eng._states.used() == 0
