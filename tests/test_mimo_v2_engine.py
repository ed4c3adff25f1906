"""arch "mimo_v2" through PagedBatcher, on the toy twin of
tests/mimo_v2_twin.py: the engine end to end, unedited for this architecture
but for the list of mixers that keep state by sequence: state ids and
checkpoints (StateAllocator, the radix cache's `match_with_state`) hold the
sliding layers' RINGS, the full layers share pages as ever, and the expert
counters tell the share's routed rows from the absent ones
(tests/test_mimo_v2.py has the model, the paged layers, the kernels and the
share alone).  Tokens are compared with the reference's greedy continuation;
a small model on the CPU."""

import dataclasses

import jax
import jax.numpy as jnp

from mimo_v2_twin import BS, KIND, WINDOW, model, tokens  # noqa: F401
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


def _held_bytes(caches):
    return sum(x.nbytes for x in jax.tree.leaves(
        (caches.ring_k, caches.ring_v)))


def test_engine_serves_hits_from_checkpoints_of_rings(model):
    """A 5-block prompt cold (past the point where a ring of 32 wraps), then
    requests that share 3, 3, 5 and 2 of its blocks: a hit is used only as
    far as a checkpoint of the rings reaches (cut back to nothing where none
    does), every reply the reference's greedy tokens; the expert counters
    tell routed rows from absent ones (2 of 32 experts held) and count the
    rows the grouped product padded its groups to."""
    cfg, params = model
    eng = _engine(model)
    try:
        assert sum(p is not None for p in eng.caches.ring_k) == 2
        assert sum(p is not None for p in eng.caches.kp) == 2
        base = tokens(5 * BS + 3, seed=11)
        cold = _run(eng, base, max_new=12)
        assert not cold.cache_hit and _is_greedy(cfg, params, base,
                                                 cold.tokens)
        used = []
        for n in (3, 3, 5, 2):
            prompt = base[:n * BS] + tokens(9, seed=20 + len(used))
            hit = _run(eng, prompt)
            used.append(hit.cached_tokens // BS)
            assert _is_greedy(cfg, params, prompt, hit.tokens)
        assert used == [0, 3, 5, 0]
        st = eng.kv_stats()
        assert st["state"]["restores"] == 2
        assert st["state"]["ids_used"] == st["state"]["checkpoints"]
        assert st["state"]["unbacked_tokens"] == (3 + 2) * BS
        moe = st["moe"]
        assert moe["layer_steps"] > 0 and moe["layer_steps"] % 3 == 0
        assert moe["picked_rows"] == moe["routed_rows"] + moe["absent_rows"]
        assert 0.01 < moe["routed_rows"] / moe["picked_rows"] < 0.2
        assert moe["padded_rows"] % 16 == 0
        assert moe["padded_rows"] >= 16 * moe["experts_touched"] \
            > moe["routed_rows"]
    finally:
        eng.stop()


def test_two_slots_decode_beside_an_admission(model):
    """A long reply decodes (its rings wrap) while a second request is
    admitted beside it and a third takes the slot the second leaves: all
    three the reference's."""
    cfg, params = model
    eng = _engine(model, decode_chunk=2)
    try:
        a = eng.submit(tokens(20, seed=8), max_new=40)
        b = eng.submit(tokens(37, seed=9), max_new=5)
        assert b.done.wait(300) and b.error is None
        c = eng.submit(tokens(18, seed=10), max_new=5)
        assert a.done.wait(300) and c.done.wait(300)
        for req in (a, b, c):
            assert req.error is None
            assert _is_greedy(cfg, params, req.prompt, req.tokens)
    finally:
        eng.stop()


def test_what_a_sliding_layer_holds_does_not_grow_with_the_context(model):
    """C1: the rings are `num_states` windows whatever is served: an engine
    that has answered a context of 200 holds what it holds after one of
    2,000, to the byte; the full layers alone used pages in proportion."""
    cfg, params = model
    cfg = dataclasses.replace(cfg, max_seq=2112)
    eng = _engine((cfg, params), max_len=2112, prompt_pad=2048,
                  kv_num_blocks=160)
    held, blocks = [], []
    try:
        for n in (200, 2000):
            req = _run(eng, tokens(n, seed=n), max_new=4)
            assert _is_greedy(cfg, params, req.prompt, req.tokens)
            held.append(_held_bytes(eng.caches))
            st = eng.kv_stats()["blocks"]
            blocks.append(st["used"] + st["cached"])
    finally:
        eng.stop()
    per_id = 2 * WINDOW * cfg.sliding_kv_heads * (256 + 128) * 4
    assert held[0] == held[1] == 11 * per_id
    assert blocks[1] - blocks[0] > 9 * blocks[0]
