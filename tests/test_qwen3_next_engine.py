"""arch "qwen3_next" through PagedBatcher, on the toy twin of
tests/qwen3_next_twin.py: the engine end to end, unedited for this
architecture: state ids and checkpoints (StateAllocator, the radix cache's
`match_with_state`) beside an expert layer's share, and the expert counters
with the padded rows among them (tests/test_qwen3_next.py has the model, the
paged layers and the share alone).  Tokens are compared with the reference's
greedy continuation; a small model on the CPU."""

import jax.numpy as jnp

from qwen3_next_twin import BS, KIND, model, tokens  # noqa: F401
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


def test_engine_serves_hits_from_checkpoints_and_counts_padded_rows(model):
    """A 5-block prompt cold, then requests that share 3, 3, 5 and 2 of its
    blocks: cut back to the deepest checkpoint as for any architecture with
    linear layers, every reply the reference's greedy tokens; the expert
    counters tell routed rows from absent ones (8 of 32 experts held) and
    count the rows the grouped product padded its groups to."""
    cfg, params = model
    eng = _engine(model)
    try:
        assert sum(p is not None for p in eng.caches.state_pool) == 6
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
        moe = st["moe"]
        assert moe["layer_steps"] > 0 and moe["layer_steps"] % 8 == 0
        assert moe["picked_rows"] == moe["routed_rows"] + moe["absent_rows"]
        assert 0.1 < moe["routed_rows"] / moe["picked_rows"] < 0.5
        # every group padded to whole tiles of 16 rows
        assert moe["padded_rows"] % 16 == 0
        assert moe["padded_rows"] >= 16 * moe["experts_touched"] \
            > moe["routed_rows"]
    finally:
        eng.stop()


def test_two_slots_decode_beside_an_admission(model):
    """A long reply decodes while a second request is admitted beside it and
    a third takes the slot the second leaves: all three the reference's."""
    cfg, params = model
    eng = _engine(model, decode_chunk=2)
    try:
        a = eng.submit(tokens(20, seed=8), max_new=24)
        b = eng.submit(tokens(37, seed=9), max_new=5)
        assert b.done.wait(300) and b.error is None
        c = eng.submit(tokens(18, seed=10), max_new=5)
        assert a.done.wait(300) and c.done.wait(300)
        for req in (a, b, c):
            assert req.error is None
            assert _is_greedy(cfg, params, req.prompt, req.tokens)
    finally:
        eng.stop()
