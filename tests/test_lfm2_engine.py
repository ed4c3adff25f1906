"""arch "lfm2" through PagedBatcher, on the toy twin of tests/lfm2_twin.py:
prefix hits that restore a conv layer's tail at every block boundary, and a
prompt cut by the dispatch's token budget (tests/test_lfm2.py has the
forward pass and the paged layers alone).  Tokens are compared with the
reference's greedy continuation; a small model on the CPU."""

import jax.numpy as jnp

from lfm2_twin import BS, KIND, model, tokens  # noqa: F401
from ray_tpu.serve import llm


def _is_greedy(cfg, params, prompt, got):
    n = len(prompt) + len(got)     # padded: one compiled shape for many
    seq = jnp.asarray(list(prompt) + list(got) + [0] * (-n % 64))
    lg = KIND.reference_logits(KIND.hyper(cfg), params, seq)[
        len(prompt) - 1:n - 1]
    top2 = jnp.sort(lg, axis=-1)[:, -2:]
    assert float(jnp.min(top2[:, 1] - top2[:, 0])) > 1e-4, "a tie"
    return jnp.argmax(lg, axis=-1).tolist() == list(got)


def test_engine_restores_tails_on_every_hit(model):
    """PagedBatcher end to end: a 5-block prompt cold, then requests that
    share its first n blocks for every n, each equal to the reference's
    greedy continuation, decoding across a block boundary."""
    cfg, params = model
    eng = llm.PagedBatcher(params, cfg, num_slots=2, max_len=160,
                           prompt_pad=128, decode_chunk=4, kv_block_size=BS,
                           kv_num_blocks=80, attn_impl="reference")
    try:
        assert sum(t is not None for t in eng.caches.tail_pool) == 7
        base = tokens(5 * BS + 3, seed=11)
        cold = eng.submit(base, max_new=20)
        assert cold.done.wait(300) and cold.error is None
        assert not cold.cache_hit
        assert _is_greedy(cfg, params, base, cold.tokens)
        for n in range(1, 6):
            prompt = base[:n * BS] + tokens(9, seed=20 + n)
            hit = eng.submit(prompt, max_new=6)
            assert hit.done.wait(300) and hit.error is None
            assert hit.cached_tokens == n * BS
            assert _is_greedy(cfg, params, prompt, hit.tokens)
        # a slot used before, no hit: its conv layers start from zeros
        fresh = tokens(30, seed=40)
        again = eng.submit(fresh, max_new=5)
        assert again.done.wait(300) and not again.cache_hit
        assert _is_greedy(cfg, params, fresh, again.tokens)
        assert eng.kv_stats()["prefix_cache"]["hit_tokens"] == \
            (1 + 2 + 3 + 4 + 5) * BS
    finally:
        eng.stop()


def test_engine_cuts_a_long_prompt_by_the_token_budget(model, monkeypatch):
    """Prompts longer than one dispatch's budget (cut to 32 here) beside a
    short request that decodes on meanwhile: both the reference's."""
    monkeypatch.setattr(llm, "PREFILL_CHUNK", 32)
    cfg, params = model
    eng = llm.PagedBatcher(params, cfg, num_slots=2, max_len=160,
                           prompt_pad=128, decode_chunk=2, kv_block_size=BS,
                           kv_num_blocks=40, attn_impl="reference",
                           prefix_cache=False)
    try:
        short, long_ = tokens(9, seed=8), tokens(100, seed=9)
        a = eng.submit(short, max_new=20)
        b = eng.submit(long_, max_new=6)
        assert a.done.wait(300) and b.done.wait(300)
        assert _is_greedy(cfg, params, short, a.tokens)
        assert _is_greedy(cfg, params, long_, b.tokens)
        assert eng.kv_stats()["prefill"]["multi_chunk_requests"] == 1
    finally:
        eng.stop()
