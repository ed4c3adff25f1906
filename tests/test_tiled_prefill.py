"""The fused prefill's rows are TILES of a request's uncached tokens
(serve/llm.py PREFILL_TILE, models/decoding.py _paged_prefill_core): a
request of several tiles takes several rows of one call, or of several
dispatches where the dispatch's budget (PREFILL_CHUNK) is spent, and a
dispatch runs the narrowest of a ladder of compiled widths
(PREFILL_RUNGS, in positions) that holds its rows.  Here, for arch "llama"
and "afmoe" at toy widths on the reference attention path, with a tile of
one block and of two and rungs that are no powers of two: the tiled
prefill fills the pool and continues greedily exactly as the un-tiled one
(one row a request), at the device function and through the engine's host
loop, and the counters count what the dispatches carried."""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import decoding
from ray_tpu.models import transformer as tfm
from ray_tpu.serve import llm

BLOCK, SLOTS, PAD, MAX_LEN = 4, 4, 64, 96

CONFIGS = {
    "llama": dict(vocab_size=97, d_model=32, n_heads=4, n_kv_heads=2,
                  n_layers=2, d_ff=64, max_seq=128, dtype=jnp.float32,
                  remat=False),
    "afmoe": dict(vocab_size=128, d_model=64, n_layers=3, n_heads=4,
                  n_kv_heads=2, d_head=16, d_ff=32, max_seq=128,
                  arch="afmoe", rope_theta=10000.0, dtype=jnp.float32,
                  param_dtype=jnp.float32, sliding_window=8,
                  layer_kinds=(("sliding", "dense"), ("sliding", "experts"),
                               ("full", "experts")),
                  moe_experts=8, moe_top_k=2, moe_d_ff=16,
                  moe_shared_experts=1, moe_route_scale=2.826, remat=False),
}


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def model(request):
    cfg = tfm.TransformerConfig(**CONFIGS[request.param])
    return cfg, tfm.init_params(cfg, jax.random.PRNGKey(0))


def prompt(n, seed):
    return np.random.RandomState(seed).randint(1, 97, n).tolist()


# -- the device function ----------------------------------------------------
W = MAX_LEN // BLOCK


def _table(slot, shared=()):
    own = list(range(1 + slot * W, 1 + (slot + 1) * W))
    return list(shared) + own[len(shared):]


def _prefill(cfg, params, caches, reqs, width, steps=3):
    """`reqs` [(slot, prompt, positions already in the pool, table)] as the
    engine packs them into rows of `width`, empty rows up to the next of
    4, 7, 14 and 28 -> (caches', each request's first token, the
    dispatch's tokens [steps, SLOTS]: the pass's, then the decode
    steps')."""
    up = decoding.FusedUpload(width, W, SLOTS, sets=False)
    rows, closing = [], []
    for slot, toks, done, table in reqs:
        for start in range(done, len(toks), width):
            n = min(width, len(toks) - start)
            rows.append((toks[start:start + n], n, start, slot, up.MORE,
                         table))
        rows[-1] = rows[-1][:4] + (up.CLOSES, table)
        closing.append(slot)
    N = next(n for n in (4, 7, 14, 28) if n >= len(rows))
    packed = up.empty(N)
    for r, (toks, n, start, slot, flag, table) in enumerate(rows):
        packed[r, :n] = toks
        packed[r, up.scalars] = (n, start, slot, flag)
        packed[r, up.table] = table
    caches, toks, _ = decoding.paged_prefill_decode_packed(
        params, caches, jnp.asarray(packed), cfg, steps, width,
        attn_impl="reference")
    return caches, [int(toks[0, s]) for s in closing], np.asarray(toks)


def _same_state(a, b):
    """Every block but the scratch block, and every slot's state."""
    for x, y in zip(jax.tree.leaves((a.kp, a.vp)),
                    jax.tree.leaves((b.kp, b.vp))):
        np.testing.assert_allclose(np.asarray(x)[..., 1:, :, :, :],
                                   np.asarray(y)[..., 1:, :, :, :],
                                   rtol=1e-4, atol=1e-5)
    for name in ("block_tables", "lengths", "last_token"):
        np.testing.assert_array_equal(getattr(a, name), getattr(b, name))


@pytest.mark.parametrize("tile", [BLOCK, 2 * BLOCK])
@pytest.mark.parametrize("case", ["one_tile", "two_rows_one_slot",
                                  "two_requests_in_a_rung_not_full",
                                  "ragged_batch", "hit_under_a_tile"])
def test_tiled_rows_fill_the_pool_as_whole_rows_do(model, case, tile):
    """Rows of one block or two against rows of 32 (one a request): the
    same first tokens, the same two decode steps of every admitted slot
    after them, the same pool, tables, lengths and last tokens."""
    cfg, params = model
    lens = {"one_tile": [3], "two_rows_one_slot": [13],
            "two_requests_in_a_rung_not_full": [6, 11],
            "ragged_batch": [3, 8, 9, 21], "hit_under_a_tile": [11]}[case]
    out = []
    for width in (tile, 32):
        caches = decoding.init_paged_caches(cfg, SLOTS, SLOTS * W, BLOCK,
                                            MAX_LEN)
        reqs = [(slot, prompt(n, seed=10 + slot), 0, _table(slot))
                for slot, n in enumerate(lens)]
        if case == "hit_under_a_tile":
            # slot 0's request first, whole; then one that shares its two
            # full blocks and brings three tokens of its own
            caches, _, _ = _prefill(cfg, params, caches, reqs, 32, steps=1)
            donor = reqs[0][1]
            reqs = [(1, donor[:8] + prompt(3, seed=20), 8,
                     _table(1, shared=_table(0)[:2]))]
        out.append(_prefill(cfg, params, caches, reqs, width))
    (tiled, first_t, toks_t), (whole, first_w, toks_w) = out
    assert first_t == first_w
    live = [r[0] for r in reqs]
    np.testing.assert_array_equal(toks_t[:, live], toks_w[:, live])
    _same_state(tiled, whole)
    assert np.asarray(tiled.lengths)[live].tolist() == [
        len(r[1]) + 2 for r in reqs]


def test_rows_of_one_slot_leave_one_winner(model):
    """Three rows name slot 2 and only the last closes: the slot's length,
    table and first token are that row's, whatever order a scatter takes
    duplicates in; the slots of rows that do not close are untouched."""
    cfg, params = model
    caches = decoding.init_paged_caches(cfg, SLOTS, SLOTS * W, BLOCK,
                                        MAX_LEN)
    toks = prompt(20, seed=5)
    table = jnp.asarray([_table(2)] * 3, jnp.int32)
    starts = jnp.asarray([0, 8, 16])
    rows = np.zeros((3, 8), np.int32)
    for r, s in enumerate((0, 8, 16)):
        rows[r, :len(toks[s:s + 8])] = toks[s:s + 8]
    new, first, *_ = decoding._paged_prefill_core(
        params, caches, jnp.asarray(rows), jnp.asarray([8, 8, 4]), starts,
        jnp.asarray([2, 2, 2]), jnp.ones((3,), bool),
        jnp.asarray([False, False, True]), table, cfg, "reference")
    assert np.asarray(new.lengths).tolist() == [0, 0, 20, 0]
    assert int(new.last_token[2]) == int(first[2])
    np.testing.assert_array_equal(new.block_tables[2], table[0])
    assert not np.asarray(new.block_tables)[[0, 1, 3]].any()


# -- the engine's host loop ---------------------------------------------------
@contextlib.contextmanager
def held(eng):
    """No dispatch while the body runs: what it submits is admitted
    together."""
    for _ in range(eng.pipeline_depth):
        assert eng._slots_sem.acquire(timeout=120)
    try:
        yield
    finally:
        for _ in range(eng.pipeline_depth):
            eng._slots_sem.release()


BUDGET = 48                 # tokens a dispatch: six rows of 8, three of 16
RUNGS = (16, BUDGET)        # positions: rows [2, 6] of 8, [1, 3] of 16
WHOLE = (PAD, 64 * PAD, (PAD, 2 * PAD, 4 * PAD))    # one row a request


def _engine(model, monkeypatch, tile, budget=BUDGET, rungs=RUNGS):
    cfg, params = model
    monkeypatch.setattr(llm, "PREFILL_TILE", tile)
    monkeypatch.setattr(llm, "PREFILL_CHUNK", budget)
    monkeypatch.setattr(llm, "PREFILL_RUNGS", rungs)
    return llm.PagedBatcher(params, cfg, num_slots=SLOTS, max_len=MAX_LEN,
                            prompt_pad=PAD, decode_chunk=4, kv_block_size=8,
                            kv_num_blocks=48, attn_impl="reference")


def _record(monkeypatch):
    """Every fused dispatch's upload, as [(rows compiled, [(tokens, slot,
    flag) of each live row])]."""
    seen = []
    real = decoding.paged_prefill_decode_packed

    def spy(params, caches, packed, cfg, chunk, width, **kw):
        up = decoding.FusedUpload.of(width, caches)
        p = np.asarray(packed)[:-1]
        live = [(int(r[up.suffix_len]), int(r[up.slot]), int(r[up.flag]))
                for r in p if r[up.flag]]
        if live:                        # warm-up's calls carry no row
            seen.append((len(p), live))
        return real(params, caches, packed, cfg, chunk, width, **kw)

    monkeypatch.setattr(decoding, "paged_prefill_decode_packed", spy)
    return seen


# name -> (requests that are decoding when the others arrive, the others
# [(prompt length, max_new)], rows compiled per fused dispatch of the others
# {tile: [rows]}: first in, first served, while the budget's rows last)
SCENARIOS = {
    "one_request_one_tile": ([], [(5, 6)], {8: [2], 16: [1]}),
    "two_rows_one_slot": ([], [(21, 6)], {8: [6], 16: [3]}),
    # 2 + 2 rows of 8 in the program of six, 1 + 1 of 16 in that of three
    "two_requests_in_a_rung_not_full": (
        [], [(9, 4), (10, 4)], {8: [6], 16: [3]}),
    "more_admissions_than_the_narrowest_rows": (
        [], [(5, 4), (6, 4), (7, 4)], {8: [6], 16: [3]}),
    # 3 + 4 rows of 8 (2 + 2 of 16): the second request is cut at the
    # budget and finishes with the next dispatch
    "over_budget_two_dispatches": (
        [(5, 24)], [(20, 4), (30, 4)], {8: [6, 2], 16: [3, 1]}),
    # 3 + 5 + 8 rows of 8: 3 + 3, 2 + 4, 4; 2 + 3 + 4 rows of 16: 2 + 1,
    # 2 + 1, 3: the second and the third request are cut
    "over_budget_three_dispatches": (
        [(5, 24)], [(20, 12), (40, 12), (64, 12)],   # none drains before
        {8: [6, 6, 6], 16: [3, 3, 3]}),
}


def _run(eng, early, late):
    reqs = [eng.submit(prompt(n, seed=40 + i), max_new=m)
            for i, (n, m) in enumerate(early)]
    for r in reqs:                      # decoding before the others arrive
        while not r.tokens:
            assert not r.done.wait(0.01) or r.tokens
    mark = eng.kv_stats()["prefill"]
    with held(eng):
        reqs += [eng.submit(prompt(n, seed=60 + i), max_new=m)
                 for i, (n, m) in enumerate(late)]
    for r in reqs:
        assert r.done.wait(200) and r.error is None
    return reqs, mark


@pytest.mark.parametrize("tile", [8, 16])
@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_engine_tiles_a_batch_like_the_untiled_engine(model, monkeypatch,
                                                      name, tile):
    """Tiles of one block (8) or two under a budget of 48 tokens and rungs
    of 16 and 48 positions, against one row a request under no budget:
    every request's tokens are the same, no dispatch carries more rows
    than the widest program, and the counters are the dispatches'
    arithmetic."""
    early, late, want_rows = SCENARIOS[name]
    want_rows = want_rows[tile]
    whole = _engine(model, monkeypatch, *WHOLE)
    try:
        assert (whole._tile, whole._prefill_rows) == (PAD, [1, 2, 4])
        want, _ = _run(whole, early, late)
    finally:
        whole.stop()
    seen = _record(monkeypatch)
    eng = _engine(model, monkeypatch, tile)
    try:
        ladder = {8: [2, 6], 16: [1, 3]}[tile]
        assert (eng._tile, eng._prefill_rows) == (tile, ladder)
        got, mark = _run(eng, early, late)
        st = eng.kv_stats()["prefill"]
    finally:
        eng.stop()
    for g, w in zip(got, want):
        assert g.tokens == w.tokens and len(g.tokens) == g.max_new
    fused = seen[len(seen) - len(want_rows):]   # the late requests' own
    assert [n for n, _ in fused] == want_rows
    assert all(n * tile <= BUDGET for n, _ in seen)
    # a request's rows are in order, full but the last, one slot, and only
    # the prompt's last row closes
    for (n_late, _), req in zip(late, got[len(early):]):
        mine = [(t, f) for _, rows in fused for t, s, f in rows
                if s == req.slot]
        assert [t for t, _ in mine] == [tile] * (n_late // tile) + (
            [n_late % tile] if n_late % tile else [])
        assert [f for _, f in mine] == [2] * (len(mine) - 1) + [1]
    assert st["padded_tokens"] == tile * sum(n for n, _ in seen)
    assert st["padded_tokens"] - mark["padded_tokens"] == tile * sum(
        want_rows)
    assert st["chunk_tokens"] == sum(n for n, _ in early + late)
    assert st["chunks"] == len(early) + sum(
        len({s for _, s, _ in rows}) for _, rows in fused)
    assert st["multi_chunk_requests"] == {
        "over_budget_two_dispatches": 1,
        "over_budget_three_dispatches": 2}.get(name, 0)
    # which program ran, by its positions: every compiled width has a
    # count from the start, and they add up to the fused dispatches
    assert st["rung_dispatches"] == {
        str(n * tile): sum(1 for m, _ in seen if m == n) for n in ladder}
    assert {k: v - mark["rung_dispatches"][k]
            for k, v in st["rung_dispatches"].items()} == {
        str(n * tile): want_rows.count(n) for n in ladder}


def test_a_hit_whose_suffix_is_under_a_tile_takes_one_row(model,
                                                          monkeypatch):
    """A second prompt shares 16 cached tokens and brings 3: one row of
    one tile, the tokens of the un-tiled engine; and the counters after
    this known sequence of admissions (24 tokens in three rows of 8 in the
    program of six, then 3 in one row in the program of two)."""
    base = prompt(24, seed=7)
    second = base[:16] + prompt(3, seed=8)
    out = []
    for shape in (WHOLE, (8,)):
        seen = _record(monkeypatch)
        eng = _engine(model, monkeypatch, *shape)
        try:
            a = eng.submit(base, max_new=4)
            assert a.done.wait(200) and a.error is None
            b = eng.submit(second, max_new=6)
            assert b.done.wait(200) and b.error is None
            assert b.cache_hit and b.cached_tokens == 16
            out.append((a.tokens, b.tokens))
            st = eng.kv_stats()["prefill"]
        finally:
            eng.stop()
        monkeypatch.undo()
    assert out[0] == out[1]
    assert seen[-1] == (2, [(3, b.slot, 1)])
    assert seen[0] == (6, [(8, a.slot, 2), (8, a.slot, 2), (8, a.slot, 1)])
    assert st == {"chunks": 2, "chunk_tokens": 24 + 3,
                  "padded_tokens": (6 + 2) * 8, "multi_chunk_requests": 0,
                  "carried_rows": 0,      # nothing was live at either
                  "rung_dispatches": {"16": 1, "48": 1}}


@pytest.mark.parametrize("slots, pad, block, want", [
    (4, 512, 16, (16, [16, 40, 56, 128])),
    (32, 512, 16, (16, [16, 40, 56, 128])),
    (32, 16896, 16, (16, [16, 40, 56, 128])),
    (32, 512, 8, (16, [16, 40, 56, 128])),          # two blocks a row
    (32, 512, 64, (64, [4, 10, 14, 32])),           # a row is whole blocks
    (8, 64, 16, (16, [16, 32])),   # every slot's longest prompt: 512 tokens
    (2, 16, 16, (16, [2])),
])
def test_the_budget_is_in_tokens_whatever_the_slots_are(slots, pad, block,
                                                        want):
    """The widest program holds PREFILL_CHUNK tokens at 4 slots as at 32
    (not a row a slot), in at most six programs, none more than 384
    positions wider than the one before it up to 896."""
    tile, ladder = llm.prefill_shapes(slots, pad, block)
    assert (tile, ladder) == want and len(ladder) <= 6
    if slots * pad >= llm.PREFILL_CHUNK:
        widths = [0] + [tile * n for n in ladder]
        assert widths[-1] == llm.PREFILL_CHUNK
        assert all(b - a <= 384 for a, b in zip(widths, widths[1:])
                   if b <= 896)
