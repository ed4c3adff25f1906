"""New K/V reaches the pool a page at a time (models/decoding.py
`_write_rows`): a prefill row's blocks go in as whole slabs indexed by the
block alone, a slot's one position as its page read, patched and written
back.  Held here, on the four pool forms the serving cells have and with the
indices the programs themselves build (`prefill_rows`, `decode_rows`,
`_pass_tokens`), to the write it replaced, a scatter of D-wide rows: equal
bit for bit on every LIVE position, every block no live position lies in
untouched (a shared prefix block among them), the rest of a slot's page as
it was; rows that are not whole blocks are refused; and through the engine:
a prefix hit's blocks are byte-identical after the dispatch that hits
them."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import decoding
from ray_tpu.models import transformer as tfm
from ray_tpu.serve import llm

BS, NB, W, SLOTS = 16, 64, 8, 6

# pool of one layer [NB, Hkv', bs, lanes]; [Hkv, D] as the layer hands its
# rows over; layers that share one buffer (the stacked pool, layer i's block
# b at i * NB + b)
FORMS = {
    "mistral": ((NB, 8, BS, 128), (8, 128), 3),     # two pools, stacked
    "trinity": ((NB, 4, BS, 128), (4, 128), 1),
    "lfm2": ((NB, 4, BS, 128), (8, 64), 1),         # heads of 64 side by side
    "axk1": ((NB, 1, BS, 640), (1, 576), 1),        # one pool of latent rows
}


def _row_scatter(pool, blocks, offsets, new):
    """The write this replaced, in numpy: one D-wide row a (position,
    head), later duplicates (the scratch block's) winning."""
    out = np.array(pool)
    hkv, lanes = out.shape[1], out.shape[3]
    rows = np.asarray(new, out.dtype).reshape(len(blocks), hkv, lanes)
    for b, o, r in zip(blocks, offsets, rows):
        out[b, :, o] = r
    return out


SCENES = {"full": [(0, 0, None, True)],
          "partial": [(1, 0, None, True), (1, None, 5, True)],
          "hit": [(2, 2 * BS, 1, True)],
          "dead": [(2, 0, 3, False)]}
SCENES["all"] = sum(SCENES.values(), [])


def _scene(name, P):
    """Prefill rows of P (slot, prefix, live positions, valid; None: P) and
    the slots' decode rows, as `prefill_rows` / `decode_rows` lay them out:
    a full row, a request of two rows whose second is partly filled, a
    request after a 2-block prefix hit with one live position, a row that is
    not valid; slots 3..5 decode: one at offset 0 of a block, one at offset
    bs - 1, one inactive.  -> (rows, step, N)."""
    rng = np.random.RandomState(0)
    tables = (1 + rng.permutation(NB - 1)[:SLOTS * W]).reshape(
        SLOTS, W).astype(np.int32)      # every slot its own blocks
    rows = [(s, P if a is None else a, P if n is None else n, v)
            for s, a, n, v in SCENES[name]]
    pr = decoding.prefill_rows(
        jnp.asarray(tables[[r[0] for r in rows]]),
        jnp.asarray([r[1] for r in rows], jnp.int32),
        jnp.asarray([r[2] for r in rows], jnp.int32),
        jnp.asarray([r[3] for r in rows]), P, BS)
    lengths = np.zeros(SLOTS, np.int32)
    lengths[3:] = (2 * BS, 3 * BS - 1, 7)
    step = decoding.decode_rows(
        jnp.asarray(tables), jnp.asarray(lengths),
        jnp.asarray([False, False, False, True, True, False]), BS)
    return pr, step, len(rows)


def _scatters(jaxpr, found):
    for e in jaxpr.eqns:
        if e.primitive.name == "scatter":
            found.append(e)
        for v in e.params.values():
            for j in (v if isinstance(v, (tuple, list)) else (v,)):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    _scatters(j, found)
    return found


def _check(pool0, out, blocks, offsets, live, new, first, pages):
    """`out` against the row scatter of the live positions; `pages`: the
    (block, offset) of the single positions, whose page keeps the rest."""
    pool0, out = np.asarray(pool0, np.float32), np.asarray(out, np.float32)
    new = np.asarray(new, np.float32)
    blocks, offsets, live = (np.asarray(a).reshape(-1)
                             for a in (blocks, offsets, live))
    want = _row_scatter(pool0, blocks[live], offsets[live],
                        new.reshape(len(blocks), -1)[live])
    assert live.any()
    for b, o in zip(blocks[live], offsets[live]):
        np.testing.assert_array_equal(out[b, :, o], want[b, :, o])
    written = set(blocks[live].tolist())
    for b in range(pool0.shape[0]):
        if b not in written and b != first:     # scratch: anything
            np.testing.assert_array_equal(out[b], pool0[b], err_msg=str(b))
    for b, o in pages:
        keep = np.arange(BS) != o
        np.testing.assert_array_equal(out[b][:, keep], pool0[b][:, keep])


def _writer(form):
    return decoding._write_latent if form == "axk1" else decoding._write_rows


def _lanes(new, shape):
    """[T, Hkv, D] as the pool lays a position: [T, Hkv', lanes] (a latent
    row padded with zeros)."""
    new = np.asarray(new, np.float32).reshape(new.shape[0], shape[1], -1)
    return np.pad(new, ((0, 0), (0, 0), (0, shape[3] - new.shape[-1])))


@pytest.mark.parametrize("P", [BS, 2 * BS])
@pytest.mark.parametrize("rows", ["full", "partial", "hit", "dead", "all"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_pass_is_written_by_page(form, rows, P):
    """Prompt rows (one block or two a row) with the live slots' decode rows
    beside them, as a fused dispatch's pass writes them."""
    shape, (hkv, D), layers = FORMS[form]
    pr, step, N = _scene(rows, P)
    _, valid, blocks, offsets = decoding._pass_tokens(pr, step)
    T = blocks.shape[0]
    pool0 = jax.random.normal(jax.random.PRNGKey(1),
                              (layers * NB,) + shape[1:], jnp.bfloat16)
    new = jax.random.normal(jax.random.PRNGKey(2), (T, hkv, D), jnp.bfloat16)
    write = _writer(form)
    for layer in range(layers):
        first = layer * NB
        out = jax.jit(write, static_argnums=4)(
            pool0, first + blocks, offsets, new, (N, P))
        assert out.shape == pool0.shape and out.dtype == pool0.dtype
        live = np.asarray(valid).reshape(-1)
        sb, so = np.asarray(step.blocks), np.asarray(step.offsets)
        pages = [(first + int(b), int(o)) for b, o, a in
                 zip(sb, so, np.asarray(step.active)) if a]
        assert sorted(o for _, o in pages) == [0, BS - 1]
        _check(pool0, out, first + np.asarray(blocks), offsets, live,
               _lanes(new, shape), first, pages)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_decode_step_patches_each_slots_page(form):
    shape, (hkv, D), layers = FORMS[form]
    _, step, _ = _scene("full", BS)
    pool0 = jax.random.normal(jax.random.PRNGKey(3),
                              (layers * NB,) + shape[1:], jnp.bfloat16)
    new = jax.random.normal(jax.random.PRNGKey(4), (SLOTS, hkv, D),
                            jnp.bfloat16)
    write = _writer(form)
    first = (layers - 1) * NB
    out = jax.jit(write)(pool0, first + step.blocks, step.offsets, new)
    active = np.asarray(step.active)
    pages = [(first + int(b), int(o)) for b, o, a in
             zip(np.asarray(step.blocks), np.asarray(step.offsets), active)
             if a]
    _check(pool0, out, first + np.asarray(step.blocks), step.offsets, active,
           _lanes(new, shape), first, pages)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_rows_that_are_not_whole_blocks_are_refused(form):
    """P % bs != 0: there is no write but by page, and the refusal names
    where an engine's rows come from (`prefill_shapes` cuts them in whole
    blocks; an engine whose tile is not whole blocks is refused when it is
    built).  A decode step's rows alone (no prompt row) are written
    whatever P is."""
    shape, (hkv, D), layers = FORMS[form]
    P = BS // 2
    pr, step, N = _scene("partial", P)
    _, _, blocks, offsets = decoding._pass_tokens(pr, step)
    pool0 = jnp.zeros((NB,) + shape[1:], jnp.bfloat16)
    new = jnp.zeros((blocks.shape[0], hkv, D), jnp.bfloat16)
    write = _writer(form)
    with pytest.raises(ValueError, match="prefill_shapes"):
        jax.make_jaxpr(write, static_argnums=4)(pool0, blocks, offsets, new,
                                                (N, P))
    jax.make_jaxpr(write, static_argnums=4)(
        pool0, blocks[N * P:], offsets[N * P:], new[N * P:], (0, P))


@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_written_program_indexes_the_pool_by_block_alone(form):
    """The jaxpr of a pass's write: scatters whose updates are whole pages
    [.., Hkv', bs, lanes], none into the pool seen as rows of lanes."""
    shape, (hkv, D), _ = FORMS[form]
    pr, step, N = _scene("all", BS)
    _, _, blocks, offsets = decoding._pass_tokens(pr, step)
    pool0 = jnp.zeros(shape, jnp.bfloat16)
    new = jnp.zeros((blocks.shape[0], hkv, D), jnp.bfloat16)
    write = _writer(form)
    jaxpr = jax.make_jaxpr(write, static_argnums=4)(
        pool0, blocks, offsets, new, (N, BS))
    scatters = _scatters(jaxpr.jaxpr, [])
    assert len(scatters) == 2       # the rows' slabs, the slots' pages
    for e in scatters:
        assert e.invars[0].aval.shape == shape
        assert e.invars[2].aval.shape[1:] == shape[1:]
        dn = e.params["dimension_numbers"]
        assert dn.scatter_dims_to_operand_dims == (0,)
        assert dn.update_window_dims == (1, 2, 3)


# -- through the engine ---------------------------------------------------
@pytest.fixture(scope="module")
def engine():
    cfg = tfm.TransformerConfig(vocab_size=97, d_model=32, n_heads=4,
                                n_kv_heads=2, n_layers=2, d_ff=64,
                                max_seq=128, dtype=jnp.float32, remat=False)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    eng = llm.PagedBatcher(params, cfg, num_slots=4, max_len=64,
                           prompt_pad=32, decode_chunk=4, kv_block_size=4,
                           kv_num_blocks=96, attn_impl="reference")
    yield eng
    eng.stop()


def _pools(eng):
    time.sleep(0.05)
    return np.asarray(eng.caches.kp), np.asarray(eng.caches.vp)


def test_a_shared_prefix_block_is_byte_identical_after_a_hit(engine):
    rng = np.random.RandomState(7)
    system = rng.randint(1, 97, 12).tolist()        # three whole blocks
    first = engine.submit(system + [5, 6, 7], max_new=3)
    assert first.done.wait(300) and first.error is None
    shared = list(first._blocks[:3])
    before = [p[:, shared].copy() for p in _pools(engine)]
    hits = engine.kv_stats()["prefix_cache"]["hit_tokens"]
    again = [engine.submit(system + rng.randint(1, 97, n).tolist(),
                           max_new=5) for n in (2, 9)]
    for r in again:
        assert r.done.wait(300) and r.error is None
    assert engine.kv_stats()["prefix_cache"]["hit_tokens"] >= hits + 24
    for r in again:
        assert list(r._blocks[:3]) == shared
    for was, now in zip(before, _pools(engine)):
        assert was.tobytes() == now[:, shared].tobytes()


def test_an_engine_whose_tile_is_not_whole_blocks_is_refused_when_built():
    """prefill_shapes cuts a prompt_pad under PREFILL_TILE into one tile of
    that many tokens: where that is not whole blocks the constructor says
    so, before a thread is started or a program traced."""
    cfg = tfm.TransformerConfig(vocab_size=97, d_model=32, n_heads=4,
                                n_kv_heads=2, n_layers=2, d_ff=64,
                                max_seq=128, dtype=jnp.float32, remat=False)
    assert llm.prefill_shapes(4, 6, 4)[0] == 6
    with pytest.raises(ValueError, match="whole blocks"):
        llm.PagedBatcher(None, cfg, num_slots=4, max_len=64, prompt_pad=6,
                         kv_block_size=4, attn_impl="reference")
