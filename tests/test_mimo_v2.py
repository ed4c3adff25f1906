"""arch "mimo_v2" (models/mimo_v2.py) against its plain float32 reference
(benchmarks/kinds/sink-window-moe.py: masked attention over the whole
sequence, no ring, experts as a loop over the held ones), on a toy twin of the
benchmark's configuration (tests/data/mimo_v2_tiny.json: four layers, one of
each kind and a second sliding one, 4 query heads with keys of 192 and values
of 128 on 1 kv head (full) and 2 (sliding), a window of 32 with a sink, 2 held
experts of a router 32 wide): `transformer.forward`, the configuration and the
program's tree, the share of the expert layer, and the engine's own device
functions driven as its host loop drives them (a sliding layer's window a RING
by state id).  tests/test_mimo_v2_kernels.py has the ring and paged kernels,
what the benchmark's `correct` runs and the wrong programs its limits refuse;
tests/test_mimo_v2_engine.py the engine end to end: three files on the same
twin, so that `--dist loadfile` gives them to three workers.  Logits are
compared, not tokens; a small model on the CPU."""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.lib import spec
from ray_tpu.models import afmoe, decoding, mimo_v2
from ray_tpu.models import transformer as tfm
from ray_tpu.ops import window_ring as wr

from mimo_v2_twin import (BS, HERE, KIND, LIMIT, T, TWIN, WINDOW,  # noqa: F401
                          model, tiny, tokens)
from test_olmo_hybrid import Device as _Device

CONFIG = "mimo-v2-flash-l7-ep16"


class Device(_Device):
    """Every fused call of this file padded to eight rows: one compiled
    shape for them all (and one with carried decode rows)."""

    def prefill(self, parts, rows=8, carried=None):
        return super().prefill(parts, rows, carried)


def _real():
    with open(os.path.join(spec.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def _catalog():
    """The catalog row's `config` (model-configs guide, architectures.jsonl,
    MiMo-V2-Flash), key for key, as the issue and the configuration file's
    `published` carry it."""
    real = _real()
    row = {k: v for k, v in real.items()
           if k not in ("kind", "source", "torch_dtype", "router_width",
                        "experts_held_first", "published", "reduced",
                        "assumed", "notes", "deployment", "serve")}
    row.update({k: real["published"][k] for k in real["reduced"]})
    return row


# -- the model against the reference ------------------------------------------
def test_forward_matches_reference(model):
    """90 positions: the window of 32 has slid, every layer kind has run."""
    cfg, params = model
    toks = jnp.asarray(tokens(90))
    h = mimo_v2.forward_hidden(params, toks[None], cfg)
    got = jnp.einsum("sd,dv->sv", h[0], params["lm_head"])
    want = KIND.reference_logits(KIND.hyper(cfg), params, toks)
    assert KIND.rel_rms(got, want) < 1e-5
    assert KIND.rel_rms(tfm.forward(params, toks[None], cfg)[0], want) < 1e-5


def test_route_is_the_references(model):
    """`afmoe.route` with sigmoid scores, a bias that only selects and the
    1e-20: the reference's picks and weights; the softmax program another
    one."""
    cfg, params = model
    p = params["layers"][1]
    m = jax.random.normal(jax.random.PRNGKey(3), (40, cfg.d_model))
    idx, w = afmoe.route(cfg, p, m)
    picks, weights, _, _ = KIND.reference_route(KIND.hyper(cfg), p, m)
    np.testing.assert_array_equal(idx, picks)
    np.testing.assert_allclose(w, weights, rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(w, axis=1), 1.0, rtol=1e-5)
    assert cfg.moe_route_eps == 1e-20 and cfg.moe_score_fn == "sigmoid"
    other = tiny(moe_score_fn="softmax")
    assert float(jnp.abs(afmoe.route(other, p, m)[1] - w).max()) > 0.02


def test_params_are_the_files(model):
    """The tree the program makes has the parameters the kind counts, at
    the toy's sizes and at the benchmark's (shapes only), which are the
    issue's numbers; a layer can be made alone; the cache's arithmetic; no
    training path."""
    cfg, params = model
    assert tfm.num_params(params) == KIND.param_counts(TWIN)["total"]
    layer_key = jax.random.split(jax.random.PRNGKey(0), 8)[0]
    for name, w in mimo_v2.init_layer(cfg, layer_key, 3).items():
        np.testing.assert_array_equal(w, params["layers"][3][name])
    for i in (0, 1, 2):
        for name, w in KIND._weights(cfg, 0)["layer"](i).items():
            np.testing.assert_allclose(w, params["layers"][i][name],
                                       atol=1e-6)
    assert "sink" in params["layers"][1] and "sink" not in params["layers"][2]
    real = _real()
    big = tfm.TransformerConfig(**{
        **KIND.transformer_kwargs(real, max_seq=64, param_dtype="bfloat16"),
        "dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16})
    assert (big.rotary_dim, big.head_dim, big.v_head_dim) == (64, 192, 128)
    assert (big.kv_heads, big.sliding_kv_heads, big.sliding_window) == (4, 8,
                                                                       128)
    assert (big.rope_theta, big.sliding_rope_theta) == (5e6, 1e4)
    assert (big.moe_experts, big.router_width, big.moe_top_k) == (16, 256, 8)
    assert big.layer_kinds == (("full", "dense"),) + (
        ("ring", "experts"),) * 4 + (("full", "experts"), ("ring", "experts"))
    shapes = jax.eval_shape(lambda k: tfm.init_params(big, k),
                            jax.random.PRNGKey(0))
    counts = KIND.param_counts(real)
    assert tfm.num_params(shapes) == counts["total"] == 3_429_955_392
    assert (counts["attention"], counts["sliding"]) == (89_128_960,
                                                        94_371_904)
    assert counts["dense_ffn"] == 201_326_592
    assert counts["expert_ffn"] == 1_048_832 + 402_653_184
    assert counts["expert"] == 25_165_824
    assert KIND.kv_bytes_per_token(real) == 5_120
    assert KIND.state_bytes_per_sequence(real) == 3_276_800
    # what is HELD: keys of 192 in 256 lanes, values of 128 in 128
    assert decoding.unrolled_pool_shape(big, 8192, 16) == (8193, 4, 16, 256)
    assert decoding.unrolled_pool_shape(big, 8192, 16, values=True) == (
        8193, 4, 16, 128)
    assert wr.ring_shapes(256, 8, 128, 192, 128) == (
        (257, 8, 128, 256), (257, 8, 128, 128))
    # uncut: the published 309 B
    uncut = dict(real, **{k: real["published"][k] for k in real["reduced"]})
    assert round(KIND.param_counts(uncut)["total"] / 1e9, 2) == 308.78
    with pytest.raises(NotImplementedError, match="no training path"):
        tfm.loss_fn(params, jnp.asarray(tokens(16))[None], cfg)
    with pytest.raises(ValueError, match="ring|full"):
        tfm.init_params(tiny(layer_kinds=[["linear", "dense"]] * 4),
                        jax.random.PRNGKey(0))


def test_the_configuration_is_the_catalog_row():
    """Every key of the catalog row's `config` stands in the file unchanged
    but the five under `reduced`, which carry what they were; the catalog
    itself, where this machine has it, says the same."""
    real, row = _real(), _catalog()
    changed = {k for k, v in row.items() if real[k] != v}
    assert changed == set(real["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "hybrid_layer_pattern", "moe_layer_freq"}
    assert (real["num_hidden_layers"], real["n_routed_experts"],
            real["vocab_size"]) == (7, 16, 19_072)
    assert (row["num_hidden_layers"], row["n_routed_experts"],
            row["vocab_size"]) == (48, 256, 152_576)
    assert real["router_width"] == 256 and real["vocab_size"] * 8 == 152_576
    for k in ("hybrid_layer_pattern", "moe_layer_freq"):
        assert real[k] == row[k][:7] == real["reduced"][k]["here"]
        assert len(row[k]) == 48
    assert real["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1]
    assert real["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        theirs = next(r for r in rows if r["name"] == "MiMo-V2-Flash")
        assert theirs["config"] == row
        assert theirs["source_url"] == real["source"]
    assert TWIN["twin_of"] == CONFIG and set(row) <= set(TWIN)
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(changed)
    assert entry["source"] == real["source"]


def test_the_twins_pools():
    """Full layers get K pools of keys of 192 in 256 lanes beside V pools of
    128; sliding layers no pages at all but rings by state id."""
    cfg = tiny()
    c = decoding.init_paged_caches(cfg, 4, 24, BS, 96, num_states=9)
    for i, (mixer, _) in enumerate(cfg.layer_kinds):
        if mixer == "ring":
            assert c.kp[i] is None and c.vp[i] is None
            assert c.ring_k[i].shape == (10, 2, WINDOW, 256)
            assert c.ring_v[i].shape == (10, 2, WINDOW, 128)
        else:
            assert c.kp[i].shape == (25, 1, BS, 256)
            assert c.vp[i].shape == (25, 1, BS, 128)
            assert c.ring_k[i] is None and c.ring_v[i] is None
    assert c.slot_state.shape == (4,)
    assert c.tail_pool == () and c.state_pool == ()
    assert decoding.FusedUpload.of(T, c).states
    # every other architecture's caches have no ring and no state column
    plain = decoding.init_paged_caches(tfm.PRESETS["tiny"], 4, 24, BS, 96)
    assert plain.ring_k == () and not decoding.FusedUpload.of(T, plain).states


def test_importing_the_engine_imports_no_mimo_module():
    """models/mimo_v2.py and ops/window_ring.py are imported only where a
    configuration names the architecture (C4)."""
    import subprocess
    code = ("import sys, ray_tpu.serve.llm, ray_tpu.models.decoding; "
            "print([m for m in sys.modules if 'mimo' in m "
            "or 'window_ring' in m])")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=os.path.dirname(HERE),
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.stdout.strip() == "[]", out.stdout + out.stderr[-500:]


def test_the_hashed_programs_are_the_parents_and_this_ones():
    """tests/data/serving_program_hashes.json keeps the six accepted serving
    configurations' thirty programs as the parent has them, and this
    configuration's five beside them (tests/test_tpu_aot.py compares them
    with what the tree lowers)."""
    import subprocess
    path = os.path.join(HERE, "data", "serving_program_hashes.json")
    with open(path) as f:
        kept = json.load(f)
    assert len(kept) == 7 and len(kept[CONFIG]) == 5
    parent = subprocess.run(
        ["git", "show", "f96fb146d4d7db650f6a8287ca502d9a78f79439:"
         "tests/data/serving_program_hashes.json"], capture_output=True,
        text=True, cwd=os.path.dirname(HERE))
    if parent.returncode == 0:      # (a checkout with its history)
        was = json.loads(parent.stdout)
        assert len(was) == 6
        assert {k: kept[k] for k in was} == was


# -- the share ------------------------------------------------------------------
def _share(first):
    return tiny(config=dict(TWIN, experts_held_first=first))


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """One layer with all 32 experts, and the same layer as its sixteen EP
    shares (experts 2 i, 2 i + 1 of the same weights): the shares' routed
    parts, with the residual and the mixer counted once, are the uncut
    layer's output; every share counts its absent picks."""
    uncut = tiny(config=dict(TWIN, n_routed_experts=32))
    assert uncut.moe_experts == uncut.router_width == 32
    p = mimo_v2.init_layer(uncut, jax.random.PRNGKey(2), 3)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 40, uncut.d_model))
    pos = jnp.broadcast_to(jnp.arange(40, dtype=jnp.int32), (2, 40))
    attend = mimo_v2._attend_plain(WINDOW)
    kind = ("ring", "experts")
    want, counts = mimo_v2.layer(uncut, kind, p, x, pos, attend)
    assert int(counts[4]) == 0 and int(counts[1]) == 2 * 40 * 4

    def held(first, scale=1.0):
        mine = {k: v[first:first + 2] * (scale if k == "w_down" else 1.0)
                for k, v in p.items() if k in ("w_gate", "w_up", "w_down")}
        return mimo_v2.layer(_share(first), kind, {**p, **mine}, x, pos,
                             attend)

    once, _ = held(0, scale=0.0)    # the residual and the mixer
    total, routed = once, 0
    for first in range(0, 32, 2):
        out, c = held(first)
        total = total + (out - once)
        routed += int(c[1])
        assert int(c[1]) + int(c[4]) == 2 * 40 * 4
    assert routed == 2 * 40 * 4
    assert float(jnp.abs(total - want).max()) < 2e-5
    assert float(jnp.abs(once - want).max()) > 0.05


@pytest.mark.parametrize("first", [0, 16])
def test_a_share_is_the_references_share(model, first):
    """The program holding experts `first`, `first` + 1 against the
    reference told the same: logits of the whole model."""
    cfg = _share(first)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(tokens(40, seed=5))
    want = KIND.reference_logits(KIND.hyper(cfg), params, toks)
    assert KIND.rel_rms(tfm.forward(params, toks[None], cfg)[0], want) < 1e-5
    if first:
        other = KIND.reference_logits(KIND.hyper(model[0]), params, toks)
        assert KIND.rel_rms(other, want) > LIMIT


def test_padded_rows_are_counted(model):
    """An expert layer's counts end with the rows the grouped product
    computed; a dense layer counts nothing."""
    cfg, params = model
    assert mimo_v2.MOE_COUNTS[-1] == afmoe.PADDED_ROWS
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 40, cfg.d_model))
    pos = jnp.arange(40, dtype=jnp.int32)[None]
    picks = []
    _, counts = mimo_v2.layer(cfg, ("full", "experts"), params["layers"][2],
                              x, pos, mimo_v2._attend_plain(None),
                              tap=lambda name, m, p, w: picks.append(p))
    sizes = np.bincount(np.asarray(picks[0]).ravel(), minlength=32)[:2]
    assert int(counts[1]) == sizes.sum() and int(counts[4]) == 160 - sizes.sum()
    assert int(counts[5]) == sum(-(-int(s) // 16) * 16 for s in sizes)
    _, none = mimo_v2.layer(cfg, ("full", "dense"), params["layers"][0], x,
                            pos, mimo_v2._attend_plain(None))
    assert none.shape == (6,) and int(none.sum()) == 0


# -- the engine's layers: tiled paged prefill, then paged decode -------------
def test_prefill_and_decode_give_the_reference_logits(model):
    """The first token and three decode steps' logits, against ONE pass of
    the reference over the prompt and the tokens the program chose (54
    positions: the rings of 32 have wrapped)."""
    cfg, params = model
    dev = Device(cfg, params)
    prompt = tokens(54, seed=3)
    first, _ = dev.prefill([(0, dev.table(), prompt, 0, 54, 0, 1, {})])
    got = dev.decode([0], steps=3)[:, 0]
    chosen = [first[0]] + jnp.argmax(got, axis=-1).tolist()
    want = KIND.reference_logits(KIND.hyper(cfg), params,
                                 jnp.asarray(prompt + chosen[:3]))
    assert int(jnp.argmax(want[53])) == first[0]
    assert KIND.rel_rms(got, want[54:]) < 3e-4


@pytest.mark.parametrize("cuts", [(32, 22), (16, 16, 22), (48, 6)])
def test_a_prompt_over_several_dispatches_is_the_prompt_in_one(model, cuts):
    """The rings are carried from dispatch to dispatch in the request's own
    id: the same logits as the prompt in one call."""
    cfg, params = model
    prompt = tokens(54, seed=4)
    whole = Device(cfg, params)
    whole.prefill([(0, whole.table(), prompt, 0, 54, 0, 1, {})])
    want = whole.decode([0], steps=2)
    dev = Device(cfg, params)
    table, done = dev.table(), 0
    for take in cuts:
        dev.prefill([(0, table, prompt, done, take, 1 if done else 0, 1,
                      {})])
        done += take
    assert KIND.rel_rms(dev.decode([0], steps=2), want) < 3e-4


def test_rows_of_several_requests_in_one_dispatch(model):
    """Three requests' rows in one call, each from its own start and into
    its own id, beside each alone."""
    cfg, params = model
    prompts = [tokens(n, seed=30 + n) for n in (40, 17, 33)]
    alone = []
    for i, p in enumerate(prompts):
        dev = Device(cfg, params)
        dev.prefill([(0, dev.table(), p, 0, len(p), 0, 1, {})])
        alone.append(dev.decode([0], steps=2)[:, 0])
    dev = Device(cfg, params)
    dev.prefill([(i, dev.table(), p, 0, len(p), 0, i + 1, {})
                 for i, p in enumerate(prompts)])
    got = dev.decode([0, 1, 2], steps=2)
    for i in range(3):
        assert KIND.rel_rms(got[:, i], alone[i]) < 3e-4


@pytest.mark.parametrize("blocks,ckpt", [(1, 1), (2, 2), (3, 3), (4, 4),
                                         (5, 5), (3, 1), (5, 2), (2, 0)])
def test_a_hit_at_a_block_boundary_yields_the_cold_logits(model, blocks,
                                                          ckpt):
    """A checkpoint of the rings at every block boundary of a 5-block prompt
    (the window is two blocks).  A request that shares `blocks` of them and
    finds a checkpoint there starts from it and the full layers' K/V blocks;
    one that finds none there is cut back to the checkpoint at block `ckpt`
    (0: to nothing), shares the blocks before it and prefills the rest again:
    both decode the logits they decode cold (C2)."""
    cfg, params = model
    base = tokens(5 * BS + 3, seed=11)
    prompt = base[:blocks * BS] + tokens(9, seed=20 + blocks)
    cold = Device(cfg, params)
    cold.prefill([(0, cold.table(), prompt, 0, len(prompt), 0, 1, {})])
    want = cold.decode([0], steps=2)
    dev = Device(cfg, params)
    t0 = dev.table()
    dev.prefill([(0, t0, base, 0, len(base), 0, 1,
                  {b: 3 + b for b in range(1, 6)})])
    dev.prefill([(1, dev.table(t0[:ckpt]), prompt, ckpt * BS,
                  len(prompt) - ckpt * BS, 3 + ckpt if ckpt else 0, 2, {})])
    assert KIND.rel_rms(dev.decode([1], steps=2), want) < 3e-4


def test_a_hit_from_another_checkpoint_is_refused(model):
    """The pages of three blocks with the rings of two: the window's
    positions [16, 48) are not the ones held, and the logits say so."""
    cfg, params = model
    base = tokens(5 * BS + 3, seed=11)
    prompt = base[:3 * BS] + tokens(9, seed=23)
    cold = Device(cfg, params)
    cold.prefill([(0, cold.table(), prompt, 0, len(prompt), 0, 1, {})])
    want = cold.decode([0], steps=2)
    dev = Device(cfg, params)
    t0 = dev.table()
    dev.prefill([(0, t0, base, 0, len(base), 0, 1,
                  {b: 3 + b for b in range(1, 6)})])
    dev.prefill([(1, dev.table(t0[:3]), prompt, 3 * BS, len(prompt) - 3 * BS,
                  3 + 2, 2, {})])
    assert KIND.rel_rms(dev.decode([1], steps=2), want) > LIMIT


def test_carried_decode_rows_in_a_fused_pass_are_a_decode_step(model):
    """Slot 0 decodes; its next position rides in the pass that prefills
    slot 1: the same token and the same rings as a decode-only step."""
    cfg, params = model
    p0, p1 = tokens(30, seed=5), tokens(21, seed=6)
    alone = Device(cfg, params)
    alone.prefill([(0, alone.table(), p0, 0, 30, 0, 1, {})])
    want = alone.decode([0], steps=3)
    dev = Device(cfg, params)
    dev.prefill([(0, dev.table(), p0, 0, 30, 0, 1, {})])
    first = dev.decode([0], steps=1)
    carried = jnp.zeros((3,), bool).at[0].set(True)
    _, step_tok = dev.prefill([(1, dev.table(), p1, 0, 21, 0, 2, {})],
                              carried=carried)
    assert int(step_tok[0]) == int(jnp.argmax(want[1, 0]))
    assert KIND.rel_rms(first, want[:1]) < 3e-4
    assert KIND.rel_rms(dev.decode([0], steps=1), want[2:]) < 3e-4
    for i, (mixer, _) in enumerate(cfg.layer_kinds):
        if mixer == "ring":
            assert float(jnp.abs(dev.caches.ring_k[i][1]
                                 - alone.caches.ring_k[i][1]).max()) < 3e-4


