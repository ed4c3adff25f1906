"""arch "axk1" (models/axk1.py: latent attention in the absorbed form, an
expert layer that holds a share of its router's experts) against its plain
float32 reference in the PUBLISHED, expanded form (benchmarks/kinds/
mla-moe.py), on a toy twin of the benchmark's configuration
(tests/data/axk1_tiny.json: a latent row of 48 values, a router 16 wide of
which 4 experts are held, top-4, a shared expert): `transformer.forward`,
the latent pool and its two attention functions, the paged prefill and
decode layers the engine's dispatches are made of, prefix hits, the
engine's host loop and its counters, the share of the guide's section 4,
the expert product over blocks of F, and the limits of the benchmark's
`correct` shown to refuse four wrong programs and the control.  Logits are
compared, not tokens; a small model on the CPU."""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import benchmark_names
from benchmarks.lib import spec
from ray_tpu.models import afmoe, axk1, decoding
from ray_tpu.models import transformer as tfm
from ray_tpu.ops import grouped_ffn as gf
from ray_tpu.ops import paged_attention as pa
from ray_tpu.serve import llm

KIND = spec.model_kind("mla-moe")
HERE = os.path.dirname(__file__)
with open(os.path.join(HERE, "data", "axk1_tiny.json")) as f:
    TWIN = json.load(f)
with open(os.path.join(spec.BENCH_DIR, "configs", "axk1-l7-ep16.json")) as f:
    REAL = json.load(f)
LIMIT = KIND.TOLERANCES["logits_prefill_err"]
# bf16 at this toy's width of 64 errs more than at the published 7168: its
# own bound, still well under what the wrong programs and the control read
TOY_BF16 = {"logits_prefill_err": 0.04, "logits_decode_err": 0.04,
            "latent_row_err": 0.02, "logits_after_hit_err": 1e-6,
            "route_mismatch_share": 0.05}
T = BS = 16                 # the engine's tile and the block


def tiny(dtype="float32", **kw):
    kwargs = KIND.transformer_kwargs(TWIN, max_seq=256, param_dtype=dtype,
                                     dtype=dtype, **kw)
    for k in ("dtype", "param_dtype"):
        kwargs[k] = jnp.dtype(kwargs[k]).type
    return tfm.TransformerConfig(**kwargs)


def real(**kw):
    return tfm.TransformerConfig(**{
        **KIND.transformer_kwargs(REAL, max_seq=64, param_dtype="bfloat16"),
        "dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16, **kw})


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, tfm.init_params(cfg, jax.random.PRNGKey(0))


def tokens(n, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                              TWIN["vocab_size"]).tolist()


# -- the reference and the plain forward --------------------------------------
def test_forward_matches_reference(model):
    """The absorbed form (the program) is the expanded one (the reference)."""
    cfg, params = model
    toks = jnp.asarray(tokens(40))
    got = tfm.forward(params, toks[None], cfg)[0]
    want = KIND.reference_logits(KIND.hyper(cfg), params, toks)
    assert KIND.rel_rms(got, want) < 1e-5


@pytest.mark.parametrize("wrong", ["no_mscale", "rope_on_latent",
                                   "no_kv_norm", "held_normalised"])
def test_limits_refuse_a_wrong_program(model, wrong):
    cfg, params = model
    hp, toks = KIND.hyper(cfg), jnp.asarray(tokens(40))
    want = KIND.reference_logits(hp, params, toks)
    bad = KIND.reference_logits(hp, params, toks, wrong=wrong)
    assert KIND.rel_rms(bad, want) > 3 * LIMIT


def test_yarn_table_is_the_closed_form():
    """At the published numbers the ramp runs from pair 10 to pair 23; the
    frequencies below it are kept, those above divided by 32; the softmax
    scale carries m^2."""
    cfg = real()
    assert axk1.yarn_range(cfg) == KIND.yarn_range(KIND.hyper(cfg)) \
        == (10, 23)
    inv = axk1.yarn_inv_freq(cfg)
    i = np.arange(32)
    plain = 10000.0 ** (-2.0 * i / 64)
    ramp = np.clip((i - 10) / 13.0, 0, 1)
    np.testing.assert_allclose(inv, plain / 32 * ramp + plain * (1 - ramp),
                               rtol=1e-6)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 32, rtol=1e-6)
    np.testing.assert_allclose(inv, KIND.yarn_inv_freq(KIND.hyper(cfg)),
                               rtol=1e-6)
    m = 0.1 * np.log(32.0) + 1.0
    assert abs(m - 1.34657) < 1e-5
    assert abs(axk1.softmax_scale(cfg) - 0.130861) < 1e-6
    assert abs(axk1.softmax_scale(cfg) - 192 ** -0.5 * m * m) < 1e-9
    # no yarn: the plain frequencies and the plain scale
    flat = real(rope_factor=1.0)
    np.testing.assert_allclose(axk1.yarn_inv_freq(flat), plain, rtol=1e-6)
    assert abs(axk1.softmax_scale(flat) - 192 ** -0.5) < 1e-9


def test_params_are_the_files(model):
    """The tree the program makes has the parameters the kind counts, at
    the toy's sizes and at the benchmark's (shapes only); a layer can be
    made alone; no training path."""
    cfg, params = model
    assert tfm.num_params(params) == KIND.param_counts(TWIN)["total"]
    layer_key = jax.random.split(jax.random.PRNGKey(0), 8)[0]
    for name, w in axk1.init_layer(cfg, layer_key, 2).items():
        np.testing.assert_array_equal(w, params["layers"][2][name])
    shapes = jax.eval_shape(lambda k: tfm.init_params(real(), k),
                            jax.random.PRNGKey(0))
    assert tfm.num_params(shapes) == KIND.param_counts(REAL)["total"] \
        == 4_841_331_712
    assert shapes["layers"][1]["w_router"].shape == (7168, 192)
    assert shapes["layers"][1]["w_gate"].shape == (12, 7168, 2048)
    assert shapes["layers"][1]["w_uk"].shape == (64, 128, 512)
    assert "route_bias" not in shapes["layers"][1]
    assert KIND.kv_bytes_per_token(REAL) == 7 * 1152 == 8064
    assert KIND.pool_bytes_per_token(REAL) == 7 * 1280
    with pytest.raises(NotImplementedError, match="no training path"):
        tfm.loss_fn(params, jnp.asarray(tokens(16))[None], cfg)
    with pytest.raises(ValueError, match="latent"):
        tfm.init_params(tiny(layer_kinds=[["full", "dense"]] * 3),
                        jax.random.PRNGKey(0))


def test_the_configuration_file_is_the_catalog_row():
    """Every number of the row's config under the same key; the three keys
    changed are the ones `reduced` lists."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "A.X-K1")
    changed = {k for k, v in row["config"].items() if REAL.get(k) != v}
    assert changed == set(REAL["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert REAL["source"] == row["source_url"]
    assert (REAL["router_width"], REAL["n_routed_experts"],
            REAL["experts_held_first"]) == (192, 12, 0)


# -- the latent pool and its two attention functions --------------------------
def _latent_scene(N, W, c=128, r=32, H=8, seed=0, dtype=jnp.float32):
    """A pool of rows [c | k_r | zeros] behind shuffled tables, per-head
    up-projections, and what the published form makes of them: per-head
    keys and values at every position of every sequence."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    NB, n, vd = 1 + N * W, 16, 24
    Dp = pa.latent_lanes(c + r)
    rows = jax.random.normal(ks[0], (NB, 1, BS, c + r), jnp.float32)
    pool = jnp.pad(rows, ((0, 0), (0, 0), (0, 0), (0, Dp - c - r))
                   ).astype(dtype)
    tables = (1 + jax.random.permutation(ks[1], N * W).reshape(N, W)
              ).astype(jnp.int32)
    w_uk = jax.random.normal(ks[2], (H, n, c)) / np.sqrt(c)
    w_uv = jax.random.normal(ks[3], (H, c, vd)) / np.sqrt(c)
    seq = rows[tables, 0].reshape(N, W * BS, c + r)         # [N, M, c + r]
    k = jnp.concatenate([
        jnp.einsum("nmc,hdc->nmhd", seq[..., :c], w_uk),
        jnp.broadcast_to(seq[:, :, None, c:], (N, W * BS, H, r))], axis=-1)
    v = jnp.einsum("nmc,hcv->nmhv", seq[..., :c], w_uv)
    return pool, tables, w_uk, w_uv, k, v, ks[4]


def _absorb(q, w_uk, n=16):
    return jnp.concatenate([jnp.einsum("...hn,hnc->...hc", q[..., :n], w_uk),
                            q[..., n:]], axis=-1)


@pytest.mark.parametrize("impl", ["reference", "kernel"])
def test_latent_paged_attention_is_attention_over_expanded_keys(impl):
    """One query a sequence in the absorbed form over the latent pool (the
    gather; the kernel in the interpreter here, on the chip in tests_tpu/)
    against plain attention over per-head k and v rebuilt from the rows."""
    B, W, H, scale = 4, 9, 8, 0.21
    pool, tables, w_uk, w_uv, k, v, key = _latent_scene(B, W)
    q = jax.random.normal(key, (B, H, 16 + 32))
    lens = jnp.asarray([1, 37, 0, 144])
    s = jnp.einsum("bhd,bmhd->bhm", q, k) * scale
    seen = (jnp.arange(W * BS)[None] < lens[:, None])[:, None]
    w = jnp.where(seen, jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), 0)
    want = jnp.einsum("bhm,bmhv->bhv", w, v)
    o = pa.mla_paged_attention(_absorb(q, w_uk), pool, tables, lens,
                               scale=scale, v_dim=128, impl=impl)
    assert o.shape == (B, H, 128)
    got = jnp.einsum("bhc,hcv->bhv", o, w_uv)
    assert float(jnp.max(jnp.abs(got - want))) < 3e-5
    assert float(jnp.max(jnp.abs(o[2]))) == 0.0     # nothing cached: zeros


@pytest.mark.parametrize("impl", ["reference", "kernel"])
def test_latent_prefix_attention_is_attention_over_expanded_keys(impl):
    """A chunk's queries over the rows before and among them, causal."""
    N, W, P, H, scale = 3, 6, 32, 8, 0.21
    pool, tables, w_uk, w_uv, k, v, key = _latent_scene(N, W, seed=3)
    q = jax.random.normal(key, (N, P, H, 16 + 32))
    pre, suf = jnp.asarray([0, 32, 48]), jnp.asarray([32, 20, 0])
    s = jnp.einsum("nphd,nmhd->nhpm", q, k) * scale
    qpos = pre[:, None] + jnp.arange(P)[None]
    seen = (jnp.arange(W * BS)[None, None] <= qpos[..., None])[:, None]
    w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    want = jnp.einsum("nhpm,nmhv->nphv", w, v)
    o = pa.mla_prefix_attention(_absorb(q, w_uk), pool, tables, pre, suf,
                                scale=scale, v_dim=128, impl=impl)
    got = jnp.einsum("nphc,hcv->nphv", o, w_uv)
    live = (jnp.arange(P)[None, :] < suf[:, None])[..., None, None]
    assert float(jnp.max(jnp.abs(jnp.where(live, got - want, 0)))) < 3e-5


def test_latent_kernels_in_bfloat16_meet_the_gather():
    """bf16 pools: the kernels' p . v is a bf16 product accumulated in
    float32 (64 heads share every row), the gather's is float32."""
    B, W = 3, 5
    pool, tables, w_uk, _, _, _, key = _latent_scene(B, W, seed=5,
                                                     dtype=jnp.bfloat16)
    q = jax.random.normal(key, (B, 8, 160)).astype(jnp.bfloat16)
    lens = jnp.asarray([80, 33, 7])
    kw = dict(scale=0.1, v_dim=128)
    want = pa.mla_paged_attention_reference(q, pool, tables, lens, **kw)
    got = pa.mla_paged_attention(q, pool, tables, lens, impl="kernel", **kw)
    assert KIND.rel_rms(got, want) < 0.01
    qp = jnp.broadcast_to(q[:, None], (B, 16, 8, 160))
    pre, suf = lens - 16, jnp.full((B,), 16)
    pre = jnp.maximum(pre, 0)
    want = pa.mla_prefix_attention_reference(qp, pool, tables, pre, suf, **kw)
    got = pa.mla_prefix_attention(qp, pool, tables, pre, suf, impl="kernel",
                                  **kw)
    assert KIND.rel_rms(got, want) < 0.01


@pytest.mark.parametrize("H,W,B,case,dtype", [
    (64, 24, 40, "sets", "bfloat16"), (8, 24, 40, "sets", "float32"),
    (8, 200, 6, "laps", "bfloat16"), (4, 24, 40, "none", "float32")], ids=str)
def test_latent_rows_that_slots_share_are_read_once(H, W, B, case, dtype):
    """`mla_paged_attention` with `shared` (tests/test_paged_kv.py
    shared_scene: sets of 17, 9, 8 and 2 and what lies beside them) == the
    gather, which knows of no sets: a program's q block is its 8 members'
    H heads over the one pool."""
    from test_paged_kv import shared_scene
    dtype = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    pool = jnp.pad(jax.random.normal(ks[0], (1 + B * W, 1, BS, 160)),
                   ((0, 0), (0, 0), (0, 0), (0, 96))).astype(dtype)
    q = jax.random.normal(ks[1], (B, H, 160)).astype(dtype)
    tables, lens, shared = shared_scene(case, B, W, BS)
    kw = dict(scale=0.1, v_dim=128)
    want = pa.mla_paged_attention_reference(q, pool, tables, lens, **kw)
    got = pa.mla_paged_attention(q, pool, tables, lens, impl="kernel",
                                 shared=shared, **kw)
    live = np.asarray(lens) > 0
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert KIND.rel_rms(got[live], want[live]) < (
        1e-5 if dtype == jnp.float32 else 0.01)


def test_latent_attention_refuses_what_it_cannot_take():
    pool = jnp.zeros((4, 1, BS, 128))
    q = jnp.zeros((2, 4, 48))
    bt, lens = jnp.zeros((2, 3), jnp.int32), jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="pool must be"):
        pa.mla_paged_attention(q, jnp.zeros((4, 2, BS, 128)), bt, lens,
                               scale=1.0, v_dim=40)
    with pytest.raises(ValueError, match="whole 128-lane rows"):
        pa.mla_paged_attention(q, pool, bt, lens, scale=1.0, v_dim=40,
                               impl="kernel")
    with pytest.raises(ValueError, match="unknown"):
        pa.mla_paged_attention(q, pool, bt, lens, scale=1.0, v_dim=40,
                               impl="flash")
    assert pa.latent_lanes(576) == 640 and pa.latent_lanes(512) == 512


def test_the_twins_pools():
    """A latent layer has ONE pool, of rows with no head axis in whole rows
    of lanes; its `vp` is None and it has no tails.  At the published widths
    a row of 576 values lies in 640 lanes."""
    cfg = tiny()
    c = decoding.init_paged_caches(cfg, 4, 24, BS, 96)
    for i in range(cfg.n_layers):
        assert c.kp[i].shape == (25, 1, BS, 128) and c.vp[i] is None
    assert c.tail_pool == () and c.slot_tail == ()
    assert decoding.block_size_of(c) == BS
    assert decoding.unrolled_pool_shape(real(), 8192, BS, "latent") == (
        8193, 1, BS, 640)
    assert decoding.unrolled_pool_shape(     # Trinity-Mini's: as it was
        tfm.TransformerConfig(n_heads=32, n_kv_heads=4, d_head=128,
                              d_model=2048), 8, BS) == (9, 4, BS, 128)


# -- the engine's layers: tiled paged prefill, then paged decode -------------
def _sizes(cfg, max_len=200):
    caches = decoding.init_paged_caches(cfg, 4, 24, BS, max_len)
    return KIND.parity_sizes(caches)


@pytest.mark.parametrize("dtype,impl", [("float32", "reference"),
                                        ("bfloat16", "reference")])
def test_paged_layers_match_reference(dtype, impl):
    """What the benchmark's `correct` runs on the chip, at a toy size: a
    prompt of 12 blocks in calls of rows of 16, a short request of its own
    length in every other slot (all of them rows of one call), eight decode
    steps of all slots, the pool's rows, and the last rows again after a
    prefix hit."""
    cfg = tiny(dtype)
    sizes = _sizes(cfg)
    assert sizes["prompt"] == 192 and sizes["compared"] == 96
    out = KIND.compare(cfg, 7, sizes, attn_impl=impl)
    exact = dtype == "float32"
    for name in ("logits_prefill_err", "logits_decode_err", "latent_row_err",
                 "logits_after_hit_err"):
        assert out[name] < (1e-5 if exact else TOY_BF16[name]), (name, out)
    assert out["route_mismatch_share"] <= (
        0.0 if exact else TOY_BF16["route_mismatch_share"]), out
    # float32 scores from the program's own activations, whatever their
    # precision: the reference picks the same experts from them
    assert out["route_own_input_mismatch_share"] == 0.0, out
    assert out["rows_not_followed_share"] == 0.0, out
    assert out["logits_after_hit_err"] == 0.0, out


def test_control_is_refused():
    """The reference one precision down (fp8 on q, the latent rows and the
    expert weights) in the program's place fails every limit it reads."""
    cfg = tiny()
    out = KIND.compare(cfg, 7, _sizes(cfg), control=True)
    for name in ("logits_prefill_err", "logits_decode_err", "latent_row_err"):
        assert out[name] > 1.3 * KIND.TOLERANCES[name], (name, out)


class Device:
    """The engine's own device functions, driven as its host loop drives
    them: requests in slots with tables of blocks handed out in order, their
    prompts as rows of 16 in fused calls, decode steps."""

    def __init__(self, cfg, params, slots=3, blocks=64, width=8):
        self.cfg, self.params, self.width = cfg, params, width
        self.caches = decoding.init_paged_caches(cfg, slots, blocks, BS,
                                                 width * BS)
        self.next_block = 1

    def table(self, shared=()):
        own = self.width - len(shared)
        t = list(shared) + list(range(self.next_block,
                                      self.next_block + own))
        self.next_block += own
        return t

    def prefill(self, parts, rows=None):
        """parts: [(slot, table, prompt, done, take)] -> the first tokens of
        the requests whose prompt a part ends, by slot."""
        packed, ends = [], {}
        for slot, table, prompt, done, take in parts:
            for start in range(done, done + take, T):
                n = min(T, done + take - start)
                closes = start + n == len(prompt)
                if closes:
                    ends[slot] = len(packed)
                packed.append((prompt[start:start + n] + [0] * (T - n), n,
                               start, slot, True, closes, table))
        while len(packed) < (rows or len(packed)):
            packed.append(([0] * T, 0, 0, 0, False, False, [0] * self.width))
        cols = [jnp.asarray(c) for c in zip(*packed)]
        self.caches, first, *_ = decoding._paged_prefill_core(
            self.params, self.caches, *cols, self.cfg, "reference")
        return {slot: int(first[row]) for slot, row in ends.items()}

    def decode(self, slots, steps=3):
        """-> logits [steps, len(slots), V] of the slots' next positions."""
        active = jnp.zeros(self.caches.lengths.shape, bool).at[
            jnp.asarray(slots)].set(True)
        out = []
        for _ in range(steps):
            self.caches, _, lg, _ = decoding._unrolled_decode_core(
                self.params, self.caches, active, self.cfg, "reference")
            out.append(lg[jnp.asarray(slots)])
        return jnp.stack(out)


def _alone(cfg, params, prompt, steps=3):
    dev = Device(cfg, params)
    first = dev.prefill([(0, dev.table(), prompt, 0, len(prompt))])[0]
    return first, dev.decode([0], steps)[:, 0]


def test_prefill_and_decode_give_the_reference_logits(model):
    cfg, params = model
    prompt = tokens(70, seed=2)
    first, logits = _alone(cfg, params, prompt, steps=10)
    toks = [first] + jnp.argmax(logits, axis=-1).tolist()
    want = KIND.reference_logits(KIND.hyper(cfg), params,
                                 jnp.asarray(prompt + toks[:-1]))
    assert int(jnp.argmax(want[69])) == first
    assert KIND.rel_rms(logits, want[70:]) < 1e-5


@pytest.mark.parametrize("cuts", [(32, 38), (16, 16, 38), (64, 6)])
def test_a_prompt_over_several_dispatches_is_the_prompt_in_one(model, cuts):
    """The token budget cuts a prompt after whole rows; the rows of a later
    dispatch attend to the latent rows the earlier one left."""
    cfg, params = model
    prompt = tokens(70, seed=3)
    first, want = _alone(cfg, params, prompt)
    dev = Device(cfg, params)
    table, done = dev.table(), 0
    for take in cuts:
        got = dev.prefill([(0, table, prompt, done, take)], rows=5)
        done += take
    assert got[0] == first
    assert KIND.rel_rms(dev.decode([0])[:, 0], want) < 1e-5


def test_rows_of_several_requests_in_one_dispatch(model):
    """Three requests' rows in one flat stream (the third continues a prompt
    an earlier call began) give what each gives alone."""
    cfg, params = model
    a, b, c = tokens(50, seed=4), tokens(23, seed=5), tokens(40, seed=6)
    alone = [_alone(cfg, params, p) for p in (a, b, c)]
    dev = Device(cfg, params)
    ta, tb, tc = dev.table(), dev.table(), dev.table()
    dev.prefill([(2, tc, c, 0, 16)])
    first = dev.prefill([(0, ta, a, 0, 50), (1, tb, b, 0, 23),
                         (2, tc, c, 16, 24)], rows=8)
    assert [first[s] for s in (0, 1, 2)] == [f for f, _ in alone]
    logits = dev.decode([0, 1, 2])
    for s in range(3):
        assert KIND.rel_rms(logits[:, s], alone[s][1]) < 1e-5


@pytest.mark.parametrize("blocks", [1, 2, 3, 4, 5])
def test_a_prefix_hit_yields_the_cold_logits(model, blocks):
    """A request that shares the first n blocks of a 5-block prompt, in a
    slot another request used before it: a latent row is a position like
    any other, so the hit needs no act of the engine, and the logits are
    the ones it yields cold."""
    cfg, params = model
    prompt = tokens(5 * BS + 3, seed=7)
    dev = Device(cfg, params)
    cold_table = dev.table()
    cold = dev.prefill([(0, cold_table, prompt, 0, len(prompt))])[0]
    want = dev.decode([0])[:, 0]
    other = tokens(40, seed=8)
    dev.prefill([(1, dev.table(), other, 0, 40)])
    dev.decode([1])
    hit = dev.prefill([(1, dev.table(cold_table[:blocks]), prompt,
                        blocks * BS, len(prompt) - blocks * BS)])[1]
    assert hit == cold
    assert KIND.rel_rms(dev.decode([1])[:, 0], want) < 1e-5


# -- the share of the experts (model-configs guide, section 4) ----------------
def _layer_alone(cfg, p, x, valid=None):
    """An expert layer's feed-forward branch alone, over x [1, S, D]."""
    return afmoe.experts(cfg, p, x, valid, "moe_experts_prefill")


def test_all_the_shares_add_up_to_the_uncut_layer(model):
    """The routed parts that all four shares of the toy's 16 experts give,
    plus the shared expert counted once, are the uncut reference's layer
    output; every share counts rows x top-k pairs, held or absent."""
    cfg, _ = model
    E, k, S = 16, cfg.moe_top_k, 48
    whole = dataclasses.replace(cfg, moe_experts=E, moe_router_width=0,
                                moe_experts_first=0)
    p = axk1.init_layer(whole, jax.random.PRNGKey(5), 1)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, S, cfg.d_model))
    valid = (jnp.arange(S) % 7 != 3)[None]          # some rows are padding
    rows = int(jnp.sum(valid))
    # the uncut layer, by the reference: every expert held
    hp = dict(KIND.hyper(whole))
    m = x[0]
    picks, weights, _, _ = KIND.reference_route(hp, p, m)
    dense_w = jnp.zeros((S, E)).at[jnp.arange(S)[:, None], picks].add(weights)
    with jax.default_matmul_precision("highest"):
        routed = sum(dense_w[:, e][:, None] * KIND._swiglu(
            m, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
            for e in range(E))
        shared = KIND._swiglu(m, p["ws_gate"], p["ws_up"], p["ws_down"])
    total, absent_all = jnp.zeros_like(m), 0
    for first in range(0, E, 4):
        share = dataclasses.replace(cfg, moe_experts=4, moe_router_width=E,
                                    moe_experts_first=first)
        ps = dict(p, **{n: p[n][first:first + 4]
                        for n in ("w_gate", "w_up", "w_down")})
        y, counts = _layer_alone(share, ps, x, valid)
        c = dict(zip(afmoe.MOE_COUNTS, np.asarray(counts).tolist()))
        assert c["routed_rows"] + c["absent_rows"] == rows * k
        absent_all += c["absent_rows"]
        with jax.default_matmul_precision("highest"):
            total = total + (y[0] - shared)         # this share's routed part
    assert absent_all == 3 * rows * k               # each pair is held ONCE
    live = valid[0][:, None]
    assert KIND.rel_rms(jnp.where(live, total + shared, 0),
                        jnp.where(live, routed + shared, 0)) < 1e-5
    # the uncut program is the sum too, and counts nothing absent
    y, counts = _layer_alone(whole, p, x, valid)
    assert KIND.rel_rms(jnp.where(live, y[0], 0),
                        jnp.where(live, routed + shared, 0)) < 1e-5
    assert int(counts[afmoe.MOE_COUNTS.index("absent_rows")]) == 0
    assert int(counts[1]) == rows * k


def test_a_share_is_the_references_share(model):
    """The program's share of a layer (router over 16, 4 held, top-4
    normalised over all 4 picks) against the reference's, pick for pick."""
    cfg, params = model
    assert (cfg.moe_router_width, cfg.moe_experts, cfg.moe_experts_first) \
        == (16, 4, 4)
    p = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 40, cfg.d_model))
    seen = []
    y, counts = afmoe.experts(cfg, p, x, None, "moe_experts_prefill",
                              tap=seen.append)
    hp = KIND.hyper(cfg)
    picks, weights, _, _ = KIND.reference_route(hp, p, x[0])
    np.testing.assert_array_equal(np.sort(seen[0], 1), np.sort(picks, 1))
    np.testing.assert_allclose(jnp.sum(weights, axis=1), 2.5, rtol=1e-6)
    held = (picks >= 4) & (picks < 8)
    assert int(counts[1]) == int(jnp.sum(held))
    assert int(counts[4]) == int(jnp.sum(~held))
    assert 0 < int(counts[1]) < 40 * 4


def test_route_without_a_bias_is_route_with_a_zero_bias(model):
    """`afmoe.route` on a layer with no `route_bias` (this architecture) is
    the route of a zero bias (Trinity-Mini's and LFM2's program, untouched),
    and adds no operation for the bias."""
    cfg, params = model
    p = params["layers"][1]
    m = jax.random.normal(jax.random.PRNGKey(4), (64, cfg.d_model))
    got = jax.jit(lambda p, m: afmoe.route(cfg, p, m))(p, m)
    zero = dict(p, route_bias=jnp.zeros((cfg.router_width,)))
    want = jax.jit(lambda p, m: afmoe.route(cfg, p, m))(zero, m)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    assert got[0].shape == (64, 4) and int(jnp.max(got[0])) > 8
    n_with = len(jax.make_jaxpr(lambda p, m: afmoe.route(cfg, p, m))(
        zero, m).eqns)
    n_without = len(jax.make_jaxpr(lambda p, m: afmoe.route(cfg, p, m))(
        p, m).eqns)
    assert n_without < n_with


# -- the expert product over blocks of F --------------------------------------
def _tiles(T=40, K=2, E=4, D=128, F=512, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (T, D), dtype)
    idx = jax.random.randint(ks[1], (T, K), 0, E)
    valid = jax.random.uniform(ks[2], (T,)) > 0.2
    w = [(jax.random.normal(k, s) / np.sqrt(s[1])).astype(dtype)
         for k, s in zip(ks[3:], [(E, D, F), (E, D, F), (E, F, D)])]
    tm = gf.tile_rows(T * K, E)
    row_token, dest, tile_expert, n_used, sizes = gf._plan(idx, valid, E, tm)
    return x[row_token], tile_expert, n_used, w, tm


@pytest.mark.parametrize("blocks", [1, 2, 4])
def test_expert_product_over_blocks_of_f(blocks):
    """The tile kernel (interpreter) with the expert width in 1, 2 and 4
    blocks, the down-projection accumulated over them, against the gathered
    einsum; one block is the kernel every other configuration runs."""
    xs, te, nu, w, tm = _tiles()
    want = gf._ffn_tiles_reference(xs, te, nu, *w, tm=tm)
    got = gf._ffn_tiles_kernel(xs, te, nu, *w, tm=tm, name="moe_test",
                               interpret=True, f_blocks=blocks)
    used = int(nu[0]) * tm
    np.testing.assert_allclose(got[:used], want[:used], rtol=2e-5, atol=2e-5)
    if blocks == 1:     # bit-equal to the kernel as it was: one grid axis
        plain = gf._ffn_tiles_kernel(xs, te, nu, *w, tm=tm, name="moe_test",
                                     interpret=True)
        np.testing.assert_array_equal(got[:used], plain[:used])


def test_only_experts_wider_than_vmem_get_a_second_grid_axis():
    """Trinity-Mini's and LFM2's experts keep ONE block of F (the program
    they always were: a grid of tiles alone); hidden 7168 x width 2048 is
    88 MB an expert and goes in 4 blocks of 512 (22 MB a block)."""
    sizes = {}
    for name in ("trinity-mini-l5", "lfm2-24b-a2b-l9", "axk1-l7-ep16"):
        with open(os.path.join(spec.BENCH_DIR, "configs",
                               name + ".json")) as f:
            c = json.load(f)
        sizes[name] = (c["hidden_size"], c["moe_intermediate_size"])
    assert gf._f_blocks(*sizes["trinity-mini-l5"], 2) == 1
    assert gf._f_blocks(*sizes["lfm2-24b-a2b-l9"], 2) == 1
    assert sizes["axk1-l7-ep16"] == (7168, 2048)
    assert gf._f_blocks(7168, 2048, 2) == 4
    # read off the jaxpr: the grid of the kernel each would run
    def grid(D, F):
        xs, te, nu, w, tm = _tiles(T=8, E=2, D=D, F=F, dtype=jnp.bfloat16)
        jaxpr = jax.make_jaxpr(lambda *a: gf._ffn_tiles_kernel(
            *a, tm=tm, name="moe_experts_decode", interpret=True))(
            xs, te, nu, *w)
        call = next(e for e in jaxpr.eqns if e.primitive.name == "pallas_call")
        return tuple(call.params["grid_mapping"].grid)
    assert len(grid(256, 256)) == 1
    assert grid(7168, 2048)[1:] == (4,)


def test_pairs_flagged_one_by_one_are_routed_nowhere():
    """`valid` [T, K]: a pair that is not held enters no group and no count
    and adds nothing; the token's other picks are computed as ever."""
    T, K, E, D, F = 24, 2, 4, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 7)
    x = jax.random.normal(ks[0], (T, D))
    idx = jax.random.randint(ks[1], (T, K), 0, E)
    wts = jax.random.uniform(ks[2], (T, K))
    ok = jax.random.uniform(ks[3], (T, K)) > 0.4
    w = [jax.random.normal(k, s) / 8 for k, s in
         zip(ks[4:], [(E, D, F), (E, D, F), (E, F, D)])]
    y, sizes = gf.grouped_ffn(x, idx, wts, ok, *w, impl="reference")
    assert int(jnp.sum(sizes)) == int(jnp.sum(ok))
    with jax.default_matmul_precision("highest"):
        want = sum(
            jnp.where(ok[:, j], wts[:, j], 0.0)[:, None] * jnp.stack([
                KIND._swiglu(x[t][None], w[0][idx[t, j]], w[1][idx[t, j]],
                             w[2][idx[t, j]])[0] for t in range(T)])
            for j in range(K))
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)


# -- the engine ----------------------------------------------------------------
def _is_greedy(cfg, params, prompt, got):
    n = len(prompt) + len(got)     # padded: one compiled shape for many
    seq = jnp.asarray(list(prompt) + list(got) + [0] * (-n % 64))
    lg = KIND.reference_logits(KIND.hyper(cfg), params, seq)[
        len(prompt) - 1:n - 1]
    top2 = jnp.sort(lg, axis=-1)[:, -2:]
    assert float(jnp.min(top2[:, 1] - top2[:, 0])) > 1e-4, "a tie"
    return jnp.argmax(lg, axis=-1).tolist() == list(got)


def test_engine_serves_the_reference_and_counts_the_share(model):
    """PagedBatcher end to end: a 5-block prompt cold, then requests that
    share its first n blocks for every n, each equal to the reference's
    greedy continuation, decoding across a block boundary; the expert
    layers' counters say what share of the picks was held here."""
    cfg, params = model
    eng = llm.PagedBatcher(params, cfg, num_slots=2, max_len=160,
                           prompt_pad=128, decode_chunk=4, kv_block_size=BS,
                           kv_num_blocks=80, attn_impl="reference")
    try:
        assert all(v is None for v in eng.caches.vp)
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
        stats = eng.kv_stats()
        assert stats["prefix_cache"]["hit_tokens"] == (1 + 2 + 3 + 4 + 5) * BS
        moe = stats["moe"]
        assert moe["absent_rows"] > moe["routed_rows"] > 0
        # 4 of 16 experts are held: about a quarter of the picks
        share = moe["routed_rows"] / (moe["routed_rows"] + moe["absent_rows"])
        assert 0.1 < share < 0.45, moe
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


# -- the benchmark's names -----------------------------------------------------
@pytest.mark.parametrize("cell", [w["name"] for w in
                                  spec.load_benchmark()["workloads"]])
def test_every_cell_resolves_its_names(cell):
    """All eight cells load; the new cell's metrics are its own, and it is
    judged by decode_tokens_per_s under the traffic file two other cells
    run, at its own 64 slots -> 128 callers."""
    loaded, kernels = benchmark_names.resolved(cell)
    ours = cell == "serve-axk1-agent-sessions"
    # the latent kernels' rooflines are this cell's and no other's
    assert ({"mla_paged_decode", "mla_prefix_attention"} <= kernels) == ours
    assert bool({"mla_paged_decode", "mla_prefix_attention"} & kernels) == ours
    if not ours:
        return
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "decode_tokens_per_s", "setup_s"}
    assert loaded["cell"]["chips"] == 1
    assert loaded["traffic"]["name"] == "agent-sessions"
    drive = spec.traffic_kind(loaded["traffic"]["kind"])
    assert drive.clients(loaded["traffic"], loaded["config"]["serve"]) == 128
    assert kernels == {"mla_paged_decode", "mla_prefix_attention",
                       "moe_experts_decode"} <= set(KIND.COST_FNS)


def test_cost_functions_at_the_cells_shape():
    """139 kFLOP and 1,152 B a cached position; the held experts' floor."""
    s = {"slots": 64, "live_context": 450_000.0}
    flops, bytes_ = KIND.mla_paged_decode(REAL, s)
    assert flops == 2 * 64 * (576 + 512) * 450_000
    assert abs(bytes_ - (1152 * 450_000 + 2 * 64 * 64 * 1088)) < 1
    assert abs(KIND.experts_touched_even(REAL, 32)
               - 12 * (1 - (23 / 24) ** 32)) < 1e-9
    flops, bytes_ = KIND.moe_experts_decode(REAL, s)
    assert abs(flops - 2 * 32 * 3 * 7168 * 2048) < 1
    assert 8 * 88e6 < bytes_ < 12 * 89e6
    flops, bytes_ = KIND.mla_prefix_attention(REAL, s)
    assert flops > 0 and bytes_ > 0
