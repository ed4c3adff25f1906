"""arch "lfm2" (models/lfm2.py) against its plain float32 reference
(benchmarks/kinds/lfm2-moe.py), on a toy twin of the benchmark's
configuration (tests/data/lfm2_tiny.json: the same nine layers, heads of
64): `transformer.forward`, the paged prefill and decode layers the engine's
dispatches are made of (K/V pools and conv tails), rows of several requests
in one call, prefix hits that restore a conv layer's tail, and the limits
of the benchmark's `correct` shown to refuse four wrong programs and the
control (the engine's host loop: tests/test_lfm2_engine.py, on the same
twin, tests/lfm2_twin.py).  Logits are compared, not tokens; a small model
on the CPU."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import benchmark_names
from benchmarks.lib import spec
from ray_tpu.models import afmoe, decoding, lfm2
from ray_tpu.models import transformer as tfm
from ray_tpu.ops import paged_attention as pa

from lfm2_twin import (BS, KIND, LIMIT, T, TOY_BF16, TWIN,  # noqa: F401
                       model, tiny, tokens)


# -- the reference and the plain forward --------------------------------------
def test_reference_convolution_by_hand():
    """One channel, taps (1, 10, 100), u = B * z = 1, 2, 3, 4 with C = 1:
    c_t = u_{t-2} + 10 u_{t-1} + 100 u_t, zeros before the start."""
    eye = np.zeros((1, 3, 1), np.float32)
    eye[0, :, 0] = 1.0                          # B = C = z = a
    p = {"w_in": jnp.asarray(eye), "w_out": jnp.ones((1, 1)),
         "w_conv": jnp.asarray([[1.0], [10.0], [100.0]])}
    a = jnp.asarray([[1.0], [np.sqrt(2.0)], [np.sqrt(3.0)], [2.0]])
    y, u = KIND.reference_conv(p, a)
    np.testing.assert_allclose(u[:, 0], [1, 2, 3, 4], rtol=1e-6)
    want = np.array([100, 210, 321, 432]) * np.asarray(a[:, 0])
    np.testing.assert_allclose(y[:, 0], want, rtol=1e-6)


def test_forward_matches_reference(model):
    cfg, params = model
    toks = jnp.asarray(tokens(40))
    got = tfm.forward(params, toks[None], cfg)[0]
    want = KIND.reference_logits(KIND.hyper(cfg), params, toks)
    assert KIND.rel_rms(got, want) < 1e-5


@pytest.mark.parametrize("wrong", ["taps_shifted", "no_out_gate", "no_rope",
                                   "softmax_routing"])
def test_limits_refuse_a_wrong_program(model, wrong):
    cfg, params = model
    hp, toks = KIND.hyper(cfg), jnp.asarray(tokens(40))
    want = KIND.reference_logits(hp, params, toks)
    bad = KIND.reference_logits(hp, params, toks, wrong=wrong)
    assert KIND.rel_rms(bad, want) > 3 * LIMIT


def test_params_are_the_files(model):
    """The tree the program makes has the parameters the kind counts, at
    the toy's sizes and at the benchmark's (shapes only); a layer can be
    made alone; no training path."""
    cfg, params = model
    assert tfm.num_params(params) == KIND.param_counts(TWIN)["total"]
    layer_key = jax.random.split(jax.random.PRNGKey(0), 8)[0]
    for name, w in lfm2.init_layer(cfg, layer_key, 5).items():
        np.testing.assert_array_equal(w, params["layers"][5][name])
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "lfm2-24b-a2b-l9.json")) as f:
        real = json.load(f)
    big = tfm.TransformerConfig(**{
        **KIND.transformer_kwargs(real, max_seq=64, param_dtype="bfloat16"),
        "dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16})
    shapes = jax.eval_shape(lambda k: tfm.init_params(big, k),
                            jax.random.PRNGKey(0))
    assert tfm.num_params(shapes) == KIND.param_counts(real)["total"] \
        == 5_177_950_976
    assert KIND.kv_bytes_per_token(real) == 4 * 1024
    assert KIND.tail_bytes_per_block(real) == 56 * 1024
    with pytest.raises(NotImplementedError, match="no training path"):
        tfm.loss_fn(params, jnp.asarray(tokens(16))[None], cfg)
    with pytest.raises(ValueError, match="conv|full"):
        tfm.init_params(tiny(layer_kinds=[["sliding", "dense"]] * 9),
                        jax.random.PRNGKey(0))


def test_route_without_epsilon_is_the_program_it_was():
    """moe_route_eps 0 (every configuration before this one) adds nothing to
    the program: bit-equal to the formula `afmoe.route` always had; 1e-6
    moves the weights and not the picks."""
    cfg = tiny(moe_route_eps=0.0, moe_route_scale=2.826)
    p = lfm2.init_layer(cfg, jax.random.PRNGKey(3), 4)
    m = jax.random.normal(jax.random.PRNGKey(4), (64, cfg.d_model))

    def was(p, m):
        s = jax.nn.sigmoid(jnp.einsum(
            "td,de->te", m, p["w_router"],
            precision=jax.lax.Precision.HIGHEST))
        _, idx = jax.lax.top_k(s + p["route_bias"], cfg.moe_top_k)
        picked = jnp.take_along_axis(s, idx, axis=1)
        return idx.astype(jnp.int32), cfg.moe_route_scale * picked / jnp.sum(
            picked, axis=1, keepdims=True)

    got = jax.jit(lambda p, m: afmoe.route(cfg, p, m))(p, m)
    want = jax.jit(was)(p, m)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    assert "1e-06" not in str(jax.make_jaxpr(
        lambda p, m: afmoe.route(cfg, p, m))(p, m))
    eps = jax.jit(lambda p, m: afmoe.route(
        tiny(moe_route_eps=1e-6, moe_route_scale=2.826), p, m))(p, m)
    np.testing.assert_array_equal(eps[0], want[0])
    assert float(jnp.max(jnp.abs(eps[1] - want[1]))) > 0


# -- heads of 64 side by side in a row of 128 lanes ---------------------------
def _pools(N, W, hkv=4, d=64, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    NB = 1 + N * W
    kp = jax.random.normal(ks[0], (NB, hkv, BS, d), jnp.float32)
    vp = jax.random.normal(ks[1], (NB, hkv, BS, d), jnp.float32)
    tables = 1 + jax.random.permutation(ks[2], N * W).reshape(N, W)

    def side_by_side(pool):     # [NB, Hkv, bs, D] -> [NB, Hkv / 2, bs, 2 D]
        return pool.reshape(NB, hkv // 2, 2, BS, d).transpose(
            0, 1, 3, 2, 4).reshape(NB, hkv // 2, BS, 2 * d)

    return kp, vp, side_by_side(kp), side_by_side(vp), tables.astype(
        jnp.int32)


@pytest.mark.parametrize("impl", ["reference", "kernel"])
def test_prefix_attention_over_heads_side_by_side(impl):
    """Queries of 8 heads of 64 over a pool whose rows hold two of the 4 kv
    heads each: the gather and the kernel (the interpreter here; on the
    chip in tests_tpu/) against the gather over the plain pool."""
    N, W, P = 3, 6, 32
    kp, vp, kp2, vp2, tables = _pools(N, W)
    q = jax.random.normal(jax.random.PRNGKey(9), (N, P, 8, 64), jnp.float32)
    pre, suf = jnp.asarray([0, 32, 48]), jnp.asarray([32, 20, 0])
    want = pa.prefix_attention_reference(q, kp, vp, tables, pre, suf)
    got = pa.prefix_attention(q, kp2, vp2, tables, pre, suf, impl=impl)
    live = (jnp.arange(P)[None, :] < suf[:, None])[..., None, None]
    assert float(jnp.max(jnp.abs(jnp.where(live, got - want, 0)))) < 2e-5


@pytest.mark.parametrize("impl", ["reference", "kernel"])
def test_paged_attention_over_heads_side_by_side(impl):
    B, W = 4, 5
    kp, vp, kp2, vp2, tables = _pools(B, W, seed=2)
    q = jax.random.normal(jax.random.PRNGKey(5), (B, 8, 64), jnp.float32)
    lens = jnp.asarray([1, 37, 0, 80])
    want = pa.paged_attention_reference(q, kp, vp, tables, lens)
    got = pa.paged_attention(q, kp2, vp2, tables, lens, impl=impl)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


def test_the_twins_pools():
    """Attention layers get K/V pools, two kv heads to a row of lanes; conv
    layers get none, and block tails and slot tails instead."""
    cfg = tiny()
    c = decoding.init_paged_caches(cfg, 4, 24, BS, 96)
    for i, (mixer, _) in enumerate(cfg.layer_kinds):
        if mixer == "conv":
            assert c.kp[i] is None and c.vp[i] is None
            assert c.tail_pool[i].shape == (25, 2 * 256)
            assert c.slot_tail[i].shape == (4, 2, 256)
        else:
            assert c.kp[i].shape == c.vp[i].shape == (25, 1, BS, 128)
            assert c.tail_pool[i] is None and c.slot_tail[i] is None
    assert decoding.block_size_of(c) == BS
    assert decoding.unrolled_pool_shape(     # Trinity-Mini's: as it was
        tfm.TransformerConfig(n_heads=32, n_kv_heads=4, d_head=128,
                              d_model=2048), 8, BS) == (9, 4, BS, 128)


# -- the engine's layers: tiled paged prefill, then paged decode -------------
def _sizes(cfg, max_len=200):
    caches = decoding.init_paged_caches(cfg, 4, 24, BS, max_len)
    return KIND.parity_sizes(caches)


@pytest.mark.parametrize("dtype,impl", [("float32", "reference"),
                                        ("float32", "kernel"),
                                        ("bfloat16", "reference")])
def test_paged_layers_match_reference(dtype, impl):
    """What the benchmark's `correct` runs on the chip, at a toy size: a
    prompt of 12 blocks in calls of rows of 16, a short request of its own
    length in every other slot (all of them rows of one call), eight decode
    steps of all slots, the block and slot tails, and the last rows again
    after a prefix hit."""
    cfg = tiny(dtype)
    sizes = _sizes(cfg)
    assert sizes["prompt"] == 192 and sizes["compared"] == 96
    assert KIND.short_lengths(sizes) == [22, 27, 17]
    out = KIND.compare(cfg, 7, sizes, attn_impl=impl)
    exact = dtype == "float32"
    for name in ("logits_prefill_err", "logits_decode_err", "conv_tail_err",
                 "logits_after_hit_err"):
        assert out[name] < (1e-5 if exact else TOY_BF16[name]), (name, out)
    assert out["logits_decode_err_worst_slot"] < 1.2 * (
        1e-5 if exact else TOY_BF16["logits_decode_err"]), out
    assert out["route_mismatch_share"] <= (
        0.0 if exact else TOY_BF16["route_mismatch_share"]), out
    # float32 scores from the program's own activations, whatever their
    # precision: the reference picks the same experts from them
    assert out["route_own_input_mismatch_share"] == 0.0, out
    assert out["rows_not_followed_share"] == 0.0, out


def test_control_is_refused():
    """The reference one precision down (fp8 on q, k, v, u and the expert
    weights, bfloat16 routing scores) in the program's place fails every
    limit it reads."""
    cfg = tiny()
    out = KIND.compare(cfg, 7, _sizes(cfg), control=True)
    for name in ("logits_prefill_err", "logits_decode_err", "conv_tail_err"):
        assert out[name] > 2 * KIND.TOLERANCES[name], (name, out)
    assert out["route_mismatch_share"] > TOY_BF16["route_mismatch_share"]
    assert out["rows_not_followed_share"] > 0


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bfloat16_routing_scores_are_refused(dtype):
    """The PROGRAM with its routing scores alone rounded to bfloat16
    (`moe_score_dtype`): on its own input the reference picks other
    experts, beyond the limit, while the share of picks that differ along
    the reference's own path hardly moves (0.0075 -> 0.0084 in bfloat16
    here: that number cannot tell them apart)."""
    cfg = tiny(dtype)
    out = KIND.compare(cfg, 7, _sizes(cfg), control="scores",
                       attn_impl="reference")
    assert out["route_own_input_mismatch_share"] > \
        2 * KIND.TOLERANCES["route_own_input_mismatch_share"], out
    assert out["route_mismatch_share"] < TOY_BF16["route_mismatch_share"]


def test_reference_follows_only_a_choice_within_the_margin():
    """Scores 0.9, 0.6, 0.59, 0.1 and top 2: a program that took expert 2
    for expert 1 (0.01 below) is followed and weighed by the reference's
    own scores; one that took expert 3 (0.5 below) is not, and the row
    reports its shortfall."""
    hp = {"top_k": 2, "route_scale": 1.0, "route_eps": 0.0}
    score = np.array([0.9, 0.6, 0.59, 0.1])
    p = {"w_router": jnp.eye(4), "route_bias": jnp.zeros(4)}
    m = jnp.asarray(np.tile(np.log(score / (1 - score)), (3, 1)), jnp.float32)
    follow = jnp.asarray([[0, 2], [0, 3], [-1, -1]])
    picks, w, used, shortfall = KIND.reference_route(hp, p, m, follow=follow)
    assert picks.tolist() == [[0, 1]] * 3
    assert used.tolist() == [[0, 2], [0, 1], [0, 1]]
    np.testing.assert_allclose(w[0], [0.9 / 1.49, 0.59 / 1.49], rtol=1e-5)
    np.testing.assert_allclose(shortfall, [0.01, 0.5, 0.0], atol=1e-6)
    assert 0.01 < KIND.FOLLOW_MARGIN < 0.5


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
    dispatch start from the block tails the earlier one left."""
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
    """A row whose predecessor in the call is ANOTHER request's row takes
    no tail from it: three requests' rows in one flat stream (the second
    starts cold right after the first's rows, the third continues a prompt
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
    """A request that shares the first n blocks of a 5-block prompt starts
    its conv layers from the tail of block n - 1, in a slot another request
    used before it: the logits it yields cold."""
    cfg, params = model
    prompt = tokens(5 * BS + 3, seed=7)
    dev = Device(cfg, params)
    cold_table = dev.table()
    cold = dev.prefill([(0, cold_table, prompt, 0, len(prompt))])[0]
    want = dev.decode([0])[:, 0]
    other = tokens(40, seed=8)          # leaves its tails in slot 1
    dev.prefill([(1, dev.table(), other, 0, 40)])
    dev.decode([1])
    hit = dev.prefill([(1, dev.table(cold_table[:blocks]), prompt,
                        blocks * BS, len(prompt) - blocks * BS)])[1]
    assert hit == cold
    assert KIND.rel_rms(dev.decode([1])[:, 0], want) < 1e-5


def test_a_hit_that_restores_nothing_is_refused(monkeypatch):
    """The comparison `correct` makes, with every row of a call made to
    start from the scratch block's tail whatever lies before it (a hit that
    shares the K/V blocks and forgets the tails): 0.52 where the sound
    program reads 0."""
    real = decoding.prefill_rows

    def forgetful(*args, **kw):
        rows = real(*args, **kw)
        return rows._replace(before_block=jnp.zeros_like(rows.before_block))

    cfg = tiny()
    sound = KIND.compare(cfg, 7, _sizes(cfg), attn_impl="reference")
    assert sound["logits_after_hit_err"] < 1e-6
    monkeypatch.setattr(decoding, "prefill_rows", forgetful)
    bad = KIND.compare(cfg, 7, _sizes(cfg), attn_impl="reference")
    assert bad["logits_after_hit_err"] > 50 * \
        KIND.TOLERANCES["logits_after_hit_err"]

# -- the benchmark's names -----------------------------------------------------
@pytest.mark.parametrize("cell", [w["name"] for w in
                                  spec.load_benchmark()["workloads"]])
def test_every_cell_resolves_its_names(cell):
    """No metric, cost function or kind of one cell leaks into another's
    name resolution: each cell loads, reports an end-to-end metric beside
    setup_s and at least one per-layer metric, and the new cell's metrics
    are its own."""
    loaded, kernels = benchmark_names.resolved(cell)
    if cell == "serve-lfm2-agent-sessions":
        # its rooflines read the prefill kernel and the expert product
        assert kernels == {"prefix_attention", "moe_experts_decode"}
        assert loaded["config"]["kind"] == "lfm2-moe"
    else:
        assert loaded["config"]["kind"] != "lfm2-moe"
