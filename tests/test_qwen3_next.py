"""arch "qwen3_next" (models/qwen3_next.py) against its plain float32
reference (benchmarks/kinds/gated-delta-moe.py: the STEP recurrence under a
scan, experts as a loop over the held ones), on a toy twin of the
benchmark's configuration (tests/data/qwen3_next_tiny.json: two periods of
[linear, linear, linear, full], 2 key heads shared by 4 value heads of 128,
full heads of 256 with the rotary on 64 dims, 8 held experts of a router 32
wide): `transformer.forward`, the paged layers the engine's dispatches are
made of, the share of the expert layer, the kernels' shapes the architecture
brings, and the limits of the benchmark's `correct` shown to refuse ten
wrong programs and both controls (the engine end to end:
tests/test_qwen3_next_engine.py, on the same twin).  Logits are compared,
not tokens; a small model on the CPU."""

import json
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import benchmark_names
from benchmarks.lib import reference, spec
from ray_tpu.models import afmoe, decoding, qwen3_next
from ray_tpu.models import transformer as tfm
from ray_tpu.ops import gated_delta as gd
from ray_tpu.ops import paged_attention as pa

from qwen3_next_twin import (BS, HERE, KIND, LIMIT, T, TWIN,  # noqa: F401
                             model, tiny, tokens)
from test_olmo_hybrid import Device, _rule_inputs

CONFIG = "qwen3-next-80b-a3b-l8-ep4"
CELL = "serve-qw3n-agent-sessions"
# the catalog row's `config` (model-configs guide, architectures.jsonl,
# Qwen3-Next-80B-A3B-Instruct), key for key
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
WRONG = ["sigmoid_routing", "no_renorm", "no_shared_gate", "no_output_gate",
         "rope_on_all", "plain_norm", "beta_x2", "key_head_mod", "no_decay",
         "taps_shifted"]


def _real():
    with open(os.path.join(spec.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        return json.load(f)


# -- the model against the reference ------------------------------------------
@pytest.mark.parametrize("chunk", [1, 16, 64])
def test_forward_matches_reference(model, chunk):
    cfg, params = model
    toks = jnp.asarray(tokens(70))
    h = qwen3_next.forward_hidden(params, toks[None], cfg, chunk=chunk)
    got = jnp.einsum("sd,dv->sv", h[0], params["lm_head"])
    want = KIND.reference_logits(KIND.hyper(cfg), params, toks)
    assert KIND.rel_rms(got, want) < 1e-5
    if chunk == 64:
        assert KIND.rel_rms(tfm.forward(params, toks[None], cfg)[0],
                            want) < 1e-5


@pytest.mark.parametrize("wrong", WRONG)
def test_limits_refuse_a_wrong_program(model, wrong):
    """Each fault in the reference's place reads at least four times the
    limit of the logits (the narrowest, the rotary on all 256 dims, five
    times)."""
    cfg, params = model
    hp, toks = KIND.hyper(cfg), jnp.asarray(tokens(70))
    want = KIND.reference_logits(hp, params, toks)
    bad = KIND.reference_logits(hp, params, toks, wrong=wrong)
    assert not KIND.rel_rms(bad, want) < 4 * LIMIT


def test_route_is_the_references(model):
    """`afmoe.route` under `moe_score_fn` "softmax": the reference's picks
    and weights (softmax over the router's whole width in float32, top-k,
    renormalised over the picks), and the sigmoid program another one."""
    cfg, params = model
    p = params["layers"][0]
    m = jax.random.normal(jax.random.PRNGKey(3), (40, cfg.d_model))
    idx, w = afmoe.route(cfg, p, m)
    picks, weights, _, _ = KIND.reference_route(KIND.hyper(cfg), p, m)
    np.testing.assert_array_equal(idx, picks)
    np.testing.assert_allclose(w, weights, rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(w, axis=1), 1.0, rtol=1e-5)
    other = tiny(moe_score_fn="sigmoid")
    assert float(jnp.abs(afmoe.route(other, p, m)[1] - w).max()) > 0.02
    with pytest.raises(KeyError):
        afmoe.route(tiny(moe_score_fn="tanh"), p, m)


def test_params_are_the_files(model):
    """The tree the program makes has the parameters the kind counts, at
    the toy's sizes and at the benchmark's (shapes only), which are the
    issue's numbers; a layer can be made alone; the state's arithmetic; no
    training path."""
    cfg, params = model
    assert tfm.num_params(params) == KIND.param_counts(TWIN)["total"]
    layer_key = jax.random.split(jax.random.PRNGKey(0), 8)[0]
    for name, w in qwen3_next.init_layer(cfg, layer_key, 5).items():
        np.testing.assert_array_equal(w, params["layers"][5][name])
    # ... and by one compiled maker a kind, its index an argument
    # (a compiled maker fuses the scaling into the draw: the last bit)
    made = jax.jit(lambda k, i: qwen3_next.init_layer(cfg, k, i, like=1))(
        layer_key, 5)
    for name, w in made.items():
        np.testing.assert_allclose(w, params["layers"][5][name], atol=1e-6)
    for i in (2, 7):
        for name, w in KIND._weights(cfg, 0)["layer"](i).items():
            np.testing.assert_allclose(w, params["layers"][i][name],
                                       atol=1e-6)
    real = _real()
    big = tfm.TransformerConfig(**{
        **KIND.transformer_kwargs(real, max_seq=64, param_dtype="bfloat16"),
        "dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16})
    assert (big.rotary_dim, big.linear_key_heads, big.linear_heads) == \
        (64, 16, 32)
    assert (big.moe_experts, big.router_width, big.moe_top_k) == (128, 512,
                                                                  10)
    shapes = jax.eval_shape(lambda k: tfm.init_params(big, k),
                            jax.random.PRNGKey(0))
    counts = KIND.param_counts(real)
    assert tfm.num_params(shapes) == counts["total"] == 3_667_251_328
    assert (counts["linear"], counts["attention"]) == (33_718_464,
                                                       27_263_488)
    assert counts["expert_ffn"] == 4_200_448 - 4_096 + 402_653_184
    assert KIND.kv_bytes_per_token(real) == 4_096
    assert KIND.state_bytes_per_sequence(real) == 12_877_824
    # a head's state is whole rows of lanes: no heads side by side
    assert gd.pool_shape(256, 32, 128, 128) == (257, 32, 128, 128)
    assert math.prod(gd.pool_shape(0, 32, 128, 128)) * 4 == 2_097_152
    assert decoding.unrolled_pool_shape(big, 8192, 16) == (8193, 2, 16, 256)
    with pytest.raises(NotImplementedError, match="no training path"):
        tfm.loss_fn(params, jnp.asarray(tokens(16))[None], cfg)
    with pytest.raises(ValueError, match="linear|full"):
        tfm.init_params(tiny(layer_kinds=[["linear", "dense"]] * 8),
                        jax.random.PRNGKey(0))


def test_the_configuration_is_the_catalog_row():
    """Every key of the catalog row's `config` stands in the file unchanged
    but the three under `reduced`, which carry what they were."""
    real = _real()
    changed = {k for k, v in CATALOG.items() if real[k] != v}
    assert changed == set(real["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    for k in changed:
        assert real["reduced"][k]["source"] == CATALOG[k] \
            == real["published"][k]
        assert real["reduced"][k]["here"] == real[k]
    assert (real["num_hidden_layers"], real["num_experts"],
            real["vocab_size"]) == (8, 128, 37_984)
    assert real["router_width"] == 512 and real["vocab_size"] * 4 == 151_936
    assert TWIN["twin_of"] == CONFIG
    assert set(CATALOG) <= set(TWIN)
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(changed)
    assert KIND.layer_kinds(real) == [
        [m, "experts"] for m in ["linear"] * 3 + ["full"]] * 2


def test_the_twins_pools():
    """Full layers get K/V pools of heads of 256, linear layers states of
    whole rows of lanes and conv inputs over [q | k | v] by state id."""
    cfg = tiny()
    c = decoding.init_paged_caches(cfg, 4, 24, BS, 96, num_states=9)
    for i, (mixer, _) in enumerate(cfg.layer_kinds):
        if mixer == "linear":
            assert c.kp[i] is None and c.vp[i] is None
            assert c.state_pool[i].shape == (10, 4, 16, 128)
            assert c.conv_pool[i].shape == (10, 3, 2 * 2 * 16 + 4 * 128)
        else:
            assert c.kp[i].shape == c.vp[i].shape == (25, 2, BS, 256)
            assert c.state_pool[i] is None and c.conv_pool[i] is None
    assert c.slot_state.shape == (4,) and c.tail_pool == ()


def test_importing_the_engine_imports_no_qwen_module():
    """models/qwen3_next.py (and what it brings: ops/gated_delta.py,
    models/olmo_hybrid.py) is imported only where a configuration names the
    architecture."""
    import subprocess
    code = ("import sys, ray_tpu.serve.llm, ray_tpu.models.decoding; "
            "print([m for m in sys.modules if 'gated_delta' in m "
            "or 'olmo_hybrid' in m or 'qwen3' in m])")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=os.path.dirname(HERE),
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.stdout.strip() == "[]", out.stdout + out.stderr[-500:]


# -- the share ------------------------------------------------------------------
def _share(first):
    return tiny(config=dict(TWIN, experts_held_first=first))


def test_four_shares_add_up_to_the_uncut_layer():
    """One layer with all 32 experts, and the same layer as its four EP
    shares (experts 8 i .. 8 i + 7 of the same weights): the shares' routed
    parts, with the residual, the mixer and the shared expert counted once,
    are the uncut layer's output; every share counts its absent picks."""
    uncut = tiny(config=dict(TWIN, num_experts=32))
    assert uncut.moe_experts == uncut.router_width == 32
    p = qwen3_next.init_layer(uncut, jax.random.PRNGKey(2), 3)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, uncut.d_model))
    pos = jnp.broadcast_to(jnp.arange(24, dtype=jnp.int32), (2, 24))
    attend = afmoe._attend_plain(uncut, None)
    kind = ("full", "experts")
    want, counts = qwen3_next.layer(uncut, kind, p, x, pos, attend)
    assert int(counts[4]) == 0 and int(counts[1]) == 2 * 24 * 4

    def held(first, scale=1.0):
        mine = {k: v[first:first + 8] * (scale if k == "w_down" else 1.0)
                for k, v in p.items() if k in ("w_gate", "w_up", "w_down")}
        return qwen3_next.layer(_share(first), kind, {**p, **mine}, x, pos,
                                attend)

    once, _ = held(0, scale=0.0)    # the residual, the mixer, the shared one
    total, routed = once, 0
    for first in (0, 8, 16, 24):
        out, c = held(first)
        total = total + (out - once)
        routed += int(c[1])
        assert int(c[1]) + int(c[4]) == 2 * 24 * 4
    assert routed == 2 * 24 * 4
    assert float(jnp.abs(total - want).max()) < 2e-5
    assert float(jnp.abs(once - want).max()) > 0.05


@pytest.mark.parametrize("first", [0, 16])
def test_a_share_is_the_references_share(model, first):
    """The program holding experts `first` .. + 7 against the reference
    told the same: logits of the whole model."""
    cfg = _share(first)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(tokens(40, seed=5))
    want = KIND.reference_logits(KIND.hyper(cfg), params, toks)
    assert KIND.rel_rms(tfm.forward(params, toks[None], cfg)[0], want) < 1e-5
    if first:
        other = KIND.reference_logits(KIND.hyper(model[0]), params, toks)
        assert KIND.rel_rms(other, want) > 4 * LIMIT


def test_padded_rows_are_counted(model):
    """This architecture's layers count the rows the grouped product
    computed: every expert's group padded to whole tiles of 16; the other
    architectures' counts are the five they were."""
    cfg, params = model
    assert qwen3_next.MOE_COUNTS == afmoe.MOE_COUNTS + ("padded_rows",)
    assert afmoe.no_counts().shape == (5,)
    m = jax.random.normal(jax.random.PRNGKey(8), (1, 40, cfg.d_model))
    picks = []
    _, counts = afmoe.experts(cfg, params["layers"][0], m, None,
                              "moe_experts_decode", picks.append,
                              count_padded=True)
    sizes = np.bincount(np.asarray(picks[0]).ravel(), minlength=32)[:8]
    assert int(counts[1]) == sizes.sum() and int(counts[4]) == 160 - sizes.sum()
    assert int(counts[5]) == sum(-(-int(s) // 16) * 16 for s in sizes)
    assert afmoe.experts(cfg, params["layers"][0], m, None,
                         "moe_experts_decode")[1].shape == (5,)


# -- the kernels' shapes the architecture brings ------------------------------
@pytest.mark.parametrize("impl", ["reference", "kernel"])
def test_delta_step_at_thirty_two_whole_heads(impl):
    """`gated_delta_step` at the cell's [32, 128, 128] a sequence (a head a
    row of lanes: no pair masks) against `step_rule`."""
    H, dk, dv = 32, 128, 128
    assert gd.heads_side_by_side(H, dv) == 1
    pool = jax.random.normal(jax.random.PRNGKey(5), gd.pool_shape(4, H, dk,
                                                                  dv))
    ids = jnp.asarray([3, 0, 1], jnp.int32)
    q, k, v, la, beta = _rule_inputs((3,), H, dk, dv, seed=2)
    la, beta = la.at[1].set(0), beta.at[1].set(0)
    o, after = gd.gated_delta_step(pool, ids, q, k, v, la, beta, impl=impl)
    for row, sid in ((0, 3), (2, 1)):
        S, want = gd.step_rule(pool[sid], q[row], k[row], v[row], la[row],
                               beta[row])
        assert float(jnp.abs(after[sid] - S).max()) < 1e-5
        assert float(jnp.abs(o[row] - want).max()) < 1e-5
    for sid in (2, 4):
        np.testing.assert_array_equal(after[sid], pool[sid])


@pytest.mark.parametrize("impl", ["reference", "kernel"])
def test_delta_chunk_at_thirty_two_whole_heads(impl):
    """`gated_delta_chunk`, three rows of 16 of one sequence from zeros and a
    restored one, at [32, 128, 128]: every o and the states left behind are
    the step recurrence's."""
    H, dk, dv = 32, 128, 128
    q, k, v, la, beta = _rule_inputs((2, 48), H, dk, dv, seed=3)
    step_o, step_S = gd.delta_sequence(q, k, v, la, beta, chunk=1)
    mid = gd.delta_sequence(*(x[1:, :32] for x in (q, k, v, la, beta)),
                            chunk=1)[1][0]
    pool = jnp.zeros(gd.pool_shape(3, H, dk, dv), jnp.float32).at[2].set(mid)

    def rows(x):
        return jnp.concatenate([x[0].reshape(3, T, *x.shape[2:]),
                                x[1, 32:].reshape(1, T, *x.shape[2:])])

    src = jnp.asarray([0, -1, -1, 2])
    dst = jnp.asarray([[0, 0], [0, 3], [1, 0], [2, 0]])
    o, after = gd.gated_delta_chunk(pool, src, dst,
                                    *(rows(x) for x in (q, k, v, la, beta)),
                                    impl=impl)
    assert float(jnp.abs(o[:3].reshape(48, H, dv) - step_o[0]).max()) < 3e-5
    assert float(jnp.abs(o[3] - step_o[1, 32:]).max()) < 3e-5
    assert float(jnp.abs(after[1] - step_S[0]).max()) < 3e-5
    assert float(jnp.abs(after[2] - step_S[1]).max()) < 3e-5
    want3 = gd.delta_sequence(*(x[:1, :32] for x in (q, k, v, la, beta)),
                              chunk=1)[1][0]
    assert float(jnp.abs(after[3] - want3).max()) < 3e-5


@pytest.mark.parametrize("impl", ["reference", "kernel"])
def test_paged_attention_at_heads_of_256(impl):
    """`paged_attention` and `prefix_attention` at 16 query / 2 kv heads of
    256 over pools [NB, 2, 16, 256], against the benchmark's plain gather
    (lib/reference.py) and plain causal attention."""
    H, Hkv, D, bs, NB, W = 16, 2, 256, 16, 24, 6
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    kp, vp = (jax.random.normal(k, (NB, Hkv, bs, D), jnp.float32)
              for k in ks[:2])
    tables = jnp.asarray([[1, 2, 3, 4, 5, 6], [7, 8, 9, 0, 0, 0],
                          [10, 11, 0, 0, 0, 0]], jnp.int32)
    ctx = jnp.asarray([90, 33, 17], jnp.int32)
    q = jax.random.normal(ks[2], (3, H, D), jnp.float32)
    got = pa.paged_attention(q, kp, vp, tables, ctx, impl=impl)
    want = reference.paged_attention(q, kp, vp, tables, ctx)
    assert reference.max_abs_err(got, want) < 2e-3
    # a prefill's rows: 32 queries after a prefix of 48 / 16 / 0 positions
    prefix, suffix = jnp.asarray([48, 16, 0]), jnp.asarray([32, 17, 16])
    qs = jax.random.normal(ks[3], (3, 32, H, D), jnp.float32)
    out = pa.prefix_attention(qs, kp, vp, tables, prefix, suffix, impl=impl)
    for n in range(3):
        S = int(prefix[n] + suffix[n])
        rows = [jnp.moveaxis(pool[tables[n]], 1, 0).reshape(Hkv, W * bs, D
                                                            )[:, :S]
                for pool in (kp, vp)]
        qn = jnp.zeros((S, H, D)).at[int(prefix[n]):].set(
            qs[n, :int(suffix[n])])
        full = reference.attention(qn.swapaxes(0, 1)[None], rows[0][None],
                                   rows[1][None])[0].swapaxes(0, 1)
        assert reference.max_abs_err(out[n, :int(suffix[n])],
                                     full[int(prefix[n]):]) < 2e-3


# -- the engine's layers: tiled paged prefill, then paged decode -------------
def _sizes(cfg, max_len=200):
    caches = decoding.init_paged_caches(cfg, 4, 24, BS, max_len, 8)
    return KIND.parity_sizes(caches)


@pytest.mark.parametrize("dtype,impl", [("float32", "reference"),
                                        ("float32", "kernel"),
                                        ("bfloat16", "reference")])
def test_paged_layers_match_reference(dtype, impl):
    """What the benchmark's `correct` runs on the chip, at a toy size: a
    prompt of 12 blocks in calls of rows of 16, its state carried in its
    id and a checkpoint taken 6 blocks before its end; a short request in
    every other slot, all rows of one call; eight decode steps of all
    slots; the last rows again after a hit restored from the checkpoint; the
    reference following the program's picks."""
    cfg = tiny(dtype)
    sizes = _sizes(cfg)
    assert sizes["prompt"] == 192 and sizes["compared"] == 96
    out = KIND.compare(cfg, 7, sizes, attn_impl=impl)
    assert out["route_picks_compared"] == 200 * 4 * 8
    if dtype == "float32":
        for name in ("logits_prefill_err", "logits_decode_err", "state_err",
                     "logits_decode_err_worst_slot"):
            assert out[name] < 3e-5, (name, out)
        assert out["route_mismatch_share"] == 0.0, out
    else:       # bf16 at the toy's width of 128 errs far more than at 2048
        assert out["logits_decode_err"] < 0.2 and out["state_err"] < 0.3, out
    # a checkpoint is a copy: what a hit restores is exact in any precision
    assert out["logits_after_hit_err"] == 0.0, out
    # the rule's and the router's own arithmetic on the program's own inputs
    # is float32's in any precision of the activations
    assert out["state_own_input_err"] < 1e-5, out
    assert out["state_own_input_err_bf16"] > \
        3 * KIND.TOLERANCES["state_own_input_err"], out
    assert out["route_own_input_mismatch_share"] == 0.0, out


@pytest.mark.parametrize("control", ["fp8", "state_bf16"])
def test_controls_are_refused(control):
    """The reference one precision down in the program's place, at the
    toy's width: fp8 on the projections' outputs, q, k, v and the expert
    weights fails every limit; a state kept in bfloat16 reads 0.015 on the
    state here, under its limit (at the cell's widths too, bfloat16
    ACTIVATIONS err more than a bfloat16 state does: PERF.md section 2), and
    is refused by `state_own_input_err`, whose control stands beside it in
    every line of the sound program (`test_paged_layers_match_reference`)."""
    cfg = tiny()
    out = KIND.compare(cfg, 7, _sizes(cfg), control=control)
    if control == "fp8":
        for name in ("state_err", "logits_prefill_err", "logits_decode_err",
                     "route_mismatch_share"):
            assert out[name] > KIND.TOLERANCES[name], (name, out)
    else:
        assert out["state_err"] > 100 * 3e-5, out


def test_bfloat16_scores_are_refused():
    """A program that rounds its routing scores to bfloat16
    (`moe_score_dtype`): `route_own_input_mismatch_share` reads it, with 32
    scores as with 512."""
    cfg = tiny(moe_score_dtype=jnp.bfloat16)
    out = KIND.compare(cfg, 7, _sizes(cfg), attn_impl="reference")
    assert out["route_own_input_mismatch_share"] > \
        KIND.TOLERANCES["route_own_input_mismatch_share"], out


def test_prefill_and_decode_give_the_reference_logits(model):
    """The first token and three decode steps' logits, against ONE pass of
    the reference over the prompt and the tokens the program chose."""
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
    cfg, params = model
    prompts = [tokens(n, seed=30 + n) for n in (40, 17, 33)]
    alone = []
    for i, p in enumerate(prompts):
        dev = Device(cfg, params)
        dev.prefill([(0, dev.table(), p, 0, len(p), 0, 1, {})])
        alone.append(dev.decode([0], steps=2)[:, 0])
    dev = Device(cfg, params)
    dev.prefill([(i, dev.table(), p, 0, len(p), 0, i + 1, {})
                 for i, p in enumerate(prompts)], rows=8)
    got = dev.decode([0, 1, 2], steps=2)
    for i in range(3):
        assert KIND.rel_rms(got[:, i], alone[i]) < 3e-4


@pytest.mark.parametrize("blocks,ckpt", [(1, 1), (2, 2), (3, 3), (4, 4),
                                         (5, 5), (3, 1), (5, 2), (2, 0)])
def test_a_hit_at_a_block_boundary_yields_the_cold_logits(model, blocks,
                                                          ckpt):
    """A checkpoint at every block boundary of a 5-block prompt.  A request
    that shares `blocks` of them and finds a checkpoint there starts from it
    and its K/V blocks; one that finds none there is cut back to the
    checkpoint at block `ckpt` (0: to nothing), shares the blocks before it
    and prefills the rest again: both decode the logits they decode cold."""
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
    assert KIND.rel_rms(dev.decode([1], steps=2), want) > 4 * LIMIT


def test_carried_decode_rows_in_a_fused_pass_are_a_decode_step(model):
    """Slot 0 decodes; its next position rides in the pass that prefills
    slot 1: the same token and the same state as a decode-only step."""
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
        if mixer == "linear":
            assert float(jnp.abs(dev.caches.state_pool[i][1]
                                 - alone.caches.state_pool[i][1]).max()) < 3e-4


# -- the benchmark's names -----------------------------------------------------
def test_the_cell_resolves_its_names():
    loaded, kernels = benchmark_names.resolved(CELL)
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "decode_tokens_per_s", "setup_s"}
    # its rooflines read the delta rule's two kernels and the expert product
    assert kernels == {"gated_delta_step", "gated_delta_chunk",
                       "moe_experts_decode"}
    assert loaded["cell"]["chips"] == 1
    assert loaded["traffic"]["name"] == "agent-sessions"
    sv = loaded["config"]["serve"]
    assert (sv["num_slots"], sv["num_states"]) == (64, 256)
    shapes = {"slots": 64, "live_context": 5e5}
    for fn in ("gated_delta_step", "gated_delta_chunk", "moe_experts_decode"):
        flops, bytes_ = loaded["cost_fns"][fn](loaded["config"], shapes)
        assert flops > 0 and bytes_ > 0
    # a decode step's state traffic: 64 slots x 2 x 2,097,152 B and change
    _, b = loaded["cost_fns"]["gated_delta_step"](loaded["config"], shapes)
    assert 64 * 2 * 2_097_152 < b < 64 * 2 * 2_097_152 * 1.02
    # ... and its experts': 128 (1 - (511/512)^320) experts of 6,291,456 B
    _, b = loaded["cost_fns"]["moe_experts_decode"](loaded["config"], shapes)
    touched = 128 * (1 - (511 / 512) ** 320)
    assert 59 < touched < 60
    assert touched * 6_291_456 < b < touched * 6_291_456 * 1.01
    # (Olmo-Hybrid's cell reads the same two delta kernels through its own
    # kind's cost functions; no other cell does)
    for other in ("serve-axk1-agent-sessions", "serve-batch-saturated"):
        assert not benchmark_names.resolved(other)[1] & {
            "gated_delta_step", "gated_delta_chunk"}
    assert spec.load_cell("serve-olmoh-agent-sessions")["cost_fns"][
        "gated_delta_step"] is not loaded["cost_fns"]["gated_delta_step"]
    bench = spec.load_benchmark()
    assert len(bench["workloads"]) == 11 and len(bench["configs"]) == 8
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
