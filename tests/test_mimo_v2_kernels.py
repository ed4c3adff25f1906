"""arch "mimo_v2": the kernels and what the benchmark's `correct` compares, on
the toy twin of tests/mimo_v2_twin.py (tests/test_mimo_v2.py has the model,
the configuration, the share and the engine's device functions): both ring
kernels against plain masked attention, the paged kernels at keys wider than
values, the comparison of kinds/sink-window-moe.py as the chip runs it (the
fused pass with a decode step riding it, a hit restored from a checkpoint),
and its limits shown to refuse eleven wrong programs and both controls."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import benchmark_names
from benchmarks.lib import spec
from ray_tpu.models import decoding, mimo_v2
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops import window_ring as wr

from mimo_v2_twin import BS, KIND, LIMIT, model, tiny, tokens  # noqa: F401

CELL = "serve-mimo-agent-sessions"
WRONG = ["no_sink", "sink_on_full", "window_le", "rope_on_all", "one_theta",
         "kv_heads_as_full", "no_value_scale", "softmax_routing", "no_renorm",
         "bias_in_weights", "shared_expert"]


# -- the ring kernels against plain masked attention ----------------------------
def _plain_window(q, k, v, sink, W):
    """q [S, H, dk], k [S, Hkv, dk], v [S, Hkv, dv] float32 -> [S, H, dv]:
    every key against every query under the mask, the sink one more column."""
    S, H, dk = q.shape
    hkv = k.shape[1]
    qg = q.reshape(S, hkv, H // hkv, dk)
    s = jnp.einsum("qhgd,khd->hgqk", qg, k, precision="highest") / math.sqrt(
        dk)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    s = jnp.where((j <= i) & (i - j < W), s, -jnp.inf)
    b = sink.reshape(hkv, H // hkv, 1, 1)
    m = jnp.maximum(b, s.max(-1, keepdims=True))
    e = jnp.exp(s - m)
    w = e / (jnp.exp(b - m) + e.sum(-1, keepdims=True))
    return jnp.einsum("hgqk,khd->qhgd", w, v, precision="highest").reshape(
        S, H, -1)


@pytest.mark.parametrize("impl", ["reference", "kernel"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ring_kernels_are_plain_windowed_attention(impl, dtype):
    """`window_ring_chunk` (a prompt of 70 in rows of 16 over two calls, a
    checkpoint after the second row, a partly live last row) and
    `window_ring_step` (40 steps on: the ring of 32 wraps; beside it a
    sequence restored from the checkpoint, and a slot that is not live) at
    keys of 192 in 256 lanes and values of 128: every output is plain masked
    attention with the sink over the whole sequence."""
    W, C, H, hkv, dk, dv = 32, 16, 8, 2, 192, 128
    S, steps = 70, 40
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (S + steps, H, dk), dtype)
    k = jax.random.normal(ks[1], (S + steps, hkv, dk), dtype)
    v = jax.random.normal(ks[2], (S + steps, hkv, dv), dtype)
    sink = jax.random.normal(ks[3], (H,), jnp.float32)
    want = _plain_window(*(x.astype(jnp.float32) for x in (q, k, v)), sink, W)
    shk, shv = wr.ring_shapes(4, hkv, W, dk, dv)
    assert shk == (5, 2, 32, 256) and shv == (5, 2, 32, 128)
    rk, rv = jnp.zeros(shk, dtype), jnp.zeros(shv, dtype)

    def rows(a, lo, hi):
        return a[lo * C:hi * C].reshape(hi - lo, C, *a.shape[1:])

    o1, rk, rv = wr.window_ring_chunk(
        rk, rv, jnp.asarray([0, -1, -1]), jnp.asarray([[0, 0], [0, 3], [1, 0]]),
        jnp.asarray([0, 16, 32]), jnp.asarray([16, 16, 16]),
        rows(q, 0, 3), rows(k, 0, 3), rows(v, 0, 3), sink, impl=impl)
    o2, rk, rv = wr.window_ring_chunk(
        rk, rv, jnp.asarray([1, -1, -1]), jnp.asarray([[0, 0], [1, 2], [0, 0]]),
        jnp.asarray([48, 64, 0]), jnp.asarray([16, 6, 0]),
        rows(q, 3, 6), rows(k, 3, 6), rows(v, 3, 6), sink, impl=impl)
    got = jnp.concatenate([o1.reshape(48, H, dv), o2.reshape(48, H, dv)[:22]])
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert float(jnp.abs(got.astype(jnp.float32) - want[:S]).max()) < tol
    # the checkpoint (id 3: after 32 positions) is a copy a sequence can
    # start from; id 2 holds what id 1 holds
    np.testing.assert_array_equal(rk[1], rk[2])
    rk, rv = rk.at[4].set(rk[3]), rv.at[4].set(rv[3])
    untouched = rk[3]
    for t in range(steps):
        at = jnp.asarray([S + t, 32 + t, 5])
        o, rk, rv = wr.window_ring_step(
            rk, rv, jnp.asarray([1, 4, 0]), at, q[at], k[at], v[at], sink,
            impl=impl)
        for row in (0, 1):
            assert float(jnp.abs(o[row].astype(jnp.float32)
                                 - want[int(at[row])]).max()) < tol
    np.testing.assert_array_equal(rk[3], untouched)
    with pytest.raises(ValueError, match="do not divide"):
        wr.window_ring_chunk(rk, rv, jnp.zeros((1,)), jnp.zeros((1, 2)),
                             jnp.zeros((1,)), jnp.zeros((1,)),
                             q[:12][None], k[:12][None], v[:12][None], sink,
                             impl=impl)


@pytest.mark.parametrize("impl", ["reference", "kernel"])
def test_paged_attention_at_keys_of_192_and_values_of_128(impl):
    """`paged_attention` (with and without a shared prefix) and
    `prefix_attention` at 16 query heads a kv head, keys of 192 in pools of
    256 lanes beside values of 128, against the benchmark's plain gather
    (kinds/sink-window-moe.py) and plain causal attention."""
    H, Hkv, dk, dv, bs, NB, W = 32, 2, 192, 128, 16, 24, 6
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    kp = jnp.pad(jax.random.normal(ks[0], (NB, Hkv, bs, dk), jnp.float32),
                 ((0, 0),) * 3 + ((0, 64),))
    vp = jax.random.normal(ks[1], (NB, Hkv, bs, dv), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4, 5, 6], [7, 8, 9, 0, 0, 0],
                          [10, 11, 0, 0, 0, 0]], jnp.int32)
    ctx = jnp.asarray([90, 33, 17], jnp.int32)
    q = jax.random.normal(ks[2], (3, H, dk), jnp.float32)
    got = pa.paged_attention(q, kp, vp, tables, ctx, impl=impl)
    want = KIND.reference_paged_attention(q, kp, vp, tables, ctx)
    assert got.shape == (3, H, dv)
    assert float(jnp.abs(got - want).max()) < 2e-3
    # a prefill's rows: 32 queries after a prefix of 48 / 16 / 0 positions
    prefix, suffix = jnp.asarray([48, 16, 0]), jnp.asarray([32, 17, 16])
    qs = jax.random.normal(ks[3], (3, 32, H, dk), jnp.float32)
    out = pa.prefix_attention(qs, kp, vp, tables, prefix, suffix, impl=impl)
    assert out.shape == (3, 32, H, dv)
    for n in range(3):
        S = int(prefix[n] + suffix[n])
        rows = [jnp.moveaxis(pool[tables[n]], 1, 0).reshape(
            Hkv, W * bs, -1)[:, :S] for pool in (kp, vp)]
        qn = jnp.zeros((S, H, dk)).at[int(prefix[n]):].set(
            qs[n, :int(suffix[n])])
        full = mimo_v2._attend_plain(None)(
            qn[None], rows[0][None, :, :, :dk].swapaxes(1, 2),
            rows[1][None].swapaxes(1, 2))[0]
        assert float(jnp.abs(out[n, :int(suffix[n])]
                             - full[int(prefix[n]):]).max()) < 2e-3
    if impl == "kernel":    # (a key pool narrower than the keys)
        with pytest.raises(ValueError, match="pools must be"):
            pa.paged_attention(q, kp[..., :128], vp, tables, ctx, impl=impl)


# -- the engine's layers: tiled paged prefill, then paged decode -------------
def _sizes(cfg, max_len=200):
    caches = decoding.init_paged_caches(cfg, 4, 24, BS, max_len, 8)
    return KIND.parity_sizes(caches)


@pytest.mark.parametrize("dtype,impl", [("float32", "reference"),
                                        ("float32", "kernel"),
                                        ("bfloat16", "reference")])
def test_paged_layers_match_reference(dtype, impl):
    """What the benchmark's `correct` runs on the chip, at a toy size: a
    prompt of 12 blocks in fused passes of rows of 16, its rings carried in
    its id and a checkpoint taken 6 blocks before its end; a short request in
    every other slot, all rows of one pass; eight decode steps of all slots,
    the last of them riding the pass that answers the last rows again after
    a hit restored from the checkpoint; the reference following the
    program's picks."""
    cfg = tiny(dtype)
    sizes = _sizes(cfg)
    assert sizes["prompt"] == 192 and sizes["compared"] == 96
    out = KIND.compare(cfg, 7, sizes, attn_impl=impl)
    assert out["route_picks_compared"] == 200 * 4 * 3
    if dtype == "float32":
        for name in ("logits_prefill_err", "logits_decode_err", "ring_err",
                     "logits_decode_err_worst_slot"):
            assert out[name] < 3e-5, (name, out)
        assert out["route_mismatch_share"] == 0.0, out
    else:       # bf16 at the toy's width of 64 errs far more than at 4096
        assert out["logits_decode_err"] < 0.2 and out["ring_err"] < 0.05, out
    # a checkpoint is a copy: what a hit restores is exact in any precision
    assert out["logits_after_hit_err"] == 0.0, out
    # the router is float32 whatever the activations: on its own input the
    # reference's picks, and their weights to rounding
    assert out["route_own_input_mismatch_share"] == 0.0, out
    assert out["route_own_input_weight_err"] < 1e-6, out


@pytest.mark.parametrize("control", KIND.CONTROLS)
def test_controls_are_refused(control):
    """The reference one precision down (fp8 on the projections' outputs,
    q, k, v and the feed-forward weights) or without the sink, in the
    program's place at the toy's width: the decoded logits' limit refuses
    both (the short requests' windows are not yet full, so the sink holds a
    large share of their softmax), the rings' and the prompt's logits' the
    first (a ring holds k and v, which the sink never touches: what reads
    there without it is the residual stream it moved)."""
    cfg = tiny()
    out = KIND.compare(cfg, 7, _sizes(cfg), control=control)
    assert out["logits_decode_err"] > KIND.TOLERANCES["logits_decode_err"], out
    if control == "fp8":
        for name in ("logits_prefill_err", "ring_err"):
            assert out[name] > KIND.TOLERANCES[name], (name, out)
    # neither touches the router: on its own input it is the reference's
    assert out["route_own_input_weight_err"] == 0.0, out


# -- the limits of `correct` against wrong programs ---------------------------
@pytest.mark.parametrize("wrong", WRONG)
def test_limits_refuse_a_wrong_program(model, wrong):
    """Each fault in the reference's place is refused by a limit of
    `correct`: ten of them by the logits' (the narrowest, one position more
    in the window, reads 0.05 here).  The selection bias in the weights moves
    the logits of a 1/16 share by 0.004 (the bias is 0.02 beside scores of
    ~0.9, and fifteen sixteenths of the experts' output are absent): what
    refuses it is `route_own_input_weight_err`, the weights the program's
    router gives its picks against the reference's on the same input, in
    the comparison the chip runs."""
    cfg, params = model
    if wrong == "bias_in_weights":
        out = KIND.compare(cfg, 7, _sizes(cfg), control=wrong)
        assert out["route_own_input_mismatch_share"] == 0.0, out
        assert out["route_own_input_weight_err"] > 10 * KIND.TOLERANCES[
            "route_own_input_weight_err"], out
        return
    hp, toks = KIND.hyper(cfg), jnp.asarray(tokens(90))
    want = KIND.reference_logits(hp, params, toks)
    bad = KIND.reference_logits(hp, params, toks, wrong=wrong)
    assert not KIND.rel_rms(bad, want) < LIMIT


# -- the benchmark's names -----------------------------------------------------
def test_the_cell_resolves_its_names():
    loaded, kernels = benchmark_names.resolved(CELL)
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "decode_tokens_per_s", "setup_s"}
    # its rooflines read the ring's two kernels and the expert product
    assert kernels == {"window_ring_step", "window_ring_chunk",
                       "moe_experts_decode"}
    assert loaded["cell"]["chips"] == 1
    assert loaded["traffic"]["name"] == "agent-sessions"
    sv = loaded["config"]["serve"]
    assert (sv["num_slots"], sv["num_states"]) == (64, 256)
    shapes = {"slots": 64, "live_context": 5e5}
    for fn in ("window_ring_step", "window_ring_chunk", "moe_experts_decode"):
        flops, bytes_ = loaded["cost_fns"][fn](loaded["config"], shapes)
        assert flops > 0 and bytes_ > 0
    # a decode step's ring traffic: 64 slots x 655,360 B read + 5,120 written
    # and change; 64 x 128 x 320 x 2 operations a query head
    f, b = loaded["cost_fns"]["window_ring_step"](loaded["config"], shapes)
    assert 64 * 660_480 < b < 64 * 660_480 * 1.1
    assert f == 64 * 64 * 128 * 320 * 2
    # ... and its experts': 16 (1 - (255/256)^256) experts of 50,331,648 B
    _, b = loaded["cost_fns"]["moe_experts_decode"](loaded["config"], shapes)
    touched = 16 * (1 - (255 / 256) ** 256)
    assert 10 < touched < 10.2
    assert touched * 50_331_648 < b < touched * 50_331_648 * 1.01
    for other in ("serve-qw3n-agent-sessions", "serve-agent-sessions",
                  "serve-batch-saturated"):
        assert not benchmark_names.resolved(other)[1] & {
            "window_ring_step", "window_ring_chunk"}
    bench = spec.load_benchmark()
    assert len(bench["workloads"]) == 11 and len(bench["configs"]) == 8
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert len(bench["per_layer"]) <= 128
