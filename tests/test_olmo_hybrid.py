"""arch "olmo_hybrid" (models/olmo_hybrid.py) against its plain float32
reference (benchmarks/kinds/gated-delta.py: the STEP recurrence under a scan
over positions), on a toy twin of the benchmark's configuration
(tests/data/olmo_hybrid_tiny.json: two periods of [linear, linear, linear,
full], heads in pairs of 48 lanes): `transformer.forward` in chunks of 16
and 64, the paged layers the engine's dispatches are made of, the state
allocator, both kernels in the Pallas interpreter, and the limits of the
benchmark's `correct` shown to refuse six wrong programs and both controls
(the engine end to end: tests/test_olmo_hybrid_engine.py, on the same twin,
tests/olmo_hybrid_twin.py).  Logits are compared, not tokens; a small model
on the CPU."""

import json
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import benchmark_names
from benchmarks.lib import spec
from ray_tpu.models import decoding, olmo_hybrid
from ray_tpu.models import transformer as tfm
from ray_tpu.ops import gated_delta as gd
from ray_tpu.serve import llm

from olmo_hybrid_twin import (BS, HERE, KIND, LIMIT, T, TWIN,  # noqa: F401
                              model, tiny, tokens)


def _rule_inputs(shape, H=6, dk=12, dv=24, seed=0):
    """q, k L2-normed, v, ln alpha with alpha down to 0.2, beta up to 2."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(key):
        x = jax.random.normal(key, shape + (H, dk))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return (unit(ks[0]) * dk ** -0.5, unit(ks[1]),
            jax.random.normal(ks[2], shape + (H, dv)),
            jnp.log(jax.random.uniform(ks[3], shape + (H,), minval=0.2,
                                       maxval=0.999)),
            jax.random.uniform(ks[4], shape + (H,), minval=0.0, maxval=2.0))


# -- the rule: chunk form = step form -----------------------------------------
def test_reference_step_by_hand():
    """One head, dk = dv = 1, k = q = 1: S' = a S; u = b (v - S');
    S = S' + u; o = S."""
    S = jnp.full((1, 1, 1), 2.0)
    S1, o = KIND.reference_step(S, jnp.ones((1, 1)), jnp.ones((1, 1)),
                                jnp.full((1, 1), 5.0), jnp.asarray([0.5]),
                                jnp.asarray([1.5]))
    assert float(S1[0, 0, 0]) == pytest.approx(1.0 + 1.5 * (5.0 - 1.0))
    assert float(o[0, 0]) == pytest.approx(7.0)


@pytest.mark.parametrize("chunk", [16, 64])
def test_chunk_form_is_the_step_form(chunk):
    q, k, v, la, beta = _rule_inputs((2, 150))
    assert float(beta.max()) > 1.5 and float(jnp.exp(la).min()) < 0.25
    want_o, want_S = gd.delta_sequence(q, k, v, la, beta, chunk=1)
    o, S = gd.delta_sequence(q, k, v, la, beta, chunk=chunk)
    assert float(jnp.abs(o - want_o).max()) < 3e-5
    assert float(jnp.abs(S - want_S).max()) < 3e-5


@pytest.mark.parametrize("chunk", [1, 16, 64])
def test_forward_matches_reference(model, chunk):
    cfg, params = model
    toks = jnp.asarray(tokens(70))
    h = olmo_hybrid.forward_hidden(params, toks[None], cfg, chunk=chunk)
    got = jnp.einsum("sd,dv->sv", h[0], params["lm_head"])
    want = KIND.reference_logits(KIND.hyper(cfg), params, toks)
    assert KIND.rel_rms(got, want) < 3e-5
    if chunk == 64:
        assert KIND.rel_rms(tfm.forward(params, toks[None], cfg)[0],
                            want) < 3e-5


@pytest.mark.parametrize("wrong", ["no_decay", "beta_without_2", "no_l2",
                                   "taps_shifted", "rope_on_full",
                                   "pre_norm"])
def test_limits_refuse_a_wrong_program(model, wrong):
    cfg, params = model
    hp, toks = KIND.hyper(cfg), jnp.asarray(tokens(70))
    want = KIND.reference_logits(hp, params, toks)
    bad = KIND.reference_logits(hp, params, toks, wrong=wrong)
    # (without the L2 norm the recurrence's eigenvalues pass 1: not a number)
    assert not KIND.rel_rms(bad, want) < 8 * LIMIT


def test_params_are_the_files(model):
    """The tree the program makes has the parameters the kind counts, at
    the toy's sizes and at the benchmark's (shapes only); a layer can be
    made alone; the state's arithmetic; no training path."""
    cfg, params = model
    assert tfm.num_params(params) == KIND.param_counts(TWIN)["total"]
    layer_key = jax.random.split(jax.random.PRNGKey(0), 8)[0]
    for name, w in olmo_hybrid.init_layer(cfg, layer_key, 5).items():
        np.testing.assert_array_equal(w, params["layers"][5][name])
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "olmo-hybrid-7b-l12.json")) as f:
        real = json.load(f)
    big = tfm.TransformerConfig(**{
        **KIND.transformer_kwargs(real, max_seq=64, param_dtype="bfloat16"),
        "dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16})
    assert big.rope_theta is None and big.norm_after_branch
    shapes = jax.eval_shape(lambda k: tfm.init_params(big, k),
                            jax.random.PRNGKey(0))
    assert tfm.num_params(shapes) == KIND.param_counts(real)["total"] \
        == 3_268_268_508
    assert KIND.kv_bytes_per_token(real) == 46_080
    assert KIND.state_bytes_per_sequence(real) == 20_528_640
    assert gd.pool_shape(160, 30, 96, 192) == (161, 15, 96, 384)
    assert math.prod(gd.pool_shape(0, 30, 96, 192)) * 4 == 2_211_840
    with pytest.raises(NotImplementedError, match="no training path"):
        tfm.loss_fn(params, jnp.asarray(tokens(16))[None], cfg)
    with pytest.raises(ValueError, match="linear|full"):
        tfm.init_params(tiny(layer_kinds=[["conv", "dense"]] * 8),
                        jax.random.PRNGKey(0))


def test_the_twins_pools():
    """Full layers get K/V pools, linear layers none: states and conv
    inputs by state id instead, two heads side by side; no other
    architecture's caches grow a field."""
    cfg = tiny()
    c = decoding.init_paged_caches(cfg, 4, 24, BS, 96, num_states=9)
    for i, (mixer, _) in enumerate(cfg.layer_kinds):
        if mixer == "linear":
            assert c.kp[i] is None and c.vp[i] is None
            assert c.state_pool[i].shape == (10, 3, 12, 48)
            assert c.state_pool[i].dtype == jnp.float32
            assert c.conv_pool[i].shape == (10, 3, 6 * 48)
        else:
            assert c.kp[i].shape == c.vp[i].shape == (25, 3, BS, 64)
            assert c.state_pool[i] is None and c.conv_pool[i] is None
    assert c.slot_state.shape == (4,) and c.tail_pool == ()
    other = decoding.init_paged_caches(tfm.TransformerConfig(), 2, 8, BS, 64)
    assert other.state_pool == () and other.conv_pool == () \
        and other.slot_state is None


def test_importing_the_engine_imports_no_delta_module():
    """ops/gated_delta.py and models/olmo_hybrid.py are imported only where
    a configuration names the architecture."""
    import subprocess
    code = ("import sys, ray_tpu.serve.llm, ray_tpu.models.decoding; "
            "print([m for m in sys.modules if 'gated_delta' in m "
            "or 'olmo_hybrid' in m])")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=os.path.dirname(HERE),
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.stdout.strip() == "[]", out.stdout + out.stderr[-500:]


# -- the kernels in the Pallas interpreter ------------------------------------
def test_step_kernel_in_the_interpreter():
    pool = jax.random.normal(jax.random.PRNGKey(5), gd.pool_shape(6, 6, 12,
                                                                  24))
    ids = jnp.asarray([3, 0, 5], jnp.int32)
    q, k, v, la, beta = _rule_inputs((3,), seed=2)
    la, beta = la.at[1].set(0), beta.at[1].set(0)
    want_o, want = gd.gated_delta_step(pool, ids, q, k, v, la, beta,
                                       impl="reference")
    o, after = gd.gated_delta_step(pool, ids, q, k, v, la, beta,
                                   impl="kernel")
    live = jnp.asarray([0, 2])
    assert float(jnp.abs(o - want_o)[live].max()) < 1e-6
    assert float(jnp.abs(after - want)[1:].max()) < 1e-6
    # against the rule itself, and the ids no sequence named untouched
    S, o_rule = gd.step_rule(gd.from_pool(pool[3], 2), q[0], k[0], v[0],
                             la[0], beta[0])
    assert float(jnp.abs(gd.from_pool(after[3], 2) - S).max()) < 1e-6
    assert float(jnp.abs(o[0] - o_rule).max()) < 1e-6
    for i in (1, 2, 4, 6):
        np.testing.assert_array_equal(after[i], pool[i])


@pytest.mark.parametrize("impl", ["reference", "kernel"])
def test_chunk_rows_carry_restore_and_checkpoint(impl):
    """Five rows: request A's three (from zeros; a checkpoint after its
    second row, its end in two ids), request B's two (restored from id 2, a
    partial last row): every o and every state the step recurrence's."""
    H, dk, dv, g = 6, 12, 24, 2
    q, k, v, la, beta = _rule_inputs((2, 64), seed=3)
    step_o, _ = gd.delta_sequence(q, k, v, la, beta, chunk=1)

    def state_after(b, n):
        return gd.delta_sequence(*(x[b:b + 1, :n] for x in (q, k, v, la,
                                                            beta)),
                                 chunk=1)[1][0]

    pool = jax.random.normal(jax.random.PRNGKey(6), gd.pool_shape(6, H, dk,
                                                                  dv))
    pool = pool.at[2].set(gd.to_pool(state_after(1, 32), g))

    def rows(x):
        return jnp.concatenate([x[0, :48].reshape(3, T, *x.shape[2:]),
                                x[1, 32:64].reshape(2, T, *x.shape[2:])])

    live = jnp.ones((5, T), bool).at[4, 10:].set(False)[..., None]
    args = [rows(x) for x in (q, k, v, la, beta)]
    args[3], args[4] = (jnp.where(live, a, 0) for a in args[3:])
    src = jnp.asarray([0, -1, -1, 2, -1])
    dst = jnp.asarray([[0, 0], [1, 0], [4, 6], [0, 0], [2, 0]])
    o, after = gd.gated_delta_chunk(pool, src, dst, *args, impl=impl)
    assert float(jnp.abs(o[:3].reshape(48, H, dv) - step_o[0, :48]).max()) \
        < 1e-5
    assert float(jnp.abs(o[3:].reshape(32, H, dv)[:26]
                         - step_o[1, 32:58]).max()) < 3e-5
    for sid, want in ((1, state_after(0, 32)), (4, state_after(0, 48)),
                      (6, state_after(0, 48)), (2, state_after(1, 58))):
        assert float(jnp.abs(gd.from_pool(after[sid], g) - want).max()) \
            < 3e-5, sid
    np.testing.assert_array_equal(after[3], pool[3])
    np.testing.assert_array_equal(after[5], pool[5])


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
    slots; the last rows again after a hit restored from the checkpoint."""
    cfg = tiny(dtype)
    sizes = _sizes(cfg)
    assert sizes["prompt"] == 192 and sizes["compared"] == 96
    out = KIND.compare(cfg, 7, sizes, attn_impl=impl)
    if dtype == "float32":
        for name in ("logits_prefill_err", "logits_decode_err", "state_err",
                     "logits_decode_err_worst_slot"):
            assert out[name] < 3e-5, (name, out)
    else:       # bf16 at the toy's width of 192 errs far more than at 3840
        assert out["logits_decode_err"] < 0.2 and out["state_err"] < 0.3, out
    # a checkpoint is a copy: what a hit restores is exact in any precision
    assert out["logits_after_hit_err"] == 0.0, out
    # the rule's own arithmetic on the program's own inputs is float32's in
    # any precision of the activations; a state kept in bfloat16 would read
    # three orders more
    assert out["state_own_input_err"] < 1e-5, out
    assert out["state_own_input_err_bf16"] > \
        3 * KIND.TOLERANCES["state_own_input_err"], out


@pytest.mark.parametrize("control", ["fp8", "state_bf16"])
def test_controls_are_refused(control):
    """The reference one precision down in the program's place, at the
    toy's width: fp8 on the projections' outputs and q, k, v fails every
    limit; a state kept in bfloat16 fails the state's and the decode's here
    (at the cell's widths bfloat16 ACTIVATIONS err more than a bfloat16
    state does, PERF.md section 2: `state_own_input_err` is what refuses
    such a state there)."""
    cfg = tiny()
    out = KIND.compare(cfg, 7, _sizes(cfg), control=control)
    assert out["state_err"] > KIND.TOLERANCES["state_err"], out
    assert out["logits_decode_err"] > KIND.TOLERANCES["logits_decode_err"]
    if control == "fp8":
        assert out["logits_prefill_err"] > 2 * LIMIT, out


class Device:
    """The engine's own device functions, driven as its host loop drives
    them: requests in slots with tables and state ids handed out in order,
    their prompts as rows of 16 in fused calls, decode steps."""

    def __init__(self, cfg, params, slots=3, blocks=64, width=8, states=12):
        self.cfg, self.params, self.width = cfg, params, width
        self.caches = decoding.init_paged_caches(cfg, slots, blocks, BS,
                                                 width * BS, states)
        self.next_block = 1

    def table(self, shared=()):
        own = self.width - len(shared)
        t = list(shared) + list(range(self.next_block,
                                      self.next_block + own))
        self.next_block += own
        return t

    def prefill(self, parts, rows=None, carried=None):
        """parts: [(slot, table, prompt, done, take, state_from, own id,
        {rows into this part: checkpoint id})] -> the logits-argmax first
        tokens of the requests whose prompt a part ends, by slot."""
        packed, ends = [], {}
        for slot, table, prompt, done, take, src, own, ckpts in parts:
            first = len(packed)
            for start in range(done, done + take, T):
                n = min(T, done + take - start)
                closes = start + n == len(prompt)
                if closes:
                    ends[slot] = len(packed)
                r = len(packed) - first
                packed.append([prompt[start:start + n] + [0] * (T - n), n,
                               start, slot, True, closes, table,
                               src if r == 0 else -1,
                               [0, ckpts.get(r + 1, 0)]])
            packed[-1][8][0] = own
        while len(packed) < (rows or len(packed)):
            packed.append([[0] * T, 0, 0, 0, False, False,
                           [0] * self.width, -1, [0, 0]])
        cols = [jnp.asarray(c) for c in zip(*packed)]
        self.caches, first, _, step_tok = decoding._paged_prefill_core(
            self.params, self.caches, *cols[:7], self.cfg, "reference",
            carried=carried, states=(cols[7], cols[8]))
        return {slot: int(first[row]) for slot, row in ends.items()}, step_tok

    def decode(self, slots, steps=3):
        """-> logits [steps, len(slots), V] of the slots' next positions."""
        active = jnp.zeros(self.caches.lengths.shape, bool).at[
            jnp.asarray(slots)].set(True)
        out = []
        for _ in range(steps):
            self.caches, _, logits, _ = decoding._unrolled_decode_core(
                self.params, self.caches, active, self.cfg, "reference")
            out.append(logits[jnp.asarray(slots)])
        return jnp.stack(out)


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
    """The state is carried from dispatch to dispatch in the request's own
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
                 for i, p in enumerate(prompts)], rows=8)
    got = dev.decode([0, 1, 2], steps=2)
    for i in range(3):
        assert KIND.rel_rms(got[:, i], alone[i]) < 3e-4


@pytest.mark.parametrize("blocks", [1, 2, 3, 4, 5])
def test_a_hit_restored_from_a_checkpoint_yields_the_cold_logits(model,
                                                                 blocks):
    """A checkpoint at every block boundary of a 5-block prompt; a request
    that shares `blocks` of them starts from that checkpoint and its K/V
    blocks, and decodes the logits it decodes cold."""
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
    dev.prefill([(1, dev.table(t0[:blocks]), prompt, blocks * BS,
                  len(prompt) - blocks * BS, 3 + blocks, 2, {})])
    assert KIND.rel_rms(dev.decode([1], steps=2), want) < 3e-4


def test_a_hit_from_another_checkpoint_is_refused(model):
    """The same blocks shared, the state restored from the checkpoint one
    block earlier: far over the limit (the comparison sees a wrong
    restore)."""
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
    assert KIND.rel_rms(dev.decode([1], steps=2), want) > 8 * LIMIT


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


# -- the state allocator and the radix cache's checkpoints --------------------
def test_state_allocator_holds_limbo_and_lru():
    a = llm.StateAllocator(4)
    ids = [a.alloc() for _ in range(4)]
    assert ids == [1, 2, 3, 4] and a.alloc() is None and a.used() == 4
    nodes = [llm._RadixNode() for _ in range(3)]
    for sid, node in zip(ids[:3], nodes):
        a.adopt(sid, node)
    assert a.checkpoints() == 3 and nodes[0].state == 1
    a.touch(1)                      # 2 is now the least recently used
    a.hold(2)
    assert a.evict_lru() and nodes[2].state is None     # 3 went, not 2
    assert nodes[1].state == 2 and a.alloc() == 3
    a.release(2)
    assert a.evict_lru() and nodes[1].state is None
    # an id given back `later` is not handed out before settle()
    a.free(4, later=True)
    assert a.used() == 2 and a.alloc() == 2 and a.alloc() is None
    a.settle()
    assert a.alloc() == 4
    # a dear checkpoint (a thousand blocks of prefill to make again) goes
    # only when no cheap one is left, however long it has not been used
    b = llm.StateAllocator(3, dear_blocks=128)
    kept = [llm._RadixNode() for _ in range(3)]
    for node, cost in zip(kept, (1024, 2, 3)):
        b.adopt(b.alloc(), node, cost)
    assert b.evict_lru() and kept[1].state is None and kept[0].state == 1
    assert b.evict_lru() and kept[2].state is None and kept[0].state == 1
    assert b.evict_lru() and kept[0].state is None and not b.evict_lru()


@pytest.mark.parametrize("restores", [1, 5],
                         ids=["restored-once", "restored-many-times"])
def test_superseded_checkpoints_go_first(restores):
    """A system prompt (dear) under which three conversations of three turns
    branch, each turn restoring the checkpoint before it and leaving its own
    two blocks deeper.  `evict_lru` takes the turns that a deeper checkpoint
    has superseded, oldest first, then the conversations' newest in LRU
    order, then the dear ones, superseded first; never a held one; and the
    system prompt's, where the tree branches, is no superseded one however
    often it was restored (PR 47's first rule, "a checkpoint restored once
    goes first", threw it out)."""
    bs = 2
    tree, blocks = llm.RadixCache(bs), llm.BlockAllocator(256)
    a = llm.StateAllocator(16, dear_blocks=4)
    label = {}

    def leave(prompt, base, name):
        depth, sid = len(prompt) // bs, a.alloc()
        left = {depth: sid}
        tree.insert(prompt, blocks.alloc(depth), blocks, left, a, base)
        assert not left
        label[sid] = name
        return sid

    system = list(range(12))        # 6 blocks to make again: dear
    newest = [leave(system, 0, "system")] * 3
    for turn in range(3):
        for conv in range(3):
            if turn or conv < restores:
                a.touch(newest[conv])       # the restore
            history = system + [100 * (conv + 1) + i
                                for i in range(4 * (turn + 1))]
            # conversation 2 fell back to nothing twice: dear checkpoints
            base = 0 if conv == 2 and turn != 1 else 4 + 2 * turn
            newest[conv] = leave(history, base, f"c{conv}.t{turn}")
    assert label[1] == "system"
    for _ in range(restores - 3):   # further conversations start from it
        a.touch(1)
    assert not a.superseded(1)
    assert [a.superseded(s) for s in newest] == [False] * 3
    order = [label[s] for s in a._ckpts]
    held = next(s for s, n in label.items() if n == "c0.t0")
    a.hold(held)
    classes = [{"c1.t0", "c0.t1", "c1.t1", "c2.t1"},     # cheap, superseded
               {"c0.t2", "c1.t2"},                       # cheap, newest
               {"c2.t0"},                                # dear, superseded
               {"system", "c2.t2"}]                      # dear
    want = [n for cls in classes for n in order if n in cls]
    went = []
    while True:
        before = set(a._ckpts)
        if not a.evict_lru():
            break
        (gone,) = before - set(a._ckpts)
        went.append(label[gone])
    assert went == want
    assert a.superseded_evictions == 5 and list(a._ckpts) == [held]
    a.release(held)
    assert a.evict_lru() and a.superseded_evictions == 5 and a.used() == 0


def test_branches_at_reads_the_tree():
    tree, alloc = llm.RadixCache(2), llm.BlockAllocator(16)
    a = [1, 2, 3, 4, 5, 6]
    tree.insert(a, alloc.alloc(3), alloc)
    assert not tree.branches_at(a, 2)           # a's own child, alone
    assert not tree.branches_at(a + [7, 8], 3)  # a leaf: the path lengthens
    assert tree.branches_at(a[:4] + [9, 9], 2)  # a second child
    assert tree.branches_at([1, 2, 9, 9], 1)
    assert not tree.branches_at([8, 8, 9, 9], 1)    # no such path (evicted)


def test_match_is_cut_back_to_the_deepest_checkpoint():
    tree = llm.RadixCache(4)
    alloc, states = llm.BlockAllocator(16), llm.StateAllocator(4)
    toks = list(range(20))
    blocks = alloc.alloc(5)
    ckpts = {2: states.alloc(), 4: states.alloc(), 9: states.alloc()}
    assert tree.insert(toks, blocks, alloc, ckpts, states) == 5
    assert ckpts == {9: 3}          # no node at depth 9: the id is left
    assert tree.match(toks + [99]) == blocks
    assert tree.match_with_state(toks + [99]) == (blocks, 4, 2)
    assert tree.match_with_state(toks[:15]) == (blocks[:3], 2, 1)
    assert tree.match_with_state(toks[:5]) == (blocks[:1], 0, 0)
    # a node keeps the checkpoint it has
    again = {2: 3}
    tree.insert(toks, blocks, alloc, again, states)
    assert again == {2: 3} and tree.match_with_state(toks[:9])[2] == 1


# -- the benchmark's names -----------------------------------------------------
def test_the_cell_resolves_its_names():
    loaded, kernels = benchmark_names.resolved("serve-olmoh-agent-sessions")
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "decode_tokens_per_s", "setup_s"}
    # its rooflines read the delta rule's two kernels
    assert kernels == {"gated_delta_step", "gated_delta_chunk"}
    assert loaded["traffic"]["name"] == "agent-sessions"
    assert loaded["config"]["serve"]["num_states"] == 128
    for fn in ("gated_delta_step", "gated_delta_chunk"):
        flops, bytes_ = loaded["cost_fns"][fn](loaded["config"],
                                               {"slots": 32,
                                                "live_context": 3e5})
        assert flops > 0 and bytes_ > 0
    # a decode step's state traffic: 32 slots x 2 x 2,211,840 B and change
    _, b = loaded["cost_fns"]["gated_delta_step"](loaded["config"],
                                                  {"slots": 32})
    assert 32 * 2 * 2_211_840 < b < 32 * 2 * 2_211_840 * 1.02
    for other in ("serve-lfm2-agent-sessions", "serve-batch-saturated"):
        assert not benchmark_names.resolved(other)[1] & kernels
