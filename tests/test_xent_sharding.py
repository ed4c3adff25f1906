"""The chunked cross-entropy under a mesh (8-device virtual CPU mesh): the
same loss and gradients as the unchunked loss on one device, and no
collective of logits size inside its scan."""

import contextlib
import dataclasses
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from ray_tpu.models import transformer as tfm
from ray_tpu.parallel.mesh import MeshSpec, make_mesh
from ray_tpu.parallel.sharding import (shard_count, spec_for,
                                       tree_shardings, use_mesh)

MESHES = {
    "none": None,
    "fsdp4": MeshSpec(fsdp=4),
    "dp2-fsdp2": MeshSpec(dp=2, fsdp=2),
    "fsdp4-tp2": MeshSpec(fsdp=4, tp=2),
    "dp2-fsdp2-tp2": MeshSpec(dp=2, fsdp=2, tp=2),
    "sp4": MeshSpec(sp=4),
}

# (batch, sequence + 1, xent_chunk): what a device scans under fsdp=4 is
# in the comment.
SHAPES = {
    "even": (8, 65, 32),        # 2 rows = 128 tokens, 4 trips
    "ragged": (8, 61, 32),      # 120 tokens: the last block is padded
    "one-trip": (4, 33, 4096),  # a device's tokens fit one block
    "chunk-of-one": (8, 17, 1),     # a trip a token
}


def computations(hlo: str):
    """A compiled program's text as {computation's name: its lines}."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?(%?[\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(1).lstrip("%")
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    return comps


def loop_collectives(hlo: str, ops=("all-reduce", "all-to-all")):
    """[(op, elements, line)] for every `ops` instruction of a compiled
    program's text that runs inside a `while` (its body, its condition
    and whatever they call), with the elements of its largest result."""
    comps = computations(hlo)
    calls = {c: {t for line in lines
                 for t in re.findall(r"%?([\w.\-]+)", line.split(" = ")[-1])
                 if t in comps and t != c}
             for c, lines in comps.items()}
    todo = [t for lines in comps.values() for line in lines
            if " while(" in line
            for t in re.findall(r"(?:body|condition)=%?([\w.\-]+)", line)]
    inside = set()
    while todo:
        c = todo.pop()
        if c not in inside:
            inside.add(c)
            todo.extend(calls[c])
    found = []
    for c in sorted(inside):
        for line in comps[c]:
            m = re.search(r" = (.*?) (%s)(?:-start)?\(" % "|".join(ops),
                          line)
            if m:
                sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
                         for dims in re.findall(r"\w+\[([\d,]*)\]",
                                                m.group(1))]
                found.append((m.group(2), max(sizes), line.strip()))
    return found


def _loss_and_grads(cfg, mesh_spec, scaled=False):
    """jit of (params, tokens) -> (loss, gradients) of `loss_fn`, laid out
    by the rule table on the mesh (None: one device).  `scaled`: of
    `3 * loss + aux` instead, so that the loss's cotangent is not 1."""
    def objective(p, t, mesh=None):
        loss = tfm.loss_fn(p, t, cfg, mesh)[0]
        if scaled:
            loss = 3.0 * loss + 1e-2 * jnp.sum(jnp.square(p["final_norm"]))
        return loss

    if mesh_spec is None:
        return jax.jit(jax.value_and_grad(objective))
    mesh = make_mesh(mesh_spec)

    def f(p, t):
        with use_mesh(mesh):
            return jax.value_and_grad(lambda p: objective(p, t, mesh))(p)

    return jax.jit(f, in_shardings=(
        tree_shardings(tfm.logical_axes(cfg), mesh),
        NamedSharding(mesh, spec_for(("batch", None), mesh=mesh))))


@functools.lru_cache(maxsize=None)
def _reference(shape, dtype, form="plain"):
    """(cfg, params, tokens, loss, gradients): `xent_chunk=None` on one
    device, once for all the meshes of a shape.  `form`: "plain", "tied"
    (the head is `tok_embed.T`) or "scaled" (`_loss_and_grads`)."""
    B, S1, _ = SHAPES[shape]
    cfg = dataclasses.replace(tfm.PRESETS["tiny"],
                              tie_embeddings=form == "tied",
                              max_seq=128, dtype=jnp.dtype(dtype),
                              xent_chunk=None)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.RandomState(1).randint(
        0, cfg.vocab_size, size=(B, S1)).astype(np.int32))
    return (cfg, params, tokens) + _loss_and_grads(
        cfg, None, form == "scaled")(params, tokens)


# In float32 the chunked, sharded loss IS the unchunked one (measured
# <= 1e-6 in every case): every shape.  With the bf16 operands training
# uses, the distance is the model's bf16 rounding summed in another order
# (head gradient: a bf16 carry over the trips, as before this layout;
# measured <= 1.4e-2 of the largest entry): one shape, every mesh.
# Since PR 55 the loss's backward pass is its own rule (the gradients are
# made in the forward trip and scaled by the cotangent), so beside the
# plain form: a cotangent that is not 1, the head as `tok_embed.T` (its
# gradient leaves the rule through the transpose), and blocks that pad
# with the bf16 operands.
CASES = ([(m, s, "float32", 1e-5, "plain") for m in sorted(MESHES)
          for s in sorted(SHAPES)]
         + [(m, "even", "bfloat16", 3e-2, "plain") for m in sorted(MESHES)]
         + [(m, "ragged", "float32", 1e-5, form) for m in sorted(MESHES)
            for form in ("scaled", "tied")]
         + [(m, "ragged", "bfloat16", 3e-2, form)
            for m in ("none", "fsdp4", "fsdp4-tp2", "sp4")
            for form in ("plain", "scaled")])


@pytest.mark.parametrize(
    "mesh_name,shape,dtype,tol,form", CASES,
    ids=["-".join(c[:3] + c[4:]).removesuffix("-plain") for c in CASES])
def test_chunked_loss_matches_unchunked_single_device(
        cpu_mesh_devices, mesh_name, shape, dtype, tol, form):
    """Loss and gradients (head, embedding, two layer weights) of the
    chunked, sharded loss against `xent_chunk=None` on one device."""
    cfg, params, tokens, want, want_g = _reference(shape, dtype, form)
    got, got_g = _loss_and_grads(
        dataclasses.replace(cfg, xent_chunk=SHAPES[shape][2]),
        MESHES[mesh_name], form == "scaled")(params, tokens)
    np.testing.assert_allclose(float(got), float(want), rtol=tol / 10)
    for path in (("lm_head",), ("tok_embed",), ("layers", "wq"),
                 ("layers", "w_down"), ("final_norm",)):
        if path[0] not in want_g:       # tied: no head of its own
            continue
        g, w = got_g, want_g
        for k in path:
            g, w = g[k], w[k]
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= tol * np.abs(w).max(), path


@pytest.mark.parametrize("mesh_name", ["none", "fsdp4", "fsdp4-tp2", "sp4"])
def test_padded_targets_add_nothing(cpu_mesh_devices, mesh_name):
    """Targets of -1 (a caller's padding) carry no loss and no gradient;
    the mean stays over all B x S positions, as without a mesh."""
    cfg = dataclasses.replace(tfm.PRESETS["tiny"], xent_chunk=32,
                              dtype=jnp.float32)
    B, S, D, V = 8, 40, cfg.d_model, cfg.vocab_size
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(B, S, D).astype(np.float32))
    w = jnp.asarray(rs.randn(D, V).astype(np.float32) * 0.1)
    targets = rs.randint(0, V, size=(B, S)).astype(np.int32)
    targets[:, 29:] = -1
    targets[3, 5] = -1
    logp = jax.nn.log_softmax(x @ w, axis=-1)
    picked = np.take_along_axis(
        np.asarray(logp), np.maximum(targets, 0)[..., None], -1)[..., 0]
    want = -(picked * (targets >= 0)).sum() / (B * S)

    def f(x, w):
        return tfm.fused_cross_entropy(x, w, jnp.asarray(targets), cfg)

    def plain(x, w):
        logp = jax.nn.log_softmax(x @ w, axis=-1)
        picked = jnp.take_along_axis(
            logp, jnp.maximum(targets, 0)[..., None], -1)[..., 0]
        return -jnp.sum(picked * (targets >= 0)) / (B * S)

    spec = MESHES[mesh_name]
    with use_mesh(make_mesh(spec)) if spec else contextlib.nullcontext():
        got, (gx, gw) = jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(x, w)
    np.testing.assert_allclose(float(got), want, rtol=1e-5)
    assert not np.asarray(gx)[np.asarray(targets) < 0].any()
    assert np.asarray(gx)[np.asarray(targets) >= 0].any()
    want_gx, want_gw = jax.grad(plain, argnums=(0, 1))(x, w)
    for g, wg in ((gx, want_gx), (gw, want_gw)):
        assert np.abs(np.asarray(g) - np.asarray(wg)).max() <= (
            1e-5 * np.abs(np.asarray(wg)).max())


def test_shard_count_follows_the_rule_table(cpu_mesh_devices):
    assert shard_count("batch") == 1
    with use_mesh(make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))):
        assert [shard_count(a) for a in ("batch", "seq", "vocab")] == [
            4, 1, 2]
    assert shard_count("seq", mesh=make_mesh(MeshSpec(dp=2, sp=4))) == 4


@pytest.mark.parametrize("mesh_name", ["fsdp4", "dp2-fsdp2"])
def test_no_logits_sized_collective_inside_the_scan(cpu_mesh_devices,
                                                    mesh_name):
    """The invariant of `fused_cross_entropy`, on the CPU partitioner at
    toy widths: the compiled loss + gradients hold no all-reduce or
    all-to-all of one device's chunk x vocab elements inside a loop, nor
    one of the head's d_model x vocab (PR 55: every device sums its own
    tokens' head gradient over the trips; up to PR 54 this partitioner
    all-reduced `f32[512,128]` a trip, which only the TPU compiler moved
    out of the loop).
    (tests/test_tpu_aot.py holds the cells' own steps to it for v5e.)"""
    # chunk x vocab (131,072) and the head (65,536) are above every weight
    # gradient of the toy (49,152), which the layer scan does all-reduce.
    # (No `tp` case: its layers all-reduce [rows, S, D] activations, as
    # large at toy widths.)
    B, S1, chunk = 8, 513, 256
    cfg = dataclasses.replace(tfm.PRESETS["tiny"], tie_embeddings=False,
                              max_seq=512, xent_chunk=chunk)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((B, S1), jnp.int32)
    hlo = _loss_and_grads(cfg, MESHES[mesh_name]).lower(
        params, tokens).compile().as_text()
    assert " while(" in hlo
    big = [c for c in loop_collectives(hlo)
           if c[1] >= min(chunk, cfg.d_model) * cfg.vocab_size]
    assert not big, big


def test_undifferentiated_loss_pays_for_no_gradient(cpu_mesh_devices):
    """The primal of the loss's `custom_vjp` runs ONE product of head size
    a trip; differentiated, three, all in the one loop (the census of the
    cells' own steps for v5e is tests/test_tpu_aot.py's)."""
    from test_tpu_aot import _products

    cfg = dataclasses.replace(tfm.PRESETS["tiny"], xent_chunk=64,
                              dtype=jnp.float32)
    B, S, D, V = 4, 64, cfg.d_model, cfg.vocab_size
    args = (jnp.zeros((B, S, D)), jnp.zeros((D, V)),
            jnp.zeros((B, S), jnp.int32))

    def loss(x, w, t):
        return tfm.fused_cross_entropy(x, w, t, cfg)

    def head_products(f):
        text = jax.jit(f).lower(*args).compile().as_text()
        assert " while(" in text
        return [p for p in _products(text) if {D, V} <= p.dims]

    assert len(head_products(loss)) == 1
    assert len(head_products(jax.grad(loss, argnums=(0, 1)))) == 3
