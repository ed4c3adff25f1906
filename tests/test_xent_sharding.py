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


def _loss_and_grads(cfg, mesh_spec):
    """jit of (params, tokens) -> (loss, gradients) of `loss_fn`, laid out
    by the rule table on the mesh (None: one device)."""
    if mesh_spec is None:
        return jax.jit(jax.value_and_grad(
            lambda p, t: tfm.loss_fn(p, t, cfg)[0]))
    mesh = make_mesh(mesh_spec)

    def f(p, t):
        with use_mesh(mesh):
            return jax.value_and_grad(
                lambda p: tfm.loss_fn(p, t, cfg, mesh)[0])(p)

    return jax.jit(f, in_shardings=(
        tree_shardings(tfm.logical_axes(cfg), mesh),
        NamedSharding(mesh, spec_for(("batch", None), mesh=mesh))))


@functools.lru_cache(maxsize=None)
def _reference(shape, dtype):
    """(cfg, params, tokens, loss, gradients): `xent_chunk=None` on one
    device, once for all the meshes of a shape."""
    B, S1, _ = SHAPES[shape]
    cfg = dataclasses.replace(tfm.PRESETS["tiny"], tie_embeddings=False,
                              max_seq=128, dtype=jnp.dtype(dtype),
                              xent_chunk=None)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.RandomState(1).randint(
        0, cfg.vocab_size, size=(B, S1)).astype(np.int32))
    return (cfg, params, tokens) + _loss_and_grads(cfg, None)(params, tokens)


# In float32 the chunked, sharded loss IS the unchunked one (measured
# <= 1e-6 in every case): every shape.  With the bf16 operands training
# uses, the distance is the model's bf16 rounding summed in another order
# (head gradient: a bf16 carry over the trips, as before this layout;
# measured <= 1.4e-2 of the largest entry): one shape, every mesh.
CASES = ([(m, s, "float32", 1e-5) for m in sorted(MESHES)
          for s in sorted(SHAPES)]
         + [(m, "even", "bfloat16", 3e-2) for m in sorted(MESHES)])


@pytest.mark.parametrize("mesh_name,shape,dtype,tol", CASES,
                         ids=["-".join(c[:3]) for c in CASES])
def test_chunked_loss_matches_unchunked_single_device(
        cpu_mesh_devices, mesh_name, shape, dtype, tol):
    """Loss and gradients (head, embedding, two layer weights) of the
    chunked, sharded loss against `xent_chunk=None` on one device."""
    cfg, params, tokens, want, want_g = _reference(shape, dtype)
    got, got_g = _loss_and_grads(
        dataclasses.replace(cfg, xent_chunk=SHAPES[shape][2]),
        MESHES[mesh_name])(params, tokens)
    np.testing.assert_allclose(float(got), float(want), rtol=tol / 10)
    for path in (("lm_head",), ("tok_embed",), ("layers", "wq"),
                 ("layers", "w_down")):
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

    spec = MESHES[mesh_name]
    with use_mesh(make_mesh(spec)) if spec else contextlib.nullcontext():
        got, gx = jax.jit(jax.value_and_grad(f))(x, w)
    np.testing.assert_allclose(float(got), want, rtol=1e-5)
    assert not np.asarray(gx)[np.asarray(targets) < 0].any()
    assert np.asarray(gx)[np.asarray(targets) >= 0].any()


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
    all-to-all of one device's chunk x vocab elements inside a loop.
    (tests/test_tpu_aot.py holds the cells' own steps to it for v5e.)"""
    # chunk x vocab (131,072) is above every weight gradient of the toy
    # (49,152), which the layer scan does all-reduce.  (No `tp` case: its
    # layers all-reduce [rows, S, D] activations, as large at toy widths.)
    B, S1, chunk = 8, 513, 256
    cfg = dataclasses.replace(tfm.PRESETS["tiny"], tie_embeddings=False,
                              max_seq=512, xent_chunk=chunk)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((B, S1), jnp.int32)
    hlo = _loss_and_grads(cfg, MESHES[mesh_name]).lower(
        params, tokens).compile().as_text()
    assert " while(" in hlo
    big = [c for c in loop_collectives(hlo)
           if c[1] >= chunk * cfg.vocab_size]
    assert not big, big
