"""Full steps compiled for a TPU v5e from this CPU host.

libtpu describes a `v5e:2x2` topology without a chip
(`jax.experimental.topologies`), so XLA and Mosaic compile the real
programs — the llama-1b train step on one chip and under `fsdp=4`, and
every step the paged serving engine warms up — exactly as they would on
the machine with the chip.  What compiles here can still be wrong on
the chip (numerics: tests_tpu/), but what the compiler refuses is caught
before any chip time is spent.  About a minute; `slow` lane.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies
    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices


def _custom_calls(compiled) -> int:
    return sum("tpu_custom_call" in line
               for line in compiled.as_text().splitlines())


@pytest.mark.parametrize("chips,batch", [(1, 2), (4, 8)])
def test_llama_1b_train_step_compiles_for_v5e(v5e_devices, chips, batch):
    """chip_smoke.py's train step.  Under fsdp=4 the flash kernel must
    run per shard (shard_map): XLA cannot partition a Mosaic call."""
    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.train.train_step import CompiledTrainStep, make_optimizer

    seq = 2048
    cfg = dataclasses.replace(
        tfm.PRESETS["llama-1b"], max_seq=seq, remat=True,
        remat_policy="names", xent_chunk=2048, attn_block_k=1024,
        attn_impl="flash")
    mesh = make_mesh(MeshSpec(fsdp=chips), devices=v5e_devices[:chips])
    step = CompiledTrainStep(
        cfg, mesh, optimizer=make_optimizer(total_steps=1000,
                                            kind="adafactor"))
    state = jax.eval_shape(step._init, jax.random.PRNGKey(0))
    compiled = step._step.lower(
        state, jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32)).compile()
    # One forward and two backward kernels in the layer scan: the
    # remat policy keeps the forward's residuals, also under shard_map.
    assert _custom_calls(compiled) == 3
    # The ahead-of-time plan is pessimistic (the chip ran batch 4
    # against a plan of 16.9 GiB, PERF.md), so a plan that fits is a
    # step that fits.
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert per_chip < 15.75 * 2 ** 30, f"{per_chip / 2 ** 30:.2f} GiB"


@pytest.mark.parametrize("model", ["llama-1b", "gpt2-small"])
def test_paged_serving_steps_compile_for_v5e(v5e_devices, model):
    """Every shape PagedBatcher's warm-up compiles, at LLMDeployment's
    defaults, with the Pallas paged kernel."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from ray_tpu.models import decoding, transformer as tfm

    on_chip = NamedSharding(Mesh(np.array(v5e_devices[:1]), ("x",)),
                            PartitionSpec())

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=on_chip), tree)

    cfg = tfm.PRESETS[model]
    slots, max_len, bs, prompt_pad, chunk = 8, 256, 16, 64, 8
    W = decoding.paged_table_width(max_len, bs)
    params = shapes(jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0))))
    caches = shapes(jax.eval_shape(lambda: decoding.init_paged_caches(
        cfg, slots, slots * W, bs, max_len)))
    active = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=on_chip)
    for N in (4, slots):
        for P in (16, prompt_pad):
            packed = jax.ShapeDtypeStruct(
                (N + 1, max(P + 4 + W, slots)), jnp.int32,
                sharding=on_chip)
            assert _custom_calls(decoding.paged_prefill_decode_packed.lower(
                params, caches, packed, cfg, chunk, P,
                attn_impl="kernel").compile()) >= 1
    assert _custom_calls(decoding.paged_decode_steps.lower(
        params, caches, active, cfg, chunk,
        attn_impl="kernel").compile()) >= 1
    assert _custom_calls(decoding.paged_decode_step.lower(
        params, caches, active, cfg, attn_impl="kernel").compile()) >= 1


def test_afmoe_serving_steps_compile_for_v5e(v5e_devices, monkeypatch):
    """benchmarks/configs/trinity-mini-l5.json as the cell runs it: the
    widest fused prefill + decode and the decode-only chunk, with the
    window in the paged kernel, `prefix_attention` and the grouped expert
    product as Mosaic kernels, inside one chip's memory beside 8.5 GB of
    weights.  (`impl="auto"` asks jax.default_backend(): steered here, in
    the test, as it would read on the chip.)"""
    import json
    import os

    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from benchmarks.lib import spec, worker_util
    from ray_tpu.models import decoding, transformer as tfm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    on_chip = NamedSharding(Mesh(np.array(v5e_devices[:1]), ("x",)),
                            PartitionSpec())

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=on_chip), tree)

    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "trinity-mini-l5.json")) as f:
        config = json.load(f)
    sv = config["serve"]
    cfg = tfm.TransformerConfig(**worker_util.with_dtypes(
        spec.model_kind("afmoe").transformer_kwargs(
            config, max_seq=sv["max_len"], param_dtype=sv["param_dtype"])))
    W = decoding.paged_table_width(sv["max_len"], sv["kv_block_size"])
    params = shapes(jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0))))
    caches = shapes(jax.eval_shape(lambda: decoding.init_paged_caches(
        cfg, sv["num_slots"], sv["kv_num_blocks"], sv["kv_block_size"],
        sv["max_len"])))
    N, P = sv["num_slots"], 512
    packed = jax.ShapeDtypeStruct((N + 1, P + 4 + W), jnp.int32,
                                  sharding=on_chip)
    fused = decoding.paged_prefill_decode_packed.lower(
        params, caches, packed, cfg, sv["decode_chunk"], P,
        attn_impl="kernel").compile()
    # per layer: prefix attention + (4 of 5) experts, then paged + experts
    assert _custom_calls(fused) >= 5 + 4 + 5 + 4
    mem = fused.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75 * 2 ** 30)
    active = jax.ShapeDtypeStruct((N,), jnp.bool_, sharding=on_chip)
    assert _custom_calls(decoding.paged_decode_steps.lower(
        params, caches, active, cfg, sv["decode_chunk"],
        attn_impl="kernel").compile()) >= 9
