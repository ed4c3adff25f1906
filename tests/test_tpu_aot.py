"""Full steps compiled for a TPU v5e from this CPU host.

libtpu describes a `v5e:2x2` topology without a chip
(`jax.experimental.topologies`), so XLA and Mosaic compile the real
programs — the llama-1b train step on one chip and under `fsdp=4`, and
every step the paged serving engine warms up — exactly as they would on
the machine with the chip.  What compiles here can still be wrong on
the chip (numerics: tests_tpu/), but what the compiler refuses is caught
before any chip time is spent.  About a minute; `slow` lane.
"""

import collections
import dataclasses
import math
import re

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


def _assert_pools_written_by_page(compiled, caches):
    """What a serving program does to its KV pools, read off the compiled
    text: every scatter into a pool is indexed by the block alone and its
    update is whole pages [.., Hkv', bs, lanes] (a scatter of D-wide rows
    costs the chip ~68 ns a row: PERF.md, PR 45), and nothing copies or
    converts a whole pool (the pool is one buffer updated in place through
    the layer scans and the unrolled layers)."""
    pools = {tuple(p.shape) for name in ("kp", "vp")
             for p in jax.tree.leaves(getattr(caches, name))}
    # the stacked pool [L, NB, ...] is carried as ONE pool of L * NB blocks
    pools |= {(s[0] * s[1],) + s[2:] for s in pools if len(s) == 5}
    # (XLA drops a dimension of 1: a latent pool's head axis)
    pools = {tuple(d for d in s if d > 1) for s in pools if len(s) == 4}
    sizes = {math.prod(s) for s in pools}
    shape = re.compile(r"= \(?[a-z0-9]+\[([0-9,]+)\]")
    scatters = 0
    for line in compiled.as_text().splitlines():
        op = re.search(r"[\]}] (scatter|copy|convert)\(", line)
        found = shape.search(line)
        if not op or not found:
            continue
        dims = tuple(int(d) for d in found.group(1).split(","))
        if math.prod(dims) not in sizes:
            continue
        assert op.group(1) == "scatter", line[:200]
        assert dims in pools, line[:200]
        window = ",".join(str(d) for d in range(1, len(dims)))
        assert f"update_window_dims={{{window}}}" in line \
            and "scatter_dims_to_operand_dims={0}" in line, line[:300]
        scatters += 1
    assert scatters, "no write into a pool found"


def _per_chip_bytes(compiled, what: str) -> int:
    """The ahead-of-time plan of one chip's memory, printed (`-s`) so the
    next reader sees its size."""
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{what}: plan {per_chip / 2 ** 30:.2f} GiB a chip "
          f"(temporaries {mem.temp_size_in_bytes / 2 ** 30:.2f})")
    return per_chip


def _compile_fsdp_step(cfg, devices, chips, batch, seq, optimizer):
    """The whole train step under `MeshSpec(fsdp=chips)`, from shapes."""
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.train.train_step import CompiledTrainStep

    step = CompiledTrainStep(
        cfg, make_mesh(MeshSpec(fsdp=chips), devices=devices[:chips]),
        optimizer=optimizer)
    state = jax.eval_shape(step._init, jax.random.PRNGKey(0))
    return step._step.lower(
        state, jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32)).compile()


def _assert_loss_keeps_its_tokens(compiled, cfg):
    """`fused_cross_entropy`'s invariant: no all-reduce or all-to-all of
    logits size (one device's `xent_chunk` x vocabulary) inside a loop,
    nor one of the head's size (d_model x vocabulary)."""
    from test_xent_sharding import loop_collectives
    big = [c for c in loop_collectives(compiled.as_text())
           if c[1] >= min(cfg.xent_chunk, cfg.d_model) * cfg.vocab_size]
    assert not big, big


@pytest.mark.parametrize("chips,batch", [(1, 2), (4, 8)])
def test_llama_1b_train_step_compiles_for_v5e(v5e_devices, chips, batch):
    """chip_smoke.py's train step.  Under fsdp=4 the flash kernel must
    run per shard (shard_map): XLA cannot partition a Mosaic call."""
    from ray_tpu.models import transformer as tfm
    from ray_tpu.train.train_step import make_optimizer

    seq = 2048
    cfg = dataclasses.replace(
        tfm.PRESETS["llama-1b"], max_seq=seq, remat=True,
        remat_policy="names", xent_chunk=2048, attn_block_k=1024,
        attn_impl="flash")
    compiled = _compile_fsdp_step(
        cfg, v5e_devices, chips, batch, seq,
        make_optimizer(total_steps=1000, kind="adafactor"))
    # One forward and two backward kernels in the layer scan: the
    # remat policy keeps the forward's residuals, also under shard_map.
    assert _custom_calls(compiled) == 3
    # The ahead-of-time plan is pessimistic (the chip ran batch 4
    # against a plan of 16.9 GiB, PERF.md), so a plan that fits is a
    # step that fits.
    per_chip = _per_chip_bytes(compiled, f"llama-1b fsdp={chips}")
    assert per_chip < 15.75 * 2 ** 30, f"{per_chip / 2 ** 30:.2f} GiB"
    _assert_loss_keeps_its_tokens(compiled, cfg)
    _assert_loss_runs_three_products(compiled, cfg)


Product = collections.namedtuple(
    "Product", "instruction einsum dims op_name computation")


def _products(text: str):
    """Every matrix product of a compiled step (loops' bodies once): the
    instruction that runs it (the fusion around the convolution), the
    einsum it came from, the dimensions of its result and operands, its
    whole `op_name`, and the computation that instruction stands in.
    (`dot`: what the CPU compiler keeps them as.)"""
    from test_xent_sharding import computations
    bodies = computations(text)
    called_by = {}     # a fusion's body -> (the fusion instruction, its home)
    for computation, lines in bodies.items():
        for line in lines:
            name = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
            for callee in re.findall(r"calls=%?([\w.\-]+)", line):
                called_by[callee] = (name.group(1), computation)
    found = []
    for computation, lines in bodies.items():
        dims = {m.group(1): m.group(2) for m in (
            re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]", line)
            for line in lines) if m}
        for line in lines:
            product = re.search(
                r"%?([\w.\-]+) = .*[\]})] (?:convolution|dot)\((.*?)\)", line)
            if product:
                names = [product.group(1)] + [
                    n.strip().lstrip("%") for n in product.group(2).split(",")]
                op_name = re.search(r'op_name="([^"]*)"', line)
                einsum = re.search(r'op_name="[^"]*?([\w,>\-]+)/dot_general',
                                   line)
                instruction, home = called_by.get(
                    computation, (product.group(1), computation))
                found.append(Product(
                    instruction, einsum.group(1) if einsum else "?",
                    {int(d) for n in names for d in dims[n].split(",") if d},
                    op_name.group(1) if op_name else "", home))
    return found


def _head_products(text: str, cfg):
    """The products with the head's dimensions (d_model and the vocabulary
    among their result's and operands'), each with the `while` body that
    runs it (or None) in `computation`'s place."""
    loops = set(re.findall(r" while\(.*?body=%?([\w.\-]+)", text))
    return [p._replace(computation=p.computation
                       if p.computation in loops else None)
            for p in _products(text)
            if {cfg.d_model, cfg.vocab_size} <= p.dims]


def _assert_loss_runs_three_products(compiled, cfg):
    """PR 55: the chunked loss makes its gradients in the trip that has the
    logits.  Exactly three products with the head's dimensions (logits,
    `dx`, the head's gradient), all in ONE loop's body, and that loop is
    the forward scan: a fourth, or one in a loop the backward pass runs,
    is the logits product made again."""
    head = _head_products(compiled.as_text(), cfg)
    assert len(head) == 3, head
    assert len({p.computation for p in head}) == 1 and head[0].computation
    # (what names the LOOP comes before "/while": a forward trip may well
    # transpose inside itself)
    assert not [p for p in head
                if "transpose(jvp" in p.op_name.split("/while")[0]], head


def _loop_carried(text: str):
    """(dtype, dims) of every array a `while` of the program carries."""
    for line in text.splitlines():
        carried = re.search(r" = \((.*?)\) while\(", line)
        if carried:
            for dtype, dims in re.findall(r"(\w+)\[([\d,]+)\]",
                                          carried.group(1)):
                yield dtype, tuple(int(d) for d in dims.split(","))


def test_fsdp4_cell_step_keeps_its_tokens(v5e_devices):
    """`train-4k-fsdp4`'s own step (benchmarks/lib/train_cell.py: the
    `train` block of mistral-7b-l16, batch 16 x 4,097 under fsdp=4).  Its
    plan over-states (the chip runs it: PERF.md), so the loss's invariant is
    held here, and the census of PR 53 (the layers' weight gradients stay in
    the compute dtype through the backward scan, train/train_step.py):

    * 30 products, the one-chip step's count (a layer's 11 of FFN size and
      12 of attention size, the loss's 3, ...; 31 with the loss's 4 up to
      PR 54), and none in an instruction
      XLA's rematerialisation pass cloned (`.remat` in its name): with
      float32 stacks of the gradients the plan was 1.75 GiB a chip short
      and the pass ran `dy . W_down^T` and the `wo` recompute twice a layer;
    * the loss's three in ONE loop's body, the forward scan's, and no other
      loop holds a product with the head's dimensions (PR 55: a regression
      to four would show in the backward scan);
    * no loop carries a float32 array of a stacked product weight's shape
      (on one chip of four: any one dimension split);
    * the plan's temporaries at least 1.5 GiB under the 17.52 GiB they
      were (PR 53 left 15.77 GiB; PR 55's loss 14.90)."""
    import json
    import os

    from benchmarks.lib import spec, worker_util
    from ray_tpu.models import transformer as tfm
    from ray_tpu.train.train_step import make_optimizer

    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "mistral-7b-l16.json")) as f:
        mc = json.load(f)
    tr, seq, chips = mc["train"], 4096, 4
    cfg = tfm.TransformerConfig(**worker_util.with_dtypes(
        spec.model_kind(mc["kind"]).transformer_kwargs(
            mc, max_seq=seq, param_dtype=tr["param_dtype"], remat=True,
            remat_policy=tr["remat_policy"], xent_chunk=tr["xent_chunk"],
            attn_block_k=tr["attn_block_k"], attn_impl="flash")))
    compiled = _compile_fsdp_step(
        cfg, v5e_devices, chips, tr["batch_per_chip"] * chips, seq,
        make_optimizer(total_steps=10_000, kind=tr["optimizer"]))
    _per_chip_bytes(compiled, "mistral-7b-l16 fsdp=4")
    _assert_loss_keeps_its_tokens(compiled, cfg)

    text = compiled.as_text()
    products = _products(text)
    cloned = [p for p in products if ".remat" in p.instruction]
    assert not cloned and len(products) == 30, (len(products), cloned)
    _assert_loss_runs_three_products(compiled, cfg)
    stacks = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))["layers"]
    on_a_chip = {tuple(sorted(d // chips if i == split else d
                              for i, d in enumerate(stacks[name].shape)))
                 for name in tfm.PRODUCT_WEIGHTS
                 for split in range(1, stacks[name].ndim)}
    carried = list(_loop_carried(text))
    assert ("bf16", (16, 1024, 14336)) in carried       # the parser sees them
    widened = [(dtype, dims) for dtype, dims in carried
               if dtype == "f32" and tuple(sorted(dims)) in on_a_chip]
    assert not widened, widened
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    print(f"temporaries {temporaries / 2 ** 30:.2f} GiB "
          f"(the parent's, PR 54: 15.77)")
    assert temporaries <= (17.52 - 1.5) * 2 ** 30, temporaries / 2 ** 30


def _on_chip_shapes(v5e_devices):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    on_chip = NamedSharding(Mesh(np.array(v5e_devices[:1]), ("x",)),
                            PartitionSpec())

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=on_chip), tree)

    return on_chip, shapes


@pytest.mark.parametrize("model", ["llama-1b", "gpt2-small"])
def test_paged_serving_steps_compile_for_v5e(v5e_devices, model,
                                             monkeypatch):
    """Every shape PagedBatcher's warm-up compiles, at LLMDeployment's
    defaults, as `impl="auto"` reads on the chip: the Pallas paged kernel
    in the decode steps, and (heads of 64) the gather in the prefill."""
    from ray_tpu.models import decoding, transformer as tfm
    from ray_tpu.serve import llm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    on_chip, shapes = _on_chip_shapes(v5e_devices)
    cfg = tfm.PRESETS[model]
    slots, max_len, bs, prompt_pad, chunk = 8, 256, 16, 64, 8
    W = decoding.paged_table_width(max_len, bs)
    params = shapes(jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0))))
    caches = shapes(jax.eval_shape(lambda: decoding.init_paged_caches(
        cfg, slots, slots * W, bs, max_len)))
    active = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=on_chip)
    tile, ladder = llm.prefill_shapes(slots, prompt_pad, bs)
    # 8 slots of one 64-token prompt each: the widest program is theirs
    assert tile * ladder[-1] == 8 * 64 and ladder == sorted(set(ladder))
    upload = decoding.FusedUpload.of(tile, caches)
    for N in ladder:
        packed = jax.ShapeDtypeStruct(upload.empty(N).shape, jnp.int32,
                                      sharding=on_chip)
        assert _custom_calls(decoding.paged_prefill_decode_packed.lower(
            params, caches, packed, cfg, chunk, tile).compile()) >= 1
    assert _custom_calls(decoding.paged_decode_steps.lower(
        params, caches, active, cfg, chunk).compile()) >= 1


# (the pass of a fused program, a decode step) per layer: the pass calls
# `prefix_attention` for its prompt rows AND `paged_attention` for the live
# slots' step that rides in it, a decode step `paged_attention`; Trinity's 4
# expert layers add one grouped product to each (the pass's is ONE over both
# kinds of row); LFM2's 2 attention layers (heads of 64, two to a row of
# lanes in the pool) and 8 expert layers likewise, its 7 conv layers none;
# A.X-K1's 7 latent layers call `mla_prefix_attention` and
# `mla_paged_attention` (rows of 640 lanes, [64, 640] query tiles), its 6
# expert layers the product over 4 blocks of F; Olmo-Hybrid's 3 full layers
# the two paged kernels at 30 heads, its 9 linear layers `gated_delta_chunk`
# for a pass's prompt rows and `gated_delta_step` for its carried rows, a
# decode step `gated_delta_step`; Qwen3-Next's 2 full layers the two paged
# kernels at 16 / 2 heads of 256, its 6 linear layers the two delta kernels
# at 32 heads of [128, 128], and all 8 layers the grouped product over 128
# held experts of 2048 x 512
CELL_KERNELS = {"mistral-7b-l16": (1 + 1, 1),
                "trinity-mini-l5": (5 + 5 + 4, 5 + 4),
                "lfm2-24b-a2b-l9": (2 + 2 + 8, 2 + 8),
                "axk1-l7-ep16": (7 + 7 + 6, 7 + 6),
                "olmo-hybrid-7b-l12": (3 + 3 + 9 + 9, 3 + 9),
                "qwen3-next-80b-a3b-l8-ep4": (2 + 2 + 6 + 6 + 8, 2 + 6 + 8),
                "mimo-v2-flash-l7-ep16": (2 + 2 + 5 + 5 + 6, 2 + 5 + 6)}


# LFM2's nine unrolled layers compile ~40 s a program here: one test a
# program (its four rungs, then the decode-only chunk), each well inside
# the suite's limit for a test
CELL_PROGRAMS = [pytest.param("mistral-7b-l16", None, id="mistral-7b-l16"),
                 pytest.param("trinity-mini-l5", None, id="trinity-mini-l5")
                 ] + [pytest.param(name, i, id=f"{name}-{i}")
                      for name in ("lfm2-24b-a2b-l9", "axk1-l7-ep16",
                                   "olmo-hybrid-7b-l12",
                                   "qwen3-next-80b-a3b-l8-ep4",
                                   "mimo-v2-flash-l7-ep16")
                      for i in range(5)]


def _cell_shapes(v5e_devices, config_name):
    """A serving cell as it runs (benchmarks/configs/<name>.json), as shapes
    on one v5e chip -> (cfg, serve sizes, params, caches, tile, ladder, the
    packed upload of N rows, the active mask)."""
    import json
    import os

    from benchmarks.lib import spec, worker_util
    from ray_tpu.models import decoding, transformer as tfm
    from ray_tpu.serve import llm

    on_chip, shapes = _on_chip_shapes(v5e_devices)
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           config_name + ".json")) as f:
        config = json.load(f)
    sv = config["serve"]
    cfg = tfm.TransformerConfig(**worker_util.with_dtypes(
        spec.model_kind(config["kind"]).transformer_kwargs(
            config, max_seq=sv["max_len"], param_dtype=sv["param_dtype"])))
    params = shapes(jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0))))
    caches = shapes(jax.eval_shape(lambda: decoding.init_paged_caches(
        cfg, sv["num_slots"], sv["kv_num_blocks"], sv["kv_block_size"],
        sv["max_len"], *([sv["num_states"]] if "num_states" in sv else []))))
    tile, ladder = llm.prefill_shapes(sv["num_slots"], sv["prompt_pad"],
                                      sv["kv_block_size"])
    # (without the last row's sets: the hashed programs' upload is the one
    # tests/data/serving_program_hashes.json was written with)
    upload = decoding.FusedUpload.of(tile, caches)._replace(sets=False)

    def packed(N):
        return jax.ShapeDtypeStruct(upload.empty(N).shape, jnp.int32,
                                    sharding=on_chip)

    active = jax.ShapeDtypeStruct((sv["num_slots"],), jnp.bool_,
                                  sharding=on_chip)
    return cfg, sv, params, caches, tile, ladder, packed, active


@pytest.mark.parametrize("config_name,part", CELL_PROGRAMS)
def test_cells_fused_ladder_compiles_for_v5e(v5e_devices, monkeypatch,
                                             config_name, part):
    """The serving cells as they run (benchmarks/configs/): every fused
    prefill + decode of the engine's ladder (rows of PREFILL_TILE tokens:
    Mistral's 32 / 8 heads of 128 under 48-column tables, Trinity's 32 / 4
    under 1,072 columns, with its window in the paged kernel and the
    grouped expert product, LFM2's 32 / 8 heads of 64 side by side under
    1,072 columns beside its conv layers' tails, A.X-K1's 64 heads over
    latent rows of 640 lanes with its experts in 4 blocks of F,
    Olmo-Hybrid's 30 / 30 heads of 128 beside nine layers' states of
    [15, 96, 384] by state id, Qwen3-Next's 16 / 2 heads of 256 beside six
    layers' states of [32, 128, 128] and 128 held experts a layer,
    MiMo-V2's 64 / 4 heads with keys of 192 in 256 lanes beside values of 128
    and five layers' rings of [8, 128, 256 | 128] by state id) and the
    decode-only chunk, as Mosaic kernels,
    inside one chip's memory beside the weights.  (`impl="auto"` asks
    jax.default_backend(): steered here, in the test, as it would read on
    the chip.)"""
    from ray_tpu.models import decoding
    from ray_tpu.serve import llm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, sv, params, caches, tile, ladder, packed, active = _cell_shapes(
        v5e_devices, config_name)
    assert caches.block_tables.shape[1] == {
        "mistral-7b-l16": 48, "trinity-mini-l5": 1072,
        "lfm2-24b-a2b-l9": 1072, "axk1-l7-ep16": 1072,
        "olmo-hybrid-7b-l12": 1072,
        "qwen3-next-80b-a3b-l8-ep4": 1072,
        "mimo-v2-flash-l7-ep16": 1072}[config_name]
    # the budget is PREFILL_CHUNK tokens whatever the slots are, in at
    # most six programs, none more than 384 positions wider than the one
    # before it up to 896
    for slots in (4, sv["num_slots"]):
        assert llm.prefill_shapes(slots, sv["prompt_pad"],
                                  sv["kv_block_size"]) == (tile, ladder)
    assert tile * ladder[-1] == llm.PREFILL_CHUNK and len(ladder) <= 6
    widths = [0] + [tile * n for n in ladder]
    assert all(b - a <= 384 for a, b in zip(widths, widths[1:])
               if b <= 896)
    for N in ladder if part is None else ladder[part:part + 1]:
        fused = decoding.paged_prefill_decode_packed.lower(
            params, caches, packed(N), cfg, sv["decode_chunk"], tile,
            attn_impl="kernel").compile()
        assert _custom_calls(fused) >= sum(CELL_KERNELS[config_name])
        _assert_pools_written_by_page(fused, caches)
        mem = fused.memory_analysis()
        assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                < 15.75 * 2 ** 30)
    if part is not None and part < len(ladder):
        return
    steps = decoding.paged_decode_steps.lower(
        params, caches, active, cfg, sv["decode_chunk"],
        attn_impl="kernel").compile()
    assert _custom_calls(steps) >= CELL_KERNELS[config_name][1]
    _assert_pools_written_by_page(steps, caches)


# The serving configurations whose programs must stay what they were when a
# PR adds an architecture beside them: the lowered text of every program a
# cell's engine warms up (its fused rungs and the decode-only chunk), hashed.  tests/data/serving_program_hashes.json holds the hashes of
# the tree that last meant to change one; `UPDATE_PROGRAM_HASHES=1` writes it
# anew (a PR that changes a program on purpose says so and does).
HASHED_CONFIGS = ("mistral-7b-l16", "trinity-mini-l5", "lfm2-24b-a2b-l9",
                  "axk1-l7-ep16", "olmo-hybrid-7b-l12",
                  "qwen3-next-80b-a3b-l8-ep4", "mimo-v2-flash-l7-ep16")
HASH_FILE = "serving_program_hashes.json"


def _location_free(text: str) -> str:
    """A lowered program's text with every Mosaic kernel's serialized body
    (MLIR bytecode, which carries the checkout's path and the line of every
    call site) replaced by the hash of its text without locations: the
    same program from another directory, or after an edit that only moves
    lines, reads the same."""
    import base64
    import hashlib

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    ctx = jax_mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True

    def body(found):
        with ctx:
            asm = ir.Module.parse(base64.b64decode(found.group(1))
                                  ).operation.get_asm(enable_debug_info=False)
        return "body=" + hashlib.sha256(asm.encode()).hexdigest()

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)


@pytest.mark.parametrize("config_name", HASHED_CONFIGS)
def test_accepted_cells_compile_the_programs_they_did(v5e_devices,
                                                      monkeypatch,
                                                      config_name):
    import hashlib
    import json
    import os

    from ray_tpu.models import decoding

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, sv, params, caches, tile, ladder, packed, active = _cell_shapes(
        v5e_devices, config_name)
    lowered = {f"fused-{N * tile}": decoding.paged_prefill_decode_packed.lower(
        params, caches, packed(N), cfg, sv["decode_chunk"], tile,
        attn_impl="kernel") for N in ladder}
    lowered["decode-steps"] = decoding.paged_decode_steps.lower(
        params, caches, active, cfg, sv["decode_chunk"], attn_impl="kernel")
    got = {name: hashlib.sha256(_location_free(low.as_text()).encode()
                                ).hexdigest()
           for name, low in lowered.items()}
    path = os.path.join(os.path.dirname(__file__), "data", HASH_FILE)
    try:
        with open(path) as f:
            kept = json.load(f)
    except FileNotFoundError:
        kept = {}
    if os.environ.get("UPDATE_PROGRAM_HASHES"):
        kept[config_name] = got
        with open(path, "w") as f:
            json.dump(kept, f, indent=1, sort_keys=True)
            f.write("\n")
    assert kept.get(config_name) == got, (
        f"{config_name}'s serving programs are not the ones "
        f"tests/data/{HASH_FILE} holds: "
        f"{[n for n in got if kept.get(config_name, {}).get(n) != got[n]]}")


def test_delta_kernels_and_thirty_heads_compile_for_v5e(v5e_devices):
    """The kernels arch "olmo_hybrid" brings, alone at the cell's shapes, so
    that a Mosaic refusal (a pair of heads in 384 lanes, keys of 96, the
    products with the state in float32, the state's copies by id) shows
    here: `gated_delta_step` at 32 slots, `gated_delta_chunk` at the
    narrowest and the widest rung's rows; and the two paged kernels at 30 kv
    heads of 128 with a group of 1, under 1,072-column tables (the ring
    holds 2 groups of 8 pages there, where Mistral's 8 heads hold 8)."""
    from ray_tpu.ops import gated_delta as gd
    from ray_tpu.ops import paged_attention as pa

    on_chip, _ = _on_chip_shapes(v5e_devices)
    f32, i32 = jnp.float32, jnp.int32

    def S(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)

    H, dk, dv, B = 30, 96, 192, 32
    pool = S(gd.pool_shape(128, H, dk, dv))
    assert pool.shape == (129, 15, 96, 384)
    assert _custom_calls(jax.jit(
        lambda *a: gd.gated_delta_step(*a, impl="kernel")).lower(
        pool, S((B,), i32), S((B, H, dk)), S((B, H, dk)), S((B, H, dv)),
        S((B, H)), S((B, H))).compile()) == 1
    for N in (16, 128):
        assert _custom_calls(jax.jit(
            lambda *a: gd.gated_delta_chunk(*a, impl="kernel")).lower(
            pool, S((N,), i32), S((N, 2), i32), S((N, 16, H, dk)),
            S((N, 16, H, dk)), S((N, 16, H, dv)), S((N, 16, H)),
            S((N, 16, H))).compile()) == 1
    bf = jnp.bfloat16
    kp = S((4097, 30, 16, 128), bf)
    assert pa._ring_shape(1072, 30, 16, 128, 2) == (8, 2, 1)
    assert pa._ring_shape(48, 8, 16, 128, 2)[1] == 8
    assert _custom_calls(jax.jit(
        lambda q, k, v, bt, n: pa.paged_attention(
            q, k, v, bt, n, impl="kernel")).lower(
        S((B, 30, 128), bf), kp, kp, S((B, 1072), i32), S((B,), i32)
    ).compile()) == 1
    for N, P in ((32, 64), (16, 16)):
        assert _custom_calls(jax.jit(
            lambda q, k, v, bt, a, b: pa.prefix_attention(
                q, k, v, bt, a, b, impl="kernel")).lower(
            S((N, P, 30, 128), bf), kp, kp, S((N, 1072), i32), S((N,), i32),
            S((N,), i32)).compile()) == 1


def test_ring_kernels_and_keys_of_192_compile_for_v5e(v5e_devices,
                                                      monkeypatch):
    """The kernels arch "mimo_v2" brings, alone at the cell's shapes, so that
    a Mosaic refusal shows here: `window_ring_step` at 64 slots over rings of
    [8, 128, 256 | 128] by state id (the 16 slots around the new one written
    back in place), `window_ring_chunk` at the narrowest and the widest
    rung's rows; and the two paged kernels at 64 query / 4 kv heads with keys
    of 192 in pools of 256 lanes beside values of 128 under 1,072-column
    tables, alone and with the sets of a shared prefix: NONE through the
    narrow form (a head size that is no multiple of 128 took it before)."""
    from ray_tpu.ops import paged_attention as pa
    from ray_tpu.ops import window_ring as wr

    def no_narrow(*args, **kw):
        raise AssertionError("the narrow paged form")

    monkeypatch.setattr(pa, "_narrow_call", no_narrow)
    on_chip, _ = _on_chip_shapes(v5e_devices)
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32

    def S(shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)

    H, hkv, W, dk, dv, B = 64, 8, 128, 192, 128, 64
    P = B // 2
    shk, shv = wr.ring_shapes(256, hkv, W, dk, dv)
    assert (shk, shv) == ((257, 8, 128, 256), (257, 8, 128, 128))
    assert _custom_calls(jax.jit(
        lambda *a: wr.window_ring_step(*a, impl="kernel"),
        donate_argnums=(0, 1)).lower(
        S(shk), S(shv), S((B,), i32), S((B,), i32), S((B, H, dk)),
        S((B, hkv, dk)), S((B, hkv, dv)), S((H,), f32)).compile()) == 1
    for N in (16, 128):
        assert _custom_calls(jax.jit(
            lambda *a: wr.window_ring_chunk(*a, impl="kernel"),
            donate_argnums=(0, 1)).lower(
            S(shk), S(shv), S((N,), i32), S((N, 2), i32), S((N,), i32),
            S((N,), i32), S((N, 16, H, dk)), S((N, 16, hkv, dk)),
            S((N, 16, hkv, dv)), S((H,), f32)).compile()) == 1
    kp, vp = S((8193, 4, 16, 256)), S((8193, 4, 16, 128))
    assert pa._ring_shape(1072, 4, 16, 192, 2) == (8, 8, 2)
    alone = jax.jit(lambda q, k, v, bt, n: pa.paged_attention(
        q, k, v, bt, n, impl="kernel")).lower(
        S((B, H, dk)), kp, vp, S((B, 1072), i32), S((B,), i32))
    assert alone.out_info.shape == (B, H, dv)
    assert _custom_calls(alone.compile()) == 1
    together = jax.jit(lambda q, k, v, bt, n, *rows: pa.paged_attention(
        q, k, v, bt, n, impl="kernel", shared=pa.SharedRows(*rows))).lower(
        S((B, H, dk)), kp, vp, S((B, 1072), i32), S((B,), i32),
        S((P, 8), i32), S((P, 1072), i32), S((P,), i32), S((B,), i32),
        S((B,), i32), S((), jnp.bool_))
    assert _custom_calls(together.compile()) == 2
    # the exact-bytes key layout the on-chip test times against this one
    # (tests_tpu/test_mimo_kernels_on_device.py): pairs of kv heads side by
    # side in 384 | 256 lanes through the same kernel
    pairs = jax.jit(lambda q, k, v, bt, n: pa.paged_attention(
        q, k, v, bt, n, impl="kernel", scale=dk ** -0.5)).lower(
        S((B, H, 2 * dk)), S((8193, 2, 16, 2 * dk)),
        S((8193, 2, 16, 2 * dv)), S((B, 1072), i32), S((B,), i32))
    assert pairs.out_info.shape == (B, H, 2 * dv)
    assert _custom_calls(pairs.compile()) == 1
    assert _custom_calls(jax.jit(
        lambda q, k, v, bt, n, *rows: pa.paged_attention(
            q, k, v, bt, n, impl="kernel", scale=dk ** -0.5,
            shared=pa.SharedRows(*rows))).lower(
        S((B, H, 2 * dk)), S((8193, 2, 16, 2 * dk)),
        S((8193, 2, 16, 2 * dv)), S((B, 1072), i32), S((B,), i32),
        S((P, 8), i32), S((P, 1072), i32), S((P,), i32), S((B,), i32),
        S((B,), i32), S((), jnp.bool_)).compile()) == 2
    for N, rows in ((32, 64), (16, 16)):
        prefix = jax.jit(lambda q, k, v, bt, a, b: pa.prefix_attention(
            q, k, v, bt, a, b, impl="kernel")).lower(
            S((N, rows, H, dk)), kp, vp, S((N, 1072), i32), S((N,), i32),
            S((N,), i32))
        assert prefix.out_info.shape == (N, rows, H, dv)
        assert _custom_calls(prefix.compile()) == 1


def test_latent_and_blocked_kernels_compile_for_v5e(v5e_devices):
    """The kernels arch "axk1" brings, alone at the cell's shapes, so that a
    Mosaic refusal (a 640-lane row, a [64, 640] query tile, two 22 MB weight
    blocks in VMEM) shows here: `mla_paged_attention` at 64 slots,
    `mla_prefix_attention` at a rung's 80 attention rows of 64 queries and
    at ungrouped rows of 16, the expert product at hidden 7168 x width 2048
    for a decode step's rows (tiles of 16) and a pass's (tiles of 256)."""
    from ray_tpu.ops import grouped_ffn as gf
    from ray_tpu.ops import paged_attention as pa

    on_chip, _ = _on_chip_shapes(v5e_devices)

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)

    kw = dict(scale=0.130861, v_dim=512, impl="kernel")
    pool, i32 = S((8193, 1, 16, 640)), jnp.int32
    assert _custom_calls(jax.jit(
        lambda q, p, bt, n: pa.mla_paged_attention(q, p, bt, n, **kw)).lower(
        S((64, 64, 576)), pool, S((64, 1072), i32), S((64,), i32)
    ).compile()) == 1
    for N, P in ((80, 64), (16, 16)):
        assert _custom_calls(jax.jit(
            lambda q, p, bt, a, b: pa.mla_prefix_attention(
                q, p, bt, a, b, **kw)).lower(
            S((N, P, 64, 576)), pool, S((N, 1072), i32), S((N,), i32),
            S((N,), i32)).compile()) == 1
    D, F, E = 7168, 2048, 12
    assert gf._f_blocks(D, F, 2) == 4
    for T in (64, 2112):
        assert _custom_calls(jax.jit(
            lambda x, i, w, v, a, b, c: gf.grouped_ffn(
                x, i, w, v, a, b, c, name="moe_experts_decode",
                impl="kernel")).lower(
            S((T, D)), S((T, 8), i32), S((T, 8), jnp.float32),
            S((T, 8), jnp.bool_), S((E, D, F)), S((E, D, F)), S((E, F, D))
        ).compile()) == 1


@pytest.mark.parametrize("tokens", [64, 2112])
def test_expert_plan_is_one_sort_and_no_scatter_on_v5e(v5e_devices, tokens):
    """What `grouped_ffn` runs around its kernel, read off the compiled text
    at Qwen3-Next's decode step (64 slots) and widest pass (2,048 positions
    + 64 slots' rows), 10 picks over a router of 512 of which 128 are held:
    under the scope `moe_route` ONE sort, no scatter (a scattered element
    costs the chip tens of ns: PERF.md, PR 45 and 52), no loop (a
    `searchsorted`, a gather of windows), and beside the two gathers of rows
    (x into the tiles, the tiles' output back to the pairs) at most one
    gather with more indices than there are tiles."""
    from ray_tpu.ops import grouped_ffn as gf

    on_chip, _ = _on_chip_shapes(v5e_devices)

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)

    E, width, K, D, F = 128, 512, 10, 2048, 512
    tm = gf.tile_rows(tokens * K, width)
    n_tiles = -(-(tokens * K + E * (tm - 1)) // tm) + 1
    compiled = jax.jit(
        lambda x, i, w, v, a, b, c: gf.grouped_ffn(
            x, i, w, v, a, b, c, name="moe_experts_prefill", impl="kernel",
            router_width=width)).lower(
        S((tokens, D)), S((tokens, K), jnp.int32),
        S((tokens, K), jnp.float32), S((tokens, K), jnp.bool_),
        S((E, D, F)), S((E, D, F)), S((E, F, D))).compile()
    assert _custom_calls(compiled) == 1
    found = {"sort": 0, "scatter": 0, "while": 0, "rows": 0, "long": 0}
    for line in compiled.as_text().splitlines():
        op = re.search(r"[\]})] (sort|scatter|while|gather)\(", line)
        if not op or "moe_route" not in line:
            continue
        if op.group(1) != "gather":
            found[op.group(1)] += 1
            continue
        out = math.prod(int(d) for d in re.search(
            r"= [a-z0-9]+\[([0-9,]+)\]", line).group(1).split(","))
        taken = math.prod(int(d) for d in re.search(
            r"slice_sizes=\{([0-9,]+)\}", line).group(1).split(","))
        if out // taken > n_tiles:
            found["rows" if taken == D else "long"] += 1
    assert found["sort"] == 1 and not found["scatter"] \
        and not found["while"], found
    assert found["rows"] == 2 and found["long"] <= 1, found


@pytest.mark.parametrize("pool_kind", ["keys-and-values", "latent"])
def test_shared_prefix_kernels_compile_for_v5e(v5e_devices, pool_kind):
    """The decode kernels with `shared` (a step's two calls behind their
    branch), alone at the cells' shapes, so that a Mosaic refusal shows
    before the chip: Olmo-Hybrid's 30 kv heads with 8 members' rows a head
    and Mistral's 8 with 32, under 1,072-column tables; the latent pool's q
    block of 8 members x 64 heads = 512 rows of 640 lanes."""
    from ray_tpu.ops import paged_attention as pa

    on_chip, _ = _on_chip_shapes(v5e_devices)
    bf, i32 = jnp.bfloat16, jnp.int32

    def S(shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)

    def shared(B, W=1072):
        P = B // 2
        return pa.SharedRows(S((P, pa.SHARED_MEMBERS), i32), S((P, W), i32),
                             S((P,), i32), S((B,), i32), S((B,), i32),
                             S((), jnp.bool_))

    if pool_kind == "latent":
        kw = dict(scale=0.130861, v_dim=512, impl="kernel")
        compiled = jax.jit(
            lambda q, p, bt, n, sh: pa.mla_paged_attention(
                q, p, bt, n, shared=sh, **kw)).lower(
            S((64, 64, 576)), S((8193, 1, 16, 640)), S((64, 1072), i32),
            S((64,), i32), shared(64)).compile()
        assert _custom_calls(compiled) == 2
        return
    for B, H, hkv in ((32, 30, 30), (32, 32, 8)):
        kp = S((4097, hkv, 16, 128))
        compiled = jax.jit(
            lambda q, k, v, bt, n, sh: pa.paged_attention(
                q, k, v, bt, n, impl="kernel", shared=sh)).lower(
            S((B, H, 128)), kp, kp, S((B, 1072), i32), S((B,), i32),
            shared(B)).compile()
        assert _custom_calls(compiled) == 2
