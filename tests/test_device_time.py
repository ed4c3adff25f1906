"""Device time by program, then by scope inside it (ISSUE 57): the reader
`profiling.device_time` on the two recorded v5e traces, its wire decoder
against `ProfileData`, scopes and passes from name stacks the TPU compiler
wrote, labels from the engine's launch spans, the engine's always-on
counters by program, and the six metric files that read them.  CPU: events,
recorded durations and counts; never a device number of this host."""

import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.lib import (reductions, spec, trace_reduce,  # noqa: E402
                            worker_util)
from ray_tpu.models import transformer as tfm  # noqa: E402
from ray_tpu.ops import scopes  # noqa: E402
from ray_tpu.serve import llm  # noqa: E402
from ray_tpu.util import profiling  # noqa: E402
from ray_tpu.util.profiling import COMPILER, NO_SCOPE  # noqa: E402

DATA = os.path.join(spec.BENCH_DIR, "tests", "data")
TINY = os.path.join(DATA, "tiny_v5e.xplane.pb")      # three calls, a kernel
SPANS = os.path.join(DATA, "spans_v5e.xplane.pb")    # six, under engine.*
# (scope, category): (events, device ms) as ISSUE 57's table has them, read
# there with tensorflow's xplane_pb2; the program's id and module calls.
RECORDED = {
    TINY: (1571230938277938502, 3, {
        ("paged_attention", "custom-call"): (3, 0.5525),
        (NO_SCOPE, "convolution fusion"): (12, 0.1388),
        (COMPILER, "data formatting"): (15, 0.0163),
        (COMPILER, "copy-done"): (12, 0.0078)}),
    SPANS: (10758763052923717233, 6, {
        (NO_SCOPE, "convolution fusion"): (24, 0.2774),
        (COMPILER, "copy-done"): (12, 0.0391),
        (COMPILER, "data formatting"): (24, 0.0127)}),
}


# -- (i) the reader on the recorded traces --------------------------------------
@pytest.fixture(scope="module", params=[TINY, SPANS],
                ids=["tiny_v5e", "spans_v5e"])
def read(request):
    return request.param, profiling.device_time(request.param)


def test_one_program_by_its_id_with_its_module_calls(read):
    path, out = read
    program_id, calls, _ = RECORDED[path]
    prog, = out["programs"]
    assert (prog["program_id"], prog["module"]) == (program_id,
                                                    "jit_tiny_program")
    assert prog["calls"] == calls and out["devices"] == 1
    # (the first and the last event of a line may be cut by the window:
    # these are not, and the mean of the others is the mean of all)
    assert prog["ms_per_call"] == pytest.approx(
        1e3 * prog["seconds"] / calls, rel=0.02)
    assert 0 < prog["op_seconds"] <= prog["seconds"]
    assert out["busy_s"] == pytest.approx(prog["op_seconds"])
    assert out["read_s"] < 5.0


def test_scopes_and_categories_are_the_recorded_ones(read):
    path, out = read
    rows = {(scope, part["category"]): (part["events"], part["seconds"])
            for scope, under in out["programs"][0]["scopes"].items()
            for part in under["parts"]}
    for key, (events, ms) in RECORDED[path][2].items():
        assert rows[key][0] == events, key
        assert round(1e3 * rows[key][1], 4) == ms, key
    # the toy programs enter no scope of the table but the kernel's name
    assert {s for s, _ in rows} <= {"paged_attention", NO_SCOPE, COMPILER}


def test_parts_sum_to_the_programs_op_seconds(read):
    _, out = read
    prog, = out["programs"]
    parts = [part for under in prog["scopes"].values()
             for part in under["parts"]]
    assert sum(p["seconds"] for p in parts) == pytest.approx(
        prog["op_seconds"], rel=1e-12)
    for under in prog["scopes"].values():
        assert under["seconds"] == pytest.approx(
            sum(p["seconds"] for p in under["parts"]))
    # (compiler): what has no name stack, listed by category, never dropped
    assert prog["scopes"][COMPILER]["seconds"] > 0
    assert all(p["pass"] == "" for p in prog["scopes"][COMPILER]["parts"])
    # XLA's own counts ride along: the products have operations and bytes,
    # the Mosaic call has neither
    # (a [1024, 1024] bf16 product: 2 x 1024^3 operations and change, two
    # operands read and one result written)
    product, = [p for p in parts if p["category"] == "convolution fusion"]
    assert product["flops"] / product["events"] == pytest.approx(
        2 * 1024 ** 3, rel=0.01)
    assert product["bytes_accessed"] == product["events"] * 3 * 2 * 1024 ** 2
    for p in parts:
        if p["category"] == "custom-call":
            assert p["flops"] == 0


def test_the_printed_form_names_every_program_and_scope(read):
    _, out = read
    text = profiling.format_device_time(out, scopes=3)
    prog, = out["programs"]
    assert f"jit_tiny_program({prog['program_id']})" in text
    assert f"{prog['calls']:g} calls" in text and "TFLOP/s" in text
    assert "more rows" in text and "100.0 % of busy" in text


# -- (ii) the wire decoder against ProfileData -----------------------------------
@pytest.mark.parametrize("path", [TINY, SPANS], ids=["tiny_v5e", "spans_v5e"])
def test_decoder_reads_the_events_profile_data_reads(path):
    theirs, = trace_reduce.read_xplane(path)
    ours, = [p for p in profiling.read_xspace(path)
             if p["name"] == theirs["name"]]
    lines = {ln["name"]: ln["events"] for ln in ours["lines"]}
    assert set(lines) == {"XLA Modules", "XLA Ops"}
    for line, key in (("XLA Ops", "ops"), ("XLA Modules", "modules")):
        assert len(lines[line]) == len(theirs[key])
        for (mid, start, duration, _), (name, s, e) in zip(lines[line],
                                                          theirs[key]):
            assert trace_reduce.op_name(ours["metadata"][mid]["name"]) == name
            # (ProfileData hands out whole nanoseconds; the file has ps)
            assert start == pytest.approx(s, abs=1.0)
            assert duration == pytest.approx(e - s, abs=1.0)


@pytest.mark.parametrize("path", [TINY, SPANS], ids=["tiny_v5e", "spans_v5e"])
def test_decoder_reads_the_host_spans_profile_data_reads(path):
    theirs = profiling.read_host_spans(path)
    ours = []
    for plane in profiling.read_xspace(path):
        if plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                for ev in line["events"]:
                    name = plane["metadata"][ev[0]]["name"]
                    if name.startswith(profiling.SPAN_PREFIXES):
                        ours.append((name, ev[1], ev[2],
                                     profiling.event_stats(plane, ev)))
    assert len(ours) == len(theirs) == {TINY: 0, SPANS: 15}[path]
    for (name, start, duration, stats), sp in zip(ours, theirs):
        assert name == sp["name"] and stats == sp["stats"]
        assert start / 1e9 == pytest.approx(sp["start"], abs=1e-9)
        assert duration / 1e9 == pytest.approx(sp["end"] - sp["start"],
                                               abs=1e-9)


def test_the_reader_needs_only_the_standard_library_and_jax():
    """`device_time` is the program's: where the chip is, tensorflow's
    xplane_pb2 need not be."""
    import subprocess
    code = ("import sys; from ray_tpu.util import profiling\n"
            f"profiling.device_time({TINY!r})\n"
            "print(sorted(m for m in sys.modules if m.startswith(("
            "'tensorflow', 'tsl', 'google.protobuf', 'numpy', 'jax'))))")
    out = subprocess.run([sys.executable, "-c", code], text=True, timeout=120,
                         capture_output=True, cwd=spec.ROOT)
    assert out.stdout.strip() == "[]", (out.stdout, out.stderr[-2000:])


def test_a_file_that_is_no_xplane_is_refused(tmp_path):
    bad = tmp_path / "bad.xplane.pb"
    bad.write_bytes(b"\x0b\x00\x00")        # wire type 3: a group
    with pytest.raises(ValueError, match="not an xplane"):
        profiling.read_xspace(str(bad))


# -- (iii) scope and pass from a name stack ---------------------------------------
# Name stacks as the TPU compiler kept them (`metadata={op_name=...}` of the
# programs tests/test_tpu_aot.py compiles for v5e:2x2: train-4k-1chip's step,
# serve-batch-saturated's and serve-qw3n-agent-sessions' decode chunk and
# narrowest fused pass; the trace carries the same strings as `tf_op`, with
# a ":" after them).
STEP = "jit(step_fn)/"
TRIP = "while/body/closed_call/"
DECODE = "jit(paged_decode_steps)/" + TRIP
FUSED = "jit(paged_prefill_decode_packed)/" + TRIP


@pytest.mark.parametrize("stack, scope, pass_", [
    (STEP + "jvp()/" + TRIP + "attn_qkv/bsd,dhk->bshk/dot_general",
     "attn_qkv", "fwd"),
    (STEP + "jvp()/" + TRIP + "ffn_gate_up/jit(silu)/exp",
     "ffn_gate_up", "fwd"),
    (STEP + "jvp()/" + TRIP + "ffn_down/bsf,fd->bsd/dot_general",
     "ffn_down", "fwd"),
    (STEP + "jvp(embed)/gather", "embed", "fwd"),
    (STEP + "jvp(norm)/rsqrt", "norm", "fwd"),
    (STEP + "transpose(jvp(norm))/reduce_sum", "norm", "bwd"),
    (STEP + "transpose(jvp())/" + TRIP + "checkpoint/ffn_down/"
     "bsf,fd->bsd/dot_general", "ffn_down", "bwd"),
    (STEP + "transpose(jvp())/" + TRIP + "checkpoint/attn_qkv/"
     "bsd,dhk->bshk/dot_general", "attn_qkv", "bwd"),
    (STEP + "transpose(jvp())/" + TRIP + "checkpoint/rematted_computation/"
     "ffn_gate_up/bsd,df->bsf/dot_general", "ffn_gate_up", "recompute"),
    (STEP + "transpose(jvp())/" + TRIP + "checkpoint/rematted_computation/"
     "attn_out/bshk,hkd->bsd/dot_general", "attn_out", "recompute"),
    (STEP + "transpose(jvp())/" + TRIP + "checkpoint/rematted_computation/"
     "norm/mul", "norm", "recompute"),
    # the chunked loss makes its gradient inside its forward trip
    (STEP + "jvp(xent)/" + TRIP + "bcd,bdv->bcv/dot_general",
     "xent", "fwd"),
    (STEP + "jvp(xent)/" + TRIP + "jvp()/abs", "xent", "fwd"),
    (STEP + "jvp(xent)/" + TRIP + "transpose(jvp())/mul", "xent", "bwd"),
    (STEP + "jvp(xent)/" + TRIP + "jvp(jit(take_along_axis))/gather",
     "xent", "fwd"),
    # the flash kernels have no name: the scope around them has
    (STEP + "jvp()/" + TRIP + "attn/cond/" + STEP + "jvp()/" + TRIP
     + "attn/cond/branch_0_fun/pallas_call", "attn", "fwd"),
    (STEP + "transpose(jvp())/" + TRIP + "checkpoint/attn/cond/"
     "branch_0_fun/pallas_call", "attn", "bwd"),
    (STEP + "optimizer/sqrt", "optimizer", "fwd"),
    (STEP + "transpose(jvp())/" + TRIP + "remat2", NO_SCOPE, "bwd"),
    (STEP + "jvp()/while/body/dynamic_slice", NO_SCOPE, "fwd"),
    # serving: a kernel's own name is the innermost scope
    (DECODE + TRIP + "attn/jit(paged_attention_kernel)/cond/branch_0_fun/"
     "paged_attention/pallas_call", "paged_attention", "fwd"),
    (DECODE + TRIP + "attn/jit(paged_attention_kernel)/cond/branch_0_fun/"
     "jit(_pad)/pad", "attn", "fwd"),
    (DECODE + TRIP + "kv_write/jit(_where)/select_n", "kv_write", "fwd"),
    (DECODE + TRIP + "ffn_gate_up/jit(silu)/exp", "ffn_gate_up", "fwd"),
    (DECODE + "head/bsd,dv->bsv/dot_general", "head", "fwd"),
    (DECODE + "embed/gather", "embed", "fwd"),
    (FUSED + "attn/jit(prefix_attention_kernel)/cond/branch_0_fun/"
     "prefix_attention/pallas_call", "prefix_attention", "fwd"),
    (FUSED + "norm/rsqrt", "norm", "fwd"),
    ("jit(paged_prefill_decode_packed)/jit(take_along_axis)/gather",
     NO_SCOPE, "fwd"),
    # the recorded traces' own: a kernel under a platform switch, a product
    ("jit(tiny_program)/cond/branch_0_fun/paged_attention/pallas_call:",
     "paged_attention", "fwd"),
    ("jit(tiny_program)/while/body/closed_call/dot_general:",
     NO_SCOPE, "fwd"),
    # an expert layer's parts, a mixer's
    (FUSED + "moe_shared/moe_shared/bsd,df->bsf/dot_general",
     "moe_shared", "fwd"),
    (FUSED + "jit(grouped_ffn)/moe_route/sort", "moe_route", "fwd"),
    ("jit(paged_prefill_decode_packed)/jit(grouped_ffn)/cond/branch_0_fun/"
     "moe_experts_prefill/pallas_call", "moe_experts_prefill", "fwd"),
    ("jit(paged_prefill_decode_packed)/delta_rule/jit(gated_delta_chunk)/"
     "cond/branch_0_fun/gated_delta_chunk/pallas_call",
     "gated_delta_chunk", "fwd"),
    # (a jitted wrapper that has its kernel's name: what it does around the
    # call is the kernel's too, told apart by category)
    (DECODE + "delta_rule/jit(gated_delta_step)/cond/branch_0_fun/"
     "jit(_pad)/pad", "gated_delta_step", "fwd"),
    (FUSED + "gated_attn_q/norm/rsqrt", "norm", "fwd"),
    ("", COMPILER, ""),
    (":", COMPILER, ""),
])
def test_scope_is_the_innermost_name_of_the_table(stack, scope, pass_):
    assert profiling.scope_of(stack) == (scope, pass_)
    assert profiling.scope_of(stack + ":") == (scope, pass_)


def test_the_table_holds_every_name_the_programs_enter():
    """One table: no model or kernel names a scope with a literal."""
    import re
    root = os.path.join(spec.ROOT, "ray_tpu")
    literal = re.compile(r"named_scope\(\s*[\"']|pallas_call\([^)]*name=[\"']",
                         re.S)
    for sub in ("models", "ops", "train", "serve"):
        for name in sorted(os.listdir(os.path.join(root, sub))):
            if name.endswith(".py"):
                with open(os.path.join(root, sub, name)) as f:
                    assert not literal.search(f.read()), f"{sub}/{name}"
    assert {"attn_qkv", "attn_out", "ffn_gate_up", "ffn_down", "norm",
            "embed", "head", "xent", "kv_write", "short_conv", "moe_route",
            "moe_shared", "moe_experts_decode", "moe_experts_prefill",
            "mla_q", "mla_kv", "mla_out", "delta_proj", "delta_rule",
            "delta_out", "gated_attn_q", "gated_attn_out", "ring_attn_qkv",
            "full_attn_qkv", "ring_attn", "ring_out", "full_attn_out",
            "paged_attention", "prefix_attention", "mla_paged_attention",
            "mla_prefix_attention", "gated_delta_step", "gated_delta_chunk",
            "window_ring_step", "window_ring_chunk"} <= scopes.SCOPES
    assert all(isinstance(s, str) and s and "/" not in s and "(" not in s
               for s in scopes.SCOPES)


def test_the_dense_layers_enter_their_scopes():
    """The Mistral path's programs carry the names in every instruction's
    stack (read off the CPU compile: the same stacks the TPU's keeps)."""
    import re
    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=1,
        d_ff=64, max_seq=32, arch="llama", rope_theta=10000.0,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=True,
        remat_policy="names", xent_chunk=16)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 17), jnp.int32)
    text = jax.jit(jax.grad(lambda p: tfm.loss_fn(p, tokens, cfg)[0])).lower(
        params).compile().as_text()
    found = {profiling.scope_of(s)
             for s in re.findall(r'op_name="([^"]*)"', text)}
    for scope in ("attn_qkv", "attn", "attn_out", "ffn_gate_up", "ffn_down",
                  "norm"):
        assert (scope, "fwd") in found and (scope, "bwd") in found, scope
    assert ("ffn_gate_up", "recompute") in found
    assert {("embed", "fwd"), ("xent", "fwd"), ("xent", "bwd")} <= found


# -- (iv) labels: module events against the host's launch spans ------------------
def test_the_recorded_launches_pair_with_the_recorded_modules():
    """spans_v5e: a feeder thread in the engine's own span names, two
    launches a dispatch, six module events; its dispatch spans carry a
    `seq` and no `kind`, so the launches have no label to give."""
    planes = profiling.read_xspace(SPANS)
    launches = profiling._launches(planes)
    assert len(launches) == 6 and {lb for _, lb in launches} == {None}
    assert launches == sorted(launches)
    out = profiling.device_time(SPANS)
    prog, = out["programs"]
    assert prog["label"] is None
    assert out["unlabelled"] == [{
        "program_id": prog["program_id"], "module": "jit_tiny_program",
        "labels": {"None": 6.0}, "unpaired": 0.0}]
    # tiny_v5e has no span at all: nothing pairs, and the result says so
    out = profiling.device_time(TINY)
    assert out["programs"][0]["label"] is None
    assert out["unlabelled"][0]["labels"] == {}
    assert out["unlabelled"][0]["unpaired"] == 3.0


@pytest.mark.parametrize("labels, label, reported", [
    (["decode"] * 6, "decode", None),
    (["640", "640", "decode", "640", "640", "640"], None,
     {"640": 5.0, "decode": 1.0}),
    ([profiling.TRAIN_LABEL] * 6, profiling.TRAIN_LABEL, None),
])
def test_a_program_takes_the_one_label_its_events_agree_on(
        monkeypatch, labels, label, reported):
    """The recorded spans with `kind`s written in: one program whose six
    events agree is labelled; a doctored pairing that gives it two labels
    is reported, never folded in."""
    real = profiling._launches

    def doctored(planes):
        return [(t, lb) for (t, _), lb in zip(real(planes), labels)]

    monkeypatch.setattr(profiling, "_launches", doctored)
    out = profiling.device_time(SPANS)
    prog, = out["programs"]
    assert prog["label"] == label
    if reported is None:
        assert out["unlabelled"] == []
    else:
        assert out["unlabelled"][0]["labels"] == reported
        assert "unlabelled" in profiling.format_device_time(out)


def _engine_window(kinds, first=0, last=None, lag=0.004, busy=0.1):
    """A device that runs `kinds` back to back, `busy` seconds each, fed two
    ahead: launch i starts `lag` after module i - 2 ended.  The trace holds
    modules [first:] and launches [:last]."""
    modules, launches, end = [], [], []
    for i, kind in enumerate(kinds):
        launch = (end[i - 2] if i >= 2 else 0.0) + lag
        start = max(launch + 0.001, end[i - 1] if i else 0.0)
        launches.append((launch, kind))
        modules.append((start, 7 if kind == "decode" else int(kind)))
        end.append(start + busy)
    return modules[first:], launches[:last]


@pytest.mark.parametrize("first, last, skew", [
    (0, None, 0.0), (2, None, 0.0), (0, 9, 0.0), (3, 10, 0.0),
    (1, None, -0.001), (2, 11, -0.0015), (0, None, 0.001)])
def test_pairing_finds_where_a_cut_window_starts(first, last, skew):
    """A window cut anywhere, the device's clock a millisecond off either
    way: every module still meets its own launch."""
    kinds = ["decode", "640", "decode", "256", "2048", "decode", "640",
             "decode", "896", "640", "decode", "256"]
    modules, launches = _engine_window(kinds, first, last)
    modules = [(t + skew, p) for t, p in modules]
    o = profiling.pair_launches(modules, launches)
    assert o == first
    paired = [(program, launches[i + o][1])
              for i, (_, program) in enumerate(modules)
              if i + o < len(launches)]
    assert len(paired) >= 7
    assert all(program == (7 if label == "decode" else int(label))
               for program, label in paired)


def test_pairing_of_nothing_pairs_nothing():
    assert profiling.pair_launches([], []) == 0
    assert profiling.pair_launches([(0.0, 1)], []) == 0
    assert profiling.pair_launches([], [(0.0, "decode")]) == 1
    # a module that started long before the only launch: not its module
    assert profiling.pair_launches([(0.0, 1)], [(5.0, "decode")]) == 1


# -- (v) the engine's counters by program -----------------------------------------
def rung_engine():
    """A CPU engine with the cells' own ladder: 256 / 640 / 896 / 2,048."""
    cfg = tfm.TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq=640, arch="llama", rope_theta=10000.0,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False)
    params = tfm.init_params(cfg, jax.random.PRNGKey(11))
    eng = llm.PagedBatcher(params, cfg, num_slots=4, max_len=576,
                           prompt_pad=512, decode_chunk=4, kv_block_size=16,
                           kv_num_blocks=160)
    deadline = time.time() + 200
    while not eng._warmed and time.time() < deadline:
        time.sleep(0.05)
    assert eng._warmed
    return eng


def serve(eng, lengths, max_new=6):
    reqs = [eng.submit([(n * 31 + 7 * i) % 251 + 1 for i in range(n)],
                       max_new=max_new) for n in lengths]
    assert all(r.done.wait(200) and r.error is None for r in reqs)


def drained(eng):
    deadline = time.time() + 60
    while time.time() < deadline:
        with eng._dev_lock:
            if eng._empty_since is not None and not eng._inflight:
                return
        time.sleep(0.01)
    raise AssertionError("the engine did not drain")


@pytest.fixture(scope="module")
def counted(tmp_path_factory):
    """An engine that ran every program it has, under the profiler, and
    what serve_cell would hand a metric file for it: after - before of
    stats()'s numbers."""
    import glob
    directory = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0             # as the harness traces
    eng = rung_engine()
    try:
        fresh = eng.program_stats()
        before = worker_util.numeric_leaves(
            {"program": fresh, "host": eng.host_stats()})
        jax.profiler.start_trace(directory, profiler_options=options)
        t0 = time.perf_counter()
        for lengths in ([40], [300, 250], [200] * 4, [500, 500, 500, 400]):
            serve(eng, lengths)
        drained(eng)
        wall = time.perf_counter() - t0
        jax.profiler.stop_trace()
        after = worker_util.numeric_leaves(
            {"program": eng.program_stats(), "host": eng.host_stats()})
        rungs = dict(eng.kv_stats()["prefill"]["rung_dispatches"])
    finally:
        eng.stop()
    path, = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return {"fresh": fresh, "before": before, "after": after, "wall": wall,
            "rungs": rungs, "deltas": worker_util.deltas(before, after),
            "trace": path}


LABELS = ["decode", "256", "640", "896", "2048"]


def test_every_label_has_its_key_at_zero_from_construction(counted):
    """`worker_util.deltas` keeps only what the first reading held."""
    assert counted["fresh"] == {
        "device_s": dict.fromkeys(LABELS, 0.0),
        "dispatches": dict.fromkeys(LABELS, 0)}
    for label in LABELS:
        assert counted["before"][f"program.device_s.{label}"] == 0.0
        assert counted["before"][f"program.dispatches.{label}"] == 0.0


def test_dispatches_by_program_add_up_to_the_launches(counted):
    d = counted["deltas"]
    assert sum(d[f"program.dispatches.{lb}"] for lb in LABELS) == \
        d["host.dispatches"] > 0
    # the fused ones are the rungs the engine counts at its launches
    for width, n in counted["rungs"].items():
        assert d[f"program.dispatches.{width}"] == n > 0
    assert d["program.dispatches.decode"] > 0


def test_device_seconds_by_program_fit_in_the_wall_time(counted):
    """Stretches that do not overlap: from the arrival before (or the
    launch, where the device was empty) to the arrival."""
    d = counted["deltas"]
    seconds = [d[f"program.device_s.{lb}"] for lb in LABELS]
    assert all(s > 0 for s in seconds)
    assert sum(seconds) <= counted["wall"]
    # ... and beside the starved stretches still inside it
    assert sum(seconds) + d["host.device_starved"] <= counted["wall"] * 1.001


def test_a_launch_is_labelled_by_the_dispatch_around_it(counted):
    """The engine's own trace (this host: no device plane in it): every
    `engine.launch` takes "decode" or its fused pass's positions from the
    `engine.dispatch` span around it, as many of each as the counters say
    were read back."""
    import collections
    launches = profiling._launches(profiling.read_xspace(counted["trace"]))
    got = collections.Counter(label for _, label in launches)
    want = {lb: counted["deltas"][f"program.dispatches.{lb}"]
            for lb in LABELS}
    assert got == want and None not in got
    assert launches == sorted(launches)
    out = profiling.device_time(counted["trace"])
    assert (out["devices"], out["programs"], out["busy_s"]) == (0, [], 0.0)


PROGRAM_METRICS = {
    "engine_decode_program_ms": "decode",
    "chat_decode_program_ms": "decode",
    "engine_fused_256_program_ms": "256",
    "engine_fused_640_program_ms": "640",
    "engine_fused_896_program_ms": "896",
    "engine_fused_2048_program_ms": "2048",
}


@pytest.mark.parametrize("name", sorted(PROGRAM_METRICS))
def test_program_metric_file_reads_a_number_in_its_cells(counted, name):
    bench = spec.load_benchmark()
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    assert (entry["source"], entry["layer"], entry["better"], entry["unit"]
            ) == ("program_counter", "Decode/prefill steps", "lower", "ms")
    chat = name.startswith("chat_")
    moved, = [m for m in bench["end_to_end"] if m["name"] == (
        "tpot_p95_ms" if chat else "decode_tokens_per_s")]
    assert entry["moves"] == moved["name"]
    # Only the cells whose every window runs the program: a traced line that
    # lacks a metric of its cell is refused (chat's decode chunk runs in all
    # of its one cell).
    assert entry["workloads"] and \
        set(entry["workloads"]) <= set(moved["workloads"])
    assert not chat or entry["workloads"] == moved["workloads"]
    label = PROGRAM_METRICS[name]
    d = counted["deltas"]
    want = 1e3 * d[f"program.device_s.{label}"] / \
        d[f"program.dispatches.{label}"]
    obs = {"counters": d, "series": {}, "trace": {}}
    for cell in entry["workloads"]:
        loaded = spec.load_cell(cell)
        m, = [m for m in loaded["layer_metrics"] if m["name"] == name]
        assert cell in m["cells"] and m["moves"] == entry["moves"]
        value = reductions.read_metric(m, obs)
        assert isinstance(value, float) and value == pytest.approx(want)
    # A program without the counters (the parent), and a rung the window
    # never ran: nothing to read, the line leaves the metric out.
    assert reductions.read_metric(m, dict(obs, counters={})) is None
    never = dict(d, **{f"program.dispatches.{label}": 0.0,
                       f"program.device_s.{label}": 0.0})
    assert reductions.read_metric(m, dict(obs, counters=never)) is None
