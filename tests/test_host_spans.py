"""The engine's and the trainer's host spans on the device profiler's clock,
the always-on accounting of whether the device had anything to run, the
reader that lays an idle gap at a span, and the metric files that read the
new counters (ISSUE 42).  CPU: spans, counts and seconds of sleeps; never a
device number."""

import glob
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.lib import (reductions, spec, trace_reduce,  # noqa: E402
                            worker_util)
from ray_tpu.models import transformer as tfm  # noqa: E402
from ray_tpu.serve import llm  # noqa: E402
from ray_tpu.train.telemetry import TrainTelemetry  # noqa: E402
from ray_tpu.util import profiling  # noqa: E402

DISPATCHER_SPANS = [llm.SPAN_PERMIT_WAIT, llm.SPAN_STARVED, llm.SPAN_DISPATCH,
                    llm.SPAN_ADMIT, llm.SPAN_PACK, llm.SPAN_LAUNCH,
                    llm.SPAN_POST_ADMIT]
PROCESSOR_SPANS = [llm.SPAN_READ_WAIT, llm.SPAN_HAND_OUT]
TRAIN_SPANS = ["train.data_wait", "train.checkpoint", "train.sync",
               "train.resize", "train.device_step", "train.end_step"]
RATE_CELLS = ["serve-batch-saturated", "serve-prefix-sessions",
              "serve-agent-sessions", "serve-lfm2-agent-sessions"]
NEW_METRICS = {
    "engine_device_starved_share": RATE_CELLS,
    "chat_device_starved_share": ["serve-chat-steady"],
    "engine_host_ms_per_dispatch": RATE_CELLS,
    "engine_admit_ms_per_dispatch": RATE_CELLS,
    "engine_hand_out_ms_per_dispatch": RATE_CELLS,
}


def tiny_engine(**kw):
    cfg = tfm.TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq=128, arch="llama", rope_theta=10000.0,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False)
    params = tfm.init_params(cfg, jax.random.PRNGKey(11))
    eng = llm.PagedBatcher(params, cfg, num_slots=4, max_len=96,
                           prompt_pad=48, decode_chunk=4, kv_block_size=8,
                           kv_num_blocks=64, **kw)
    deadline = time.time() + 200
    while not eng._warmed and time.time() < deadline:
        time.sleep(0.05)
    assert eng._warmed
    return eng


def prompt(n, seed):
    return [(seed * 31 + 7 * i) % 251 + 1 for i in range(n)]


def serve_some(eng, n=3, max_new=9):
    reqs = [eng.submit(prompt(20 + 3 * i, i), max_new=max_new)
            for i in range(n)]
    assert all(r.done.wait(200) and r.error is None for r in reqs)
    return reqs


# -- (i) the spans land on the profiler's host plane ---------------------------
@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One trace of a tiny engine serving a few requests and of a telemetry
    session stepping, read back: {name: [span]}."""
    directory = str(tmp_path_factory.mktemp("trace"))
    eng = tiny_engine()
    tel = TrainTelemetry(run="spans", client=None, tokens_per_step=8)
    try:
        jax.profiler.start_trace(directory)
        serve_some(eng)
        time.sleep(0.15)            # an idle stretch: engine.starved
        serve_some(eng, n=1)
        for _ in range(2):
            for phase in (tel.data_wait, tel.checkpoint, tel.sync,
                          tel.resize, tel.device_step):
                with phase():
                    time.sleep(0.001)
            tel.end_step()
        jax.profiler.stop_trace()
    finally:
        eng.stop()
        tel.stop()
    path, = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    by_name = {}
    for sp in profiling.read_host_spans(path):
        by_name.setdefault(sp["name"], []).append(sp)
    return {"spans": by_name, "path": path}


@pytest.mark.parametrize("name",
                         DISPATCHER_SPANS + PROCESSOR_SPANS + TRAIN_SPANS)
def test_span_is_on_the_host_plane(traced, name):
    assert traced["spans"].get(name), sorted(traced["spans"])


@pytest.mark.parametrize("child", [llm.SPAN_ADMIT, llm.SPAN_PACK,
                                   llm.SPAN_LAUNCH, llm.SPAN_POST_ADMIT])
def test_child_spans_lie_inside_a_dispatch(traced, child):
    parents = traced["spans"][llm.SPAN_DISPATCH]
    for sp in traced["spans"][child]:
        assert any(p["line"] == sp["line"] and p["start"] <= sp["start"]
                   and sp["end"] <= p["end"] for p in parents), sp


def test_a_dispatch_shares_its_seq_across_the_two_threads(traced):
    spans = traced["spans"]
    launched = [d for d in spans[llm.SPAN_DISPATCH]
                if d["stats"]["kind"] != "none"]
    seqs = [d["stats"]["seq"] for d in launched]
    assert seqs == sorted(set(seqs)) and len(seqs) >= 3
    for name in PROCESSOR_SPANS:
        assert sorted(s["stats"]["seq"] for s in spans[name]) == seqs
    fused = [d["stats"] for d in launched if d["stats"]["kind"] == "fused"]
    assert fused and all(d["positions"] == 16 * d["rows"] > 0
                         and d["admitted"] >= 1 for d in fused)
    assert all(d["stats"]["positions"] == 0 for d in launched
               if d["stats"]["kind"] == "decode")
    # the launch of a dispatch ends before its result has been waited for
    for d in launched:
        read, = [s for s in spans[llm.SPAN_READ_WAIT]
                 if s["stats"]["seq"] == d["stats"]["seq"]]
        launch, = [s for s in spans[llm.SPAN_LAUNCH]
                   if d["start"] <= s["start"] and s["end"] <= d["end"]]
        assert launch["end"] <= read["end"] and read["line"] != d["line"]
    assert all(s["stats"]["step"] in (0, 1)
               for n in TRAIN_SPANS for s in spans[n])


def test_a_host_only_trace_has_no_gap_to_lay(traced):
    assert profiling.idle_attribution(traced["path"]) == {
        "gaps": [], "by_span": {}, "devices": 0}


def test_gaps_are_laid_at_the_one_thread_that_feeds_the_device(traced):
    """The trace holds three threads with spans: the dispatcher, the
    processor beside it, and a train loop.  The reader takes the line that
    spends most time in a feeder span (here the train loop's two
    device_steps of 1 ms against dispatches of the tiny engine: whichever,
    it is one line) and nothing of the others."""
    every = [sp for spans in traced["spans"].values() for sp in spans]
    assert len({sp["line"] for sp in every}) == 3
    fed = profiling.feeder_spans(every)
    names = {name for name, _, _ in fed}
    engine = names <= set(DISPATCHER_SPANS)
    assert engine or names <= set(TRAIN_SPANS), names
    # With the train loop's spans taken out, the dispatcher's line is left.
    fed = profiling.feeder_spans([sp for sp in every
                                  if not sp["name"].startswith("train.")])
    assert {name for name, _, _ in fed} == set(DISPATCHER_SPANS)
    assert len(fed) == sum(len(traced["spans"][n]) for n in DISPATCHER_SPANS)
    # The processor's line alone feeds nothing.
    assert profiling.feeder_spans(
        [sp for n in PROCESSOR_SPANS for sp in traced["spans"][n]]) == []


# -- (ii) the counters ---------------------------------------------------------
def test_phase_seconds_sum_into_dispatch_and_launches_are_counted():
    eng = tiny_engine()
    try:
        serve_some(eng)
        h = eng.host_stats()
    finally:
        eng.stop()
    parts = h["admit"] + h["pack"] + h["launch"] + h["post_admit"]
    assert 0 < parts <= h["dispatch"]
    assert h["radix_match"] <= h["admit"] and h["evict"] <= h["admit"]
    assert 0 < h["radix_insert"] <= h["post_admit"]
    assert h["dispatches"] == eng.steps // 4 > 0
    assert h["work"] == pytest.approx(h["permit_wait"] + h["dispatch"])
    for old in ("permit_wait", "dispatch", "starved", "read_wait", "process"):
        assert h[old] > 0


def test_an_engine_with_no_request_is_unasked_not_starved():
    eng = tiny_engine()
    try:
        before = eng.host_stats()
        time.sleep(0.5)
        after = eng.host_stats()
    finally:
        eng.stop()
    assert after["device_unasked"] - before["device_unasked"] > 0.2
    assert after["device_starved"] == before["device_starved"] == 0.0
    # A tick that finds nothing to launch adds to no phase of a dispatch.
    assert after["dispatches"] == after["dispatch"] == after["admit"] == 0


def test_a_slow_admission_starves_the_device_and_says_so(monkeypatch, capfd):
    monkeypatch.setattr(llm, "DEVICE_EMPTY_WARN_S", 0.1)
    eng = tiny_engine()
    try:
        serve_some(eng, n=1)            # every shape has run once
        time.sleep(0.2)
        real = eng._pop_admissions

        def slow(free):
            if eng.queue_depth():
                time.sleep(0.3)
            return real(free)
        monkeypatch.setattr(eng, "_pop_admissions", slow)
        t0, before = time.perf_counter(), eng.host_stats()
        req = eng.submit(prompt(20, 5), max_new=2)
        assert req.done.wait(200) and req.error is None
        after, wall = eng.host_stats(), time.perf_counter() - t0
    finally:
        eng.stop()
    # Bounds that a loaded machine keeps: the sleep from below, the wall
    # clock of the request from above.
    starved = after["device_starved"] - before["device_starved"]
    unasked = after["device_unasked"] - before["device_unasked"]
    assert 0.3 <= starved <= wall, (starved, wall)
    assert unasked < starved    # at most the tick the request arrived in
    assert after["admit"] - before["admit"] >= 0.3
    line, = re.findall(r"\[engine\] device empty (\d+\.\d) s with (\d+) "
                       r"waiting / (\d+) live; dispatcher in (\S+)",
                       capfd.readouterr().err)
    assert float(line[0]) >= 0.1 and int(line[1]) == 1
    assert line[3] == llm.SPAN_ADMIT


def test_the_two_threads_keep_one_empty_clock():
    """Dispatcher and processor stamp the same few fields: with the
    interpreter switching threads every 10 us through 24 requests, the
    seconds laid at an empty device never outrun the wall clock or the
    dispatcher's own, and an idle engine ends with its clock running."""
    eng = tiny_engine()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        t0, h0 = time.perf_counter(), eng.host_stats()
        for wave in range(6):
            serve_some(eng, n=4, max_new=5)
        time.sleep(0.12)
        h1, wall = eng.host_stats(), time.perf_counter() - t0
        assert eng._empty_since is not None and not eng._inflight
    finally:
        sys.setswitchinterval(interval)
        eng.stop()
    starved = h1["device_starved"] - h0["device_starved"]
    unasked = h1["device_unasked"] - h0["device_unasked"]
    assert starved >= 0 and unasked > 0 and starved + unasked <= wall
    assert starved <= h1["work"] - h0["work"]
    assert h1["dispatches"] - h0["dispatches"] == (eng.steps // 4)


# -- (iii) the reader's pure half ----------------------------------------------
SPANS = [("engine.dispatch", 0.0, 10.0), ("engine.admit", 1.0, 4.0),
         ("engine.pack", 4.0, 5.0), ("engine.launch", 5.0, 9.0),
         ("engine.permit_wait", 10.0, 30.0),
         ("engine.dispatch", 30.0, 70.0), ("engine.admit", 31.0, 69.0),
         ("engine.dispatch", 70.0, 80.0), ("engine.admit", 71.0, 74.0)]


@pytest.mark.parametrize("gap, span", [
    ((2.0, 3.0), "engine.admit"),           # inside one span, innermost
    ((9.2, 9.8), "engine.dispatch"),        # inside the parent only
    ((3.5, 4.4), "engine.admit"),           # across two: the larger part
    ((9.5, 12.0), "engine.permit_wait"),    # across parent and a neighbour
    ((3.0, 8.0), "engine.launch"),          # across three children
    ((100.0, 101.0), profiling.NO_SPAN),    # under none
    ((79.5, 90.0), "engine.dispatch"),      # mostly under none: what touches
])
def test_a_gap_goes_to_the_span_that_covers_it(gap, span):
    out = profiling.attribute_gaps([gap], SPANS)
    assert out["gaps"][0][0] == span
    assert out["by_span"] == {span: pytest.approx(gap[1] - gap[0])}


def test_a_stretched_span_stands_out_against_its_median():
    out = profiling.attribute_gaps([(32.0, 60.0), (2.0, 2.5), (200.0, 200.1)],
                                   SPANS, top=2)
    assert out["gaps"] == [["engine.admit", 28.0, 38.0, 3.0],
                           ["engine.admit", 0.5, 3.0, 3.0]]
    assert out["by_span"] == {"engine.admit": 28.5,
                              profiling.NO_SPAN: pytest.approx(0.1)}
    assert profiling.attribute_gaps([], SPANS) == {"gaps": [], "by_span": {}}
    assert profiling.attribute_gaps([(0.0, 1.0)], [])["gaps"] == [
        [profiling.NO_SPAN, 1.0, None, None]]


def test_the_device_half_reads_a_recorded_trace_as_the_harness_does():
    """The v5e trace the harness's reduction is checked on (three calls 20
    ms apart, no host_span in it): the program's gap rule and
    trace_reduce.summarize's find the same gaps, and with no span to lay
    them at every one is `no_span`."""
    path = os.path.join(spec.BENCH_DIR, "tests", "data", "tiny_v5e.xplane.pb")
    planes = profiling.device_gaps(path)
    assert len(planes) == 1
    theirs = trace_reduce.summarize(trace_reduce.read_xplane(path))["gaps_s"]
    ours = sorted((e - s for s, e in planes[0]), reverse=True)
    assert ours[:10] == pytest.approx(theirs, rel=1e-6, abs=1e-12)
    out = profiling.idle_attribution(path, top=2)
    assert out["devices"] == 1 and list(out["by_span"]) == [profiling.NO_SPAN]
    assert out["by_span"][profiling.NO_SPAN] == pytest.approx(sum(ours))
    assert [g[0] for g in out["gaps"]] == [profiling.NO_SPAN] * 2
    assert [round(g[1], 3) for g in out["gaps"]] == [0.022, 0.022]


# -- (iv) the metric files that read the counters ------------------------------
@pytest.fixture(scope="module")
def cpu_counters():
    """What serve_cell hands a metric file, from a CPU engine: after -
    before of every numeric leaf of stats()'s host table."""
    eng = tiny_engine()
    try:
        before = worker_util.numeric_leaves({"host": eng.host_stats()})
        serve_some(eng)
        after = worker_util.numeric_leaves({"host": eng.host_stats()})
    finally:
        eng.stop()
    return worker_util.deltas(before, after)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_file_reads_a_number_in_its_cells(cpu_counters, name):
    entry, = [m for m in spec.load_benchmark()["per_layer"]
              if m["name"] == name]
    # (among its cells: a later PR may list more)
    assert set(NEW_METRICS[name]) <= set(entry["workloads"])
    assert (entry["source"], entry["layer"], entry["better"]) == (
        "program_counter", "Engine host loop", "lower")
    obs = {"counters": cpu_counters, "series": {}, "trace": {}}
    for cell in NEW_METRICS[name]:
        loaded = spec.load_cell(cell)
        m, = [m for m in loaded["layer_metrics"] if m["name"] == name]
        assert cell in m["cells"] and m["moves"] == entry["moves"]
        assert m["moves"] in {e["name"] for e in loaded["end_to_end"]}
        value = reductions.read_metric(m, obs)
        assert isinstance(value, float) and value >= 0.0
    # A program without the counters (the parent): nothing to read.
    assert reductions.read_metric(m, dict(obs, counters={})) is None


def test_the_profiler_wrappers_nothing_called_are_gone():
    assert not hasattr(profiling, "tpu_trace")
    assert not hasattr(profiling, "annotate")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for rel in ("README.md", "ray_tpu/util/profiling.py"):
        with open(os.path.join(root, rel)) as f:
            text = f.read()
        assert "tpu_trace" not in text and "annotate(" not in text
        assert "host_span" in text and "idle_attribution" in text


def test_host_span_never_imports_jax():
    """A jax-free train loop's phases must not pay jax's import (seconds,
    once a worker: it cost the elastic storm drill its goodput margin)."""
    import subprocess
    code = ("import sys; from ray_tpu.util import profiling\n"
            "with profiling.host_span('train.data_wait', step=1) as sp:\n"
            "    sp.set_metadata(kind='x')\n"
            "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], text=True, timeout=120,
                         capture_output=True, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert out.stdout.strip() == "False", out.stderr[-2000:]


def test_host_span_costs_microseconds_with_no_trace_running():
    """A count of calls, not a rate: 20,000 spans with two stats each in
    well under a second (about a microsecond each where measured)."""
    t0 = time.perf_counter()
    for i in range(20000):
        with profiling.host_span("engine.dispatch", seq=i, kind="decode"):
            pass
    assert time.perf_counter() - t0 < 1.0
