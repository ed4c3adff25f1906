"""Tracing/profiling: runtime timeline + user spans + host spans on
the device profiler's clock.

Two clocks, two tools:
* scheduling time: `timeline(filename)` (reference `ray.timeline`, a
  chrome-trace export of profile events) with `span()` / `@profiled` /
  `record_span()` recorded into the same per-node event ring workers
  feed with task execution spans; `export_otlp()` hands them on;
* device time: `host_span(name, **attrs)` puts what a host thread is
  doing into `jax.profiler`'s own trace (a `TraceAnnotation`: about a
  microsecond with no trace running), where the engine's `engine.*`
  and the trainer's `train.*` spans already are, and
  `idle_attribution(path)` reads one such trace back and lays every
  gap of the device's timeline at the span the host was in, and
  `device_time(path)` sums its busy time by program and, inside a
  program, by the scope (`ray_tpu/ops/scopes.py`) its instructions were
  traced under.  Start the trace itself with `jax.profiler.start_trace`
  / `trace`.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import re
import statistics
import struct
import sys
import time
from bisect import bisect_left, bisect_right
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ray_tpu._private import tracing
from ray_tpu._private.client import get_global_client
from ray_tpu.ops.scopes import SCOPES


def _client():
    c = get_global_client()
    if c is None:
        raise RuntimeError("ray_tpu is not initialized")
    return c


def current_trace_id() -> Optional[str]:
    """The ambient trace id (set inside `span()` bodies and task
    executions), or None outside any trace."""
    ctx = tracing.current()
    return ctx["trace_id"] if ctx else None


def timeline_events(cluster: bool = True) -> List[dict]:
    """Raw profile events: task execution spans (name/start/end/pid/
    node) + custom `span()` records."""
    return _client().timeline_events(cluster=cluster)


_TRACE_ARG_KEYS = ("failed", "extra", "trace_id", "span_id",
                   "parent_span_id", "task_id")


def timeline(filename: Optional[str] = None) -> Any:
    """Chrome-trace export (open in chrome://tracing or Perfetto).
    Returns the event list; writes JSON when `filename` is given.

    Task-lifecycle records expand into per-stage child spans
    (submit/queued/dispatch/executing) on the worker's row, linked to
    the proxy/router/user spans of the same request by `trace_id` in
    `args` — one flame per request across processes.
    Reference: ray.timeline."""
    traced = []
    for ev in timeline_events():
        args = {k: v for k, v in ev.items() if k in _TRACE_ARG_KEYS
                and v is not None}
        if ev.get("kind") == "gcs_restart":
            args["epoch"] = ev.get("epoch")
            args["resync_s"] = ev.get("resync_s")
        if ev.get("kind") == "stall":
            # Sentinel capture: elapsed/threshold plus (a bounded
            # slice of) the worker stack ride in the span args.
            args["elapsed_s"] = ev.get("elapsed_s")
            args["threshold_s"] = ev.get("threshold_s")
            stack = ev.get("stack") or ""
            args["stack"] = stack[:4000]
        if ev.get("kind") == "slow_rpc":
            # Slow-RPC sentinel: same shape as a stall capture plus
            # the handler method and a size-bounded args summary.
            args["method"] = ev.get("method")
            args["elapsed_s"] = ev.get("elapsed_s")
            args["threshold_s"] = ev.get("threshold_s")
            args["rpc_args"] = ev.get("rpc_args")
            stack = ev.get("stack") or ""
            args["stack"] = stack[:4000]
        if ev.get("kind") == "sched":
            # Batched scheduler-decision span: outcome counts for the
            # scheduling episode the span covers.
            args["outcomes"] = ev.get("outcomes")
            args["decisions"] = ev.get("decisions")
        row = {
            "name": ev.get("name", "<span>"),
            "cat": ("lifecycle" if ev.get("kind") == "lifecycle" else
                    "drain" if ev.get("kind") == "drain" else
                    "stall" if ev.get("kind") == "stall" else
                    "slow_rpc" if ev.get("kind") == "slow_rpc" else
                    "sched" if ev.get("kind") == "sched" else
                    "gcs_restart" if ev.get("kind") == "gcs_restart"
                    else "actor" if ev.get("actor") else
                    "user" if ev.get("user") else "task"),
            "ph": "X",
            "ts": ev["start"] * 1e6,
            "dur": max(ev["end"] - ev["start"], 0.0) * 1e6,
            "pid": ev.get("node_id", "node")[:8],
            "tid": ev.get("pid", 0),
            "args": args,
        }
        traced.append(row)
        if ev.get("kind") == "lifecycle":
            base = ev.get("task_name") or ev.get("name", "<task>")
            for stage, s0, s1 in tracing.stage_intervals(
                    ev.get("stages") or {}):
                traced.append({
                    "name": f"{base}:{stage}",
                    "cat": "lifecycle",
                    "ph": "X",
                    "ts": s0 * 1e6,
                    "dur": max(s1 - s0, 0.0) * 1e6,
                    "pid": row["pid"],
                    "tid": row["tid"],
                    "args": dict(args, stage=stage),
                })
    traced.sort(key=lambda e: e["ts"])
    if filename:
        with open(filename, "w") as f:
            json.dump(traced, f)
    return traced


def record_span(name: str, start: float, end: float,
                trace_ctx: Optional[Dict[str, str]] = None,
                **extra) -> None:
    """Record a span with explicit timestamps (e.g. a latency
    decomposition measured after the fact).  Attaches the ambient
    trace context — or an explicit `trace_ctx` captured earlier, for
    spans finalized outside the originating context (generator
    drains, callbacks) — so the span joins the request's trace."""
    ev: Dict[str, Any] = {"name": name, "start": start, "end": end,
                          "pid": os.getpid(), "user": True,
                          "extra": extra or None}
    ctx = trace_ctx if trace_ctx is not None else tracing.current()
    if ctx is not None:
        ev["trace_id"] = ctx["trace_id"]
        ev["span_id"] = tracing.new_span_id()
        ev["parent_span_id"] = ctx["span_id"]
    try:
        _client().profile_event(ev)
    except Exception:
        pass


@contextlib.contextmanager
def span(name: str, **extra):
    """Record a custom span from driver or task code into the runtime
    timeline (reference: ray.util.tracing spans / ray.profile).

    Opens a child of the ambient trace context (or roots a new trace),
    and activates it for the body — so tasks submitted inside the span
    carry the trace across processes."""
    info = tracing.child_span()
    token = tracing.set_current(info)
    t0 = time.time()
    try:
        yield
    finally:
        tracing.reset(token)
        try:
            _client().profile_event({
                "name": name, "start": t0, "end": time.time(),
                "pid": os.getpid(), "user": True,
                "trace_id": info["trace_id"],
                "span_id": info["span_id"],
                "parent_span_id": info["parent_span_id"],
                "extra": extra or None})
        except Exception:
            pass


def profiled(fn=None, *, name: Optional[str] = None):
    """Decorator form of `span()`."""
    def deco(f):
        @functools.wraps(f)
        def wrapper(*a, **kw):
            with span(name or f.__qualname__):
                return f(*a, **kw)
        return wrapper
    return deco(fn) if fn is not None else deco


_TraceAnnotation = None


class _NoProfiler:
    """host_span in a process that has not imported jax: there is no
    profiler to write to, and importing jax (seconds) is not a span's
    to pay."""

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set_metadata(self, **attrs) -> None:
        return None


_NO_PROFILER = _NoProfiler()


def host_span(name: str, **attrs):
    """What this thread does from here on, on the device profiler's
    clock: a `jax.profiler.TraceAnnotation` context manager (plane
    `/host:CPU` of the trace, `attrs` as the event's stats; its
    `set_metadata(**attrs)` adds what is known only inside).  No client,
    no RPC; with no trace running, entering and leaving costs about a
    microsecond, so callers enter it always.  jax is never imported for
    it: a process without jax (a jax-free train loop) gets a span that
    does nothing."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        if "jax" not in sys.modules:
            return _NO_PROFILER
        from jax.profiler import TraceAnnotation as _TraceAnnotation
    return _TraceAnnotation(name, **attrs)


# -- reading a device trace back: which span was the host in when the
# device had nothing to run ------------------------------------------------
SPAN_PREFIXES = ("engine.", "train.")
# The thread that feeds the device is the one whose line holds these: the
# engine's dispatcher, the train loop.  What runs beside it (the engine's
# processor thread) is on another line of the trace.
FEEDER_SPANS = ("engine.dispatch", "train.device_step")
NO_SPAN = "no_span"
# An "XLA Ops" event is named by its HLO instruction ("%while.3 = ...");
# control-flow containers span their bodies' events.
_CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]*(?=[\s=(]|$)")

Interval = Tuple[float, float]


def attribute_gaps(gaps: Sequence[Interval],
                   spans: Sequence[Tuple[str, float, float]],
                   top: int = 10) -> Dict[str, Any]:
    """Lay each of `gaps` [(start, end)] at one of `spans` [(name, start,
    end)]: the spans of ONE host thread (nested or apart, never
    crossing), both in one unit on one clock.  A gap goes to the
    innermost span that covers more than half of it; where none does, to
    the span that covers most of it; `no_span` where none touches it.
    -> {"gaps": the `top` longest as [span, length, that span's own
    length, the median length of its name], "by_span": {span: summed
    lengths of ALL gaps}}.  A span far longer than its name's median was
    stretched by something (the tracer, the GIL, the OS); one near it is
    long by nature."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][1], -spans[i][2]))
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    # stacks[k]: the spans open between cuts[k] and cuts[k + 1], outermost
    # first (a parent starts no later and ends no sooner than its child).
    stacks: List[List[int]] = [[] for _ in cuts[1:]]
    for i in order:
        _, s, e = spans[i]
        for k in range(bisect_left(cuts, s), bisect_left(cuts, e)):
            stacks[k].append(i)
    lengths: Dict[str, List[float]] = {}
    for name, s, e in spans:
        lengths.setdefault(name, []).append(e - s)
    medians = {n: statistics.median(v) for n, v in lengths.items()}

    def owner(gs: float, ge: float) -> Optional[int]:
        cover: Dict[int, float] = {}
        first = max(bisect_right(cuts, gs) - 1, 0)
        for k in range(first, min(bisect_left(cuts, ge), len(stacks))):
            part = min(ge, cuts[k + 1]) - max(gs, cuts[k])
            if part > 0:
                for i in stacks[k]:
                    cover[i] = cover.get(i, 0.0) + part
        if not cover:
            return None

        def span_len(i: int) -> float:
            return spans[i][2] - spans[i][1]
        most = [i for i, c in cover.items() if c > (ge - gs) / 2]
        if most:
            return min(most, key=span_len)
        return max(cover, key=lambda i: (cover[i], -span_len(i)))

    rows = []
    by_span: Dict[str, float] = {}
    for gs, ge in gaps:
        i = owner(gs, ge)
        name = NO_SPAN if i is None else spans[i][0]
        by_span[name] = by_span.get(name, 0.0) + (ge - gs)
        rows.append((ge - gs, i))
    rows.sort(key=lambda r: -r[0])
    listed = [[NO_SPAN, g, None, None] if i is None else
              [spans[i][0], g, spans[i][2] - spans[i][1],
               medians[spans[i][0]]] for g, i in rows[:top]]
    return {"gaps": listed, "by_span": by_span}


def _profile(trace):
    """`trace`: the path of an `.xplane.pb`, or one already read."""
    if isinstance(trace, (str, os.PathLike)):
        from jax.profiler import ProfileData
        return ProfileData.from_file(os.fspath(trace))
    return trace


def read_host_spans(trace, prefixes: Sequence[str] = SPAN_PREFIXES
                    ) -> List[Dict[str, Any]]:
    """Every `host_span` of an `.xplane.pb` whose name starts with one of
    `prefixes`: [{name, start, end (seconds on the trace's clock), line
    (its thread: the line's number among the trace's host lines),
    stats}]."""
    out = []
    prefixes = tuple(prefixes)
    lines = (line for plane in _profile(trace).planes
             if plane.name.startswith("/host:") for line in plane.lines)
    for n, line in enumerate(lines):
        for ev in line.events:
            if ev.name.startswith(prefixes):
                out.append({"name": ev.name, "start": ev.start_ns / 1e9,
                            "end": (ev.start_ns + ev.duration_ns) / 1e9,
                            "line": n, "stats": dict(ev.stats)})
    return out


def feeder_spans(spans: Sequence[Dict[str, Any]]
                 ) -> List[Tuple[str, float, float]]:
    """Of `read_host_spans`' spans, those of the ONE thread that feeds the
    device, as `attribute_gaps` takes them: the line that spends the
    most seconds in FEEDER_SPANS (where a process holds several engines
    or loops, the busiest).  [] where no line has one."""
    seconds: Dict[int, float] = {}
    for sp in spans:
        if sp["name"] in FEEDER_SPANS:
            seconds[sp["line"]] = (seconds.get(sp["line"], 0.0)
                                   + sp["end"] - sp["start"])
    if not seconds:
        return []
    line = max(seconds, key=seconds.get)
    return [(sp["name"], sp["start"], sp["end"]) for sp in spans
            if sp["line"] == line]


def device_gaps(trace) -> List[List[Interval]]:
    """The idle gaps of each device timeline in an `.xplane.pb`, one list
    a `/device:` plane, in seconds on the trace's clock: what lies
    between the merged "XLA Ops" events, control-flow containers (while,
    conditional, call) left out because they span their bodies."""
    planes: List[List[Interval]] = []
    for plane in _profile(trace).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            busy: List[List[float]] = []
            for s, e in sorted(
                    (ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events
                    if not _CONTAINER.match(ev.name)):
                if busy and s <= busy[-1][1]:
                    busy[-1][1] = max(busy[-1][1], e)
                else:
                    busy.append([s, e])
            planes.append([(a[1] / 1e9, b[0] / 1e9)
                           for a, b in zip(busy, busy[1:])])
    return planes


def idle_attribution(path: str, top: int = 10) -> Dict[str, Any]:
    """From one `.xplane.pb`: every idle gap of the device laid at the
    `engine.*` / `train.*` span that the thread which feeds it was in
    (`feeder_spans` picks the thread, `attribute_gaps` has the rule and
    the result's form; seconds).  Of several devices (one program across
    chips) each one's gaps are laid separately: "gaps" lists the longest
    of any device, "by_span" is the mean over "devices" of them, one
    device's idle seconds."""
    trace = _profile(path)
    planes = device_gaps(trace)
    out = attribute_gaps([g for gaps in planes for g in gaps],
                         feeder_spans(read_host_spans(trace)), top)
    out["by_span"] = {name: v / len(planes)
                      for name, v in out["by_span"].items()}
    out["devices"] = len(planes)
    return out


# -- reading a device trace back: the device's BUSY time, by program and,
# inside a program, by the scope its instructions were traced under ---------
# `jax.profiler.ProfileData` shows an event's own stats.  The file holds
# more: every XEvent points (metadata_id) at an XEventMetadata of its plane,
# and on a TPU those carry the instruction's `program_id` (the fingerprint
# in the module event's name, `jit_f(<fingerprint>)`), `tf_op` (the name
# stack it was traced under: what the compiled text has as `op_name`),
# `hlo_category`, `flops` and `bytes_accessed` (XLA's own cost analysis).
# So the file is read here by its wire format (varint, 64-bit,
# length-delimited, 32-bit; field numbers: tsl's xplane.proto), with the
# standard library alone.
COMPILER = "(compiler)"     # no name stack: the compiler's copies, re-layouts
NO_SCOPE = "(no scope)"     # a name stack that enters none of ops.scopes
# One v5e chip's published peaks (cloud.google.com/tpu/docs/v5e), for the
# printed table's shares.
V5E_FLOPS_PER_S = 197e12
V5E_HBM_BYTES_PER_S = 819e9
# Where the host hands the device a program.  An engine's launch is
# labelled by the `engine.dispatch` span around it (`kind`, `positions`).
ENGINE_LAUNCH_SPAN, DISPATCH_SPAN = "engine.launch", "engine.dispatch"
TRAIN_LAUNCH_SPAN = "train.device_step"
TRAIN_LABEL = "train_step"
# A module event may read this much earlier than its launch's span starts:
# two clocks, which the profiler aligns to about a millisecond (the recorded
# v5e trace reads its modules 0.9-1.05 ms before their launches).
_LAUNCH_SLACK_S = 2e-3
_DEVICE_LINES = ("XLA Modules", "XLA Ops")
# Control flow that spans its bodies' events, by XLA's category: an
# instruction's NAME need not say it (`%cond.3.clone.18 = ... conditional(`),
# which `_CONTAINER` goes by.
_CONTAINER_CATEGORIES = ("while", "conditional", "call")

_MODULE = re.compile(r"^(.*)\((\d+)\)$")
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int, end: int):
    """The fields of the protobuf message buf[i:end] -> (number, value): a
    varint as an int, a length-delimited field as its (start, end) in
    `buf`, a fixed one as its bytes."""
    while i < end:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane")
        yield tag >> 3, value


def _text(buf, span: Tuple[int, int]) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span: Tuple[int, int]) -> Tuple[int, Tuple[int, int]]:
    key, value = 0, (0, 0)
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stats(buf, spans, stat_names: Dict[int, str]) -> Dict[str, Any]:
    """XStat messages -> {name: value}; a ref is the name it points at."""
    out: Dict[str, Any] = {}
    for span in spans:
        name, value = "", None
        for f, v in _fields(buf, *span):
            if f == 1:
                name = stat_names.get(v, str(v))
            elif f == 2:
                value = struct.unpack("<d", v)[0]
            elif f == 3:
                value = v
            elif f == 4:        # int64: two's complement in the varint
                value = v - (1 << 64) if v >> 63 else v
            elif f == 5:
                value = _text(buf, v)
            elif f == 6:
                value = bytes(buf[v[0]:v[1]])
            elif f == 7:
                value = stat_names.get(v, str(v))
        out[name] = value
    return out


def _event(buf, t0: int, i: int, end: int) -> tuple:
    """One XEvent, buf[i:end], of a line that starts at `t0` ns ->
    (metadata id, start_ns, duration_ns, its stats' (start, end)s).  The
    loop every event of a trace goes through: `_fields` without the
    generator, for the fields an XEvent has (1-3 and 5 varints, 4 a
    stat)."""
    mid = offset = duration = 0
    stats = ()
    while i < end:
        tag = buf[i]
        value = buf[i + 1]
        i += 2
        if value >= 0x80:
            value, i = _varint(buf, i - 1)
        if tag == 0x22:
            stats += ((i, i + value),)
            i += value
        elif tag == 0x08:
            mid = value
        elif tag == 0x10:
            offset = value
        elif tag == 0x18:
            duration = value
        elif tag != 0x28:
            raise ValueError(f"field tag {tag} in an XEvent at byte {i}")
    return mid, t0 + offset / 1e3, duration / 1e3, stats


def read_xspace(path: str) -> List[Dict[str, Any]]:
    """An `.xplane.pb` by its wire format -> a plane: {name, metadata {id:
    {name, display_name, stats {..}}}, lines [{name, events [(metadata id,
    start_ns, duration_ns, stats)]}]}.  Times are floats on the trace's
    clock, as `ProfileData` gives them (the line's timestamp + the event's
    offset).  An event's `stats` stay their (start, end)s in the file
    until `event_stats(plane, event)` is asked: a device line holds
    hundreds of thousands of events whose stats (their offset and duration
    again) nobody reads.  Of a `/device:` plane only the lines "XLA
    Modules" and "XLA Ops" are read."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = []
    for f, span in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, line_spans, meta_spans = "", [], []
        stat_names: Dict[int, str] = {}
        for f2, v in _fields(buf, *span):
            if f2 == 2:
                name = _text(buf, v)
            elif f2 == 3:
                line_spans.append(v)
            elif f2 == 4:
                meta_spans.append(v)
            elif f2 == 5:
                key, value = _map_entry(buf, v)
                for f3, v3 in _fields(buf, *value):
                    if f3 == 2:
                        stat_names[key] = _text(buf, v3)
        metadata: Dict[int, Dict[str, Any]] = {}
        for key, value in (_map_entry(buf, ms) for ms in meta_spans):
            m: Dict[str, Any] = {"name": "", "display_name": ""}
            stats = []
            for f3, v3 in _fields(buf, *value):
                if f3 == 2:
                    m["name"] = _text(buf, v3)
                elif f3 == 4:
                    m["display_name"] = _text(buf, v3)
                elif f3 == 5:
                    stats.append(v3)
            m["stats"] = _stats(buf, stats, stat_names)
            metadata[key] = m
        lines = []
        for ls in line_spans:
            line_name, t0, event_spans = "", 0, []
            for f3, v3 in _fields(buf, *ls):
                if f3 == 2:
                    line_name = _text(buf, v3)
                elif f3 == 3:
                    t0 = v3
                elif f3 == 4:
                    event_spans.append(v3)
            if name.startswith("/device:") and line_name not in _DEVICE_LINES:
                continue
            lines.append({"name": line_name, "events": [
                _event(buf, t0, i, end) for i, end in event_spans]})
        planes.append({"name": name, "metadata": metadata, "lines": lines,
                       "_buf": buf, "_stat_names": stat_names})
    return planes


def event_stats(plane: Dict[str, Any], event: tuple) -> Dict[str, Any]:
    """The own stats of one of `read_xspace`'s events (a host span's
    attributes)."""
    return _stats(plane["_buf"], event[3], plane["_stat_names"])


def scope_of(tf_op: str) -> Tuple[str, str]:
    """(scope, pass) of an instruction from its name stack as jax 0.9 writes
    it (`jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/
    rematted_computation/ffn_gate_up/bsd,df->bsf/dot_general`): components
    joined by "/", a transform wrapped around one or standing alone.
    Scope: the innermost component that, its wrappers peeled
    (`jvp(norm)`), is one of `ops.scopes.SCOPES`; COMPILER for no stack at
    all, NO_SCOPE for one that enters none.  Pass: `recompute` under
    `rematted_computation` (a checkpoint policy's second run of the
    forward, inside the backward), else `bwd` under a `transpose(`, else
    `fwd`."""
    stack = tf_op.split(":")[0]
    if not stack:
        return COMPILER, ""
    parts = stack.split("/")
    scope = NO_SCOPE
    for part in reversed(parts):
        while part not in SCOPES:
            wrapped = _WRAPPED.match(part)
            if wrapped is None:
                break
            part = wrapped.group(1)
        if part in SCOPES:
            scope = part
            break
    if "rematted_computation" in parts:
        return scope, "recompute"
    return scope, "bwd" if "transpose(" in stack else "fwd"


def _launches(planes: List[Dict[str, Any]]
              ) -> List[Tuple[float, Optional[str]]]:
    """(start in seconds, label) of every launch span of the host thread
    that has the most of them, in order.  An `engine.launch` is labelled
    by the `engine.dispatch` around it: "decode", or a fused pass's
    `positions`; None where that span does not say."""
    best: List[Tuple[float, Optional[str]]] = []
    for plane in planes:
        if not plane["name"].startswith("/host:"):
            continue
        names = {mid: m["name"] for mid, m in plane["metadata"].items()}
        for line in plane["lines"]:
            found, around = [], None    # around: the newest engine.dispatch
            for ev in sorted(line["events"], key=lambda ev: ev[1]):
                name = names.get(ev[0], "")
                if name == DISPATCH_SPAN:
                    around = ev
                elif name == TRAIN_LAUNCH_SPAN:
                    found.append((ev[1] / 1e9, TRAIN_LABEL))
                elif name == ENGINE_LAUNCH_SPAN:
                    label = None
                    if around and around[1] + around[2] >= ev[1] + ev[2]:
                        st = event_stats(plane, around)
                        label = {"decode": "decode",
                                 "fused": str(st.get("positions"))
                                 }.get(st.get("kind"))
                    found.append((ev[1] / 1e9, label))
            if len(found) > len(best):
                best = found
    return best


def pair_launches(modules: Sequence[Tuple[float, Any]],
                  launches: Sequence[Tuple[float, Any]]) -> int:
    """The device runs what it is handed in order, so a device's module
    events [(start in seconds, program)] and the host's launches [(start,
    label)] pair off in order: module i with launch i + o.  A trace starts
    and stops anywhere (modules whose launch was before it, launches the
    device had not reached), so o is found, not assumed: no module starts
    before its launch does, and the largest o that keeps to that is the
    pairing (a later launch's span starts well after an earlier module
    did: the engine keeps `pipeline_depth` dispatches in flight and
    launches one only when the oldest has been read back).  "Before" is
    taken with _LAUNCH_SLACK_S to spare, which on a host quick enough can
    let one offset too many through: of that o and the one below it, the
    one under which fewer programs collect two labels.  -> o;
    len(launches) where nothing pairs."""
    def clashes(o: int) -> Optional[int]:
        labels: Dict[Any, set] = {}
        for i in range(max(0, -o), min(len(modules), len(launches) - o)):
            if modules[i][0] + _LAUNCH_SLACK_S < launches[i + o][0]:
                return None
            labels.setdefault(modules[i][1], set()).add(launches[i + o][1])
        return sum(len(v) - 1 for v in labels.values())

    for o in range(len(launches) - 1, -len(modules), -1):
        here = clashes(o)
        if here is not None:
            below = clashes(o - 1) if o - 1 > -len(modules) else None
            return o - 1 if below is not None and below < here else o
    return len(launches)


def device_time(path: str) -> Dict[str, Any]:
    """One `.xplane.pb`'s device BUSY time by program and, inside a
    program, by scope.  Of several device planes (one program across
    chips) the mean, as `idle_attribution` gives it.  ->
    {"devices", "busy_s" (every program's op seconds), "read_s" (what
    this call took), "programs": [by seconds, the longest first:
      {"program_id", "module" (`jit_<fn>`), "label", "calls", "seconds"
       (its "XLA Modules" events), "ms_per_call" (of those, the ones that
       are neither the first nor the last of their device's line, where
       the program has such: a window cut from a busy device cuts the
       event it starts in and the one it stops in),
       "op_seconds" (its "XLA Ops" events, control-flow containers left
       out: they span their bodies),
       "scopes": {scope: {"seconds", "parts": [{"pass", "category",
                  "events", "seconds", "flops", "bytes_accessed"}]}}}],
     "unlabelled": [{"program_id", "module", "labels" {label: events},
                     "unpaired" (events with no launch in the trace)}]}.

    An instruction belongs to the program its metadata names
    (`program_id`), never to a name: two programs' `fusion.455` do not
    meet.  Its scope and pass are `scope_of` its `tf_op`; its category
    XLA's `hlo_category`; `flops` and `bytes_accessed` are XLA's cost
    analysis of one run times the runs (zero for a Mosaic call, whose
    body XLA does not see).  A FUSION CARRIES ONE `tf_op`, its root's:
    what XLA fused across two scopes is counted under one of them, so a
    scope's seconds are exact for a kernel and near for a layer's part.
    A program's parts sum to its op seconds.

    Labels: a `program_id` says nothing a person can read and an
    engine's four fused programs share one module name, so a program is
    labelled from the host's launch spans on the same clock
    (`pair_launches`): "decode", a fused pass's positions ("640"),
    "train_step".  A program whose events collect two labels, or none, is
    not labelled and is listed in "unlabelled", as is one with events
    that no launch of the trace belongs to (the window's first)."""
    t_read = time.perf_counter()
    planes = read_xspace(path)
    launches = _launches(planes)
    programs: Dict[Any, Dict[str, Any]] = {}

    def program(pid) -> Dict[str, Any]:
        return programs.setdefault(pid, {
            "program_id": pid, "module": "", "label": None, "calls": 0,
            "seconds": 0.0, "whole": [0, 0.0], "parts": {}, "labels": {},
            "unpaired": 0})

    devices = 0
    for plane in planes:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        if not plane["name"].startswith("/device:") or "XLA Ops" not in lines:
            continue
        devices += 1
        meta = plane["metadata"]
        per_id: Dict[int, List[float]] = {}
        for mid, _, duration, _ in lines["XLA Ops"]:
            acc = per_id.get(mid)
            if acc is None:
                per_id[mid] = [1, duration]
            else:
                acc[0] += 1
                acc[1] += duration
        for mid, (n, ns) in per_id.items():
            m = meta.get(mid, {"name": "", "stats": {}})
            st = m["stats"]
            if (_CONTAINER.match(m["name"])
                    or st.get("hlo_category") in _CONTAINER_CATEGORIES):
                continue
            key = scope_of(st.get("tf_op") or "") + (
                st.get("hlo_category") or "",)
            part = program(st.get("program_id"))["parts"].setdefault(
                key, [0, 0.0, 0.0, 0.0])
            part[0] += n
            part[1] += ns / 1e9
            part[2] += n * float(st.get("flops") or 0)
            part[3] += n * float(st.get("bytes_accessed") or 0)
        ran = []
        modules = sorted(lines.get("XLA Modules", ()), key=lambda ev: ev[1])
        for at, (mid, start, duration, _) in enumerate(modules):
            named = _MODULE.match(meta.get(mid, {"name": ""})["name"])
            prog = program(int(named.group(2)) if named else None)
            prog["module"] = named.group(1) if named else prog["module"]
            prog["calls"] += 1
            prog["seconds"] += duration / 1e9
            if 0 < at < len(modules) - 1:
                prog["whole"][0] += 1
                prog["whole"][1] += duration / 1e9
            ran.append((start / 1e9, prog))
        o = pair_launches([(t, pr["program_id"]) for t, pr in ran], launches)
        for i, (_, prog) in enumerate(ran):
            if 0 <= i + o < len(launches):
                label = launches[i + o][1]
                prog["labels"][label] = prog["labels"].get(label, 0) + 1
            else:
                prog["unpaired"] += 1

    k = max(devices, 1)
    out, unlabelled = [], []
    for prog in programs.values():
        labels = prog.pop("labels")
        unpaired = prog.pop("unpaired")
        if len(labels) == 1 and None not in labels:
            prog["label"], = labels
        if prog["label"] is None or unpaired:
            unlabelled.append({
                "program_id": prog["program_id"], "module": prog["module"],
                "labels": {str(n): c / k for n, c in labels.items()},
                "unpaired": unpaired / k})
        scopes: Dict[str, Dict[str, Any]] = {}
        for (scope, pass_, category), (n, s, flops, bytes_) in sorted(
                prog.pop("parts").items(), key=lambda kv: -kv[1][1]):
            under = scopes.setdefault(scope, {"seconds": 0.0, "parts": []})
            under["seconds"] += s / k
            under["parts"].append({
                "pass": pass_, "category": category, "events": n / k,
                "seconds": s / k, "flops": flops / k,
                "bytes_accessed": bytes_ / k})
        whole_n, whole_s = prog.pop("whole")
        prog["ms_per_call"] = (1e3 * whole_s / whole_n if whole_n else
                               1e3 * prog["seconds"] / prog["calls"]
                               if prog["calls"] else None)
        prog["calls"] /= k
        prog["seconds"] /= k
        prog["op_seconds"] = sum(u["seconds"] for u in scopes.values())
        prog["scopes"] = dict(sorted(scopes.items(),
                                     key=lambda kv: -kv[1]["seconds"]))
        out.append(prog)
    out.sort(key=lambda p: -max(p["seconds"], p["op_seconds"]))
    return {"devices": devices,
            "busy_s": sum(p["op_seconds"] for p in out),
            "programs": out, "unlabelled": unlabelled,
            "read_s": time.perf_counter() - t_read}


def format_device_time(result: Dict[str, Any], scopes: int = 12) -> str:
    """`device_time`'s result for a person: a line a program (label,
    calls, ms a call, its share of the busy time) and under it its
    `scopes` longest rows of scope, pass and category, each with its share
    of the program, and the TFLOP/s and GB/s it reached by XLA's own
    counts beside a v5e's peaks."""
    busy = result["busy_s"] or 1.0
    rows = [f"{result['devices']} device(s), busy {result['busy_s']:.4f} s,"
            f" read in {result['read_s']:.2f} s"]
    for p in result["programs"]:
        per_call = ("" if p["ms_per_call"] is None
                    else f"{p['ms_per_call']:.3f} ms a call, ")
        rows.append(
            f"{p['label'] or '(unlabelled)'}  {p['module']}"
            f"({p['program_id']}): {p['calls']:g} calls, {per_call}"
            f"{100 * p['op_seconds'] / busy:.1f} % of busy")
        parts = sorted(
            ((scope, part) for scope, under in p["scopes"].items()
             for part in under["parts"]), key=lambda sp: -sp[1]["seconds"])
        for scope, part in parts[:scopes]:
            s = part["seconds"]
            tflops = part["flops"] / s / 1e12 if s else 0.0
            gbs = part["bytes_accessed"] / s / 1e9 if s else 0.0
            rows.append(
                f"  {scope:<22} {part['pass']:<9} {part['category']:<20}"
                f" {1e3 * s:10.3f} ms {100 * s / (p['op_seconds'] or 1):5.1f}"
                f" %  {tflops:6.1f} TFLOP/s"
                f" ({100 * tflops * 1e12 / V5E_FLOPS_PER_S:4.1f} %)"
                f"  {gbs:6.1f} GB/s"
                f" ({100 * gbs * 1e9 / V5E_HBM_BYTES_PER_S:4.1f} %)")
        rest = sum(part["seconds"] for _, part in parts[scopes:])
        if rest:
            rows.append(f"  ({len(parts) - scopes} more rows)"
                        f"{'':<43} {1e3 * rest:10.3f} ms")
    for u in result["unlabelled"]:
        rows.append(f"unlabelled: {u['module']}({u['program_id']}) labels "
                    f"{u['labels']} unpaired {u['unpaired']:g}")
    return "\n".join(rows)


def export_otlp(filename: Optional[str] = None,
                endpoint: Optional[str] = None,
                service_name: str = "ray_tpu") -> dict:
    """Export the profile spans as OTLP/JSON (the OpenTelemetry
    ExportTraceServiceRequest schema), so any OTLP-ingesting backend
    (Jaeger, Tempo, collector) can read them — the reference's
    util/tracing/tracing_helper.py role without requiring the otel SDK
    in the image.  Writes to `filename` and/or POSTs to `endpoint`
    (an OTLP/HTTP traces URL); returns the payload."""
    import os
    import urllib.request

    def span_id(n: int) -> str:
        return f"{n & 0xFFFFFFFFFFFFFFFF:016x}"

    spans = []
    # Fallback trace for legacy events recorded without a trace
    # context; traced events carry their own per-request trace ids.
    trace_id = os.urandom(16).hex()
    for i, ev in enumerate(timeline_events()):
        attrs = [{"key": "node.id",
                  "value": {"stringValue": str(ev.get("node_id", ""))[:16]}},
                 {"key": "process.pid",
                  "value": {"intValue": str(ev.get("pid", 0))}}]
        for k, v in (ev.get("extra") or {}).items() \
                if isinstance(ev.get("extra"), dict) else []:
            attrs.append({"key": str(k),
                          "value": {"stringValue": str(v)}})
        sp = {
            "traceId": ev.get("trace_id") or trace_id,
            "spanId": ev.get("span_id") or span_id(i + 1),
            "name": ev.get("name", "<span>"),
            "kind": 1,  # SPAN_KIND_INTERNAL
            "startTimeUnixNano": str(int(ev["start"] * 1e9)),
            "endTimeUnixNano": str(int(max(ev["end"], ev["start"]) * 1e9)),
            "attributes": attrs,
            "status": ({"code": 2} if ev.get("failed")
                       else {"code": 1}),
        }
        if ev.get("parent_span_id"):
            sp["parentSpanId"] = ev["parent_span_id"]
        spans.append(sp)
    payload = {"resourceSpans": [{
        "resource": {"attributes": [
            {"key": "service.name",
             "value": {"stringValue": service_name}}]},
        "scopeSpans": [{
            "scope": {"name": "ray_tpu.profiling"},
            "spans": spans,
        }],
    }]}
    if filename:
        with open(filename, "w") as f:
            json.dump(payload, f)
    if endpoint:
        req = urllib.request.Request(
            endpoint, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req, timeout=10).read()
    return payload


def stack_traces(timeout: float = 10.0,
                 cluster: bool = True) -> Dict[Any, str]:
    """On-demand stack dump of every live worker process in the
    cluster (reference: the dashboard reporter's py-spy integration).
    Returns {pid: formatted stacks}; workers on remote nodes appear
    under "pid@node" keys (pids collide across hosts).  cluster=False
    restricts to the local node — which used to be the silent behavior
    of this documented "every live worker" API."""
    return _client().conn.call({"type": "stack_dump",
                                "timeout": timeout,
                                "cluster": cluster},
                               timeout=timeout + 15.0)["stacks"]


def stack_task(task_id: str, timeout: float = 10.0) -> Dict[Any, str]:
    """Targeted stack capture of the worker(s) currently executing the
    task whose id matches the hex prefix `task_id` (anywhere in the
    cluster) — the on-demand face of the stall sentinel's automatic
    captures.  Returns {} when the task is not executing."""
    return _client().conn.call({"type": "stack_dump",
                                "timeout": timeout,
                                "task_id": task_id,
                                "cluster": True},
                               timeout=timeout + 15.0)["stacks"]


def folded_stacks(samples: int = 40, interval_s: float = 0.02,
                  timeout: float = 10.0, cluster: bool = True,
                  task_id: Optional[str] = None) -> Dict[str, int]:
    """Cluster flamegraph sampling: every live worker captures its
    thread stacks `samples` times, `interval_s` apart; the node layer
    merges the folded-stack counts across workers and nodes.  With a
    `task_id` hex prefix, only the worker(s) executing that task are
    sampled.  Returns {"thread;frame;frame;...": count}."""
    msg = {"type": "stack_dump", "timeout": timeout,
           "cluster": cluster, "samples": samples,
           "interval_s": interval_s}
    if task_id:
        msg["task_id"] = task_id
    reply = _client().conn.call(
        msg, timeout=timeout + samples * interval_s + 15.0)
    return reply.get("folded") or {}


def flamegraph(samples: int = 40, interval_s: float = 0.02,
               timeout: float = 10.0, cluster: bool = True,
               task_id: Optional[str] = None,
               filename: Optional[str] = None) -> str:
    """`folded_stacks()` rendered in the flamegraph.pl folded format
    (one "stack count" line per distinct stack) — pipe the output into
    flamegraph.pl / speedscope, or read hot frames straight off the
    counts.  Writes to `filename` when given; returns the text."""
    folded = folded_stacks(samples=samples, interval_s=interval_s,
                           timeout=timeout, cluster=cluster,
                           task_id=task_id)
    text = "\n".join(f"{stack} {count}" for stack, count in
                     sorted(folded.items(),
                            key=lambda kv: (-kv[1], kv[0])))
    if filename:
        with open(filename, "w") as f:
            f.write(text + ("\n" if text else ""))
    return text
