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
  gap of the device's timeline at the span the host was in.  Start
  the trace itself with `jax.profiler.start_trace` / `trace`.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import re
import statistics
import sys
import time
from bisect import bisect_left, bisect_right
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ray_tpu._private import tracing
from ray_tpu._private.client import get_global_client


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
