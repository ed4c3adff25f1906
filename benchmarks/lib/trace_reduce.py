"""From a profiler trace (.xplane.pb) to numbers: the busy union, the idle
share, time per operation by stable name, program (dispatch) spans, idle
gaps, and the exposed part of collectives.  Checked against the small
recorded trace in tests/data/ (tests/test_trace_reduce.py).

A TPU device plane ("/device:TPU:<n>") carries, among others, the lines
"XLA Modules" (one event per executed program = one dispatch) and
"XLA Ops" (one event per operation, on the core's own sequential
timeline).  Control-flow containers (while, conditional, call) span their
bodies' events and are left out of both the busy union and the
per-operation sums, or their time would count twice.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

_CONTAINER = re.compile(r"^(while|conditional|call)([.\d]*)$")
COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|send|recv)")


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of union `a` not covered by union `b` (both sorted unions)."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


_HLO_NAME = re.compile(r"^%?([^\s=(]+)")


def op_name(event_name: str) -> str:
    """An "XLA Ops" event is named by its whole HLO instruction
    ("%fusion.3 = bf16[...] fusion(...)"); a module by "jit_f(<hash>)".
    Both reduce to the leading identifier."""
    m = _HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


def read_xplane(path: str) -> List[Dict[str, Any]]:
    """[{name, ops: [(name, start_ns, end_ns)], modules: [...]}] for each
    accelerator plane of the trace (empty on a host-only trace)."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue

        def events(name):
            ln = lines.get(name)
            if ln is None:
                return []
            return [(op_name(e.name), float(e.start_ns),
                     float(e.start_ns) + float(e.duration_ns))
                    for e in ln.events]

        planes.append({"name": plane.name, "ops": events("XLA Ops"),
                       "modules": events("XLA Modules")})
    return planes


def summarize(planes: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Numbers averaged over the device planes.  Seconds throughout."""
    if not planes:
        return {}
    busy = window = exposed = 0.0
    op_s: Dict[str, float] = {}
    op_n: Dict[str, int] = {}
    gaps: List[float] = []
    for pl in planes:
        leaf = [(n, s, e) for n, s, e in pl["ops"]
                if not _CONTAINER.match(n)]
        if not leaf:
            continue
        every = leaf + pl["modules"]
        t0 = min(s for _, s, _ in every)
        t1 = max(e for _, _, e in every)
        u = union([(s, e) for _, s, e in leaf])
        busy += total(u) / 1e9
        window += (t1 - t0) / 1e9
        gaps += [(b[0] - a[1]) / 1e9 for a, b in zip(u, u[1:])]
        coll = union([(s, e) for n, s, e in leaf if COLLECTIVE.search(n)])
        comp = union([(s, e) for n, s, e in leaf
                      if not COLLECTIVE.search(n)])
        exposed += total(subtract(coll, comp)) / 1e9
        for n, s, e in leaf:
            op_s[n] = op_s.get(n, 0.0) + (e - s) / 1e9
            op_n[n] = op_n.get(n, 0) + 1
    k = len(planes)
    first = planes[0]
    return {
        "devices": k,
        "busy_s": busy / k,
        "window_s": window / k,
        "collective_exposed_s": exposed / k,
        "op_seconds": {n: v / k for n, v in op_s.items()},
        "op_counts": {n: v / k for n, v in op_n.items()},
        "module_ms": [(n, (e - s) / 1e6) for n, s, e in first["modules"]],
        "gaps_s": sorted(gaps, reverse=True)[:10],
    }


def matching(summary: Dict[str, Any], pattern: str,
             key: str = "op_seconds") -> float:
    rx = re.compile(pattern)
    return sum(v for n, v in summary[key].items() if rx.search(n))


def breakdown(summary: Dict[str, Any]) -> Dict[str, Any]:
    ops = sorted(summary["op_seconds"].items(), key=lambda kv: -kv[1])[:10]
    # Gaps stay "unattributed" until the program puts host spans on the
    # trace's clock (PERF.md, Open questions: the `tracing` issue).
    return {"device_ops": [[n, v] for n, v in ops],
            "idle_gaps": [["unattributed", g]
                          for g in summary["gaps_s"][:10]]}
