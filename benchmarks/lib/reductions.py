"""The menu a per-layer metric's file chooses from.  A metric is one JSON
file (layer_metrics/<name>.json): where its numbers come from (`source`:
counter | series | trace) and one `reduction`:

  value           counter `key`
  ratio           counter `numerator` / counter `denominator` (x `scale`)
  p50, p95        of series `series` (client clock or telemetry), or of
                  trace program spans whose name matches `module_pattern`
  idle_share      1 - busy / window of the trace, %
  share_of_busy   time of trace ops matching `op_pattern` / busy, %
  share_of_window exposed collective time / window, %
  roofline_share  for `kernels` [{op_pattern, cost_fn}]: the least time
                  the chip could take for the calls the trace shows /
                  the time they took, %.  `cost_fn` names a function of
                  the cell's kind (kinds/<kind>.py COST_FNS) or of the
                  shared table (lib/peaks.py)

A reader that finds nothing to read returns None, and the harness leaves
that metric out of the line.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional, Sequence

from benchmarks.lib import peaks, trace_reduce


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        return None
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def read_metric(spec: Dict[str, Any], obs: Dict[str, Any]
                ) -> Optional[float]:
    """`obs`: {"counters": {}, "series": {}, "trace": summary or {},
    "config": {}, "shapes": {}, "device_kind": str, "cost_fns": the cell's
    table (spec.cost_fns: its kind's, then the shared one)}."""
    red = spec["reduction"]
    counters, series, tr = obs["counters"], obs["series"], obs["trace"]
    if red == "value":
        return counters.get(spec["key"])
    if red == "ratio":
        num, den = (counters.get(spec["numerator"]),
                    counters.get(spec["denominator"]))
        if num is None or not den:
            return None
        return spec.get("scale", 1.0) * num / den
    if red in ("p50", "p95"):
        q = 0.5 if red == "p50" else 0.95
        if "module_pattern" in spec:
            rx = re.compile(spec["module_pattern"])
            xs = [ms for n, ms in tr.get("module_ms", ()) if rx.search(n)]
        else:
            xs = series.get(spec["series"], ())
        return percentile(xs, q)
    if not tr or not tr.get("busy_s"):
        return None
    if red == "idle_share":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    if red == "share_of_busy":
        return 100.0 * trace_reduce.matching(tr, spec["op_pattern"]) \
            / tr["busy_s"]
    if red == "share_of_window":
        return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
    if red == "roofline_share":
        least = took = 0.0
        for k in spec["kernels"]:
            n = trace_reduce.matching(tr, k["op_pattern"], "op_counts")
            t = trace_reduce.matching(tr, k["op_pattern"])
            flops, bytes_ = obs.get("cost_fns", peaks.COST_FNS)[
                k["cost_fn"]](obs["config"], obs["shapes"])
            least += n * peaks.roofline_seconds(
                flops, bytes_, obs["device_kind"])[0]
            took += t
        return 100.0 * least / took if took else None
    raise ValueError(f"metric {spec['name']!r}: unknown reduction {red!r}")
