"""What runs in the process that holds the chip, shared by both kinds of
cell: the compile counter, the device description, the profiler window."""

from __future__ import annotations

import glob
import os
import shutil
import threading
from typing import Any, Dict

from benchmarks.lib import trace_reduce

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts XLA backend compilations in this process (cache hits
    included: a program first needed inside the window is a fault either
    way).  jax.monitoring listeners cannot be removed, so one counter
    lives for the life of the worker."""

    def __init__(self) -> None:
        import jax.monitoring
        self._lock = threading.Lock()
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw: Any) -> None:
        if event == _COMPILE_EVENT:
            with self._lock:
                self.count += 1


def with_dtypes(kw: Dict[str, Any]) -> Dict[str, Any]:
    """A kind's transformer_kwargs carry dtypes as strings (they are made
    in the jax-free driver too): here they become jnp dtypes."""
    import jax.numpy as jnp
    out = dict(kw)
    for k in ("dtype", "param_dtype"):
        out[k] = jnp.dtype(out[k]).type
    return out


def numeric_leaves(tree: Dict[str, Any], prefix: str = ""
                   ) -> Dict[str, float]:
    """Every number in a nested dict of the program's counters, under its
    dotted path ("prefix_cache.hit_tokens"); strings, lists, None and
    booleans are left out."""
    out: Dict[str, float] = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(numeric_leaves(v, f"{prefix}{k}."))
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[f"{prefix}{k}"] = float(v)
    return out


def deltas(before: Dict[str, float], after: Dict[str, float]
           ) -> Dict[str, float]:
    """`after - before` of every number both readings hold, under its
    dotted path.  For a count that is what the run caused; for a gauge
    (`prefix_cache.cached_blocks`, `blocks.used`) it is only the change
    from the first reading to the last, and for a constant 0: a metric
    file names the counts."""
    return {k: after[k] - before[k] for k in after if k in before}


def beside(program: Dict[str, float], own: Dict[str, float]
           ) -> Dict[str, float]:
    """The program's counters and the benchmark's own in one table.  A name
    both hold stays the benchmark's (a metric file that reads it keeps its
    meaning when a later PR makes the program count something under that
    name) and the program's number is then under `engine.<name>`: nothing is
    shadowed, and no later PR has to edit the benchmark."""
    out = {(f"engine.{k}" if k in own else k): v for k, v in program.items()}
    return {**out, **own}


def device_info(require_tpu: bool) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise RuntimeError(f"the benchmark measures a TPU; jax found "
                           f"{devs[0].platform!r}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_stats_fullest() -> Dict[str, Any]:
    """Every counter the backend keeps, for the chip with the highest
    peak: goes into the line's `extra`, for whoever sizes a cell."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()]
    return dict(max(stats, key=lambda s: s.get("peak_bytes_in_use", 0)))


def memory_peak_bytes() -> int:
    """Peak on the fullest chip: the buffers the process held at their
    peak plus what the runtime reserved for its programs' temporaries.
    `peak_bytes_in_use` alone misses the temporaries (3.8 GB for a train
    step whose gradients and activations take 12.5 GB more: the two add up
    to the chip's limit, `extra.memory_stats`, my chip run, PR 23)."""
    import jax
    return max(int(s.get("peak_bytes_in_use", 0))
               + int(s.get("peak_bytes_reserved", 0))
               for s in (d.memory_stats() or {} for d in jax.devices()))


class TraceWindow:
    """jax.profiler around a short steady window, reduced in place: only
    the process that holds the chip can trace it, and the .xplane.pb is
    too large to send anywhere."""

    def __init__(self, directory: str) -> None:
        self.directory = directory

    def start(self) -> None:
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        jax.profiler.start_trace(self.directory)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def summary(self, keep: bool = False) -> Dict[str, Any]:
        files = glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb"))
        summary = (trace_reduce.summarize(trace_reduce.read_xplane(files[0]))
                   if files else {})
        if not keep:
            shutil.rmtree(self.directory, ignore_errors=True)
        return summary
