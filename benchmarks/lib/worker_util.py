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
