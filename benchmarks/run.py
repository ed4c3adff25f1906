#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s>
                              --trace <0|1>

This process never initialises a JAX backend: it calls ray_tpu.init() and
every device touch happens in a worker the node service spawned
(TpuTrainer(...).fit() for a training cell, serve.run(serve.deployment(
BenchLLM)) for a serving cell).  The last line of stdout is the result.
Without a TPU (or with fewer chips than the cell asks for) it exits
non-zero and prints no result; `--rehearsal` (the benchmark's own tests,
tiny sizes, CPU) is the only way onto another platform, and marks its
line with the platform it ran on.
"""

from __future__ import annotations

import time

T_START = time.time()       # set-up is counted from here

import argparse             # noqa: E402
import importlib            # noqa: E402
import json                 # noqa: E402
import os                   # noqa: E402
import shutil               # noqa: E402
import signal               # noqa: E402
import sys                  # noqa: E402
import tempfile             # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

DEADLINE_S = 1150           # the first run of a cell compiles


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _stat(pid: int):
    """(state, parent pid) of a process, or None once it is gone."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
    except OSError:
        return None
    return fields[0], int(fields[1])


def _descendants(root: int) -> list:
    """Every process below `root`: the node service runs inside this
    process, so the workers are its children."""
    parent = {}
    for name in os.listdir("/proc"):
        if name.isdigit() and (st := _stat(int(name))) is not None:
            parent[int(name)] = st[1]
    out, frontier = [], [root]
    while frontier:
        cur = frontier.pop()
        kids = [p for p, pp in parent.items() if pp == cur]
        out += kids
        frontier += kids
    return out


def _wait_gone(pids: list, deadline_s: float = 90.0) -> None:
    """A chip is free only when its holder's process has ended, and the
    TPU runtime takes seconds to shut down: the next run of this cell
    would find /dev/vfio/<n> busy (seen on the four-chip host, PR 23)."""
    t0 = time.time()
    alive = pids
    while alive and time.time() - t0 < deadline_s:
        alive = [p for p in alive
                 if (st := _stat(p)) is not None and st[0] != "Z"]
        time.sleep(0.1)
    if alive:
        log(f"processes still alive after {deadline_s:.0f}s: {alive}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # The benchmark's own use: tests and the one-off capacity probe.
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU; never a device number")
    ap.add_argument("--traffic", default="",
                    help="run the cell's configuration under another "
                         "traffic file (the capacity probe)")
    ap.add_argument("--benchmark", default="BENCHMARK.json",
                    help="another benchmark file (the rehearsal's)")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the .xplane.pb in .bench_trace/")
    args = ap.parse_args()

    try:
        import ray_tpu
        from ray_tpu._private.accelerators import (detect_num_chips,
                                                   use_compile_cache)
    except ImportError as e:
        log(f"the system under test is not here: {e}")
        return 3
    # Every name the cell gives (configuration, model kind, traffic mix and
    # kind, per-layer metrics, cost functions) is resolved here, before a
    # worker starts: an unknown one fails with the list of what was found.
    from benchmarks.lib import reductions, spec
    cell = spec.load_cell(args.workload, args.traffic, args.benchmark)
    if args.seconds is None:
        args.seconds = float(cell["run_seconds"])
    chips = cell["cell"]["chips"]
    runner = importlib.import_module(
        "benchmarks.lib." + spec.traffic_kind(cell["traffic"]["kind"]).CELL)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}").strip()
    else:
        platforms = os.environ.get("JAX_PLATFORMS", "")
        if platforms and "tpu" not in platforms.split(","):
            log(f"JAX_PLATFORMS={platforms} holds JAX off the TPU")
            return 2
        found = detect_num_chips()
        if found < chips:
            log(f"{found} TPU chip(s) found, the cell needs {chips}")
            return 2
    # JAX_COMPILATION_CACHE_DIR, or the checkout's .jax_cache/: workers
    # inherit it (the node gives TPU workers the same; CPU ones nothing).
    use_compile_cache(os.environ)

    def on_deadline(signum, frame):
        raise TimeoutError(f"not finished after {DEADLINE_S}s")
    signal.signal(signal.SIGALRM, on_deadline)
    signal.alarm(DEADLINE_S)

    scratch = tempfile.mkdtemp(prefix="bench_")
    trace_dir = (os.path.join(ROOT, ".bench_trace") if args.keep_trace
                 else os.path.join(scratch, "trace"))
    ray_tpu.init(_system_config={"session_dir_prefix": os.path.join(
        tempfile.gettempdir(), "ray_tpu")})
    out = None
    try:
        out = runner.run(cell, args, trace_dir, scratch)
    except BaseException as e:
        import traceback
        traceback.print_exc()
        log(f"FAILED: {type(e).__name__}: {e}")
    finally:
        signal.alarm(0)
        started = _descendants(os.getpid())
        ray_tpu.shutdown()          # stops every worker it started ...
        _wait_gone(started)         # ... and this waits until each ended
        shutil.rmtree(scratch, ignore_errors=True)
    if out is None:
        return 1

    rep = out["report"]
    for name, (value, limit) in rep["checks"].get("compared", {}).items():
        log(f"compared: {name} {value:.4g}, limit {limit:g}")
    for f in out["faults"]:
        log(f"not correct: {f}")
    values = dict(out["end_to_end"],
                  setup_s=rep["window_start_unix"] - T_START)
    if args.trace:
        obs = {"counters": rep["counters"], "series": rep["series"],
               "trace": rep["trace"], "config": cell["config"],
               "shapes": rep["shapes"], "device_kind": rep["device"]["kind"],
               "cost_fns": cell["cost_fns"]}
        metrics = {}
        for m in cell["layer_metrics"]:
            v = reductions.read_metric(m, obs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    device = dict(rep["device"], memory_peak_bytes=rep["memory_peak_bytes"])
    line = {"correct": not out["faults"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device,
            "workload": args.workload, "seed": args.seed,
            "checks": rep["checks"], "counters": rep["counters"],
            "extra": rep.get("extra", {})}
    if args.trace and rep["trace"]:
        from benchmarks.lib import trace_reduce
        device["busy_s"] = rep["trace"]["busy_s"]
        device["window_s"] = rep["trace"]["window_s"]
        line["breakdown"] = trace_reduce.breakdown(rep["trace"])
    if args.rehearsal:
        line["rehearsal"] = True
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
