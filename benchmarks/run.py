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

The run owns the chips' hand-over (lib/chips.py; opening a chip's file is
not initialising a backend).  It starts only when EVERY chip of the host
can be opened, whatever the cell asks for: up to BEFORE_S for the run
before it to give them back, after killing what a dead run orphaned.  It
returns only when they can be opened again: up to AFTER_S after
ray_tpu.shutdown(), which is what protects a run of an older harness that
follows this one.  `setup_s` is counted from when the chips were free.  A
worker that still loses the race ("Device or resource busy") is given one
more try; nothing else is retried.  The line's `device` says what was
waited: `chip_wait_s`, `release_wait_s`, `busy_retries`.

Exit codes:
    0  a result line was printed
    2  no TPU, or fewer chips than the cell needs
    3  the system under test cannot be imported
    4  the chips were not free: after BEFORE_S, or busy twice
    5  the run passed DEADLINE_S
    6  the device ran out of memory (RESOURCE_EXHAUSTED)
    1  anything else; the `[bench] FAILED:` line says what
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
import traceback            # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import chips    # noqa: E402  (jax-free)

DEADLINE_S = 1150           # the first run of a cell compiles
BEFORE_S = 180.0            # for the run before this one to free the chips
AFTER_S = 120.0             # for this run's own workers to free them
BUSY = "Device or resource busy"    # a worker's open() of a held chip
WAIT_WORTH_A_LINE_S = 0.1   # a look at free chips takes under a millisecond


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _stat(pid: int):
    """(state, parent pid) of a process, or None once it is gone."""
    st = chips.proc_stat(pid)
    return st and st[1:]


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


class NoTpu(RuntimeError):
    """Exit code 2."""


class DeadlinePassed(TimeoutError):
    """Exit code 5: only the run's own alarm raises it."""


def exit_code(error) -> int:
    """What the run ended with, in the one place the ledger keeps."""
    if error is None:
        return 0
    text = str(error)
    if isinstance(error, NoTpu):
        return 2
    if isinstance(error, ImportError):
        return 3
    if isinstance(error, chips.ChipsBusy) or BUSY in text:
        return 4
    if isinstance(error, DeadlinePassed):
        return 5
    if "RESOURCE_EXHAUSTED" in text:
        return 6
    return 1


def attempts(attempt, release):
    """`attempt()` is one whole try, init to shutdown; `release()` waits
    until the chips open again and returns the seconds.  A try that a
    worker lost to a busy chip (it opens its chips before anything else,
    so no window had opened) is made once more; any other error, and a
    second busy one, ends the run.  Returns (result or None, the last
    error or None, seconds waited after the last try, retries)."""
    retries = 0
    while True:
        out = error = None
        try:
            out = attempt()
        except BaseException as e:
            traceback.print_exc()
            log(f"FAILED: {type(e).__name__}: {e}")
            error = e
        released = release()
        if retries or exit_code(error) != 4:
            return out, error, released, retries
        retries += 1
        log(f"a worker found its chips busy; they opened again after "
            f"{released:.1f}s: running once more")


def setup_seconds(window_start_unix: float, lost_s: float) -> float:
    """Set-up counted from when the chips were free: `lost_s` is what went
    by between the first look at them and the start of the try that ran."""
    return window_start_unix - T_START - lost_s


def main(argv=None, before_s: float = BEFORE_S,
         after_s: float = AFTER_S) -> int:
    """`before_s` and `after_s` are arguments for the benchmark's own tests
    (a run held off its chips), never of the command line."""
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
    args = ap.parse_args(argv)

    try:
        import ray_tpu
        from ray_tpu._private.accelerators import (detect_num_chips,
                                                   use_compile_cache)
    except ImportError as e:
        log(f"the system under test is not here: {e}")
        return exit_code(e)
    # Every name the cell gives (configuration, model kind, traffic mix and
    # kind, per-layer metrics, cost functions) is resolved here, before a
    # worker starts: an unknown one fails with the list of what was found.
    from benchmarks.lib import reductions, spec
    cell = spec.load_cell(args.workload, args.traffic, args.benchmark)
    if args.seconds is None:
        args.seconds = float(cell["run_seconds"])
    need = cell["cell"]["chips"]
    runner = importlib.import_module(
        "benchmarks.lib." + spec.traffic_kind(cell["traffic"]["kind"]).CELL)

    def on_deadline(signum, frame):
        raise DeadlinePassed(f"not finished after {DEADLINE_S}s")
    signal.signal(signal.SIGALRM, on_deadline)
    deadline = time.monotonic() + DEADLINE_S

    def arm() -> None:
        signal.alarm(max(1, int(deadline - time.monotonic())))

    def wait_free(limit_s: float) -> float:
        return 0.0 if args.rehearsal else chips.wait_free(limit_s)

    first_look = time.time()
    try:
        if args.rehearsal:
            os.environ["JAX_PLATFORMS"] = "cpu"
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={need}").strip()
        else:
            platforms = os.environ.get("JAX_PLATFORMS", "")
            if platforms and "tpu" not in platforms.split(","):
                raise NoTpu(f"JAX_PLATFORMS={platforms} holds JAX off the "
                            f"TPU")
            found = detect_num_chips()
            if found < need:
                raise NoTpu(f"{found} TPU chip(s) found, the cell needs "
                            f"{need}")
            chips.clear_orphans(log)
        chip_wait_s = wait_free(before_s)
    except (NoTpu, chips.ChipsBusy) as e:
        log(str(e))
        return exit_code(e)
    if chip_wait_s >= WAIT_WORTH_A_LINE_S:
        log(f"waited {chip_wait_s:.1f}s for the chips to be given back")
    # JAX_COMPILATION_CACHE_DIR, or the checkout's .jax_cache/: workers
    # inherit it (the node gives TPU workers the same; CPU ones nothing).
    use_compile_cache(os.environ)
    started_at = []

    def attempt():
        started_at.append(time.time())
        arm()
        scratch = tempfile.mkdtemp(prefix="bench_")
        trace_dir = (os.path.join(ROOT, ".bench_trace") if args.keep_trace
                     else os.path.join(scratch, "trace"))
        try:
            ray_tpu.init(_system_config={"session_dir_prefix": os.path.join(
                tempfile.gettempdir(), "ray_tpu")})
            return runner.run(cell, args, trace_dir, scratch)
        finally:
            signal.alarm(0)
            started = _descendants(os.getpid())
            ray_tpu.shutdown()      # stops every worker it started ...
            _wait_gone(started)     # ... and this waits until each ended
            shutil.rmtree(scratch, ignore_errors=True)

    def release() -> float:
        try:
            return wait_free(after_s)
        except chips.ChipsBusy as e:
            log(f"returning with the chips not given back: {e}")
            return e.waited

    out, error, release_wait_s, busy_retries = attempts(attempt, release)
    if release_wait_s >= WAIT_WORTH_A_LINE_S:
        log(f"waited {release_wait_s:.1f}s for the chips after shutdown")
    if out is None:
        return exit_code(error)

    rep = out["report"]
    for name, (value, limit) in rep["checks"].get("compared", {}).items():
        log(f"compared: {name} {value:.4g}, limit {limit:g}")
    for f in out["faults"]:
        log(f"not correct: {f}")
    values = dict(out["end_to_end"], setup_s=setup_seconds(
        rep["window_start_unix"], started_at[-1] - first_look))
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
    device = dict(rep["device"], memory_peak_bytes=rep["memory_peak_bytes"],
                  chip_wait_s=chip_wait_s, release_wait_s=release_wait_s,
                  busy_retries=busy_retries)
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
