"""The chips' hand-over between runs, from the jax-free driver process.

A process that held four chips at 16.6 GB each gives them back some time
after it was killed, reaped or seen as a zombie: the device file is released
by whichever of its threads leaves last, and nothing under /proc/<pid>/fd
shows it meanwhile (PERF.md section 7).  So a run asks the device itself:
`probe` opens the chip's file and closes it at once.  A VFIO group opens
once, so `EBUSY` means held; an open that sets no container starts and
resets nothing.  /dev/accel<n> opens any number of times, so there the
question is whether a process shows it among its files.

Nothing here imports jax or ray_tpu."""

from __future__ import annotations

import errno
import glob
import os
import signal
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

POLL_S = 0.5
ORPHAN_KILL_S = 30.0
# What a run.py killed from outside leaves behind (its workers, or a node
# service started on its own): `python -m <one of these>`.
RAY_TPU_PROCESSES = ("ray_tpu._private.worker_main",
                     "ray_tpu._private.node_service")

Holder = Dict[str, Any]     # pid, ppid, cmdline, files


class ChipsBusy(RuntimeError):
    """The chips could not be opened within the limit."""

    def __init__(self, waited: float, busy: Sequence[str],
                 held_by: Sequence[Holder]) -> None:
        self.waited, self.busy, self.held_by = waited, list(busy), list(held_by)
        super().__init__("\n    ".join(
            [f"{', '.join(busy)} still held after {waited:.1f}s"]
            + (describe(held_by) or ["no process shows them among its files"])))


def chip_paths() -> List[str]:
    """Every chip of the host, as accelerators.detect_num_chips counts
    them: /dev/accel<n>, else the numbered VFIO groups."""
    return (sorted(glob.glob("/dev/accel[0-9]*"))
            or sorted(glob.glob("/dev/vfio/[0-9]*")))


def _is_chip_file(target: str) -> bool:
    return target.startswith(("/dev/vfio/", "/dev/accel"))


def _open_files(pid: str, proc: str) -> List[str]:
    """The chip files a process has open.  Its threads share one table, but
    a leader that has exited shows none while a thread still holds them: so
    the first task whose table can be read answers for the process."""
    tasks = [pid]
    try:
        tasks += [t for t in os.listdir(f"{proc}/{pid}/task") if t != pid]
    except OSError:
        pass
    for tid in tasks:
        fd_dir = (f"{proc}/{pid}/fd" if tid == pid
                  else f"{proc}/{pid}/task/{tid}/fd")
        try:
            fds = os.listdir(fd_dir)
        except OSError:
            continue
        if not fds:
            continue
        found = []
        for fd in fds:
            try:
                target = os.readlink(f"{fd_dir}/{fd}")
            except OSError:
                continue
            if _is_chip_file(target):
                found.append(target)
        return sorted(set(found))
    return []


def proc_stat(pid, proc: str = "/proc"):
    """(name, state, parent pid) of a process, or None once it is gone."""
    try:
        with open(f"{proc}/{pid}/stat") as f:
            comm, rest = f.read().split("(", 1)[1].rsplit(")", 1)
    except (OSError, IndexError):
        return None
    state, ppid = rest.split()[:2]
    return comm, state, int(ppid)


def _describe(pid: str, proc: str) -> Optional[Dict[str, Any]]:
    st = proc_stat(pid, proc)
    if st is None:
        return None
    try:
        with open(f"{proc}/{pid}/cmdline", "rb") as f:
            cmdline = f.read().replace(b"\0", b" ").decode(
                "utf-8", "replace").strip()
    except OSError:
        return None
    return {"pid": int(pid), "ppid": st[2], "cmdline": cmdline or f"[{st[0]}]"}


def holders(proc: str = "/proc") -> List[Holder]:
    """Every process that has a /dev/vfio/* or /dev/accel* file open, with
    its pid, its parent's and its command line."""
    out = []
    for pid in os.listdir(proc):
        if not pid.isdigit() or int(pid) == os.getpid():
            continue
        files = _open_files(pid, proc)
        if files and (who := _describe(pid, proc)) is not None:
            out.append(dict(who, files=files))
    return out


def describe(held_by: Sequence[Holder]) -> List[str]:
    """One line a holder, for the log."""
    return [f"pid {h['pid']} (parent {h['ppid']}) holds "
            f"{', '.join(h['files'])}: {h['cmdline']}" for h in held_by]


def probe(path: str) -> bool:
    """Can this chip be opened now?"""
    if path.startswith("/dev/vfio/"):
        try:
            os.close(os.open(path, os.O_RDWR))
            return True
        except OSError as e:
            if e.errno == errno.EBUSY:
                return False
            # Not ours to open (permissions, a node that went away): what
            # is left is whether anybody shows it.
    return not any(path in h["files"] for h in holders())


def wait_free(limit_s: float, *, paths: Optional[Sequence[str]] = None,
              probe: Callable[[str], bool] = probe,
              clock: Callable[[], float] = time.monotonic,
              sleep: Callable[[float], None] = time.sleep,
              find_holders: Callable[[], List[Holder]] = holders) -> float:
    """Returns once every chip of the host opens, with the seconds that
    took: a fraction of a millisecond a chip when all are free, and the
    whole of it when an open itself had to wait (beside a worker on its
    way out the open blocks until the group is released, then succeeds:
    2.35 s once, PERF.md section 7).  After `limit_s` it raises ChipsBusy,
    which names the chips still held and who shows them among its files."""
    busy = list(chip_paths() if paths is None else paths)
    t0 = clock()
    while True:
        busy = [p for p in busy if not probe(p)]
        waited = clock() - t0
        if not busy:
            return waited
        if waited >= limit_s:
            raise ChipsBusy(waited, busy, find_holders())
        sleep(min(POLL_S, limit_s - waited))


def is_orphan(holder: Holder) -> bool:
    """A worker or node service of ray_tpu whose parent is gone (it was
    handed to pid 1).  A live run's worker has its run.py for a parent,
    and any other program that holds a chip is not ours to end."""
    return holder["ppid"] == 1 and any(
        name in holder["cmdline"].split() for name in RAY_TPU_PROCESSES)


def _alive(pid: int, proc: str = "/proc") -> bool:
    return os.path.exists(f"{proc}/{pid}")


def clear_orphans(log: Callable[[str], None], *,
                  find_holders: Callable[[], List[Holder]] = holders,
                  kill: Callable[[int, int], None] = os.kill,
                  alive: Callable[[int], bool] = _alive,
                  clock: Callable[[], float] = time.monotonic,
                  sleep: Callable[[float], None] = time.sleep) -> List[int]:
    """Before the first wait: what a dead run left on the chips is killed
    and waited for; whoever else holds one is reported and left alone.
    Returns the pids it killed."""
    killed = []
    for h in find_holders():
        line = describe([h])[0]
        if not is_orphan(h):
            log(f"a chip is held, not by an orphan of ray_tpu: {line}")
            continue
        log(f"killing an orphan of a dead run: {line}")
        try:
            kill(h["pid"], signal.SIGKILL)
        except ProcessLookupError:
            continue
        except PermissionError as e:
            log(f"cannot kill pid {h['pid']}: {e}")
            continue
        killed.append(h["pid"])
    t0 = clock()
    left = killed
    while left and clock() - t0 < ORPHAN_KILL_S:
        sleep(0.1)
        left = [p for p in left if alive(p)]
    if left:
        log(f"orphans still there {ORPHAN_KILL_S:.0f}s after SIGKILL: {left}")
    return killed
