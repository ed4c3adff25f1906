"""The chips' hand-over between runs (lib/chips.py) and what run.py makes
of a failure: the two waits, the one retry, where set-up starts, the exit
codes.  No device, no worker: probes, clocks and processes are arguments.
Under a second each."""

import os
import signal

import pytest

from benchmarks import run
from benchmarks.lib import chips

FOUR = [f"/dev/vfio/{n}" for n in range(4)]


class Clock:
    """A clock that only `sleep` moves."""

    def __init__(self):
        self.now, self.slept = 100.0, []

    def __call__(self):
        return self.now

    def sleep(self, s):
        self.slept.append(s)
        self.now += s


def busy_n_times(n, path="/dev/vfio/0"):
    """A probe under which `path` is held for the first n looks."""
    looks = []

    def probe(p):
        looks.append(p)
        return not (p == path and looks.count(path) <= n)
    return probe, looks


WORKER = {"pid": 4242, "ppid": 1, "files": ["/dev/vfio/0", "/dev/vfio/3"],
          "cmdline": "/usr/bin/python3 -m ray_tpu._private.worker_main "
                     "--node 127.0.0.1:7001"}


def test_free_chips_cost_no_wait():
    clock = Clock()
    probe, looks = busy_n_times(0)
    assert chips.wait_free(180, paths=FOUR, probe=probe, clock=clock,
                           sleep=clock.sleep) == 0.0
    assert looks == FOUR and clock.slept == []


@pytest.mark.parametrize("n", [1, 3, 19])
def test_a_held_chip_is_waited_for_and_the_seconds_reported(n):
    clock = Clock()
    probe, looks = busy_n_times(n)
    waited = chips.wait_free(180, paths=FOUR, probe=probe, clock=clock,
                             sleep=clock.sleep)
    assert waited == pytest.approx(n * chips.POLL_S)
    assert clock.slept == [chips.POLL_S] * n
    # every chip of the host is asked, and one that opened is not asked again
    assert looks.count("/dev/vfio/0") == n + 1
    assert all(looks.count(p) == 1 for p in FOUR[1:])


def test_an_open_that_blocked_counts_as_waiting():
    """Beside a worker on its way out the open itself waits, then
    succeeds: no look read busy, and the seconds still went by."""
    clock = Clock()

    def probe(p):
        if p == "/dev/vfio/0":
            clock.now += 2.35
        return True
    assert chips.wait_free(180, paths=FOUR, probe=probe, clock=clock,
                           sleep=clock.sleep) == pytest.approx(2.35)
    assert clock.slept == []


def test_the_wait_gives_up_with_the_holders_list():
    clock = Clock()
    with pytest.raises(chips.ChipsBusy) as e:
        chips.wait_free(3.2, paths=FOUR, probe=lambda p: p != "/dev/vfio/3",
                        clock=clock, sleep=clock.sleep,
                        find_holders=lambda: [WORKER])
    assert e.value.busy == ["/dev/vfio/3"]
    assert e.value.waited == pytest.approx(3.2)
    assert e.value.held_by == [WORKER]
    assert "/dev/vfio/3 still held after 3.2s" in str(e.value)
    assert chips.describe(e.value.held_by) == [
        "pid 4242 (parent 1) holds /dev/vfio/0, /dev/vfio/3: " +
        WORKER["cmdline"]]
    assert run.exit_code(e.value) == 4


def test_the_probe_opens_a_vfio_group_and_reads_ebusy_as_held(monkeypatch):
    opened = []

    def fake_open(path, flags):
        opened.append((path, flags))
        if path == "/dev/vfio/1":
            raise OSError(16, "Device or resource busy")
        return 99
    monkeypatch.setattr(chips.os, "open", fake_open)
    monkeypatch.setattr(chips.os, "close", lambda fd: opened.append(fd))
    assert chips.probe("/dev/vfio/0") is True
    assert chips.probe("/dev/vfio/1") is False
    assert opened == [("/dev/vfio/0", os.O_RDWR), 99,
                      ("/dev/vfio/1", os.O_RDWR)]


def fake_proc(tmp_path, pid, ppid, cmdline, files, leader_gone=False):
    """A /proc entry; with `leader_gone` the leader shows no files and a
    second thread still has them (a process on its way out)."""
    d = tmp_path / str(pid)
    holder = d / "task" / str(pid + 1) / "fd" if leader_gone else d / "fd"
    holder.mkdir(parents=True)
    (d / "fd").mkdir(exist_ok=True)
    (d / "task" / str(pid)).mkdir(parents=True, exist_ok=True)
    for n, target in enumerate(files):
        os.symlink(target, holder / str(n))
    (d / "stat").write_text(f"{pid} (python3 (x)) S {ppid} 1 1 0 -1")
    (d / "cmdline").write_bytes(cmdline.replace(" ", "\0").encode())


def test_holders_are_found_by_their_open_files(tmp_path):
    fake_proc(tmp_path, 10, 1, "python3 -m ray_tpu._private.worker_main",
              ["/dev/null", "/dev/vfio/2", "/dev/vfio/vfio"])
    fake_proc(tmp_path, 20, 7, "python3 benchmarks/run.py", ["/dev/null"])
    fake_proc(tmp_path, 30, 7, "", ["/dev/accel0"], leader_gone=True)
    (tmp_path / "self").mkdir()
    found = chips.holders(proc=str(tmp_path))
    assert sorted(found, key=lambda h: h["pid"]) == [
        {"pid": 10, "ppid": 1, "files": ["/dev/vfio/2", "/dev/vfio/vfio"],
         "cmdline": "python3 -m ray_tpu._private.worker_main"},
        {"pid": 30, "ppid": 7, "files": ["/dev/accel0"],
         "cmdline": "[python3 (x)]"}]


@pytest.mark.parametrize("ppid,cmdline,orphan", [
    (1, WORKER["cmdline"], True),
    (1, "python3 -m ray_tpu._private.node_service --port 7001", True),
    # a live run's worker: its run.py is its parent
    (4100, WORKER["cmdline"], False),
    # somebody else's program on the chip, whoever its parent is
    (1, "python3 train.py --name ray_tpu._private.worker_main_copy", False),
    (1, "python3 benchmarks/run.py --workload train-4k-fsdp4", False)])
def test_an_orphan_is_told_by_parent_and_command_line(ppid, cmdline, orphan):
    assert chips.is_orphan(dict(WORKER, ppid=ppid, cmdline=cmdline)) is orphan


def test_orphans_are_killed_and_waited_for_and_others_left_alone():
    live = dict(WORKER, pid=5000, ppid=4100)
    clock, killed, said = Clock(), [], []
    gone_after = {4242: 3}

    def alive(pid):
        gone_after[pid] -= 1
        return gone_after[pid] > 0
    out = chips.clear_orphans(
        said.append, find_holders=lambda: [WORKER, live],
        kill=lambda pid, sig: killed.append((pid, sig)), alive=alive,
        clock=clock, sleep=clock.sleep)
    assert out == [4242] and killed == [(4242, signal.SIGKILL)]
    assert len(clock.slept) == 3
    assert any("killing an orphan" in s and "pid 4242" in s for s in said)
    assert any("not by an orphan" in s and "pid 5000" in s for s in said)


def test_setup_excludes_the_wait(monkeypatch):
    monkeypatch.setattr(run, "T_START", 1000.0)
    assert run.setup_seconds(1030.25, 0.0) == 30.25
    # 9 s went by before the chips were free: the same set-up
    assert run.setup_seconds(1039.25, 9.0) == 30.25


BUSY_ERROR = RuntimeError(
    "train cell failed: jaxlib.xla_extension.XlaRuntimeError: UNKNOWN: TPU "
    "initialization failed: open(/dev/vfio/0): Device or resource busy")


def drive_attempts(errors):
    """run.attempts over tries that raise `errors` in turn (None = a
    result); returns what it returned and how often each part ran."""
    errors, calls = list(errors), {"attempt": 0, "release": 0}

    def attempt():
        calls["attempt"] += 1
        e = errors.pop(0)
        if e is not None:
            raise e
        return {"report": {}}

    def release():
        calls["release"] += 1
        return 1.5
    return run.attempts(attempt, release), calls


def test_a_busy_chip_is_retried_once(capsys):
    (out, error, released, retries), calls = drive_attempts(
        [BUSY_ERROR, None])
    assert out == {"report": {}} and error is None
    assert (released, retries) == (1.5, 1)
    assert calls == {"attempt": 2, "release": 2}    # released after each
    err = capsys.readouterr().err
    assert "[bench] FAILED: RuntimeError: train cell failed" in err
    assert "running once more" in err


def test_a_chip_busy_twice_ends_the_run_with_4():
    (out, error, _, retries), calls = drive_attempts(
        [BUSY_ERROR, BUSY_ERROR, None])
    assert out is None and retries == 1 and calls["attempt"] == 2
    assert run.exit_code(error) == 4


@pytest.mark.parametrize("error", [
    RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating 2.1G"),
    run.DeadlinePassed("not finished after 1150s"),
    ValueError("a fault of the program"), KeyboardInterrupt()])
def test_no_other_error_is_retried(error):
    (out, got, released, retries), calls = drive_attempts([error, None])
    assert out is None and got is error and retries == 0
    assert calls == {"attempt": 1, "release": 1} and released == 1.5


@pytest.mark.parametrize("error,code", [
    (None, 0),
    (ValueError("no cost function 'x'"), 1),
    (TimeoutError("a get() of the program timed out"), 1),
    (run.NoTpu("1 TPU chip(s) found, the cell needs 4"), 2),
    (ModuleNotFoundError("No module named 'ray_tpu'"), 3),
    (chips.ChipsBusy(180.0, ["/dev/vfio/0"], []), 4),
    (BUSY_ERROR, 4),
    (run.DeadlinePassed("not finished after 1150s"), 5),
    (RuntimeError("train cell failed: XlaRuntimeError: RESOURCE_EXHAUSTED: "
                  "Attempting to allocate 1.2G. That was not possible"), 6)])
def test_exit_code_names_the_failure(error, code):
    assert run.exit_code(error) == code


def test_the_docstring_lists_every_exit_code():
    listed = {int(line.split()[0]) for line in run.__doc__.splitlines()
              if line.startswith("    ") and line.split()[0].isdigit()}
    assert listed == {0, 1, 2, 3, 4, 5, 6}
