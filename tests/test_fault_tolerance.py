"""Failure-path tests (reference analog: test_failure*.py, test_chaos.py,
RAY_testing_rpc_failure injection in src/ray/rpc/rpc_chaos.h)."""

import os
import signal
import time

import pytest

import ray_tpu
from ray_tpu import exceptions as exc


def test_task_retry_on_worker_crash(ray_start):
    @ray_tpu.remote(max_retries=2)
    def flaky(marker_dir):
        marker = os.path.join(marker_dir, "attempt")
        if not os.path.exists(marker):
            open(marker, "w").close()
            os._exit(1)  # simulate worker crash on first attempt
        return "recovered"

    import tempfile
    with tempfile.TemporaryDirectory() as d:
        assert ray_tpu.get(flaky.remote(d), timeout=60) == "recovered"


def test_no_retry_fails(ray_start):
    @ray_tpu.remote(max_retries=0)
    def die():
        os._exit(1)

    with pytest.raises(exc.WorkerCrashedError):
        ray_tpu.get(die.remote(), timeout=60)


def test_actor_death_fails_pending(ray_start):
    @ray_tpu.remote
    class A:
        def pid(self):
            return os.getpid()

        def die(self):
            os._exit(1)

    a = A.remote()
    pid = ray_tpu.get(a.pid.remote())
    assert pid > 0
    a.die.remote()
    with pytest.raises((exc.ActorDiedError, exc.TaskError)):
        ray_tpu.get(a.pid.remote(), timeout=60)


def test_actor_restart(ray_start):
    @ray_tpu.remote
    class Phoenix:
        def __init__(self):
            self.calls = 0

        def incr(self):
            self.calls += 1
            return self.calls

        def die(self):
            os._exit(1)

    p = Phoenix.options(max_restarts=1).remote()
    assert ray_tpu.get(p.incr.remote()) == 1
    p.die.remote()
    # After restart, state resets (no checkpointing) but the actor lives.
    deadline = time.time() + 60
    val = None
    while time.time() < deadline:
        try:
            val = ray_tpu.get(p.incr.remote(), timeout=15)
            break
        except (exc.ActorDiedError, exc.TaskError, exc.GetTimeoutError):
            time.sleep(0.3)
    assert val == 1, "restarted actor should respond with fresh state"


def test_kill_external_process(ray_start):
    @ray_tpu.remote
    class A:
        def pid(self):
            return os.getpid()

    a = A.remote()
    pid = ray_tpu.get(a.pid.remote())
    os.kill(pid, signal.SIGKILL)
    with pytest.raises((exc.ActorDiedError, exc.TaskError)):
        ray_tpu.get(a.pid.remote(), timeout=60)


def test_driver_sigkill_reaps_all_workers(tmp_path):
    """Hard driver death must not leak worker processes (r4 weak #7:
    orphaned worker_main processes observed after suite kills).

    The node service runs as threads INSIDE the driver, so SIGKILLing
    the driver closes every worker's node socket at the kernel level;
    workers must treat that disconnect as a death sentence (worker_main
    on_disconnect -> _exit), not block on their task queue forever."""
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""\
        import os, sys, time
        sys.path.insert(0, %r)
        import ray_tpu
        ray_tpu.init(num_cpus=2)

        @ray_tpu.remote
        def f():
            return os.getpid()

        pids = set(ray_tpu.get([f.remote() for _ in range(4)]))

        @ray_tpu.remote
        class A:
            def pid(self):
                return os.getpid()

        a = A.remote()
        pids.add(ray_tpu.get(a.pid.remote()))
        print("PIDS " + ",".join(map(str, pids)), flush=True)
        time.sleep(300)   # murdered long before this returns
        """) % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen([sys.executable, "-c", script], env=env,
                            stdout=subprocess.PIPE, text=True)
    try:
        line = ""
        deadline = time.time() + 120
        while time.time() < deadline:
            line = proc.stdout.readline()
            if line.startswith("PIDS "):
                break
            if not line and proc.poll() is not None:
                break   # child died before reporting: fail below
        assert line.startswith("PIDS "), "driver never reported workers"
        worker_pids = [int(p) for p in line.split()[1].split(",")]
        assert worker_pids

        def alive(pid: int) -> bool:
            try:
                os.kill(pid, 0)
                return True
            except ProcessLookupError:
                return False
            except PermissionError:
                return True

        assert any(alive(p) for p in worker_pids)
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=10)

        deadline = time.time() + 60
        while time.time() < deadline:
            leftovers = [p for p in worker_pids if alive(p)]
            if not leftovers:
                return
            time.sleep(0.5)
        raise AssertionError(
            f"workers leaked after driver SIGKILL: {leftovers}")
    finally:
        if proc.poll() is None:
            proc.kill()


# ---------------------------------------------------------------------------
# retry_exceptions: application-level retry (PR: unified retry policy)
# ---------------------------------------------------------------------------
def _attempt(marker_dir):
    """Count this attempt; returns the attempt index (1-based)."""
    import glob
    n = len(glob.glob(os.path.join(marker_dir, "a*"))) + 1
    open(os.path.join(marker_dir, f"a{n}"), "w").close()
    return n


def test_retry_exceptions_true_recovers(ray_start):
    import tempfile

    @ray_tpu.remote(max_retries=2, retry_exceptions=True)
    def flaky(d):
        if _attempt(d) == 1:
            raise ValueError("transient app error")
        return "recovered"

    with tempfile.TemporaryDirectory() as d:
        assert ray_tpu.get(flaky.remote(d), timeout=60) == "recovered"


def test_retry_exceptions_matching_list(ray_start):
    import tempfile

    @ray_tpu.remote(max_retries=3, retry_exceptions=[ValueError])
    def flaky(d):
        if _attempt(d) < 3:
            raise ValueError("transient")
        return "ok"

    with tempfile.TemporaryDirectory() as d:
        assert ray_tpu.get(flaky.remote(d), timeout=60) == "ok"


def test_retry_exceptions_non_matching_fails_once(ray_start):
    import glob
    import tempfile

    @ray_tpu.remote(max_retries=3, retry_exceptions=[KeyError])
    def wrong(d):
        _attempt(d)
        raise ValueError("not retryable")

    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(exc.TaskError):
            ray_tpu.get(wrong.remote(d), timeout=60)
        # The ValueError did not match [KeyError]: exactly one attempt.
        assert len(glob.glob(os.path.join(d, "a*"))) == 1


def test_retry_exceptions_default_off(ray_start):
    import glob
    import tempfile

    @ray_tpu.remote(max_retries=3)
    def raises(d):
        _attempt(d)
        raise ValueError("app errors don't retry by default")

    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(exc.TaskError):
            ray_tpu.get(raises.remote(d), timeout=60)
        assert len(glob.glob(os.path.join(d, "a*"))) == 1


def test_retry_exceptions_bad_value_rejected(ray_start):
    with pytest.raises(TypeError):
        ray_tpu.remote(retry_exceptions=[42])(lambda: None)


def test_retry_backoff_timing(ray_start):
    """Retries are spaced by exponential backoff with jitter: base=300ms
    gives delays in [150,300] + [300,600] ms — two retries take >=0.4s
    end to end (immediate resubmission would finish in ~0.1s)."""
    import tempfile
    from ray_tpu._private.config import config

    @ray_tpu.remote(max_retries=2, retry_exceptions=True)
    def flaky(d):
        if _attempt(d) < 3:
            raise ValueError("again")
        return "done"

    config.set("task_retry_delay_ms", 300)
    try:
        with tempfile.TemporaryDirectory() as d:
            t0 = time.time()
            assert ray_tpu.get(flaky.remote(d), timeout=60) == "done"
            elapsed = time.time() - t0
    finally:
        with config._lock:
            config._overrides.pop("task_retry_delay_ms", None)
    assert elapsed >= 0.4, f"retries resubmitted too fast: {elapsed:.3f}s"


# ---------------------------------------------------------------------------
# actor max_task_retries + ActorUnavailableError (PR: unified retry)
# ---------------------------------------------------------------------------
def test_actor_max_task_retries_rides_restart(ray_start):
    """An in-flight call lost to a worker crash replays after the actor
    restarts when the call has task-retry budget."""
    import tempfile

    @ray_tpu.remote
    class Phoenix:
        def __init__(self, d):
            self.d = d

        def flaky(self):
            m = os.path.join(self.d, "m")
            if not os.path.exists(m):
                open(m, "w").close()
                os._exit(1)
            return "ok"

    with tempfile.TemporaryDirectory() as d:
        a = Phoenix.options(max_restarts=1, max_task_retries=1).remote(d)
        assert ray_tpu.get(a.flaky.remote(), timeout=60) == "ok"


def test_actor_unavailable_without_task_budget(ray_start):
    """No task-retry budget + a restarting actor: the lost in-flight
    call fails with the TRANSIENT ActorUnavailableError, and the actor
    comes back for subsequent calls."""
    import tempfile

    @ray_tpu.remote
    class Phoenix:
        def __init__(self, d):
            self.d = d

        def flaky(self):
            m = os.path.join(self.d, "m")
            if not os.path.exists(m):
                open(m, "w").close()
                os._exit(1)
            return "ok"

    with tempfile.TemporaryDirectory() as d:
        a = Phoenix.options(max_restarts=1).remote(d)
        with pytest.raises(exc.ActorUnavailableError):
            ray_tpu.get(a.flaky.remote(), timeout=60)
        assert ray_tpu.get(a.flaky.remote(), timeout=60) == "ok"


def test_actor_died_task_started_flag(ray_start):
    """Permanent death marks queued calls task_started=False (safe to
    re-route) and keeps them typed ActorDiedError."""
    @ray_tpu.remote
    class A:
        def boom(self):
            os._exit(1)

        def after(self):
            return 1

    a = A.remote()
    a.boom.remote()
    ref = a.after.remote()
    with pytest.raises(exc.ActorDiedError) as ei:
        ray_tpu.get(ref, timeout=60)
    assert ei.value.task_started is not True


def test_retry_exceptions_locally_defined_type(ray_start):
    """A function-local exception class (unimportable by name anywhere)
    must still work: the policy ships as qualified NAMES matched
    against the raised type's MRO, never as pickled classes — a class
    in the plain-pickle task spec would kill the worker's receive
    loop instead of enabling retry."""
    import tempfile

    class Transient(Exception):
        pass

    @ray_tpu.remote(max_retries=2, retry_exceptions=[Transient])
    def flaky(d):
        if _attempt(d) == 1:
            raise Transient("first attempt")
        return "ok"

    with tempfile.TemporaryDirectory() as d:
        assert ray_tpu.get(flaky.remote(d), timeout=60) == "ok"


def test_retry_exceptions_matches_subclasses(ray_start):
    """Listing a base class retries subclass raises (MRO-name match
    preserves isinstance semantics)."""
    import tempfile

    @ray_tpu.remote(max_retries=2, retry_exceptions=[ArithmeticError])
    def flaky(d):
        if _attempt(d) == 1:
            raise ZeroDivisionError("subclass of ArithmeticError")
        return "ok"

    with tempfile.TemporaryDirectory() as d:
        assert ray_tpu.get(flaky.remote(d), timeout=60) == "ok"
