"""Actor lifecycle edge cases found in review: creation crashes, kill
races, restart with ref args, strict ordering under dependency stalls.
(Reference analog: test_actor_failures.py / gcs_actor_manager semantics.)"""

import gc
import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import exceptions as exc


def test_ordering_preserved_under_dep_stall(ray_start):
    """A later no-dep call must not overtake an earlier call whose arg is
    still being produced (sync actors guarantee submission order)."""
    @ray_tpu.remote
    def slow_value():
        time.sleep(1.0)
        return 5

    @ray_tpu.remote
    class Cell:
        def __init__(self):
            self.v = 0

        def set(self, v):
            self.v = v

        def get(self):
            return self.v

    c = Cell.remote()
    c.set.remote(slow_value.remote())
    # Submitted after set(), must observe its effect.
    assert ray_tpu.get(c.get.remote(), timeout=60) == 5


def test_crash_during_init_does_not_hang(ray_start):
    @ray_tpu.remote
    class DieOnInit:
        def __init__(self):
            os._exit(1)

        def m(self):
            return 1

    a = DieOnInit.remote()
    with pytest.raises((exc.ActorDiedError, exc.TaskError,
                        exc.WorkerCrashedError)):
        ray_tpu.get(a.m.remote(), timeout=60)


def test_kill_during_creation_no_resurrection(ray_start):
    @ray_tpu.remote
    class SlowInit:
        def __init__(self):
            time.sleep(2.0)

        def m(self):
            return 1

    a = SlowInit.remote()
    time.sleep(0.2)  # creation in flight
    ray_tpu.kill(a)
    with pytest.raises((exc.ActorDiedError, exc.TaskError,
                        exc.WorkerCrashedError)):
        ray_tpu.get(a.m.remote(), timeout=60)


def test_restart_with_ref_init_args(ray_start):
    """Restart replays the creation spec; its ObjectRef init args (and the
    >100KB packed arg blob) must still exist on the second creation."""
    big = np.arange(200_000, dtype=np.float64)  # ~1.6 MB arg blob

    @ray_tpu.remote
    class Holder:
        def __init__(self, data, ref_arg):
            self.total = float(np.sum(data)) + ref_arg

        def get_total(self):
            return self.total

        def die(self):
            os._exit(1)

    h = Holder.options(max_restarts=1).remote(big, ray_tpu.put(1.0))
    expected = float(np.sum(big)) + 1.0
    assert ray_tpu.get(h.get_total.remote(), timeout=60) == expected
    h.die.remote()
    deadline = time.time() + 60
    val = None
    while time.time() < deadline:
        try:
            val = ray_tpu.get(h.get_total.remote(), timeout=15)
            break
        except (exc.ActorDiedError, exc.TaskError, exc.GetTimeoutError):
            time.sleep(0.3)
    assert val == expected, "restarted actor must rebuild from same args"


def test_embedded_ref_survives_creation(ray_start):
    """The driver's ref passed as an init arg must remain gettable after
    the actor is created and killed (no unbalanced decref)."""
    @ray_tpu.remote
    class Eph:
        def __init__(self, x):
            self.x = x

        def ping(self):
            return 1

    data_ref = ray_tpu.put(np.ones(1000))
    e = Eph.options(max_restarts=1).remote(data_ref)
    assert ray_tpu.get(e.ping.remote()) == 1
    ray_tpu.kill(e)
    time.sleep(0.5)
    gc.collect()
    # Driver's own ref must still resolve.
    assert float(np.sum(ray_tpu.get(data_ref))) == 1000.0


def test_wait_polling_does_not_leak_waiters(ray_start):
    @ray_tpu.remote
    def never():
        time.sleep(60)

    ref = never.remote()
    for _ in range(50):
        ready, _ = ray_tpu.wait([ref], num_returns=1, timeout=0.01)
        assert ready == []
    sess = ray_tpu._session
    with sess.node_service.lock:
        entry = sess.node_service.objects.get(ref.binary())
        n_waiters = len(entry.waiters) if entry else 0
    assert n_waiters <= 2, f"waiter leak: {n_waiters} stale waiters"


def test_exit_actor_intentional_no_restart(ray_start, tmp_path):
    """ray_tpu.exit_actor(): the exiting call returns normally, the
    actor dies permanently (no restart even with budget), and later
    calls fail with the 'exited' reason (reference:
    ray.actor.exit_actor)."""
    import time

    marker = str(tmp_path / "inits")

    @ray_tpu.remote(max_restarts=3)
    class Quitter:
        def __init__(self):
            with open(marker, "a") as f:
                f.write("x")

        def leave(self):
            ray_tpu.exit_actor()

        def ping(self):
            return "pong"

    a = Quitter.remote()
    assert ray_tpu.get(a.ping.remote()) == "pong"
    assert ray_tpu.get(a.leave.remote()) is None   # call itself succeeds
    deadline = time.time() + 15
    while time.time() < deadline:
        try:
            ray_tpu.get(a.ping.remote(), timeout=5)
        except ray_tpu.exceptions.ActorDiedError as e:
            assert "exit_actor" in str(e)
            break
        time.sleep(0.1)
    else:
        raise AssertionError("actor never died after exit_actor()")
    time.sleep(0.5)                       # any restart would re-init
    assert open(marker).read() == "x"     # __init__ ran exactly once


def test_exit_actor_outside_actor_errors(ray_start):
    with __import__("pytest").raises(RuntimeError):
        ray_tpu.exit_actor()

    @ray_tpu.remote
    def not_an_actor():
        ray_tpu.exit_actor()

    with __import__("pytest").raises(ray_tpu.exceptions.TaskError):
        ray_tpu.get(not_an_actor.remote())


def test_get_tpu_ids_in_pinned_worker(ray_start_tpu):
    @ray_tpu.remote(resources={"TPU": 1})
    def ids():
        return ray_tpu.get_tpu_ids()
    assert ray_tpu.get(ids.remote()) in ([0], [1])


def test_handle_gc_releases_actor(ray_start):
    """Reference actor-lifetime semantics: the last in-scope handle to
    an unnamed, non-detached actor releases it AFTER queued work
    drains; pickled and named handles opt out of local GC."""
    import gc
    import time

    @ray_tpu.remote
    class E:
        def ping(self):
            return 1

        def slow(self):
            time.sleep(0.3)
            return "done"

    # Queued work drains before the GC kill: submit, drop the handle,
    # the result still arrives.
    a = E.options(num_cpus=0).remote()
    ref = a.slow.remote()
    del a
    gc.collect()
    assert ray_tpu.get(ref, timeout=30) == "done"

    # Sequential leak pattern: far more actors than the worker pool
    # cap complete because each release returns a worker.
    for _ in range(12):
        h = E.options(num_cpus=0).remote()
        assert ray_tpu.get(h.ping.remote(), timeout=30) == 1
        del h
        gc.collect()

    # Named actors are exempt: still reachable after the handle dies.
    E.options(name="keeper", num_cpus=0).remote()
    gc.collect()
    time.sleep(0.5)
    keeper = ray_tpu.get_actor("keeper")
    assert ray_tpu.get(keeper.ping.remote(), timeout=30) == 1


def test_actors_beyond_worker_pool_cap_start(ray_start):
    """Zero-CPU actors hold no resource, so more of them than the
    node's pooled-worker cap (8 at num_cpus=4) must all start, and a
    task submitted beside them must still get a worker: an actor's
    worker has left the pool and does not count against the cap.
    (The envelope's 12-actor churn leg hung on its ninth actor.)"""
    @ray_tpu.remote
    class A:
        def ping(self):
            return 1

    @ray_tpu.remote
    def one():
        return 1

    actors = [A.remote() for _ in range(12)]
    assert ray_tpu.get([a.ping.remote() for a in actors],
                       timeout=30) == [1] * 12
    assert ray_tpu.get(one.remote(), timeout=30) == 1
    for a in actors:
        ray_tpu.kill(a)
