"""Core-runtime microbenchmarks, JSON-logged.

Analog of the reference's microbenchmark driver
(python/ray/_private/ray_perf.py:93, `ray microbenchmark` CLI) whose
published numbers are the BASELINE.md table (release_logs/2.9.3/
microbenchmark.json): sync/async actor calls/s, task throughput, object
put rate and bandwidth, get latency.

Run: python -m ray_tpu.util.microbench [--out FILE]
Prints one JSON object; with --out also writes it to FILE.
"""

from __future__ import annotations

import argparse
import json
import time


def _rate(n: int, dt: float) -> float:
    return round(n / dt, 1)


def _settle(ray_tpu, *actors) -> None:
    """Kill a bench's actors NOW and give teardown a beat — handle-GC
    release churn (worker kills) must not run inside the next bench's
    timed window."""
    for a in actors:
        try:
            ray_tpu.kill(a)
        except Exception:
            pass
    time.sleep(0.2)


def bench_actor_calls_sync(ray_tpu, n: int = 300) -> float:
    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.x = 0

        def inc(self):
            self.x += 1
            return self.x

    a = Counter.remote()
    ray_tpu.get(a.inc.remote())  # warm: actor alive, worker hot
    t0 = time.perf_counter()
    for _ in range(n):
        ray_tpu.get(a.inc.remote())
    rate = _rate(n, time.perf_counter() - t0)
    _settle(ray_tpu, a)
    return rate


def bench_actor_calls_async(ray_tpu, n: int = 2000) -> float:
    """Pipelined (submit all, then drain) — the reference's 'async' mode."""
    @ray_tpu.remote
    class Echo:
        def ping(self):
            return 1

    a = Echo.remote()
    ray_tpu.get(a.ping.remote())
    t0 = time.perf_counter()
    refs = [a.ping.remote() for _ in range(n)]
    ray_tpu.get(refs[-1])   # single-threaded actor: strictly in order
    rate = _rate(n, time.perf_counter() - t0)
    _settle(ray_tpu, a)
    return rate


def bench_actor_calls_concurrent(ray_tpu, n: int = 2000) -> float:
    """Pipelined calls against a max_concurrency actor (reference:
    1_1_actor_calls_concurrent — threaded actor, overlapping calls)."""
    @ray_tpu.remote
    class Echo:
        def ping(self):
            return 1

    a = Echo.options(max_concurrency=8).remote()
    ray_tpu.get(a.ping.remote())
    t0 = time.perf_counter()
    refs = [a.ping.remote() for _ in range(n)]
    # Wait on ALL refs: a concurrent actor finishes out of order, so
    # refs[-1] alone would stop the clock with calls still running.
    ray_tpu.get(refs)
    rate = _rate(n, time.perf_counter() - t0)
    _settle(ray_tpu, a)
    return rate


def bench_one_to_n_actor_calls(ray_tpu, n_actors: int = 4,
                               calls: int = 500) -> float:
    """One caller fanning out over N actors (reference:
    1_n_actor_calls_async)."""
    @ray_tpu.remote
    class Echo:
        def ping(self):
            return 1

    actors = [Echo.remote() for _ in range(n_actors)]
    ray_tpu.get([a.ping.remote() for a in actors])
    t0 = time.perf_counter()
    refs = [actors[i % n_actors].ping.remote()
            for i in range(calls * n_actors)]
    ray_tpu.get(refs)
    rate = _rate(calls * n_actors, time.perf_counter() - t0)
    _settle(ray_tpu, *actors)
    return rate


def bench_n_to_n_actor_calls(ray_tpu, n_pairs: int = 4,
                             calls: int = 400) -> float:
    """N caller actors each driving their own callee (reference:
    n_n_actor_calls_async): measures dispatch-plane aggregate, not a
    single pair."""
    @ray_tpu.remote
    class Echo:
        def ping(self):
            return 1

    @ray_tpu.remote
    class Caller:
        def __init__(self, target):
            self._t = target

        def drive(self, n):
            import ray_tpu as rt
            refs = [self._t.ping.remote() for _ in range(n)]
            rt.get(refs)
            return n

    # Zero-CPU actors: the bench measures the dispatch plane, and
    # 2*n_pairs default-CPU actors would deadlock on a small host
    # (callers hold every slot, callees never schedule).
    callees = [Echo.options(num_cpus=0).remote()
               for _ in range(n_pairs)]
    callers = [Caller.options(num_cpus=0).remote(c) for c in callees]
    ray_tpu.get([c.drive.remote(5) for c in callers])   # warm
    t0 = time.perf_counter()
    done = ray_tpu.get([c.drive.remote(calls) for c in callers])
    rate = _rate(sum(done), time.perf_counter() - t0)
    _settle(ray_tpu, *(callers + callees))
    return rate


def bench_tasks_async(ray_tpu, n: int = 500) -> float:
    @ray_tpu.remote
    def nop():
        return 1

    # Warm the worker pool to steady state first (the reference's
    # harness also excludes pool growth from the measured window).
    ray_tpu.get([nop.remote() for _ in range(100)])
    t0 = time.perf_counter()
    refs = [nop.remote() for _ in range(n)]
    ray_tpu.get(refs)
    return _rate(n, time.perf_counter() - t0)


def bench_put_small(ray_tpu, n: int = 2000) -> float:
    payload = b"x" * 1024
    ray_tpu.put(payload)
    t0 = time.perf_counter()
    refs = [ray_tpu.put(payload) for _ in range(n)]
    dt = time.perf_counter() - t0
    del refs
    return _rate(n, dt)


def bench_put_gbps(ray_tpu, n: int = 10, mb: int = 64) -> float:
    import numpy as np
    payload = np.random.bytes(mb * 1024 * 1024)
    r = ray_tpu.put(payload)
    del r
    t0 = time.perf_counter()
    for _ in range(n):
        # Drop each ref immediately so the directory can free the entry;
        # holding all n would need n*mb of live store.
        r = ray_tpu.put(payload)
        del r
    dt = time.perf_counter() - t0
    return round(n * mb / 1024 / dt, 2)


def bench_multi_client_put_gbps(ray_tpu, clients: int = 4, n: int = 6,
                                mb: int = 32) -> float:
    """Aggregate put bandwidth of N separate PROCESSES writing
    concurrently (reference: multi_client_put_gigabytes, 35.9 GB/s on
    64 cores).  This is the benchmark the broker-less design exists
    for: every writer maps the shared segment and memcpys directly —
    no per-put server round-trip to serialize on (the reference's
    plasma store brokers every create through the store thread)."""
    @ray_tpu.remote
    class Putter:
        def __init__(self, mb: int) -> None:
            # Imported here, not in the enclosing scope: a closure-
            # captured module rides the pickled actor spec (RT002).
            import numpy as np
            self.payload = np.random.bytes(mb * 1024 * 1024)

        def warm(self) -> int:
            r = ray_tpu.put(self.payload)  # noqa: F841
            return 1

        def put_n(self, n: int) -> float:
            t0 = time.perf_counter()
            for _ in range(n):
                r = ray_tpu.put(self.payload)
                del r     # drop so the segment can recycle the space
            return time.perf_counter() - t0

    actors = [Putter.remote(mb) for _ in range(clients)]
    ray_tpu.get([a.warm.remote() for a in actors])
    t0 = time.perf_counter()
    ray_tpu.get([a.put_n.remote(n) for a in actors])
    wall = time.perf_counter() - t0
    _settle(ray_tpu, *actors)
    return round(clients * n * mb / 1024 / wall, 2)


def bench_multi_client_put_small(ray_tpu, clients: int = 4,
                                 n: int = 300) -> float:
    """Aggregate small-put rate of N concurrent processes (reference:
    multi_client_put_calls_Plasma_Store, 12,677/s on 64 cores)."""

    @ray_tpu.remote
    class Putter:
        def warm(self) -> int:
            ray_tpu.put(b"x" * 1024)
            return 1

        def put_n(self, n: int) -> float:
            payload = b"x" * 1024
            t0 = time.perf_counter()
            for _ in range(n):
                r = ray_tpu.put(payload)
                del r
            return time.perf_counter() - t0

    actors = [Putter.remote() for _ in range(clients)]
    ray_tpu.get([a.warm.remote() for a in actors])
    t0 = time.perf_counter()
    ray_tpu.get([a.put_n.remote(n) for a in actors])
    wall = time.perf_counter() - t0
    _settle(ray_tpu, *actors)
    return _rate(clients * n, wall)


def bench_get_latency_us(ray_tpu, n: int = 1000) -> float:
    """Median latency of get() on a small plasma-resident object."""
    import numpy as np
    ref = ray_tpu.put(np.arange(64 * 1024, dtype=np.uint8))  # shm-resident
    ray_tpu.get(ref)
    lats = []
    for _ in range(n):
        t0 = time.perf_counter()
        ray_tpu.get(ref)
        lats.append(time.perf_counter() - t0)
    lats.sort()
    return round(lats[n // 2] * 1e6, 1)


def bench_thin_client_sync(n: int = 500) -> float:
    """1:1 sync actor calls THROUGH the thin client (reference:
    client__1_1_actor_calls_sync, 515/s on m5.16xlarge) — run in a
    subprocess so the client is a genuinely separate process speaking
    TCP to the cluster node."""
    import subprocess
    import sys
    import textwrap

    import ray_tpu
    node = ray_tpu._session.node_service
    if not node.multinode:
        return 0.0
    addr = f"127.0.0.1:{node.control_port}"

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.x = 0

        def inc(self):
            self.x += 1
            return self.x

    # Named detached actor: the handle is re-fetched by name in the
    # child process, so dropping this one is deliberate.
    Counter.options(  # ray-tpu: noqa[RT006]
        name="_mb_counter", lifetime="detached").remote()
    script = textwrap.dedent(f"""
        import sys, time
        sys.path.insert(0, {__file__.rsplit('/ray_tpu/', 1)[0]!r})
        from ray_tpu.util import client
        import ray_tpu
        client.connect({addr!r})
        a = ray_tpu.get_actor("_mb_counter")
        ray_tpu.get(a.inc.remote())
        t0 = time.perf_counter()
        for _ in range({n}):
            ray_tpu.get(a.inc.remote())
        print("RATE", {n} / (time.perf_counter() - t0))
        client.disconnect()
    """)
    import os
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    for line in r.stdout.splitlines():
        if line.startswith("RATE "):
            return round(float(line.split()[1]), 1)
    raise RuntimeError(
        f"thin-client benchmark subprocess failed "
        f"(rc={r.returncode}):\n{r.stderr[-2000:]}")


def run_all(out_path: str | None = None) -> dict:
    import ray_tpu

    # Phase 1: single-node mode — the core hot paths with no GCS hop.
    ray_tpu.init(num_cpus=4, object_store_memory=1 << 30,
                 ignore_reinit_error=True)
    # Object/task benches FIRST: actor benches release their actors
    # on return (handle GC) and the resulting worker churn would
    # contaminate measurements taken while it settles.
    results = {
        "tasks_async_per_s": bench_tasks_async(ray_tpu),
        "put_small_per_s": bench_put_small(ray_tpu),
        "put_gigabytes_per_s": bench_put_gbps(ray_tpu),
        "multi_client_put_gigabytes_per_s":
            bench_multi_client_put_gbps(ray_tpu),
        "multi_client_put_per_s": bench_multi_client_put_small(ray_tpu),
        "get_64kb_median_us": bench_get_latency_us(ray_tpu),
        "actor_calls_sync_per_s": bench_actor_calls_sync(ray_tpu),
        "actor_calls_async_per_s": bench_actor_calls_async(ray_tpu),
        "actor_calls_concurrent_per_s":
            bench_actor_calls_concurrent(ray_tpu),
        "one_to_n_actor_calls_per_s":
            bench_one_to_n_actor_calls(ray_tpu),
        "n_to_n_actor_calls_per_s":
            bench_n_to_n_actor_calls(ray_tpu),
    }
    ray_tpu.shutdown()

    # Phase 2: multinode head — the thin client needs the TCP endpoint.
    from ray_tpu.cluster_utils import Cluster
    cluster = Cluster()
    ray_tpu.init(num_cpus=4, gcs_address=cluster.gcs_address)
    try:
        results["client_actor_calls_sync_per_s"] = \
            bench_thin_client_sync()
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()
    results.update({
        "note": ("this host: 1 vCPU, single client; reference numbers "
                 "are m5.16xlarge (64 vCPU) with multi-client "
                 "aggregation for put/task rates"),
        "reference_baseline": {
            # release_logs/2.9.3/microbenchmark.json on m5.16xlarge
            # (64 vCPU); this host has 1 vCPU — rates here are
            # single-core, the reference's are 64-core.
            "actor_calls_sync_per_s": 2033,
            "actor_calls_async_per_s": 8886,
            "actor_calls_concurrent_per_s": 5095,
            "one_to_n_actor_calls_per_s": 8570,
            "n_to_n_actor_calls_per_s": 27667,
            "multi_client_tasks_async_per_s": 25166,
            "multi_client_put_per_s": 12677,
            "multi_client_put_gigabytes_per_s": 35.9,
            "client_actor_calls_sync_per_s": 515,
        },
    })
    blob = json.dumps(results, indent=1)
    print(blob)
    if out_path:
        with open(out_path, "w") as f:
            f.write(blob + "\n")
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    run_all(ap.parse_args().out)
