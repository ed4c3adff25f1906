"""Scalability-envelope harness: control-plane throughput vs node count.

Analog of the reference's standing envelope suite
(release/benchmarks/README.md:7-12 — many_nodes/many_actors/many_pgs).
Runs against the in-process virtual cluster (cluster_utils.Cluster: a
real GCS + N real node-service subprocesses on this host), so the
numbers measure the CONTROL PLANE — scheduling, dispatch, GCS, PG 2PC
— not worker compute.  Per node count:
  * tasks/s          — drain N no-op tasks spread over the cluster
  * actors/s         — create+ping K actors, then kill
  * pg create/remove — sequential placement-group 2PC latency
plus an actor churn (create/ping/kill in batches) at the largest size.

tests/test_scale_envelope.py runs a shrunk envelope as the tier-1
regression gate; it is this module's only caller.  Device numbers come
from benchmarks/run.py.  Reference baselines for orientation (64-node
cluster, BASELINE.md): 334-589 tasks/s, 580 actors/s, PG 0.91/0.86 ms.
"""

from __future__ import annotations

import time
from typing import Dict, List


def measure_tasks(ray_tpu, n: int) -> float:
    @ray_tpu.remote
    def noop(i):
        return i

    # warm the worker pools
    ray_tpu.get([noop.remote(i) for i in range(8)])
    t0 = time.perf_counter()
    ray_tpu.get([noop.remote(i) for i in range(n)])
    return n / (time.perf_counter() - t0)


def measure_actors(ray_tpu, k: int) -> float:
    @ray_tpu.remote
    class A:
        def ping(self):
            return 1

    t0 = time.perf_counter()
    actors = [A.remote() for _ in range(k)]
    ray_tpu.get([a.ping.remote() for a in actors])
    rate = k / (time.perf_counter() - t0)
    for a in actors:
        ray_tpu.kill(a)
    return rate


def measure_pg(ray_tpu, n: int) -> Dict[str, float]:
    from ray_tpu.util.placement_group import (placement_group,
                                              remove_placement_group)
    create_s = 0.0
    remove_s = 0.0
    for _ in range(n):
        t0 = time.perf_counter()
        pg = placement_group([{"CPU": 0.01}], strategy="PACK")
        ray_tpu.get(pg.ready())
        create_s += time.perf_counter() - t0
        t0 = time.perf_counter()
        remove_placement_group(pg)
        remove_s += time.perf_counter() - t0
    return {"pg_create_ms": round(create_s / n * 1e3, 2),
            "pg_remove_ms": round(remove_s / n * 1e3, 2)}


def measure_actor_churn(ray_tpu, total: int, batch: int = 50) -> float:
    @ray_tpu.remote
    class A:
        def ping(self):
            return 1

    t0 = time.perf_counter()
    done = 0
    while done < total:
        k = min(batch, total - done)
        actors = [A.remote() for _ in range(k)]
        ray_tpu.get([a.ping.remote() for a in actors])
        for a in actors:
            ray_tpu.kill(a)
        done += k
    return total / (time.perf_counter() - t0)


def run_envelope(node_counts: List[int], n_tasks: int, n_actors: int,
                 n_pgs: int, churn: int) -> dict:
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    results = []
    for nodes in node_counts:
        cluster = Cluster()
        extra = nodes - 1
        for _ in range(extra):
            cluster.add_node(resources={"CPU": 2.0})
        ray_tpu.init(num_cpus=2, gcs_address=cluster.gcs_address)
        try:
            cluster.wait_for_nodes(nodes)
            row = {
                "nodes": nodes,
                "tasks_per_s": round(measure_tasks(ray_tpu, n_tasks), 1),
                "actors_per_s": round(
                    measure_actors(ray_tpu, n_actors), 1),
                **measure_pg(ray_tpu, n_pgs),
            }
            if nodes == node_counts[-1]:
                row["actor_churn_per_s"] = round(
                    measure_actor_churn(ray_tpu, churn), 1)
            results.append(row)
        finally:
            ray_tpu.shutdown()
            cluster.shutdown()
    return {"levels": results}
