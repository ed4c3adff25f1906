"""Compiled graphs v2 (PR 8): zero-copy/spill transport, streamed
cross-host edges over the binary transfer plane, pinned executor
loops, teardown-on-death, and the serve pipeline fast lane.

Complements tests/test_dag.py (which covers the channel primitive and
basic compile/execute semantics — kept green unchanged)."""

import os
import threading
import time

import pytest

import ray_tpu
from ray_tpu.dag import InputNode
from ray_tpu.experimental.channel import Channel


@pytest.fixture
def rt():
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


@ray_tpu.remote
class Stage:
    def __init__(self, k=1):
        self.k = k

    def mul(self, x):
        return x * self.k

    def slow(self, x):
        time.sleep(0.2)
        return x

    def ping(self):
        return "pong"


# ---------------------------------------------------------------------------
# transport: oversized-payload spill
# ---------------------------------------------------------------------------
def test_oversized_payload_spills_not_raises(rt):
    """A value bigger than the channel slot overflows into the shm
    object store by ref instead of raising (both directions: input
    edge and worker->driver result edge)."""
    a = Stage.remote(2)
    with InputNode() as inp:
        out = a.mul.bind(inp)
    dag = out.experimental_compile(buffer_size_bytes=64 * 1024)
    try:
        big = os.urandom(1 << 20)               # 1 MiB >> 64 KiB slot
        assert dag.execute(big).get(timeout=60) == big * 2
        # Small values still take the inline path afterwards.
        assert dag.execute(3).get(timeout=60) == 6
        # And a second oversized round trip (slot reuse after spill).
        assert dag.execute(big).get(timeout=60) == big * 2
    finally:
        dag.teardown()


def test_channel_spill_without_runtime_raises(tmp_path):
    """No connected runtime -> an oversized write still raises (the
    spill path needs the object store)."""
    w = Channel(str(tmp_path / "ch"), capacity=1, slot_size=128,
                create=True)
    with pytest.raises(ValueError, match="slot_size"):
        w.write(b"x" * 4096)
    w.close(unlink=True)


# ---------------------------------------------------------------------------
# execution: pipelined backpressure + pinned loop liveness
# ---------------------------------------------------------------------------
def test_pipelined_backpressure_blocks_not_crashes(rt):
    """capacity+1 in-flight executes block (bounded rings), not crash;
    everything completes once the consumer drains."""
    a = Stage.remote()
    with InputNode() as inp:
        out = a.slow.bind(inp)
    dag = out.experimental_compile(capacity=2)
    try:
        t0 = time.perf_counter()
        refs = [dag.execute(i) for i in range(5)]   # > capacity
        submit_s = time.perf_counter() - t0
        # The overflow executes had to wait for slots (each slow() step
        # takes 0.2s), proving backpressure blocked instead of raising.
        assert submit_s > 0.15
        assert [r.get(timeout=60) for r in refs] == list(range(5))
    finally:
        dag.teardown()


def test_actor_answers_normal_calls_while_graph_runs(rt):
    """The executor loop is pinned to its own thread: the actor still
    answers ordinary calls (Serve health checks, probes) mid-graph."""
    a = Stage.remote(3)
    with InputNode() as inp:
        out = a.mul.bind(inp)
    dag = out.experimental_compile()
    try:
        assert dag.execute(2).get(timeout=60) == 6
        # The loop is parked on its in-channel RIGHT NOW — a normal
        # call must not queue behind it.
        assert ray_tpu.get(a.ping.remote(), timeout=30) == "pong"
        assert dag.execute(4).get(timeout=60) == 12
    finally:
        dag.teardown()


# ---------------------------------------------------------------------------
# teardown: actor death, chaos kill_worker, shm-leak sweep
# ---------------------------------------------------------------------------
def _chan_files(dag) -> list:
    sess = ray_tpu._session.session_dir
    d = os.path.join(sess, "channels")
    if not os.path.isdir(d):
        return []
    return [f for f in os.listdir(d)
            if f.startswith(f"dag-{dag._dag_id}")]


def test_teardown_on_actor_death(rt):
    """An actor death mid-graph tears the graph down cleanly:
    outstanding refs surface ActorDiedError (not a hang), execute()
    refuses afterwards, teardown is idempotent, and the channel files
    are unlinked."""
    from ray_tpu import exceptions as exc
    a = Stage.remote(2)
    with InputNode() as inp:
        out = a.mul.bind(inp)
    dag = out.experimental_compile()
    assert dag.execute(1).get(timeout=60) == 2
    assert _chan_files(dag)
    ray_tpu.kill(a)
    ref = dag.execute(5)
    with pytest.raises(exc.ActorDiedError):
        ref.get(timeout=60)
    # The graph is dead: new executes surface the same error.
    with pytest.raises(exc.ActorDiedError):
        dag.execute(6)
    # Channel files were unlinked by the death-path teardown...
    assert not _chan_files(dag)
    # ...and calling teardown again is a no-op.
    dag.teardown()
    dag.teardown()


def test_chaos_kill_worker_mid_graph(rt):
    """Chaos kill_worker while a graph is pinned to the worker: the
    graph tears down and surfaces ActorDiedError on outstanding refs;
    the PR-3 retry path stays untouched (compiled graphs are
    at-most-once — no silent re-execution)."""
    from ray_tpu import exceptions as exc
    from ray_tpu.util import chaos
    a = Stage.remote(2)
    with InputNode() as inp:
        out = a.mul.bind(inp)
    dag = out.experimental_compile()
    try:
        assert dag.execute(1).get(timeout=60) == 2
        chaos.inject("dispatch", "kill_worker", n=1)
        try:
            # Any dispatch to this actor's worker triggers the kill —
            # the graph dies mid-run.
            ray_tpu.get(a.ping.remote(), timeout=30)
        except Exception:
            pass
        ref = dag.execute(5)
        with pytest.raises((exc.ActorDiedError,
                            exc.WorkerCrashedError, RuntimeError)):
            ref.get(timeout=60)
    finally:
        chaos.clear()
        dag.teardown()


def test_driver_exit_sweep_unlinks_channels(rt):
    """An un-torn-down DAG is swept at shutdown (atexit/driver-exit):
    ray_tpu.shutdown() unlinks its channel files."""
    import ray_tpu.dag as dag_mod
    a = Stage.remote(2)
    with InputNode() as inp:
        out = a.mul.bind(inp)
    dag = out.experimental_compile()
    assert dag.execute(2).get(timeout=60) == 4
    files = _chan_files(dag)
    assert files
    sess_dir = ray_tpu._session.session_dir
    dag_mod._teardown_all()     # what shutdown()/atexit runs
    chan_dir = os.path.join(sess_dir, "channels")
    left = [f for f in os.listdir(chan_dir)
            if f.startswith(f"dag-{dag._dag_id}")]
    assert not left
    assert dag._torn_down


# ---------------------------------------------------------------------------
# observability: metrics + timeline
# ---------------------------------------------------------------------------
def test_dag_metrics_and_timeline_event(rt):
    from ray_tpu.util import metrics, profiling
    a = Stage.remote(2)
    with InputNode() as inp:
        out = a.mul.bind(inp)
    dag = out.experimental_compile()
    try:
        for i in range(5):
            assert dag.execute(i).get(timeout=60) == 2 * i
    finally:
        dag.teardown()
    metrics.flush()
    time.sleep(1.2)     # worker-side flusher interval
    series = {(s["name"], s["tags"].get("edge")): s
              for s in metrics.scrape()}
    execs = series.get((metrics.DAG_EXECUTIONS_METRIC, None))
    assert execs is not None and execs["value"] >= 5
    hops = series.get((metrics.DAG_HOP_SECONDS_METRIC, "local"))
    assert hops is not None and hops["count"] >= 5
    # dag.execute lifecycle event in the timeline (trace-linked span).
    names = {e.get("name") for e in profiling.timeline_events()}
    assert "dag.execute" in names


# ---------------------------------------------------------------------------
# cross-host: compiled DAG over the binary transfer plane
# ---------------------------------------------------------------------------
_FAST_HB = {"RAY_TPU_HEARTBEAT_INTERVAL_S": "0.2"}


@pytest.fixture
def cluster():
    from ray_tpu.cluster_utils import Cluster
    for k, v in _FAST_HB.items():
        os.environ[k] = v
    c = Cluster(env=_FAST_HB)
    c.add_node(resources={"CPU": 2, "remote": 1})
    ray_tpu.init(num_cpus=2, gcs_address=c.gcs_address)
    c.wait_for_nodes(2)
    yield c
    ray_tpu.shutdown()
    c.shutdown()
    for k in _FAST_HB:
        os.environ.pop(k, None)


def test_cross_host_dag_rides_transfer_plane(cluster):
    """2-node compiled DAG: every steady-state cross-node item rides
    the persistent streamed transfer-plane edge — ZERO per-item
    control-plane chan RPCs."""
    from ray_tpu._private.client import get_global_client
    a = Stage.remote(3)                                   # head node
    b = Stage.options(resources={"remote": 1}).remote(5)  # worker node
    with InputNode() as inp:
        x = a.mul.bind(inp)
        y = b.mul.bind(x)
    dag = y.experimental_compile()
    try:
        for i in range(16):
            assert dag.execute(i).get(timeout=60) == i * 15
    finally:
        dag.teardown()
    dump = get_global_client().state_dump(cluster=True)
    per_node = dump.get("dag_channel_items") or {}
    stream = sum(v.get("stream", 0) for v in per_node.values())
    rpc = sum(v.get("rpc", 0) for v in per_node.values())
    # Two cross-node edges (a->b on the head node, b->driver on the
    # worker node), 16 items each.
    assert stream >= 32, per_node
    assert rpc == 0, per_node


def test_cross_host_backpressure_and_oversize(cluster):
    """Cross-node edges: bounded queues backpressure (no crash) and
    payloads larger than the same-node slot size cross intact."""
    b = Stage.options(resources={"remote": 1}).remote(1)
    with InputNode() as inp:
        y = b.slow.bind(inp)
    dag = y.experimental_compile(capacity=2)
    try:
        refs = [dag.execute(i) for i in range(5)]
        assert [r.get(timeout=120) for r in refs] == list(range(5))
        big = os.urandom(2 << 20)
        assert dag.execute(big).get(timeout=120) == big
    finally:
        dag.teardown()


@pytest.mark.slow
def test_two_node_dag_bench_smoke(cluster):
    """Cross-node pipeline sustains pipelined executes (slow: tier-1
    budget)."""
    a = Stage.remote(1)
    b = Stage.options(resources={"remote": 1}).remote(1)
    c2 = Stage.remote(1)
    with InputNode() as inp:
        out = c2.mul.bind(b.mul.bind(a.mul.bind(inp)))
    dag = out.experimental_compile(capacity=16)
    try:
        t0 = time.perf_counter()
        n = 100
        pend = []
        for i in range(n):
            pend.append(dag.execute(1))
            if len(pend) >= 8:
                assert pend.pop(0).get(timeout=60) == 1
        for r in pend:
            assert r.get(timeout=60) == 1
        wall = time.perf_counter() - t0
        assert wall < 60
    finally:
        dag.teardown()


# ---------------------------------------------------------------------------
# serve: compiled pipeline fast lane (flag on; default-off path is
# covered by the rest of test_serve.py)
# ---------------------------------------------------------------------------
def test_serve_compiled_pipeline_round_trip(rt):
    from ray_tpu import serve
    from ray_tpu._private.config import config
    config.set("serve_compiled_pipeline", True)
    try:
        @serve.deployment(num_replicas=1)
        class Pipe:
            def __call__(self, x):
                return x + 1

            async def triple(self, x):
                return x * 3

            def boom(self):
                raise ValueError("pipe-kaboom")

        h = serve.run(Pipe)
        assert ray_tpu.get(h.remote(1), timeout=60) == 2
        # Many requests pipeline through one compiled pipe.
        refs = [h.remote(i) for i in range(20)]
        assert ray_tpu.get(refs, timeout=60) == [i + 1
                                                 for i in range(20)]
        # Async user methods run on the replica's pipe loop.
        assert ray_tpu.get(h.method("triple").remote(2),
                           timeout=60) == 6
        # Application errors bridge as errors — WITHOUT tearing down
        # the pipe...
        with pytest.raises(Exception, match="pipe-kaboom"):
            ray_tpu.get(h.method("boom").remote(), timeout=60)
        # ...so the next request still rides it.
        assert ray_tpu.get(h.remote(5), timeout=60) == 6
        # Control plane stays live while the pipe loop is pinned.
        assert serve.status()["Pipe"]["target_replicas"] == 1
    finally:
        config.set("serve_compiled_pipeline", False)
        serve.shutdown()
