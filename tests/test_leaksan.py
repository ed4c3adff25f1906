"""Resource-leak ledger (devtools/leaksan.py): detector mechanics,
the runtime wiring (KV blocks, admission slots, spill fds), the
self-applied lifecycle fixes' regressions, and the acceptance drill —
a multi-node + serve + compiled-DAG + chaos workload under
RAY_TPU_LEAKSAN=1 reporting ZERO leaked resources at shutdown."""

import json
import os
import socket
import subprocess
import sys
import threading
import time

import pytest

from ray_tpu.devtools import leaksan

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_state():
    leaksan.reset()
    yield
    leaksan.disable_for_testing()
    leaksan.reset()


# ---------------------------------------------------------------------------
# detector mechanics (in-process, hooks enabled without install)
# ---------------------------------------------------------------------------
def test_register_discharge_roundtrip():
    leaksan.enable_for_testing()
    leaksan.register("widget", 1, detail="a")
    leaksan.register("widget", 2)
    assert leaksan.live_counts() == {"widget": 2}
    leaksan.discharge("widget", 1)
    rep = leaksan.report()
    assert rep["registered"] == {"widget": 2}
    assert rep["discharged"] == {"widget": 1}
    rows = rep["live"]["widget"]
    assert len(rows) == 1 and rows[0]["key"] == "2"
    assert "test_leaksan.py" in rows[0]["site"]
    assert rep["anomalies"] == []


def test_double_discharge_is_an_anomaly():
    leaksan.enable_for_testing()
    leaksan.register("widget", 1)
    leaksan.discharge("widget", 1)
    leaksan.discharge("widget", 1)
    rep = leaksan.report()
    assert len(rep["anomalies"]) == 1
    a = rep["anomalies"][0]
    assert a["what"] == "double_discharge" and a["kind"] == "widget"
    assert a["stack"]
    # expect=False (teardown paths racing wholesale clears) is silent.
    leaksan.discharge("widget", 99, expect=False)
    assert len(leaksan.report()["anomalies"]) == 1


def test_disabled_hooks_are_noops():
    leaksan.register("widget", 1)
    leaksan.discharge("widget", 1)
    rep = leaksan.report()
    assert rep["registered"] == {} and rep["anomalies"] == []


def test_dump_and_merge(tmp_path):
    leaksan.enable_for_testing()
    leaksan.register("widget", 7)
    path = leaksan.dump(str(tmp_path / "111.json"))
    assert path and os.path.exists(path)
    fake = {"pid": 222,
            "registered": {"spill_fd": 3},
            "discharged": {"spill_fd": 2},
            "live": {"spill_fd": [{"key": "5", "site": "x.py:1",
                                   "age_s": 1.0, "detail": ""}]},
            "live_counts": {"spill_fd": 1},
            "anomalies": [{"kind": "spill_fd", "key": "9",
                           "what": "double_discharge"}]}
    (tmp_path / "222.json").write_text(json.dumps(fake))
    merged = leaksan.merged_report(str(tmp_path))
    assert merged["processes"] >= 2
    assert merged["registered"] == {"widget": 1, "spill_fd": 3}
    assert merged["leak_counts"] == {"widget": 1, "spill_fd": 1}
    kinds = {r["kind"] for r in merged["leaks"]}
    assert kinds == {"widget", "spill_fd"}
    assert merged["anomalies"][0]["pid"] == 222
    assert merged["registrations"] == 4


def test_state_leaksan_report_surface(tmp_path):
    """state.leaksan_report works without an initialized runtime."""
    from ray_tpu.util import state
    leaksan.enable_for_testing()
    leaksan.register("widget", 1)
    leaksan.discharge("widget", 1)
    rep = state.leaksan_report(str(tmp_path))
    assert rep["registered"] == {"widget": 1}
    assert rep["leaks"] == []


def test_resources_live_metric_cells():
    from ray_tpu.util import metrics
    leaksan.enable_for_testing()
    leaksan.register("widget", 1)
    leaksan.discharge("widget", 1)
    with metrics._lock:
        vals = {}
        for m in metrics._registry:
            if m.name == metrics.RESOURCES_LIVE_METRIC:
                for ts, cell in m._cells.items():
                    vals[dict(ts).get("kind")] = cell["value"]
    assert vals.get("widget") == 0.0


# ---------------------------------------------------------------------------
# runtime wiring: block pool / admission / gauge series
# ---------------------------------------------------------------------------
def test_block_pool_ledger_conservation():
    from ray_tpu.serve.llm import BlockAllocator
    leaksan.enable_for_testing()
    a = BlockAllocator(16)
    blocks = a.alloc(4)
    assert leaksan.live_counts() == {"kv_block": 4}
    a.incref(blocks[0])                       # shared: still one entry
    a.mark_cached(blocks[1])
    a.decref(blocks[0])
    for b in blocks:
        a.decref(b)
    # blocks[1] is cached (refcount 0, retained): still live.
    assert leaksan.live_counts() == {"kv_block": 1}
    a.release_cached(blocks[1])
    assert leaksan.live_counts() == {}
    assert leaksan.report()["anomalies"] == []


def test_admission_slot_ledger_and_exactly_once():
    from ray_tpu.serve._admission import AdmissionController
    leaksan.enable_for_testing()
    gate = AdmissionController("dep")
    r1 = gate.acquire("normal", "tenant-a", 0)
    r2 = gate.acquire("high", "tenant-b", 1)
    assert leaksan.live_counts() == {"admission_slot": 2}
    r1()
    r1()          # idempotent guard: no double-discharge anomaly
    r2()
    assert leaksan.live_counts() == {}
    assert leaksan.report()["anomalies"] == []


def test_instance_gauge_series_ledger():
    from ray_tpu.util import metrics
    leaksan.enable_for_testing()
    g = metrics.Gauge("ray_tpu_test_leaksan_series",
                      tag_keys=("state", "engine"))
    g.set(1.0, tags={"state": "used", "engine": "e-1"})
    g.set(2.0, tags={"state": "used", "engine": "e-1"})   # same cell
    assert leaksan.live_counts() == {"metric_series": 1}
    g.remove(tags={"state": "used", "engine": "e-1"})
    assert leaksan.live_counts() == {}


# ---------------------------------------------------------------------------
# self-applied fix regressions
# ---------------------------------------------------------------------------
def test_spill_fd_cycle_abort_delete_zero_live(tmp_path):
    """PR-4 spilled-chunk fd cache: delete drops the cached fd, and a
    chunk request landing AFTER the delete (a fetch aborted by a
    partition whose straggler outlives the owner's global delete) must
    not re-cache an orphan fd — spill -> serve -> delete -> late-read
    cycles end with zero live spill fds."""
    from ray_tpu._private.node_objects import ObjectPlaneMixin

    class Host(ObjectPlaneMixin):
        def __init__(self):
            self._spill_fds = {}
            self._spill_fd_lock = threading.Lock()
            self._spill_dead = set()

    leaksan.enable_for_testing()
    h = Host()
    oid = b"\x01" * 16
    path = str(tmp_path / "spill-0")
    with open(path, "wb") as f:
        f.write(b"x" * 64)
    for cycle in range(3):
        assert h._spill_pread(oid, path, 0, 8) == b"x" * 8
        assert leaksan.live_counts() == {"spill_fd": 1}
        h._drop_spill_fd(oid)                       # delete path
        assert leaksan.live_counts() == {}
        # Straggling chunk request AFTER the delete: data still
        # served while the file exists, but nothing re-cached.
        assert h._spill_pread(oid, path, 8, 8) == b"x" * 8
        assert h._spill_fds == {}
        assert leaksan.live_counts() == {}
        # Re-spill of the same oid lifts the tombstone.
        with h._spill_fd_lock:
            h._spill_dead.discard(oid)
    assert leaksan.report()["anomalies"] == []


def test_spill_fd_lru_eviction_discharges(tmp_path):
    from ray_tpu._private.node_objects import ObjectPlaneMixin

    class Host(ObjectPlaneMixin):
        def __init__(self):
            self._spill_fds = {}
            self._spill_fd_lock = threading.Lock()
            self._spill_dead = set()

    leaksan.enable_for_testing()
    h = Host()
    for i in range(140):                  # cache cap is 128
        p = str(tmp_path / f"s{i}")
        with open(p, "wb") as f:
            f.write(b"y" * 8)
        h._spill_pread(bytes([i % 256]) + b"\0" * 15, p, 0, 4)
    assert len(h._spill_fds) <= 128
    assert leaksan.live_counts()["spill_fd"] == len(h._spill_fds)


def test_connection_close_joins_recv_thread():
    """protocol.Connection.close() joins its recv thread (RT014
    self-finding): no straggler holding the dead socket."""
    from ray_tpu._private.protocol import Connection
    a, b = socket.socketpair()
    conn = Connection(a)
    assert conn._recv_thread.is_alive()
    conn.close()
    assert not conn._recv_thread.is_alive()
    b.close()


def test_notice_deadline_read_leaks_no_fds(tmp_path):
    """node_drain preemption-notice poller: the old open(path).read()
    leaked one fd per poll (RT013 self-finding)."""
    from ray_tpu._private.node_drain import _read_notice_deadline
    notice = tmp_path / "notice"
    notice.write_text("12.5")
    fd_dir = f"/proc/{os.getpid()}/fd"
    before = len(os.listdir(fd_dir))
    for _ in range(64):
        assert _read_notice_deadline(str(notice)) == 12.5
    assert len(os.listdir(fd_dir)) <= before + 2
    notice.write_text(json.dumps({"deadline_s": 3.0}))
    assert _read_notice_deadline(str(notice)) == 3.0
    assert _read_notice_deadline(str(tmp_path / "missing")) is None


def test_engine_stop_fails_outstanding_requests():
    """PagedBatcher.stop() with work still queued/decoding must
    fail those requests (callers were left hanging to their timeout)
    and free every KV block — the leak-ledger engine self-finding."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.transformer import (TransformerConfig,
                                            init_params)
    from ray_tpu.serve.llm import PagedBatcher

    leaksan.enable_for_testing()
    cfg = TransformerConfig(vocab_size=97, d_model=32, n_heads=4,
                            n_kv_heads=2, n_layers=2, d_ff=64,
                            max_seq=128, dtype=jnp.float32,
                            remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    bat = PagedBatcher(params, cfg, num_slots=2, max_len=48,
                       prompt_pad=16, decode_chunk=2,
                       pipeline_depth=2, kv_block_size=4)
    req = bat.submit([5, 6, 7, 8], max_new=40)
    # Let it get admitted and start decoding, then stop mid-flight.
    deadline = time.time() + 30
    while not req.tokens and time.time() < deadline:
        time.sleep(0.01)
    bat.stop()
    assert req.done.wait(5), "stop() left the request parked"
    if req.error is not None:
        assert "engine stopped" in str(req.error)
    counts = bat._alloc.counts()
    assert counts["used"] == 0 and counts["cached"] == 0, counts
    live = leaksan.live_counts()
    assert live.get("kv_block", 0) == 0, live
    assert live.get("thread", 0) == 0, live
    # A second stop() is idempotent.
    bat.stop()


# ---------------------------------------------------------------------------
# PR-11 exactly-once regression: pipe -> task failover delegation +
# seeded chaos kill_replica, asserted via the ledger
# ---------------------------------------------------------------------------
def test_admission_release_exactly_once_across_failover():
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private.config import config
    from ray_tpu.util import chaos as chaos_api

    leaksan.enable_for_testing()
    ray_tpu.init(num_cpus=8)
    try:
        config.set("serve_compiled_pipeline", True)

        @serve.deployment(num_replicas=2, max_concurrent_queries=8,
                          admission_config={"max_queue_depth": 64})
        class D:
            def __call__(self, x):
                return x * 2

        handle = serve.run(D.bind())
        assert ray_tpu.get(handle.remote(3), timeout=60) == 6
        # Storm 1: plain traffic over the compiled pipe, with tenant/
        # priority-classed slots.
        refs = [handle.method("__call__")
                .options(priority="low", tenant_id=f"t{i % 3}")
                .remote(i) for i in range(24)]
        assert ray_tpu.get(refs, timeout=60) == [i * 2
                                                 for i in range(24)]
        # Storm 2: seeded kill_replica mid-storm — requests fail over
        # pipe -> task path, forwarding the release closure.
        config.set("chaos_seed", 13)
        config.set("chaos_spec",
                   "serve.assign:kind=kill_replica:p=1:n=1")
        chaos_api.refresh()
        chaos_api.reset_trace()
        got = [ray_tpu.get(handle.remote(i), timeout=60)
               for i in range(16)]
        assert got == [i * 2 for i in range(16)]
        assert any(k == "kill_replica"
                   for _, _, k in chaos_api.trace()), \
            "chaos kill_replica never fired"
        config.set("chaos_spec", "")
        chaos_api.refresh()
        # Every terminal outcome fired its release exactly once: zero
        # live admission slots once the waiters settle, no double
        # discharges.
        deadline = time.time() + 10
        while time.time() < deadline \
                and leaksan.live_counts().get("admission_slot"):
            time.sleep(0.05)
        rep = leaksan.report()
        assert rep["registered"].get("admission_slot", 0) >= 41
        assert leaksan.live_counts().get("admission_slot", 0) == 0, \
            rep["live"].get("admission_slot")
        slot_anoms = [a for a in rep["anomalies"]
                      if a["kind"] == "admission_slot"]
        assert slot_anoms == []
    finally:
        config.set("chaos_spec", "")
        config.set("chaos_seed", 0)
        config.set("serve_compiled_pipeline", False)
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def _leaksan_cli(tmp_path, *flags):
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu", "leaksan",
         "--dir", str(tmp_path), *flags],
        capture_output=True, text=True, timeout=60, cwd=REPO_ROOT)


def test_cli_clean_and_leaky(tmp_path):
    clean_dir = tmp_path / "clean"
    clean_dir.mkdir()
    (clean_dir / "1.json").write_text(json.dumps(
        {"pid": 1, "registered": {"kv_block": 5},
         "discharged": {"kv_block": 5}, "live": {}, "live_counts": {},
         "anomalies": []}))
    cli = _leaksan_cli(clean_dir)
    assert cli.returncode == 0, cli.stdout + cli.stderr
    assert "leaked resources: 0" in cli.stdout

    leaky_dir = tmp_path / "leaky"
    leaky_dir.mkdir()
    (leaky_dir / "2.json").write_text(json.dumps(
        {"pid": 2, "registered": {"admission_slot": 3},
         "discharged": {"admission_slot": 2},
         "live": {"admission_slot": [
             {"key": "(1, 2)", "site": "r.py:10", "age_s": 9.0,
              "detail": "dep/t1/low"}]},
         "live_counts": {"admission_slot": 1}, "anomalies": []}))
    cli = _leaksan_cli(leaky_dir)
    assert cli.returncode == 1, cli.stdout + cli.stderr
    assert "admission_slot" in cli.stdout and "r.py:10" in cli.stdout
    payload = json.loads(_leaksan_cli(leaky_dir, "--json").stdout)
    assert payload["leak_counts"] == {"admission_slot": 1}


# ---------------------------------------------------------------------------
# acceptance drill: multi-node + serve + compiled DAG + paged engine +
# chaos kill_replica/kill_worker under RAY_TPU_LEAKSAN=1
# ---------------------------------------------------------------------------
_DRILL_SCRIPT = """
import os, time
import ray_tpu                      # arms the ledger (env)
from ray_tpu._private.config import config
from ray_tpu.cluster_utils import Cluster
from ray_tpu.util import chaos as chaos_api

c = Cluster()
c.add_node(resources={"CPU": 2, "remote": 1})
ray_tpu.init(num_cpus=4, gcs_address=c.gcs_address)
c.wait_for_nodes(2)

# -- task plane with seeded kill_worker chaos --------------------------
@ray_tpu.remote
def sq(x):
    return x * x

config.set("chaos_seed", 7)
config.set("chaos_spec", "dispatch:kind=kill_worker:p=1:n=2")
chaos_api.refresh()
assert ray_tpu.get([sq.remote(i) for i in range(8)],
                   timeout=120) == [i * i for i in range(8)]
config.set("chaos_spec", "")
chaos_api.refresh()

# -- compiled-DAG plane (channel_mmap coverage) ------------------------
from ray_tpu.dag import InputNode

@ray_tpu.remote
class Stage:
    def inc(self, x):
        return x + 1

a = Stage.remote()
with InputNode() as inp:
    out = a.inc.bind(inp)
dag = out.experimental_compile()
try:
    for i in range(10):
        assert dag.execute(i).get(timeout=60) == i + 1
finally:
    dag.teardown()

# -- paged LLM engine in-process (kv_block + metric_series + threads) --
import jax, jax.numpy as jnp
from ray_tpu.models.transformer import TransformerConfig, init_params
from ray_tpu.serve.llm import PagedBatcher

cfg = TransformerConfig(vocab_size=97, d_model=32, n_heads=4,
                        n_kv_heads=2, n_layers=2, d_ff=64,
                        max_seq=128, dtype=jnp.float32, remat=False)
bat = PagedBatcher(init_params(cfg, jax.random.PRNGKey(0)), cfg,
                   num_slots=2, max_len=48, prompt_pad=16,
                   decode_chunk=2, pipeline_depth=2, kv_block_size=4)
for i in range(4):
    r = bat.generate([3 + i, 5, 7], max_new=6, timeout=120)
    assert len(r["tokens"]) > 0
bat.stop()

# -- train telemetry session (publisher thread + per-run gauges) -------
import tempfile
from ray_tpu.train import RunConfig, ScalingConfig, TpuTrainer

def _train_loop(config=None):
    import time as _t
    from ray_tpu.train import session
    ctx = session.get_context()
    tel = ctx.telemetry(tokens_per_step=64)
    for i in range(4):
        with tel.data_wait():
            _t.sleep(0.01)
        with tel.device_step():
            _t.sleep(0.01)
        tel.end_step()
        session.report({"step": i})

res = TpuTrainer(
    _train_loop, scaling_config=ScalingConfig(num_workers=1),
    run_config=RunConfig(name="drill_train",
                         storage_path=tempfile.mkdtemp())).fit()
assert res.error is None, res.error

# -- serve plane: admission slots + chaos kill_replica -----------------
from ray_tpu import serve

@serve.deployment(num_replicas=2, max_concurrent_queries=16,
                  admission_config={"max_queue_depth": 256})
class Doubler:
    def __call__(self, x):
        return x * 2

h = serve.run(Doubler.bind())
got = ray_tpu.get([h.method("__call__")
                   .options(priority="normal",
                            tenant_id=f"t{i % 4}").remote(i)
                   for i in range(90)], timeout=120)
assert got == [i * 2 for i in range(90)]
config.set("chaos_seed", 23)
config.set("chaos_spec", "serve.assign:kind=kill_replica:p=1:n=1")
chaos_api.refresh()
got = [ray_tpu.get(h.remote(i), timeout=120) for i in range(20)]
assert got == [i * 2 for i in range(20)]
config.set("chaos_spec", "")
chaos_api.refresh()
serve.shutdown()

ray_tpu.shutdown()
c.shutdown()

from ray_tpu.devtools import leaksan
time.sleep(1.0)                     # let waiter threads settle
leaksan.dump()
print("DRILL_OK")
"""


def test_leaksan_acceptance_drill(tmp_path):
    """The tier-1 acceptance drill: the whole stack under the ledger
    reports zero leaked blocks/slots/threads/fds/series at shutdown,
    with well over 100 tracked registrations."""
    env = dict(os.environ)
    env["RAY_TPU_LEAKSAN"] = "1"
    env["RAY_TPU_LEAKSAN_DIR"] = str(tmp_path)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run([sys.executable, "-c", _DRILL_SCRIPT],
                          capture_output=True, text=True,
                          timeout=480, cwd=REPO_ROOT, env=env)
    assert proc.returncode == 0, \
        f"drill failed\nstdout:{proc.stdout}\nstderr:{proc.stderr}"
    assert "DRILL_OK" in proc.stdout
    merged = leaksan.merged_report(str(tmp_path))
    assert merged["processes"] >= 1
    assert merged["registrations"] > 100, merged["registered"]
    # The headline assertion: nothing leaked, nothing double-fired.
    assert merged["leaks"] == [], json.dumps(merged["leaks"],
                                             indent=1)
    assert merged["anomalies"] == [], json.dumps(merged["anomalies"],
                                                 indent=1)
    # Multiple kinds actually exercised.
    assert {"admission_slot", "kv_block",
            "metric_series"} <= set(merged["registered"])
    # CLI contract on the clean run.
    cli = _leaksan_cli(tmp_path)
    assert cli.returncode == 0, cli.stdout + cli.stderr
    assert "leaked resources: 0" in cli.stdout
