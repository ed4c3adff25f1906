"""Shared node-plane state records + pure helpers.

Split out of node_service.py so the subsystem mixins (node_objects /
node_pg / node_streams) and the NodeService shell can all import them
without cycles.  Reference analogs: TaskSpecification
(src/ray/common/task/task_spec.h), plasma object entries
(plasma/object_lifecycle_manager.h:101), BundleSpec, WorkerPool's
worker records (raylet/worker_pool.h:174).
"""

from __future__ import annotations

import subprocess
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_tpu._private.protocol import ConnectionLost, send_msg

# Object directory entry states.
PENDING = "pending"
READY = "ready"
FAILED = "error"


class ObjectEntry:
    __slots__ = ("state", "loc", "data", "size", "refcount", "waiters",
                 "producing_task", "deleted", "embedded", "foreign",
                 "lineage", "reconstructions", "spill_path", "spilling",
                 "owner", "created_ts", "drain_replica")

    def __init__(self) -> None:
        self.state = PENDING
        self.loc = None          # "inline" | "shm" | "spilled" | "error"
        self.data: Optional[bytes] = None
        self.size = 0
        self.refcount = 1
        # Memory accounting (state.memory_summary / `ray_tpu memory`):
        # which client (driver or worker id) created this object, when
        # the entry was born, and whether it is a copy adopted from a
        # draining peer (those outlive ordinary borrow refcounting).
        self.owner: Optional[bytes] = None
        self.created_ts = time.time()
        self.drain_replica = False
        self.waiters: List[Callable[[], None]] = []
        self.producing_task: Optional[bytes] = None  # lineage hook
        self.deleted = False
        self.embedded: List[bytes] = []  # refs held by this object's payload
        # foreign: a copy whose owner directory lives on another node
        # (pulled replica / forwarded-task return).  Deleting a foreign
        # copy never removes the global GCS record.
        self.foreign = False
        # Lineage: the completed producing task's spec, kept so a lost
        # copy can be recomputed (reference:
        # core_worker/object_recovery_manager.h:41).  Plain tasks only;
        # actor results and put()s are not reconstructable (Ray parity).
        self.lineage: Optional[dict] = None
        self.reconstructions = 0
        # Spilling (reference: raylet/local_object_manager.h:110)
        self.spill_path: Optional[str] = None
        self.spilling = False


class TaskRecord:
    __slots__ = ("task_id", "spec", "deps", "state", "worker",
                 "retries_left", "is_actor_creation", "actor_id",
                 "cancelled", "stages", "had_deps", "started",
                 "locality_deadline", "drain_keep", "stall_reported")

    def __init__(self, spec: dict) -> None:
        self.task_id: bytes = spec["task_id"]
        self.spec = spec
        self.deps = {a[1] for a in spec["args"] if a[0] == "ref"}
        # Dep-free tasks must not report a deps_fetch stage (it would
        # just mirror their queue wait).
        self.had_deps = bool(self.deps)
        self.state = "pending"     # pending | dispatched | done
        self.worker: Optional[WorkerHandle] = None
        self.retries_left: int = spec.get("retries", 0)
        # Actor calls: did USER CODE begin executing?  Dispatch alone
        # doesn't set this — the worker queues dispatched calls, so
        # "in flight" at the node still means "may never have run".
        # The worker's task_started notify flips it; worker death then
        # distinguishes replayable-queued from maybe-side-effecting.
        self.started = False
        self.is_actor_creation = spec.get("is_actor_creation", False)
        self.cancelled = False
        # Locality-aware spillback: while set and in the future, a task
        # whose local dependency bytes dominate waits for local
        # capacity instead of spilling (node_objects._try_spill).
        self.locality_deadline: Optional[float] = None
        # Node drain: the handback sweep found no peer/owner for this
        # task — it may dispatch locally within the drain grace instead
        # of waiting to be handed off.
        self.drain_keep = False
        # Stall sentinel: a stack capture was already taken for this
        # execution attempt (one capture per attempt, not per sweep).
        self.stall_reported = False
        self.actor_id: Optional[bytes] = spec.get("actor_id")
        # Lifecycle checkpoints (reference: task events feeding
        # ray.util.state task summaries): submitted -> queued ->
        # [deps_fetched] -> worker_assigned -> executing -> finished.
        # "submitted" uses the client-stamped submit time when present
        # (same host in single-node mode); the rest are node-side.
        now = time.time()
        self.stages: Dict[str, float] = {
            "submitted": spec.get("submit_ts") or now,
            "queued": now,
        }


class ActorRecord:
    __slots__ = ("actor_id", "spec", "state", "worker", "queue",
                 "restarts_left", "name", "namespace", "detached",
                 "in_flight", "death_reason", "holds_released",
                 "intentional_exit", "release_on_drain", "hold_queue")

    def __init__(self, actor_id: bytes, spec: dict) -> None:
        self.actor_id = actor_id
        self.spec = spec
        self.state = "pending"     # pending | alive | restarting | dead
        self.worker: Optional[WorkerHandle] = None
        self.queue: deque = deque()    # TaskRecords awaiting aliveness/deps
        self.in_flight: Dict[bytes, TaskRecord] = {}
        self.restarts_left = spec.get("max_restarts", 0)
        self.name = spec.get("name")
        self.namespace = spec.get("namespace", "default")
        self.detached = spec.get("detached", False)
        self.death_reason = ""
        # Worker announced exit_actor(): the coming death is
        # deliberate — never restart, report "exited" not "crashed".
        self.intentional_exit = False
        # Driver GC released the last handle: die once queued +
        # in-flight work drains (reference handle-GC semantics).
        self.release_on_drain = False
        # Creation-task embedded ref holds live as long as the actor can
        # restart (the spec is replayed); released exactly once at
        # permanent death via _release_actor_holds.
        self.holds_released = False
        # Node drain: dispatch is held while the actor migrates to a
        # healthy peer (queued calls forward to the new home instead).
        self.hold_queue = False


class Bundle:
    """One reserved resource bundle of a placement group on this node
    (reference: bundle leases in gcs_placement_group_scheduler.h:283)."""

    __slots__ = ("total", "free")

    def __init__(self, resources: Dict[str, float]) -> None:
        self.total = dict(resources)
        self.free = dict(resources)


class WorkerHandle:
    __slots__ = ("worker_id", "conn_send", "proc", "state", "tpu",
                 "current_task", "actor_id", "resources_held",
                 "last_idle_time", "pid", "bundle_key", "image")

    def __init__(self, worker_id: bytes, proc: subprocess.Popen,
                 tpu: int, image: Optional[str] = None) -> None:
        self.worker_id = worker_id
        self.conn_send: Optional[Callable[[dict], None]] = None
        self.proc = proc
        self.state = "starting"    # starting | idle | busy | blocked | dead
        self.tpu = tpu             # chips leased (0 = a CPU worker)
        # Container image this worker runs inside (runtime_env
        # image_uri); image workers only take matching tasks.
        self.image = image
        self.current_task: Optional[TaskRecord] = None
        self.actor_id: Optional[bytes] = None
        self.resources_held: Dict[str, float] = {}
        self.last_idle_time = time.time()
        self.pid = proc.pid if proc else 0
        # (pg_id, bundle_index) the held resources came from, if any
        self.bundle_key: Optional[Tuple[bytes, int]] = None


class _ConnCtx:
    """Per-connection server-side context."""

    __slots__ = ("sock", "send_lock", "kind", "worker", "client_id",
                 "pid", "gcs_q")

    def __init__(self, sock: socket.socket) -> None:
        self.sock = sock
        self.send_lock = threading.Lock()
        self.kind = "unknown"
        self.worker: Optional[WorkerHandle] = None
        self.client_id: Optional[bytes] = None
        self.pid = 0
        # Lazily-created FIFO for GCS-proxied rpcs (node_service
        # _gcs_proxy): blocking GCS calls run off the conn thread, in
        # this client's submission order, so a GCS outage queues only
        # the GCS-dependent ops — not every later rpc on the conn.
        self.gcs_q = None

    def send(self, msg: dict) -> None:
        try:
            send_msg(self.sock, msg, self.send_lock)
        except (OSError, ConnectionLost):
            pass

    def reply(self, req: dict, payload: dict) -> None:
        # One-way messages (notify) carry no request id: nothing to send.
        rid = req.get("__req_id__")
        if rid is None:
            return
        payload["__reply_to__"] = rid
        self.send(payload)


def _fits(pool: Dict[str, float], res: Dict[str, float]) -> bool:
    return all(pool.get(k, 0.0) >= v - 1e-9 for k, v in res.items())


def _charge(pool: Dict[str, float], res: Dict[str, float]) -> None:
    for k, v in res.items():
        pool[k] = pool.get(k, 0.0) - v


def _uncharge(pool: Dict[str, float], res: Dict[str, float]) -> None:
    for k, v in res.items():
        pool[k] = pool.get(k, 0.0) + v


def _place_bundles(bundles: List[Dict[str, float]], strategy: str,
                   nodes: List[dict], use_avail: bool = True
                   ) -> Optional[List[dict]]:
    """Pick a node for every bundle under the given strategy, or None.

    Strategies mirror the reference (python/ray/util/placement_group.py):
    PACK (few nodes, soft), STRICT_PACK (one node), SPREAD (distinct
    nodes, soft), STRICT_SPREAD (distinct nodes, hard)."""
    pool_key = "resources_avail" if use_avail else "resources_total"
    pools = [dict(n[pool_key]) for n in nodes]
    assignment: List[Optional[dict]] = [None] * len(bundles)
    if strategy in ("PACK", "STRICT_PACK"):
        for i in range(len(nodes)):
            trial = dict(pools[i])
            ok = True
            for b in bundles:
                if not _fits(trial, b):
                    ok = False
                    break
                _charge(trial, b)
            if ok:
                return [nodes[i]] * len(bundles)
        if strategy == "STRICT_PACK":
            return None
        used: List[int] = []
        for bi, b in enumerate(bundles):
            placed = False
            for i in used:
                if _fits(pools[i], b):
                    _charge(pools[i], b)
                    assignment[bi] = nodes[i]
                    placed = True
                    break
            if not placed:
                for i in range(len(nodes)):
                    if i not in used and _fits(pools[i], b):
                        _charge(pools[i], b)
                        used.append(i)
                        assignment[bi] = nodes[i]
                        placed = True
                        break
            if not placed:
                return None
        return assignment      # type: ignore[return-value]
    if strategy in ("SPREAD", "STRICT_SPREAD"):
        order = sorted(range(len(nodes)),
                       key=lambda i: -sum(pools[i].values()))
        used_set: set = set()
        for bi, b in enumerate(bundles):
            placed = False
            for i in order:
                if i not in used_set and _fits(pools[i], b):
                    _charge(pools[i], b)
                    used_set.add(i)
                    assignment[bi] = nodes[i]
                    placed = True
                    break
            if not placed:
                if strategy == "STRICT_SPREAD":
                    return None
                for i in order:
                    if _fits(pools[i], b):
                        _charge(pools[i], b)
                        assignment[bi] = nodes[i]
                        placed = True
                        break
                if not placed:
                    return None
        return assignment      # type: ignore[return-value]
    raise ValueError(f"unknown placement strategy {strategy!r}")


def _reference_kind(e: ObjectEntry, pinned_by_actor: bool) -> str:
    """Classify one directory entry for the memory-accounting plane
    (state.memory_summary / list_objects reference_kind /
    ray_tpu_object_store_bytes{kind}).  Precedence: a drain-adopted
    replica stays visible as such even when later pinned or spilled."""
    if e.drain_replica:
        return "drain_replica"
    if e.loc == "spilled" or (e.spill_path is not None
                              and e.loc != "shm"):
        return "spilled"
    if pinned_by_actor:
        return "pinned_by_actor"
    if e.foreign:
        return "borrowed"
    return "owned"


def _unregister_waiter(entries: List[ObjectEntry], cb) -> None:
    """Remove a satisfied/expired waiter so polling loops on never-ready
    objects don't grow entry.waiters unboundedly. Caller holds the lock."""
    for e in entries:
        try:
            e.waiters.remove(cb)
        except ValueError:
            pass
    entries.clear()


def _OID(b: bytes):
    from ray_tpu._private.ids import ObjectID
    return ObjectID(b)
