"""Per-node control plane: scheduler, worker pool, object directory.

This is the analog of the reference's raylet (src/ray/raylet/node_manager.h:119
NodeManager + worker_pool.h:174 WorkerPool + scheduling/cluster_task_manager.h:42)
fused with the single-node portion of the GCS.  Differences by design:

* One coarse-grained state lock + thread-per-connection instead of an asio
  event loop — connection counts on a node are small (tens of workers).
* The object *data* plane never touches this service: payloads live in the
  native shm store (shared mmap) or inline in messages; the service holds
  only the directory (who's ready, where, refcounts) the way the
  reference's ownership tables do (core_worker/reference_count.h:64).
* Dependency tracking happens here (tasks are dispatched only when their
  top-level ObjectRef args are ready), mirroring the reference's
  raylet-side DependencyManager rather than blocking workers.
"""

from __future__ import annotations

import os
import queue
import socket
import subprocess
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_tpu._private import serialization as ser
from ray_tpu._private.accelerators import (ChipAllocator, chips_for,
                                           use_compile_cache)
from ray_tpu._private.chaos import chaos
from ray_tpu._private.config import config
from ray_tpu._private.gcs import GlobalControlState
from ray_tpu._private.node_agent import NodeAgentMixin
from ray_tpu._private.node_drain import DrainMixin
from ray_tpu._private.node_native import NativeWorkerMixin
from ray_tpu._private.node_objects import ObjectPlaneMixin
from ray_tpu._private.node_pg import PlacementGroupMixin
from ray_tpu._private.node_streams import StreamChannelMixin
from ray_tpu._private.protocol import ConnectionLost, recv_msg, send_msg
from ray_tpu.devtools import leaksan
from ray_tpu import exceptions as exc
from ray_tpu._private.node_state import (  # noqa: F401
    ActorRecord, Bundle, FAILED, ObjectEntry, PENDING, READY,
    TaskRecord, WorkerHandle, _ConnCtx, _OID, _charge, _fits,
    _place_bundles, _reference_kind, _uncharge, _unregister_waiter)


def _rpc_args_summary(msg: dict, max_len: int = 512) -> str:
    """Bounded one-line summary of an RPC message's fields for the
    slow-RPC capture: scalar values truncated, bulk payloads reduced
    to type + size (a capture must never serialize object bytes)."""
    parts = []
    for k, v in list(msg.items())[:12]:
        if k == "__req_id__":
            continue
        if isinstance(v, (bytes, bytearray)):
            parts.append(f"{k}=<{len(v)}B>")
        elif isinstance(v, (str, int, float, bool)) or v is None:
            parts.append(f"{k}={str(v)[:48]}")
        else:
            try:
                size = len(v)  # type: ignore[arg-type]
            except TypeError:
                size = -1
            parts.append(f"{k}=<{type(v).__name__}"
                         + (f" len={size}" if size >= 0 else "") + ">")
    return " ".join(parts)[:max_len]


class NodeService(ObjectPlaneMixin, PlacementGroupMixin,
                  StreamChannelMixin, NodeAgentMixin,
                  NativeWorkerMixin, DrainMixin):
    """Per-node daemon: scheduler, worker pool, object directory.

    Single-node: runs inside the driver process (threads) with an
    embedded GlobalControlState.  Multi-node (gcs_address given): the
    same object connects to a GCS process (gcs_service.GcsClient), opens
    TCP control + object-transfer listeners for its peers, heartbeats
    resources, and spills work over / pulls objects across nodes — the
    raylet role (reference: node_manager.h:119 + object_manager.h:117 +
    cluster_task_manager.h:42 spillback)."""

    def __init__(self, session_dir: str, resources: Dict[str, float],
                 store_path: str, store_capacity: int,
                 gcs: Optional[GlobalControlState] = None,
                 gcs_address: Optional[Tuple[str, int]] = None,
                 node_id: Optional[bytes] = None) -> None:
        self.session_dir = session_dir
        self.socket_path = os.path.join(session_dir, "node.sock")
        self.store_path = store_path
        self.store_capacity = store_capacity
        self.node_id = node_id or os.urandom(16)
        self.gcs_address = gcs_address
        self.multinode = gcs_address is not None
        # GCS pushes + node events are handled on a dedicated thread: the
        # GcsClient receiver thread must never block on self.lock, or a
        # blocking gcs.call() made while holding the lock would deadlock
        # (the reply is parked behind the stuck push).
        self._gcs_events: "queue.Queue" = queue.Queue()
        if self.multinode:
            from ray_tpu._private.gcs_service import GcsClient
            self.gcs = GcsClient(gcs_address[0], gcs_address[1],
                                 push_handler=lambda m:
                                 self._gcs_events.put(("push", m)),
                                 on_reconnect=lambda epoch:
                                 self._gcs_events.put(("resync", epoch)))
        else:
            self.gcs = gcs or GlobalControlState()
        # Last GCS recovery epoch this node confirmed (via registration
        # or resync); a bump means the control plane restarted and this
        # node re-published its state (ray_tpu_gcs_restarts_total).
        self._gcs_epoch: Optional[int] = None
        # Periodic gcs_status poll (wal size gauge, `ray_tpu gcs`).
        self._gcs_status: dict = {}
        self._next_gcs_status = 0.0
        # node_id -> Connection to that node's control listener
        self._peer_conns: Dict[bytes, Any] = {}
        self._peer_lock = threading.Lock()
        # task_id -> (TaskRecord, target node_id) for spilled-over tasks
        self.forwarded: Dict[bytes, Tuple[TaskRecord, bytes]] = {}
        # per-peer FIFO forward queues: one sender thread per target so
        # two calls to the same remote actor can never reorder in flight
        self._fwd_queues: Dict[bytes, "queue.Queue"] = {}
        # cluster resource view (from GCS), refreshed with each heartbeat
        self._cluster_view: List[dict] = []
        # actor_id -> node_id hint for actors living on other nodes
        self._actor_homes: Dict[bytes, bytes] = {}
        # actor_id -> death reason, for remote actors whose node died
        self._remote_actor_tombstones: Dict[bytes, str] = {}
        # object ids with an in-flight pull (owned by the pull pool)
        self._pulls_inflight: set = set()
        # pulls whose local entry was deleted mid-flight: the loop must
        # exit instead of polling a vanished GCS record forever
        self._cancelled_pulls: set = set()
        # Bounded pull-manager pool (reference: pull_manager.h request
        # pipelining; replaces thread-per-object pulls).  A heap of
        # (due, seq, oid) attempts consumed by at most
        # config.object_pull_workers threads; an attempt that can't
        # finish requeues itself with a short delay instead of camping
        # on a pool slot.
        self._pull_cond = threading.Condition()
        self._pull_heap: List[Tuple[float, int, bytes]] = []
        self._pull_due: Dict[bytes, float] = {}
        self._pull_running: set = set()
        self._pull_seq = 0
        self._pull_idle = 0
        # per-pull subscription state: oid -> {"cb", "subscribed",
        # "last_event"}
        self._pull_state: Dict[bytes, dict] = {}
        # Location cache fed by pull-time GCS lookups: oid ->
        # (frozenset(holder node ids), size).  Drives locality-aware
        # spillback scoring without a GCS round-trip under the lock.
        self._obj_loc_cache: Dict[bytes, Tuple[frozenset, int]] = {}
        # (oid, node_id) -> consecutive mid-transfer failures; two
        # strikes prune the holder from the GCS directory.
        self._holder_strikes: Dict[Tuple[bytes, bytes], int] = {}
        # Cached read fds for spilled objects served to peers
        # (os.pread instead of open+seek per chunk).
        self._spill_fds: Dict[bytes, Tuple[int, str]] = {}
        self._spill_fd_lock = threading.Lock()
        # Oids whose spill fd was dropped because the object left the
        # directory (deleted / spill file destroyed): a late chunk
        # request racing the delete — e.g. a fetch aborted by a
        # partition whose last request lands after the owner's global
        # delete — must serve its bytes WITHOUT re-caching the fd; the
        # delete already ran, so nothing would ever close a re-cached
        # entry (leak-ledger self-finding).  Cleared when the oid is
        # re-spilled.  Guarded by _spill_fd_lock; bounded.
        self._spill_dead: set = set()
        # (pg_id, bundle_index) -> Bundle reserved ON THIS NODE
        self.bundles: Dict[Tuple[bytes, int], Bundle] = {}
        # pg_id -> coordinator record for PGs created via this node:
        # {"bundles", "strategy", "name", "ready_oid",
        #  "state": pending|created|failed|removed,
        #  "nodes": [node_id per bundle]}
        self.pgs: Dict[bytes, dict] = {}
        self.control_port = 0
        self.transfer_port = 0
        self.lock = threading.RLock()
        self.objects: Dict[bytes, ObjectEntry] = {}
        self.tasks: Dict[bytes, TaskRecord] = {}
        self.pending_queue: deque = deque()          # TaskRecords to place
        self.actors: Dict[bytes, ActorRecord] = {}
        self.workers: Dict[bytes, WorkerHandle] = {}
        self.resources_total = dict(resources)
        self.resources_avail = dict(resources)
        self._chip_alloc = ChipAllocator(int(resources.get("TPU", 0)))
        self._conns: List[_ConnCtx] = []
        self._conn_threads: List[threading.Thread] = []
        self._pull_threads: List[threading.Thread] = []
        self._shutdown = False
        self._listener: Optional[socket.socket] = None
        self._next_worker_seq = 0
        self._deadline_waiters: List[Tuple[float, Callable[[], None]]] = []
        # Wakes _monitor_loop out of its wait: set by shutdown() and by
        # _add_deadline_waiter for deadlines nearer than the tick.
        self._monitor_wake = threading.Event()
        self._max_workers = int(os.environ.get(
            "RAY_TPU_MAX_WORKERS", max(8, int(resources.get("CPU", 4)) * 2)))
        # Circuit breaker: consecutive workers that died before ever
        # registering.  When tripped, stop respawning and fail pending
        # work instead of fork-bombing on a broken environment.
        self._spawn_failures = 0
        self._spawn_failure_limit = 5
        # Dead workers whose processes haven't exited yet; their shm pins
        # are reaped once the process is observed gone (escalating to
        # SIGKILL past the deadline).
        self._pending_reaps: List[Tuple[WorkerHandle, float]] = []
        # Aggregated application metrics pushed by workers/driver
        # (reference: _private/metrics_agent.py aggregation role).
        # key = (name, kind, frozenset(tag items)) -> series dict.
        self._metrics: Dict[tuple, dict] = {}
        # Control-plane RPC server telemetry: per-method latency
        # aggregates + the in-flight handler registry the slow-RPC
        # sentinel sweeps.  Own lock — the dispatch wrapper must not
        # contend with self.lock (most handlers take it themselves).
        from ray_tpu.util import metrics as _metrics_mod
        self._rpc_buckets = _metrics_mod.RPC_SERVER_BUCKETS
        self._rpc_lock = threading.Lock()
        # method -> {"buckets", "sum", "count", "inflight", "slow",
        #            "last_capture"}
        self._rpc_stats: Dict[str, dict] = {}
        # token -> {"method", "t0" (perf_counter), "tid", "msg",
        #           "flagged"} for handlers currently executing.
        self._rpc_inflight: Dict[int, dict] = {}
        self._rpc_token = 0
        # Last successful GCS round-trip (heartbeat loop) — the
        # doctor's GCS-outage signal: the heartbeat thread blocks on a
        # dead GCS, so this age grows during an outage.
        self._gcs_last_ok = time.time()
        # Scheduler decision tracing: bounded recent-decision ring +
        # cumulative outcome counts + the rate-limited `sched.decide`
        # span accumulator.  All mutated under self.lock (the
        # scheduler already holds it at every decision point).
        self._sched_recent: deque = deque(maxlen=50)
        self._sched_outcomes: Dict[str, int] = {}
        # task_ids already counted for a non-terminal outcome
        # (queue/drain_handback) — one count per queue episode, not
        # one per scheduling pass.
        self._sched_noted: set = set()
        self._sched_span: Dict[str, int] = {}
        self._sched_span_t0 = 0.0
        self._next_sched_span = 0.0
        # Spill-candidate detail stashed by _pick_spill_target for the
        # decision ring (scores of the nodes considered).
        self._sched_last_spill: Optional[dict] = None
        # Metrics history ring: (name, kind, tags) -> deque of
        # (ts, value) samples, recorded by the monitor loop at
        # metrics_history_resolution_s cadence (state.metric_history).
        self._metrics_history: Dict[tuple, deque] = {}
        # Worker stdout/stderr capture: per-file read offsets for the
        # log tailer that forwards new lines to the driver console
        # (reference: log_monitor.py `log_to_driver`).
        self._log_dir = os.path.join(session_dir, "logs")
        self._log_offsets: Dict[str, int] = {}
        # Profile/trace event ring (reference: profile events table
        # behind ray.timeline); workers attach execution spans to
        # task_done and push custom spans via profile_event.  Bounded:
        # appends go through _emit_event so evictions are counted
        # (ray_tpu_events_dropped_total) instead of silent.
        self._events: deque = deque(
            maxlen=(config.event_ring_capacity
                    or config.profile_events_max))
        # Scrape-time cache for the per-kind object-byte gauges: a
        # Prometheus scrape must not re-walk a 100k-entry directory
        # under the lock every few seconds.
        self._mem_kind_cache: Tuple[float, dict] = (0.0, {})
        # Objects a draining peer asked this node to adopt: their pull
        # registration marks the entry as a drain replica for the
        # memory-accounting plane.
        self._drain_replica_oids: set = set()
        # Streaming-generator item tables, keyed by the generator's
        # completion object id: {"items": [oid...], "done": bool}
        # (reference: streaming generator object refs in task_manager).
        self._streams: Dict[bytes, dict] = {}
        # Per-(destination, channel-key) compiled-DAG forwarder queues.
        self._chan_fwd_queues: Dict[tuple, Any] = {}
        # Cross-node channel items forwarded, by path ("stream" = the
        # persistent transfer-plane edge, "rpc" = legacy per-item
        # control-plane fallback) — state-dump visibility that the
        # steady-state path stays off the control plane.
        self._dag_items: Dict[str, int] = {}
        # In-flight on-demand stack dumps: token -> collection record.
        self._stack_dumps: Dict[bytes, dict] = {}
        # stream_id -> home node for streaming calls on REMOTE actors:
        # the item table lives on the actor's node; stream_next/release
        # proxy there (cross-node streaming generators).
        self._remote_streams: Dict[bytes, bytes] = {}
        # Compiled-DAG channel queues (cross-node channel plane;
        # reference: experimental/channel/shared_memory_channel.py for
        # same-host, torch_tensor_nccl_channel.py for cross-host).  A
        # queue lives on the CONSUMER's node; producers anywhere
        # chan_send to it (forwarded node-to-node when remote) with
        # bounded capacity + parked-reply backpressure.
        self._dag_queues: Dict[bytes, dict] = {}
        # Graceful-drain state (node_drain.DrainMixin).
        self._init_drain_state()

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def start(self) -> None:
        from ray_tpu._private.shm_store import ShmObjectStore
        ShmObjectStore(self.store_path, self.store_capacity,
                       create=True).close()
        if config.object_store_prefault:
            self._prefault_store()
        self._listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._native_init()     # C++ worker registry (node_native) —
                                # before any conn can register
        self._listener.bind(self.socket_path)
        self._listener.listen(128)
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True, name="rtpu-node-accept")
        self._accept_thread.start()
        self._monitor_thread = threading.Thread(
            target=self._monitor_loop, daemon=True,
            name="rtpu-node-monitor")
        self._monitor_thread.start()
        os.makedirs(self._log_dir, exist_ok=True)
        if config.log_to_driver:
            self._log_tail_thread = threading.Thread(
                target=self._log_tail_loop, daemon=True,
                name="rtpu-log-tailer")
            self._log_tail_thread.start()
        if self.multinode:
            self._start_multinode()
        self._start_agent()     # per-node dashboard agent (node_agent)
        # The accept/monitor threads are already running here: worker
        # prestart mutates self.workers like any other spawn path.
        with self.lock:
            for _ in range(config.worker_pool_prestart):
                self._spawn_worker(tpu=0)

    def shutdown(self) -> None:
        with self.lock:
            self._shutdown = True
            workers = list(self.workers.values())
        self._monitor_wake.set()    # don't pay a last monitor sleep
        with self._pull_cond:       # wake parked pull-pool workers
            self._pull_cond.notify_all()
        # Wake the accept loop(s) with a dummy connection and JOIN them
        # BEFORE closing the listener fds.  A thread left blocked in
        # accept() survives close(); when the fd number is reused by the
        # next session's listener, an EINTR retry (SIGCHLD from dying
        # workers) can make the stale thread steal and instantly drop the
        # new session's first connection (BrokenPipe on register_client).
        self._wake_and_join_acceptors()
        for w in workers:
            if w.conn_send:
                try:
                    w.conn_send({"type": "exit"})
                except Exception:
                    pass
        deadline = time.time() + 2.0
        killed = []
        for w in workers:
            if w.proc is None:
                continue
            try:
                w.proc.wait(timeout=max(0.05, deadline - time.time()))
            except subprocess.TimeoutExpired:
                w.proc.kill()
                killed.append(w.proc)
        # A killed worker is REAPED before shutdown returns.  One that held
        # accelerators gives them back only when the kernel has torn its
        # mappings down, which takes seconds at 16 GB a chip: a process that
        # opens the device meanwhile fails ("open(/dev/vfio/0): Device or
        # resource busy": a benchmark cell started straight after another's
        # shutdown, PR 34), and no /proc/<pid>/fd shows the chip as held.
        for proc in killed:
            try:
                proc.wait(timeout=60.0)
            except subprocess.TimeoutExpired:
                pass
        if self._listener:
            self._listener.close()
        if self.multinode:
            try:
                self._peer_listener.close()
            except Exception:
                pass
            if getattr(self, "_transfer_listener", None) is not None:
                try:
                    self._transfer_listener.close()
                except Exception:
                    pass
            with self._peer_lock:
                conns = list(self._peer_conns.values())
                self._peer_conns.clear()
            for c in conns:
                try:
                    c.close()
                except Exception:
                    pass
            try:
                self.gcs.close()
            except Exception:
                pass
        # Join every thread that can touch the shm store BEFORE the
        # caller (ray_tpu.shutdown) closes/munmaps the store client: a
        # straggler conn thread reaping a dead worker against an
        # unmapped segment is a segfault, not an exception.
        with self.lock:
            conns = list(self._conns)
            threads = list(self._conn_threads)
            pulls = list(self._pull_threads)
        for ctx in conns:
            try:
                ctx.sock.close()
            except OSError:
                pass
        deadline = time.time() + 3.0
        for t in threads + pulls + [
                getattr(self, "_monitor_thread", None),
                getattr(self, "_gcs_event_thread", None),
                # Log tailer reads worker-log files on a 0.25s tick; a
                # straggler touching the log dir after teardown was an
                # RT014 self-finding (it observes _shutdown, so this
                # join is bounded by one tick).
                getattr(self, "_log_tail_thread", None)]:
            if t is None or not t.is_alive():
                continue
            t.join(timeout=max(0.05, deadline - time.time()))
        try:
            os.unlink(self.socket_path)
        except OSError:
            pass
        try:
            os.unlink(self.store_path)
        except OSError:
            pass
        with self._spill_fd_lock:
            fds, self._spill_fds = list(self._spill_fds.values()), {}
        for fd, _ in fds:
            try:
                os.close(fd)
            except OSError:
                pass
            leaksan.discharge("spill_fd", fd, expect=False)

    # ------------------------------------------------------------------
    # connection handling
    # ------------------------------------------------------------------
    def _prefault_store(self) -> None:
        """Write-touch every page of the freshly created store so a
        put's single memcpy never pays first-touch tmpfs page faults
        (measured ~4x: 1.6 -> 6 GB/s on this host).  Safe ONLY here:
        no client has connected yet, so the value-preserving
        read-modify-write cannot race an allocator update."""
        import mmap as _mmap
        try:
            with open(self.store_path, "r+b") as f:
                mm = _mmap.mmap(f.fileno(), 0)
                mv = memoryview(mm)
                for off in range(0, len(mv), _mmap.PAGESIZE):
                    mv[off] = mv[off]
                del mv
                mm.close()
        except (OSError, ValueError):
            pass

    def _wake_and_join_acceptors(self) -> None:
        from ray_tpu._private.protocol import wake_and_join_acceptor
        wake_and_join_acceptor(getattr(self, "_accept_thread", None),
                               socket.AF_UNIX, self.socket_path)
        if self.multinode:
            wake_and_join_acceptor(
                getattr(self, "_peer_accept_thread", None),
                socket.AF_INET, (self.host, self.control_port))
            if getattr(self, "_transfer_listener", None) is not None:
                wake_and_join_acceptor(
                    getattr(self, "_transfer_accept_thread", None),
                    socket.AF_INET, (self.host, self.transfer_port))

    def _accept_loop(self) -> None:
        while not self._shutdown:
            try:
                sock, _ = self._listener.accept()
            except OSError:
                return
            if self._shutdown:
                try:
                    sock.close()
                except OSError:
                    pass
                return
            ctx = _ConnCtx(sock)
            t = threading.Thread(target=self._conn_loop, args=(ctx,),
                                 daemon=True, name="rtpu-node-conn")
            with self.lock:
                self._conns.append(ctx)
                self._conn_threads.append(t)
                if len(self._conn_threads) > 64:
                    self._conn_threads = [x for x in self._conn_threads
                                          if x.is_alive()]
            t.start()

    def _conn_loop(self, ctx: _ConnCtx) -> None:
        try:
            while not self._shutdown:
                msg = recv_msg(ctx.sock)
                self._dispatch(ctx, msg)
        except (ConnectionLost, OSError, EOFError):
            pass
        finally:
            self._on_disconnect(ctx)

    def _dispatch(self, ctx: _ConnCtx, msg: dict) -> None:
        mtype = msg["type"]
        handler = getattr(self, "_h_" + mtype, None)
        if handler is None:
            if "__req_id__" in msg:
                ctx.reply(msg, {"__error__": f"unknown rpc {mtype}"})
            return
        token = self._rpc_begin(mtype, msg)
        try:
            # Server-side chaos delay (site "rpc.<type>"): the
            # protocol-layer injector fires SENDER-side, which a
            # server-latency histogram never sees — this hook is what
            # makes slow-handler drills (and the slow-RPC sentinel
            # test) injectable.  fire_spec has a cheap disabled-path
            # early-out, so the hot path pays one attribute read.
            spec = chaos.fire_spec("rpc." + mtype, "delay")
            if spec is not None:
                lo = float(spec.get("lo_ms") or 0.0)
                hi = float(spec.get("hi_ms") or lo)
                time.sleep((lo + (hi - lo) * chaos.jitter()) / 1000.0)
            handler(ctx, msg)
        except Exception as e:  # handler bug — surface to caller
            if "__req_id__" in msg:
                ctx.reply(msg, {"__error__": e})
        finally:
            self._rpc_end(mtype, token)

    # ------------------------------------------------------------------
    # control-plane RPC server telemetry (tentpole of PR 16): every
    # dispatched handler lands in a per-method latency aggregate
    # (ray_tpu_rpc_server_seconds{method}), an in-flight registry the
    # slow-RPC sentinel sweeps, and — for listeners outside _dispatch
    # (transfer chunks, stream delivery) — the _rpc_record fold-in.
    # All under a dedicated _rpc_lock: ~two uncontended acquires per
    # RPC, never self.lock (the PR-8 hot-path rule).
    # ------------------------------------------------------------------
    def _rpc_stat_locked(self, method: str) -> dict:
        """Per-method aggregate cell (create-once).  Caller holds
        self._rpc_lock."""
        st = self._rpc_stats.get(method)
        if st is None:
            st = {"buckets": {str(b): 0 for b in self._rpc_buckets},
                  "sum": 0.0, "count": 0, "inflight": 0,
                  "slow": 0, "last_capture": 0.0}
            self._rpc_stats[method] = st
        return st

    def _rpc_begin(self, method: str, msg: dict) -> int:
        with self._rpc_lock:
            self._rpc_token += 1
            token = self._rpc_token
            self._rpc_stat_locked(method)["inflight"] += 1
            self._rpc_inflight[token] = {
                "method": method, "t0": time.perf_counter(),
                "tid": threading.get_ident(), "msg": msg,
                "flagged": False}
        return token

    def _rpc_end(self, method: str, token: int) -> None:
        end = time.perf_counter()
        with self._rpc_lock:
            entry = self._rpc_inflight.pop(token, None)
            if entry is None:
                return
            st = self._rpc_stat_locked(method)
            st["inflight"] = max(st["inflight"] - 1, 0)
            dur = end - entry["t0"]
            for b in self._rpc_buckets:
                if dur <= b:
                    st["buckets"][str(b)] += 1
                    break
            st["sum"] += dur
            st["count"] += 1

    def _rpc_record(self, method: str, dur: float) -> None:
        """Fold one completed handler duration into the per-method
        aggregates, for serving loops that don't route through
        _dispatch (transfer-plane chunk serving, DAG stream
        delivery)."""
        with self._rpc_lock:
            st = self._rpc_stat_locked(method)
            for b in self._rpc_buckets:
                if dur <= b:
                    st["buckets"][str(b)] += 1
                    break
            st["sum"] += dur
            st["count"] += 1

    def _slow_rpc_tick(self) -> None:
        """Monitor-loop sweep over in-flight handlers: flag anything
        past max(slow_rpc_min_seconds, slow_rpc_p95_multiple * that
        method's server-side p95) — the stall sentinel's contract at
        RPC scale.  Flag under _rpc_lock, capture OUTSIDE it; at most
        one stack+args capture per method per capture window."""
        floor = config.slow_rpc_min_seconds
        if floor <= 0:
            return
        from ray_tpu.util.metrics import hist_quantile
        now = time.perf_counter()
        wall = time.time()
        flagged = []
        with self._rpc_lock:
            for entry in self._rpc_inflight.values():
                if entry["flagged"]:
                    continue
                st = self._rpc_stats.get(entry["method"])
                threshold = floor
                if st is not None and \
                        st["count"] >= config.slow_rpc_min_samples:
                    threshold = max(
                        floor, config.slow_rpc_p95_multiple
                        * hist_quantile(st, 0.95))
                elapsed = now - entry["t0"]
                if elapsed < threshold:
                    continue
                entry["flagged"] = True
                st = self._rpc_stat_locked(entry["method"])
                st["slow"] += 1
                capture = (wall - st["last_capture"]
                           >= config.slow_rpc_capture_window_s)
                if capture:
                    st["last_capture"] = wall
                flagged.append((entry, elapsed, threshold, capture))
        for entry, elapsed, threshold, capture in flagged:
            from ray_tpu.util.metrics import SLOW_RPC_METRIC
            with self.lock:
                self._inc_counter(
                    SLOW_RPC_METRIC, {"method": entry["method"]},
                    "control-plane handlers flagged by the slow-RPC "
                    "sentinel")
            if capture:
                self._capture_slow_rpc(entry, elapsed, threshold)

    def _capture_slow_rpc(self, entry: dict, elapsed: float,
                          threshold: float) -> None:
        """One stack + args-summary capture of a flagged handler's
        thread, recorded as a `slow_rpc` timeline event (surfaced by
        profiling.timeline() and `ray_tpu doctor`)."""
        import traceback
        frame = sys._current_frames().get(entry["tid"])
        stack = ("".join(traceback.format_stack(frame))
                 if frame is not None else "")
        now = time.time()
        self._emit_event({
            "kind": "slow_rpc",
            "name": "rpc." + entry["method"] + ":slow",
            "method": entry["method"],
            "elapsed_s": round(elapsed, 4),
            "threshold_s": round(threshold, 4),
            "stack": stack,
            "rpc_args": _rpc_args_summary(entry.get("msg") or {}),
            "pid": os.getpid(),
            "start": now, "end": now,
            "node_id": self.node_id.hex(),
        })

    def _on_disconnect(self, ctx: _ConnCtx) -> None:
        self._native_on_disconnect(ctx)
        with self.lock:
            if ctx in self._conns:
                self._conns.remove(ctx)
            w = ctx.worker
            if w is None or w.state == "dead":
                return
            self._handle_worker_death(w, "worker connection lost")
            self._schedule()

    # ------------------------------------------------------------------
    # multi-node plane (reference: object_manager.h:117 transfer,
    # cluster_task_manager.h:42 spillback, ray_syncer.h:88 resource sync)
    # ------------------------------------------------------------------
    def _start_multinode(self) -> None:
        """Open the peer TCP listener, register with the GCS, start the
        heartbeat + event threads."""
        self._peer_listener = socket.socket(socket.AF_INET,
                                            socket.SOCK_STREAM)
        self._peer_listener.setsockopt(socket.SOL_SOCKET,
                                       socket.SO_REUSEADDR, 1)
        host = os.environ.get("RAY_TPU_NODE_HOST", "127.0.0.1")
        self._peer_listener.bind((host, 0))
        self._peer_listener.listen(64)
        self.host = host
        self.control_port = self._peer_listener.getsockname()[1]
        self._peer_accept_thread = threading.Thread(
            target=self._peer_accept_loop, daemon=True,
            name="rtpu-peer-accept")
        self._peer_accept_thread.start()
        # Dedicated object-transfer listener: raw binary chunk streams
        # (node_objects._transfer_serve_loop), kept OFF the pickled
        # control-plane listener so bulk data never queues behind
        # control rpcs (reference: object_manager.h transfer plane).
        try:
            self._transfer_listener = socket.socket(socket.AF_INET,
                                                    socket.SOCK_STREAM)
            self._transfer_listener.setsockopt(socket.SOL_SOCKET,
                                               socket.SO_REUSEADDR, 1)
            self._transfer_listener.bind((host, 0))
            self._transfer_listener.listen(64)
            self.transfer_port = \
                self._transfer_listener.getsockname()[1]
            self._transfer_accept_thread = threading.Thread(
                target=self._transfer_accept_loop, daemon=True,
                name="rtpu-xfer-accept")
            self._transfer_accept_thread.start()
        except OSError:
            # No transfer listener: advertise the control port so peers
            # fall back to the control-plane chunk RPCs.
            self._transfer_listener = None
            self.transfer_port = self.control_port
        self._gcs_event_thread = threading.Thread(
            target=self._gcs_event_loop, daemon=True,
            name="rtpu-gcs-events")
        self._gcs_event_thread.start()
        self.gcs.register_node(self.node_id, host, self.control_port,
                               self.transfer_port, self.resources_total)
        self._gcs_epoch = self.gcs.gcs_epoch
        self.gcs.sub_nodes(lambda ev, info:
                           self._gcs_events.put(("node", ev, info)))
        self._cluster_view = self.gcs.nodes()
        threading.Thread(target=self._heartbeat_loop, daemon=True,
                         name="rtpu-heartbeat").start()

    def _peer_accept_loop(self) -> None:
        while not self._shutdown:
            try:
                sock, _ = self._peer_listener.accept()
            except OSError:
                return
            if self._shutdown:
                try:
                    sock.close()
                except OSError:
                    pass
                return
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            ctx = _ConnCtx(sock)
            ctx.kind = "peer"
            t = threading.Thread(target=self._conn_loop, args=(ctx,),
                                 daemon=True, name="rtpu-peer-conn")
            with self.lock:
                self._conns.append(ctx)
                self._conn_threads.append(t)
                if len(self._conn_threads) > 64:
                    self._conn_threads = [x for x in self._conn_threads
                                          if x.is_alive()]
            t.start()

    def _heartbeat_loop(self) -> None:
        interval = config.heartbeat_interval_s
        while not self._shutdown:
            try:
                with self.lock:
                    avail = dict(self.resources_avail)
                    # Demand/idleness signal for the autoscaler
                    # (reference: resource_demand in raylet heartbeats →
                    # autoscaler/_private/monitor.py).
                    shapes = [dict(r.spec.get("resources") or {})
                              for r in list(self.pending_queue)[:20]]
                    busy = any(w.state in ("busy", "blocked")
                               for w in self.workers.values())
                    if shapes or busy:
                        self._idle_since = None
                    elif getattr(self, "_idle_since", None) is None:
                        self._idle_since = time.time()
                    # Pending placement-group demand (gang shapes the
                    # autoscaler must bin-pack into whole node sets;
                    # reference: resource_demand_scheduler PG demand).
                    pg_demand = [
                        {"pg_id": pid.hex(),
                         "bundles": [dict(b) for b in r["bundles"]],
                         "strategy": r["strategy"]}
                        for pid, r in self.pgs.items()
                        if r["state"] == "pending"][:8]
                    load = {"pending": len(self.pending_queue),
                            "shapes": shapes,
                            "pg_demand": pg_demand,
                            "idle_since": self._idle_since}
                self.gcs.heartbeat(self.node_id, avail, load)
                # Doctor's GCS-outage signal: this thread blocks (or
                # raises) on a dead GCS, so the age of the last
                # successful round-trip grows during an outage.
                self._gcs_last_ok = time.time()
                # Autoscaler lease (StandardAutoscaler refreshes a
                # timestamp in GCS KV every reconcile): gates infeasible
                # fail-fast vs wait.  A stale lease (dead autoscaler)
                # must NOT leave infeasible work pending forever.
                try:
                    raw = self.gcs.kv_get("cluster", b"autoscaler")
                    self._autoscaler_lease = (float(raw) if raw else 0.0)
                except Exception:
                    pass
                self._cluster_view = self.gcs.nodes()
                # Control-plane status card (epoch / WAL size /
                # last-snapshot age): polled at a slow cadence for the
                # ray_tpu_gcs_wal_bytes gauge and `ray_tpu gcs`.
                if time.time() >= self._next_gcs_status:
                    self._next_gcs_status = (time.time()
                                             + config.gcs_status_interval_s)
                    try:
                        self._gcs_status = self.gcs.status()
                    except Exception:
                        pass
                with self.lock:
                    self._schedule()   # peer capacity may have freed up
            except Exception:
                pass
            time.sleep(interval * 0.5)

    def _gcs_event_loop(self) -> None:
        while not self._shutdown:
            try:
                item = self._gcs_events.get(timeout=0.5)
            except queue.Empty:
                continue
            try:
                if item[0] == "node":
                    self._on_node_event(item[1], item[2])
                elif item[0] == "push":
                    self._on_gcs_push(item[1])
                elif item[0] == "resync":
                    self._gcs_resync()
            except Exception:
                pass

    def _on_gcs_push(self, msg: dict) -> None:
        if msg.get("type") == "object_deleted":
            # Owner-driven delete of an object we hold a foreign copy of.
            oid = msg["object_id"]
            with self.lock:
                e = self.objects.get(oid)
                if e is None or not e.foreign:
                    return
                was_shm = e.loc == "shm"
                if e.waiters:
                    # Someone on this node is blocked in get(): turn the
                    # entry into a lost-tombstone and wake them, instead
                    # of hanging them forever on a popped entry.
                    blob = ser.dumps(exc.ObjectLostError(
                        oid.hex(), "deleted by owner while being read"))
                    e.state = FAILED
                    e.loc, e.data, e.size = "error", blob, len(blob)
                    waiters, e.waiters = e.waiters, []
                    for wake in waiters:
                        wake()
                else:
                    self.objects.pop(oid, None)
                    e.deleted = True
            if was_shm:
                try:
                    store = self._store()
                    store.release(_OID(oid))
                    store.delete(_OID(oid))
                except Exception:
                    pass

    def _on_node_event(self, event: str, info: dict) -> None:
        nid = info["node_id"]
        if event == "node_added":
            if nid != self.node_id:
                try:
                    self._cluster_view = self.gcs.nodes()
                except Exception:
                    pass
                with self.lock:
                    self._schedule()
            return
        if event == "node_draining":
            if nid == self.node_id:
                # GCS-initiated drain of THIS node (CLI / operator):
                # the GCS already flipped the state — don't re-publish.
                self._begin_drain("gcs",
                                  info.get("reason") or "drain requested",
                                  grace_s=info.get("grace_s"),
                                  publish=False)
            else:
                # Stop targeting the draining peer immediately (the
                # heartbeat refresh would catch up within ~0.5s, but
                # every task spilled there in the window is a task it
                # must hand back).
                for n in self._cluster_view:
                    if n["node_id"] == nid:
                        n["state"] = "draining"
            return
        if event != "node_dead" or nid == self.node_id:
            return
        with self._peer_lock:
            conn = self._peer_conns.pop(nid, None)
        if conn is not None:
            try:
                conn.close()
            except Exception:
                pass
        self._cluster_view = [n for n in self._cluster_view
                              if n["node_id"] != nid]
        # Committed placement groups with bundles on the dead node get
        # re-placed whole (node_pg.py _pg_on_node_dead).
        try:
            self._pg_on_node_dead(nid)
        except Exception:
            pass
        # Tombstone every actor the GCS knew lived there, plus our hints.
        dead_reason = f"node {nid.hex()[:8]} died: " \
                      f"{info.get('reason') or 'lost heartbeats'}"
        retry, fail, pull_check = [], [], []
        dead_actors = set(info.get("dead_actors", ()))
        with self.lock:
            for aid in dead_actors:
                self._remote_actor_tombstones[aid] = dead_reason
            for aid, home in list(self._actor_homes.items()):
                if home == nid:
                    # Drop the stale hint always; tombstone only actors
                    # the GCS confirms died THERE — an actor migrated
                    # off a drained node lives elsewhere now (the GCS
                    # directory was re-pointed via set_actor_node), and
                    # the next call re-resolves it.
                    del self._actor_homes[aid]
                    if aid in dead_actors:
                        self._remote_actor_tombstones[aid] = dead_reason
            for tid, (rec, target) in list(self.forwarded.items()):
                if target != nid:
                    continue
                del self.forwarded[tid]
                pull_check.append(rec)
        # A forwarded task may have completed before the node died — its
        # returns are then in the GCS (inline) or on surviving replicas.
        # Only tasks with no published results are retried/failed.
        reconstruct: List[TaskRecord] = []
        for rec in pull_check:
            statuses = []
            for oid in rec.spec["return_ids"]:
                try:
                    locs = self.gcs.get_locations(oid)
                except Exception:
                    locs = {}
                statuses.append(
                    "ready" if locs.get("kind") is not None
                    else "lost" if locs.get("lost") else "missing")
            if all(s == "ready" for s in statuses):
                with self.lock:
                    # Completed remotely but the forward_done notify was
                    # lost with the node: release the owner-side holds
                    # here (forwarded entry already popped above).
                    for dep in rec.spec.get("embedded") or []:
                        self._decref(dep)
                    for oid in rec.spec["return_ids"]:
                        self._ensure_pull(oid)
                continue
            if (all(s in ("ready", "lost") for s in statuses)
                    and rec.actor_id is None):
                # Completed, but the only copies died with the node
                # (the GCS lost-marker proves it WAS ready): re-running
                # is lineage reconstruction, budgeted by
                # max_object_reconstructions — independent of the
                # task's retry policy, which governs never-ran work.
                reconstruct.append(rec)
                continue
            (retry if rec.retries_left > 0
             and not rec.is_actor_creation else fail).append(rec)
        with self.lock:
            for rec in retry:
                self._schedule_retry(rec, "node_death", dead_reason)
            for rec in reconstruct:
                if not self._requeue_as_reconstruction(rec,
                                                       dead_reason):
                    fail.append(rec)
            for rec in fail:
                if rec.actor_id is not None and not rec.is_actor_creation:
                    err: Exception = exc.ActorDiedError(
                        rec.actor_id.hex(), dead_reason)
                else:
                    err = exc.WorkerCrashedError(
                        f"{dead_reason} while running "
                        f"{rec.spec.get('name')}")
                self._fail_task_returns(rec, err)
                if rec.is_actor_creation:
                    # _fail_task_returns keeps creation holds for restart
                    # replay — but this actor's node is gone for good.
                    for dep in rec.spec.get("embedded") or []:
                        self._decref(dep)
            self._schedule()

    def _gcs_resync(self) -> None:
        """Bulk re-publication of this node's authoritative local state
        to the GCS after a reconnect (re-sync half of the GCS restart
        protocol; reference: raylet resubscription rebuilding a
        restarted GCS).  Re-registers the node, re-announces every
        READY object copy this node serves (the GCS object directory is
        soft state), re-points the actor directory at resident actors,
        and restores an in-progress drain.  Idempotent — runs on every
        reconnect, restart or not."""
        if not self.multinode or self._shutdown:
            return
        t0 = time.time()
        objs: List[Tuple[bytes, int]] = []
        inline: List[Tuple[bytes, int, str, bytes]] = []
        with self.lock:
            for oid, e in self.objects.items():
                if e.state not in (READY, FAILED) or e.deleted:
                    continue
                if e.foreign and e.loc != "shm":
                    continue    # pulled inline copies: record not ours
                if e.loc in ("shm", "spilled", "inline"):
                    # Same publication rule as task_done: local values
                    # (including spilled ones this node still serves)
                    # announce a holder; readers fetch from here.
                    objs.append((oid, e.size))
                elif e.loc == "error" and e.data is not None:
                    # Error blobs ride in the GCS record itself so they
                    # survive this node's death too.
                    inline.append((oid, e.size, "error", bytes(e.data)))
            actors = [aid for aid, a in self.actors.items()
                      if a.state != "dead"]
            draining = None
            if self.draining:
                draining = {"deadline": self._drain_deadline,
                            "reason": self._drain_reason}
            resources_total = dict(self.resources_total)
        try:
            out = self.gcs.resync_node(
                self.node_id, self.host, self.control_port,
                self.transfer_port, resources_total,
                objects=objs, inline=inline, actors=actors,
                draining=draining)
        except Exception:
            return      # still down; the next reconnect resyncs
        dt = time.time() - t0
        new_epoch = out.get("epoch") or self.gcs.gcs_epoch
        restarted = (new_epoch is not None
                     and self._gcs_epoch is not None
                     and new_epoch != self._gcs_epoch)
        self._gcs_epoch = new_epoch
        from ray_tpu.util.metrics import (GCS_RESTARTS_METRIC,
                                          GCS_RESYNC_BUCKETS,
                                          GCS_RESYNC_SECONDS_METRIC)
        with self.lock:
            self._observe_hist(GCS_RESYNC_SECONDS_METRIC, {}, dt,
                               GCS_RESYNC_BUCKETS,
                               "node-side GCS re-sync duration")
            if restarted:
                self._inc_counter(GCS_RESTARTS_METRIC, {},
                                  "GCS restarts observed (recovery "
                                  "epoch bumps)")
        if restarted:
            # Lifecycle event: surfaces in summarize_tasks() under
            # "node:gcs_restart" and in the timeline (like drains).
            self._emit_event({
                "kind": "gcs_restart", "name": "gcs:restart",
                "epoch": new_epoch, "resync_s": dt,
                "objects_republished": len(objs) + len(inline),
                "actors_republished": len(actors),
                "start": t0, "end": time.time(),
                "pid": 0, "node_id": self.node_id.hex()})
        self._next_gcs_status = 0.0     # refresh the status card now
        try:
            self._cluster_view = self.gcs.nodes()
        except Exception:
            pass
        with self.lock:
            self._schedule()

    # -- peer connections --------------------------------------------------
    def _peer_conn_to(self, ninfo: dict):
        """Get (or open) the persistent Connection to a peer node."""
        from ray_tpu._private.protocol import Connection, connect_tcp
        nid = ninfo["node_id"]
        if chaos.partitioned(nid):
            # Node-partition fault: this node cannot reach the target —
            # covers control forwards AND object transfer, since both
            # ride these peer connections.
            raise ConnectionLost(
                f"chaos: partitioned from node {nid.hex()[:12]}")
        with self._peer_lock:
            conn = self._peer_conns.get(nid)
            if conn is not None and not conn._closed:
                return conn
        sock = connect_tcp(ninfo["host"], ninfo["control_port"],
                           deadline_s=5.0)
        conn = Connection(sock)
        with self._peer_lock:
            existing = self._peer_conns.get(nid)
            if existing is not None and not existing._closed:
                conn.close()
                return existing
            self._peer_conns[nid] = conn
        return conn

    def _node_info(self, nid: bytes) -> Optional[dict]:
        for n in self._cluster_view:
            if n["node_id"] == nid:
                return n
        try:
            # Bounded: this runs on conn/forward threads whose serial
            # dispatch must not wedge through a GCS outage — the cached
            # view above is the ride-it-out answer.
            self._cluster_view = self.gcs.nodes(max_wait_s=2.0)
        except Exception:
            return None
        for n in self._cluster_view:
            if n["node_id"] == nid:
                return n
        return None

    # ------------------------------------------------------------------
    # message handlers (all named _h_<type>)
    # ------------------------------------------------------------------
    def _h_register_client(self, ctx: _ConnCtx, m: dict) -> None:
        with self.lock:
            ctx.kind = m["kind"]
            ctx.client_id = m["client_id"]
            ctx.pid = m.get("pid", 0)
            if m["kind"] == "worker":
                w = self.workers.get(m["client_id"])
                if w is None:
                    ctx.reply(m, {"__error__": "unknown worker"})
                    return
                ctx.worker = w
                w.conn_send = ctx.send
                w.state = "idle"
                w.last_idle_time = time.time()
                self._spawn_failures = 0
                self._schedule()
            ctx.reply(m, {"ok": True,
                          "store_path": self.store_path,
                          "session_dir": self.session_dir})

    def _infeasible_reason(self, res: Dict[str, float]) -> Optional[str]:
        """A request no node total can ever satisfy hangs forever unless
        rejected up front (reference: raylet infeasible-task errors).
        Multi-node: feasible if ANY alive node's totals cover it."""
        if not res:
            return None
        if self._local_totals_satisfy(res):
            return None
        if self.multinode:
            for n in self._cluster_view:
                if n.get("state") != "alive":
                    continue
                if all(v <= n["resources_total"].get(k, 0.0) + 1e-9
                       for k, v in res.items()):
                    return None
        return (f"resource request {res} exceeds every node's total "
                f"(local total: {self.resources_total})")

    def _h_submit_task(self, ctx: _ConnCtx, m: dict) -> None:
        spec = m["spec"]
        aid = spec.get("actor_id")
        home: Optional[bytes] = None
        if (aid is not None and not spec.get("is_actor_creation")
                and self.multinode):
            with self.lock:
                local = aid in self.actors
                home = self._actor_homes.get(aid)
            if not local and home is None:
                # Actor created elsewhere (e.g. found via get_actor):
                # resolve its home through the GCS actor directory.
                # No self.lock held — gcs.call would deadlock under it.
                try:
                    home = self.gcs.get_actor_node(aid)
                except Exception:
                    home = None
                if home is not None:
                    self._actor_homes[aid] = home
            if not local and home is not None:
                ninfo = self._cluster_node(home)
                if ninfo is None or ninfo.get("state") != "alive":
                    # Stale hint: the cached home is draining or gone —
                    # the actor may have MIGRATED (drain restarts actors
                    # elsewhere and re-points the GCS directory).
                    try:
                        fresh = self.gcs.get_actor_node(aid)
                    except Exception:
                        fresh = None
                    if fresh is not None and fresh != home:
                        home = fresh
                        self._actor_homes[aid] = home
        with self.lock:
            if (aid is not None and aid not in self.actors
                    and self.multinode):
                tomb = self._remote_actor_tombstones.get(aid)
                if tomb is not None:
                    rec = TaskRecord(spec)
                    self.tasks[rec.task_id] = rec
                    for oid in spec["return_ids"]:
                        self.objects.setdefault(oid, ObjectEntry())
                    self._fail_task_returns(rec, exc.ActorDiedError(
                        aid.hex(), tomb, task_started=False))
                    ctx.reply(m, {"ok": True})
                    return
                if home is not None and home != self.node_id:
                    rec = TaskRecord(spec)
                    if spec.get("streaming"):
                        # Remote-actor stream: the item table fills on
                        # the actor's HOME node; remember where so
                        # stream_next/release from local consumers
                        # proxy there (items themselves are ordinary
                        # GCS-located objects and pull across).
                        self._remote_streams[
                            spec["return_ids"][0]] = home
                    # Remote actor call: forward to its home node; results
                    # come back through the GCS location directory.
                    self.tasks[rec.task_id] = rec
                    for oid in spec["return_ids"]:
                        e = self.objects.setdefault(oid, ObjectEntry())
                        e.producing_task = rec.task_id
                    ninfo = self._node_info(home)
                    if ninfo is None:
                        self._fail_task_returns(rec, exc.ActorDiedError(
                            aid.hex(), "actor's node is gone"))
                    else:
                        self._forward_task(rec, ninfo)
                    ctx.reply(m, {"ok": True})
                    return
            rec = TaskRecord(spec)
            # When an autoscaler is live (it announces itself in GCS KV,
            # mirrored into _autoscaler_active by the heartbeat loop), a
            # currently unsatisfiable shape stays PENDING as demand — a
            # node with the resource may be provisioned (reference:
            # infeasible tasks wait and feed the autoscaler).  Otherwise
            # fail fast, cluster-wide totals considered.
            reason = (None if spec.get("pg") is not None
                      or self._autoscaler_live()
                      else self._infeasible_reason(spec.get("resources")))
            if (reason is None and spec.get("streaming")
                    and not self._local_totals_satisfy(
                        spec.get("resources") or {})):
                # Streaming tasks never spill (their item stream is
                # node-local); an unsatisfiable-here request would
                # otherwise hang pending forever.
                reason = ("streaming generator tasks run on the "
                          "submitting node, whose resources cannot "
                          "satisfy this request")
            if reason is not None and spec.get("actor_id") is None:
                self.tasks[rec.task_id] = rec
                for oid in spec["return_ids"]:
                    self.objects.setdefault(oid, ObjectEntry())
                self._fail_task_returns(rec, exc.InfeasibleResourceError(
                    f"task {spec.get('name')!r} is infeasible: {reason}"))
                ctx.reply(m, {"ok": True})
                return
            if self._spawn_failures >= self._spawn_failure_limit:
                self.tasks[rec.task_id] = rec
                for oid in spec["return_ids"]:
                    self.objects.setdefault(oid, ObjectEntry())
                self._fail_task_returns(rec, exc.WorkerCrashedError(
                    "worker environment is broken (spawn circuit breaker "
                    "tripped); task rejected"))
                ctx.reply(m, {"ok": True})
                return
            self.tasks[rec.task_id] = rec
            for oid in spec["return_ids"]:
                entry = self.objects.get(oid)
                if entry is None:
                    entry = ObjectEntry()
                    self.objects[oid] = entry
                entry.producing_task = rec.task_id
            # Drop deps that are already ready.
            rec.deps = {d for d in rec.deps
                        if not self._object_ready(d)}
            if rec.had_deps and not rec.deps:
                rec.stages.setdefault("deps_fetched", time.time())
            if self.multinode:
                # Deps produced on other nodes (earlier spills, remote
                # actors) must be pulled or this task waits forever;
                # _ensure_pull no-ops for locally-producing deps.
                for d in rec.deps:
                    self._ensure_pull(d)
                if rec.deps:
                    # pull_wait checkpoint: transfer-plane share of the
                    # deps_fetch stage (tracing.STAGE_DURATION_PAIRS).
                    rec.stages.setdefault("pull_wait", time.time())
            if rec.actor_id is not None and not rec.is_actor_creation:
                self._enqueue_actor_task(rec)
            else:
                self.pending_queue.append(rec)
            self._schedule()
        ctx.reply(m, {"ok": True})

    def _object_ready(self, oid: bytes) -> bool:
        """Caller holds self.lock."""
        e = self.objects.get(oid)
        return e is not None and e.state in (READY, FAILED)

    def _h_put_object(self, ctx: _ConnCtx, m: dict) -> None:
        with self.lock:
            # loc="error" puts deliver an exception as the object's
            # value (Serve failover bridges a final failure this way).
            self._register_object(m["object_id"], m["loc"],
                                  m.get("data"), m["size"],
                                  state=(FAILED if m["loc"] == "error"
                                         else READY),
                                  embedded=m.get("embedded") or [],
                                  creator_pid=ctx.pid,
                                  owner=ctx.client_id)
            self._schedule()
        ctx.reply(m, {"ok": True})

    def _register_object(self, oid: bytes, loc: str,
                         data: Optional[bytes], size: int,
                         state: str = READY,
                         embedded: Optional[List[bytes]] = None,
                         creator_pid: int = 0,
                         foreign: bool = False,
                         owner: Optional[bytes] = None) -> None:
        """Register/overwrite an object directory entry.  Caller
        holds self.lock."""
        if loc == "shm" and creator_pid and creator_pid != os.getpid():
            # Adopt the creator's pin into the directory's ledger so
            # reaping the (possibly dead) creator leaves it pinned.
            from ray_tpu._private import shm_store as shm
            try:
                store = self._store()
                rc = store.transfer_pin(_OID(oid), creator_pid, os.getpid())
                if rc == shm.NOPIN:
                    # The creator died and its pin was already reaped
                    # before this registration drained: take a fresh
                    # directory pin (or declare the object lost if the
                    # unpinned entry was evicted in the gap).
                    if store.get(_OID(oid)) is None:
                        blob = ser.dumps(exc.ObjectLostError(
                            oid.hex(), "evicted before registration "
                            "(creator process died)"))
                        loc, data, size = "error", blob, len(blob)
                        state = FAILED
            except Exception:
                pass
        entry = self.objects.get(oid)
        if entry is None:
            entry = ObjectEntry()
            # Ownership is decided at entry birth and never flips: a
            # pre-existing entry (created at submit/put on the owner)
            # stays owned even when its value arrives via a pull —
            # otherwise owner-driven global delete would be skipped and
            # forwarded-task results would leak cluster-wide.
            entry.foreign = foreign
            self.objects[oid] = entry
        entry.state = state
        entry.loc = loc
        entry.data = data
        entry.size = size
        if owner is not None and entry.owner is None:
            # First writer wins: a pulled replica arriving later must
            # not overwrite the owner recorded at put/submit time.
            entry.owner = owner
        if oid in self._drain_replica_oids:
            # Copy adopted from a draining peer: visible as its own
            # reference kind in the memory plane (it outlives ordinary
            # borrow refcounting — the adopting directory holds it).
            entry.drain_replica = True
            self._drain_replica_oids.discard(oid)
        if loc == "spilled" and data is not None:
            # Born spilled (worker wrote the return to disk because the
            # store was full of in-flight returns): track the file so
            # delete unlinks it and peers can fetch it.
            entry.spill_path = data.decode()
            # Lift any stale no-recache tombstone (oid reborn via
            # reconstruction): the fd cache may serve it again.
            with self._spill_fd_lock:
                self._spill_dead.discard(oid)
        if embedded:
            entry.embedded = list(embedded)
        if self.multinode:
            # A forwarded task's first published return means the remote
            # run completed — stop tracking it for node-death retry.
            if entry.producing_task is not None:
                self._complete_forwarded(entry.producing_task)
            # Publish to the GCS location directory (inline/error payloads
            # ride in the record itself; shm copies announce this node).
            # Pulled inline copies are already in the GCS — skip re-pub.
            if not (foreign and loc != "shm"):
                try:
                    kind = ("error" if state == FAILED
                            else ("inline" if loc == "inline" else "shm"))
                    if kind == "inline" and not entry.foreign:
                        # Local-owned small value: record the location
                        # only — remote readers fetch the payload from
                        # this node via fetch_object_meta.  Shipping
                        # every local put's bytes to the GCS would
                        # mirror the whole store there.
                        self.gcs.add_location(oid, self.node_id, size,
                                              kind="shm", data=None)
                    else:
                        # Cross-node results (foreign entries) and error
                        # blobs carry their payload in the GCS record so
                        # they survive the producing node's death.
                        self.gcs.add_location(
                            oid, self.node_id if kind == "shm" else None,
                            size, kind=kind,
                            data=data if kind != "shm" else None)
                except Exception:
                    pass
        waiters, entry.waiters = entry.waiters, []
        for wake in waiters:
            wake()
        # Unblock tasks waiting on this object.
        now = time.time()
        for rec in list(self.pending_queue):
            if oid in rec.deps:
                rec.deps.discard(oid)
                if not rec.deps:
                    rec.stages.setdefault("deps_fetched", now)
        for actor in self.actors.values():
            touched = False
            for rec in actor.queue:
                if oid in rec.deps:
                    rec.deps.discard(oid)
                    if not rec.deps:
                        rec.stages.setdefault("deps_fetched", now)
                    touched = True
            if touched:
                self._drain_actor_queue(actor)

    def _h_get_objects(self, ctx: _ConnCtx, m: dict) -> None:
        """Blocking get: reply once every requested object is ready."""
        oids: List[bytes] = m["object_ids"]
        if chaos.armed("get_objects", "evict"):
            # Store-eviction fault: vanish a requested READY object's
            # shm payload (directory entry kept READY) so the reader
            # hits the lineage-reconstruction path.  Eligibility is
            # checked BEFORE fire() so a get of inline/lineage-less
            # objects can't burn the budget (and pollute the fault
            # trace) without evicting anything.
            with self.lock:
                eligible = [o for o in oids if self._chaos_evictable(o)]
            if eligible and chaos.fire("get_objects", "evict"):
                with self.lock:
                    for oid in eligible:
                        if self._chaos_evict_entry(oid):
                            break
        timeout = m.get("timeout")
        deadline = time.time() + timeout if timeout is not None else None
        done = threading.Event()   # reply-once guard
        registered: List[ObjectEntry] = []

        def try_reply(timed_out: bool = False) -> None:
            with self.lock:
                if done.is_set():
                    return
                missing = [o for o in oids if not self._object_ready(o)]
                if missing and not timed_out:
                    return
                done.set()
                _unregister_waiter(registered, try_reply)
                results = {}
                for o in oids:
                    e = self.objects.get(o)
                    if e is None or e.state == PENDING:
                        results[o] = ("missing", None, 0)
                    else:
                        results[o] = (e.loc if e.state == READY else "error",
                                      e.data, e.size)
                ctx.reply(m, {"results": results,
                              "timed_out": bool(missing)})

        with self.lock:
            missing = [o for o in oids if not self._object_ready(o)]
            for o in missing:
                entry = self.objects.get(o)
                if entry is None:
                    entry = ObjectEntry()
                    # get for an unknown object: wait for someone to put it
                    entry.refcount = 0
                    entry.foreign = True
                    self.objects[o] = entry
                entry.waiters.append(try_reply)
                registered.append(entry)
                self._ensure_pull(o)
            if timeout == 0:
                try_reply(timed_out=True)
                return
            if deadline is not None and missing:
                self._add_deadline_waiter(
                    deadline, lambda: try_reply(timed_out=True))
        try_reply()

    def _h_wait(self, ctx: _ConnCtx, m: dict) -> None:
        oids: List[bytes] = m["object_ids"]
        num_returns: int = m["num_returns"]
        timeout = m.get("timeout")
        deadline = time.time() + timeout if timeout is not None else None
        done = threading.Event()
        registered: List[ObjectEntry] = []

        def try_reply(timed_out: bool = False) -> None:
            with self.lock:
                if done.is_set():
                    return
                ready = [o for o in oids if self._object_ready(o)]
                if len(ready) < num_returns and not timed_out:
                    return
                done.set()
                _unregister_waiter(registered, try_reply)
                satisfied = len(ready) >= num_returns
                if satisfied:
                    ready = ready[:num_returns]
                ctx.reply(m, {"ready": ready, "timed_out": not satisfied})

        with self.lock:
            for o in oids:
                if not self._object_ready(o):
                    entry = self.objects.get(o)
                    if entry is None:
                        entry = ObjectEntry()
                        entry.refcount = 0
                        entry.foreign = True
                        self.objects[o] = entry
                    entry.waiters.append(try_reply)
                    registered.append(entry)
                    self._ensure_pull(o)
            if timeout == 0:
                try_reply(timed_out=True)
                return
            if deadline is not None:
                self._add_deadline_waiter(
                    deadline, lambda: try_reply(timed_out=True))
        try_reply()

    def _h_task_started(self, ctx: _ConnCtx, m: dict) -> None:
        """Worker signal: user code for an actor call began executing.
        Until this arrives a dispatched call is still replayable (it
        sat in the worker's queue) — worker death requeues it for free
        instead of burning retry budget or surfacing an error."""
        with self.lock:
            rec = self.tasks.get(m["task_id"])
            if rec is not None:
                rec.started = True
                rec.stages.setdefault("executing", time.time())

    def _h_task_done(self, ctx: _ConnCtx, m: dict) -> None:
        notify_owner: Optional[bytes] = None
        fwd_returns: List[tuple] = []
        prof = m.get("profile")
        if prof is not None:
            prof["node_id"] = self.node_id.hex()
            self._emit_event(prof)
        with self.lock:
            rec = self.tasks.pop(m["task_id"], None)
            if (rec is not None and self.multinode
                    and rec.spec.get("owner_node") not in (None,
                                                           self.node_id)):
                notify_owner = rec.spec["owner_node"]
            w = ctx.worker
            if (m.get("failed") and m.get("app_retryable")
                    and rec is not None and rec.retries_left > 0
                    and not rec.cancelled and rec.actor_id is None):
                # retry_exceptions matched (decided worker-side): the
                # error is NOT registered on the return objects — the
                # task resubmits after backoff, waiters stay parked,
                # and the submitter's embedded holds stay live for the
                # replay.  Returning here also skips forward_done: a
                # forwarded task is only "done" for its owner once a
                # run actually completes.
                self._schedule_retry(
                    rec, "app_error",
                    "application exception matched retry_exceptions")
                if w is not None and w.state == "busy" \
                        and w.actor_id is None:
                    self._release_worker(w)
                self._schedule()
                return
            for oid, loc, data, size, embedded in m["returns"]:
                entry = self.objects.get(oid)
                if entry is not None and entry.deleted:
                    continue
                if rec is not None and rec.cancelled and loc == "error":
                    # Normalize the in-worker KeyboardInterrupt to the
                    # typed cancellation error (reference:
                    # TaskCancelledError on get()).
                    blob = ser.dumps(exc.TaskCancelledError(
                        f"task {rec.spec.get('name')!r} was cancelled"))
                    loc, data, size = "error", blob, len(blob)
                self._register_object(
                    oid, loc, data, size,
                    state=FAILED if loc == "error" else READY,
                    embedded=embedded, creator_pid=ctx.pid,
                    owner=(rec.spec.get("owner")
                           if rec is not None else None))
                if (notify_owner is not None
                        and loc in ("inline", "error")
                        and data is not None):
                    # Piggyback inline/error results on the peer-to-peer
                    # forward_done so the owner registers them without a
                    # GCS location lookup — a forwarded actor call (the
                    # Serve hot path) keeps answering through a full GCS
                    # outage.  shm-sized results still travel via the
                    # location directory + transfer plane.
                    fwd_returns.append((oid, loc, data, size))
                if oid in self._streams:
                    self.finish_stream(oid)   # wake parked consumers
            if rec is not None:
                rec.state = "done"
                self._emit_lifecycle(rec, prof=prof,
                                     failed=m.get("failed", False))
                # Lineage for reconstruction: remember how each return
                # was produced (plain tasks only — actor calls depend on
                # actor state and are not replayable).
                if rec.actor_id is None and not m.get("failed"):
                    for oid in rec.spec["return_ids"]:
                        e = self.objects.get(oid)
                        if e is not None:
                            e.lineage = rec.spec
                # Release the holds the submitter took on arg/embedded
                # refs — EXCEPT for actor creation tasks, whose spec may
                # be replayed on restart (holds released at permanent
                # actor death instead), and EXCEPT for forwarded tasks:
                # the matching increfs live on the OWNER node's entries
                # (released there via forward_done); decref'ing local
                # pulled replicas here would be unbalanced and could
                # free the only copy of an intermediate result.
                foreign_task = rec.spec.get("owner_node") not in (
                    None, self.node_id)
                if not rec.is_actor_creation and not foreign_task:
                    for dep in rec.spec.get("embedded") or []:
                        self._decref(dep)
                if rec.is_actor_creation and rec.actor_id:
                    self._on_actor_created(rec, failed=m.get("failed", False))
                actor = self.actors.get(rec.actor_id) if rec.actor_id else None
                if actor is not None:
                    actor.in_flight.pop(rec.task_id, None)
                    self._maybe_release_actor(actor)
            if w is not None and w.state == "busy" and w.actor_id is None:
                self._release_worker(w)
            elif w is not None and w.actor_id is not None:
                w.current_task = None
            self._schedule()
        if notify_owner is not None:
            self._peer_notify(notify_owner,
                              {"type": "forward_done",
                               "task_id": m["task_id"],
                               "returns": fwd_returns})

    def _peer_notify(self, nid: bytes, msg: dict) -> None:
        """One-way message to a peer, reusing that peer's FIFO sender
        when one exists (no thread churn on the task-done hot path)."""
        q = self._fwd_queues.get(nid)
        if q is not None:
            q.put(("notify", msg, None))
            return

        def _send():
            ninfo = self._node_info(nid)
            if ninfo is None:
                return
            try:
                self._peer_conn_to(ninfo).notify(msg)
            except Exception:
                pass

        threading.Thread(target=_send, daemon=True,
                         name="rtpu-peer-notify").start()

    def _h_worker_blocked(self, ctx: _ConnCtx, m: dict) -> None:
        # A worker blocked in get(): return its CPU to the pool so nested
        # tasks can run (reference: worker blocked-on-get lease release).
        with self.lock:
            w = ctx.worker
            if w is not None and w.state == "busy":
                w.state = "blocked"
                self._release_held(w)
                self._schedule()

    def _h_worker_unblocked(self, ctx: _ConnCtx, m: dict) -> None:
        with self.lock:
            w = ctx.worker
            if w is not None and w.state == "blocked":
                # Overcommit on purpose: the task must finish.
                b = (self.bundles.get(w.bundle_key)
                     if w.bundle_key else None)
                if b is not None:
                    _charge(b.free, w.resources_held)
                else:
                    self._take(w.resources_held, allow_negative=True)
                w.state = "busy"

    def _h_add_ref(self, ctx: _ConnCtx, m: dict) -> None:
        with self.lock:
            e = self.objects.get(m["object_id"])
            if e is not None:
                e.refcount += 1
        if "__req_id__" in m:
            ctx.reply(m, {"ok": True})

    def _h_remove_ref(self, ctx: _ConnCtx, m: dict) -> None:
        with self.lock:
            self._decref(m["object_id"])

    def _delete_object(self, oid: bytes, e: ObjectEntry) -> None:
        """Caller holds self.lock."""
        e.deleted = True
        e.data = None
        self.objects.pop(oid, None)
        self._obj_loc_cache.pop(oid, None)
        self._drop_spill_fd(oid)
        if e.spill_path:
            try:
                os.unlink(e.spill_path)
            except OSError:
                pass
        if oid in self._pulls_inflight:
            self._cancelled_pulls.add(oid)
        if self.multinode and e.foreign and e.loc == "shm":
            # Freed a pulled replica: prune this node from the holder set
            # so peers stop trying to fetch from us (notify — lock-safe).
            try:
                self.gcs.remove_location(oid, self.node_id)
            except Exception:
                pass
        if self.multinode and not e.foreign:
            # Owner-driven global delete: the GCS drops the record and
            # pushes object_deleted to every holder (notify — lock-safe).
            try:
                self.gcs.remove_object(oid)
            except Exception:
                pass
        if e.loc == "shm":
            # Release the creator pin the directory owns, then delete
            # (deferred store-side while readers still hold pins).
            try:
                store = self._store()
                store.release(_OID(oid))
                store.delete(_OID(oid))
            except Exception:
                pass
        # Release refs embedded in this object's payload (may cascade).
        embedded, e.embedded = e.embedded, []
        for dep in embedded:
            self._decref(dep)

    def _decref(self, oid: bytes) -> None:
        """Caller holds self.lock."""
        e = self.objects.get(oid)
        if e is None:
            return
        e.refcount -= 1
        if e.refcount <= 0:
            self._delete_object(oid, e)

    _store_client = None

    def _store(self):
        if NodeService._store_client is None:
            from ray_tpu._private.shm_store import ShmObjectStore
            NodeService._store_client = ShmObjectStore(self.store_path)
        return NodeService._store_client

    # -- GCS passthrough ---------------------------------------------------
    def _h_cancel_task(self, ctx: _ConnCtx, m: dict) -> None:
        """ray_tpu.cancel (reference: ray.cancel / CancelTask RPC):
        pending tasks fail immediately with TaskCancelledError;
        dispatched tasks get SIGINT (KeyboardInterrupt in the worker,
        the reference's in-band cancel) or SIGKILL with force=True.
        Retries never resurrect a cancelled task; actor tasks are
        rejected (only async-actor cancel exists in the reference; our
        actors are in-order queues)."""
        oid = m["object_id"]
        force = m.get("force", False)
        victim = None
        with self.lock:
            rec = None
            e = self.objects.get(oid)
            if e is not None and e.producing_task is not None:
                rec = self.tasks.get(e.producing_task)
            if rec is None:
                for r in list(self.tasks.values()):
                    if oid in r.spec["return_ids"]:
                        rec = r
                        break
            if rec is None or rec.state == "done":
                ctx.reply(m, {"ok": False, "state": "done"})
                return
            if rec.actor_id is not None and not rec.is_actor_creation:
                ctx.reply(m, {"__error__": ValueError(
                    "actor tasks cannot be cancelled")})
                return
            rec.cancelled = True
            rec.retries_left = 0
            if rec.state in ("pending", "retry_backoff"):
                # retry_backoff: the parked resubmission callback
                # checks rec.state and becomes a no-op.
                self._fail_task_returns(rec, exc.TaskCancelledError(
                    f"task {rec.spec.get('name')!r} was cancelled "
                    f"before it started"))
                self._schedule()
                ctx.reply(m, {"ok": True, "state": "pending"})
                return
            victim = rec.worker
        if victim is not None and victim.proc is not None:
            try:
                if force:
                    victim.proc.kill()
                else:
                    import signal
                    os.kill(victim.pid, signal.SIGINT)
            except OSError:
                pass
        ctx.reply(m, {"ok": True, "state": "dispatched"})

    def _gcs_proxy(self, ctx: _ConnCtx, m: dict, fn) -> None:
        """Run a blocking GCS-dependent handler off the conn thread,
        in THIS client's submission order, and reply asynchronously.

        A connection dispatches its client's rpcs serially, and
        GcsClient calls queue through a GCS outage (reconnect with
        backoff, up to gcs_reconnect_max_s): executed inline, one kv
        op during an outage would wedge every later rpc from the same
        client — including task_done from a worker, stalling results
        that never needed the GCS.  Only the CALLER of a GCS-dependent
        op should wait out the outage.  Single-node (embedded state,
        never blocks) stays inline."""
        if not self.multinode:
            try:
                ctx.reply(m, fn())
            except Exception as e:
                ctx.reply(m, {"__error__": e})
            return
        q = ctx.gcs_q
        if q is None:
            q = ctx.gcs_q = queue.Queue()

            def drain(_q=q, _ctx=ctx) -> None:
                while not self._shutdown:
                    try:
                        item = _q.get(timeout=5.0)
                    except queue.Empty:
                        # Reap the drainer once its conn is gone.
                        # Lock-free membership probe: list scans are
                        # GIL-safe and a stale answer only costs one
                        # extra 5s idle loop.
                        if _ctx not in self._conns:  # ray-tpu: noqa[RT010]
                            return
                        continue
                    req, job = item
                    try:
                        out = job()
                    except Exception as e:
                        out = {"__error__": e}
                    try:
                        _ctx.reply(req, out)
                    except Exception:
                        pass

            threading.Thread(target=drain, daemon=True,
                             name="rtpu-gcs-proxy").start()
        q.put((m, fn))

    def _h_kv_put(self, ctx: _ConnCtx, m: dict) -> None:
        self._gcs_proxy(ctx, m, lambda: {"ok": self.gcs.kv_put(
            m["ns"], m["key"], m["value"], m.get("overwrite", True))})

    def _h_kv_get(self, ctx: _ConnCtx, m: dict) -> None:
        self._gcs_proxy(ctx, m, lambda: {
            "value": self.gcs.kv_get(m["ns"], m["key"])})

    def _h_kv_wait(self, ctx: _ConnCtx, m: dict) -> None:
        """Long-poll kv read: parked until the key is put or timeout.
        Replaces 2ms client polling in process collectives (weak-spot
        #4 round 2: >=4ms latency floor per collective op)."""
        from ray_tpu._private.gcs import GlobalControlState
        ns, key = m["ns"], m["key"]
        timeout = m.get("timeout", 60.0)
        if isinstance(self.gcs, GlobalControlState):
            fired = threading.Event()

            def cb(value) -> None:
                if fired.is_set():
                    return
                fired.set()
                try:
                    ctx.reply(m, {"value": value})
                except Exception:
                    pass

            def expire() -> None:
                if fired.is_set():
                    return
                self.gcs.kv_wait_unregister(ns, key, cb_outer)
                cb(None)

            def cb_outer(value) -> None:
                # Mark the parked deadline entry dead so the monitor
                # drops it instead of scanning it for up to `timeout`.
                expire.cancelled = True
                cb(value)

            val = self.gcs.kv_wait_register(ns, key, cb_outer)
            if val is not None:
                ctx.reply(m, {"value": val})
                return

            with self.lock:
                self._add_deadline_waiter(time.time() + timeout, expire)
            return

        # Multinode: park at the GCS service via a side thread (the
        # blocking forward must not stall this connection's dispatch).
        def fwd() -> None:
            try:
                value = self.gcs.kv_wait(ns, key, timeout)
            except Exception:
                value = None
            try:
                ctx.reply(m, {"value": value})
            except Exception:
                pass

        threading.Thread(target=fwd, daemon=True,
                         name="rtpu-kv-wait").start()

    def _request_worker_stacks(self, workers: List[WorkerHandle],
                               timeout: float, cb,
                               samples: int = 0,
                               interval_s: float = 0.02) -> None:
        """Ask `workers` for stack captures; `cb(stacks, folded)` fires
        exactly once — when every reply landed or at `timeout`
        (whatever arrived by then).  One-shot mode returns formatted
        per-pid stacks; sampling mode (samples>0) additionally merges
        folded-stack counts (flamegraph input).  Shared by the
        stack_dump RPC and the stall sentinel's targeted captures."""
        token = os.urandom(8)
        rec = {"stacks": {}, "folded": {}, "pending": set(),
               "cb": cb, "done": False}
        with self.lock:
            for w in workers:
                if w.conn_send is None or w.state == "dead":
                    continue
                msg: Dict[str, Any] = {"type": "dump_stacks",
                                       "token": token}
                if samples:
                    msg["samples"] = int(samples)
                    msg["interval_s"] = float(interval_s)
                try:
                    w.conn_send(msg)
                    rec["pending"].add(w.pid)
                except Exception:
                    pass
            if rec["pending"]:
                self._stack_dumps[token] = rec

                def expire() -> None:
                    with self.lock:
                        r = self._stack_dumps.pop(token, None)
                        if r is None or r["done"]:
                            return
                        r["done"] = True
                    try:
                        cb(r["stacks"], r["folded"])
                    except Exception:
                        pass

                self._add_deadline_waiter(time.time() + timeout, expire)
                return
        try:
            cb({}, {})
        except Exception:
            pass

    def _task_workers_locked(self, task_id_hex: str
                             ) -> List[WorkerHandle]:
        """The worker(s) currently running tasks whose id matches the
        hex prefix (actor calls resolve through the actor's resident
        worker).  Caller holds self.lock."""
        out = []
        for rec in self.tasks.values():
            if not rec.task_id.hex().startswith(task_id_hex):
                continue
            w = rec.worker
            if w is None and rec.actor_id is not None:
                a = self.actors.get(rec.actor_id)
                w = a.worker if a is not None else None
            if w is not None and w.state != "dead":
                out.append(w)
        return out

    def _h_stack_dump(self, ctx: _ConnCtx, m: dict) -> None:
        """On-demand stack profiling (reference: the dashboard
        reporter's py-spy role).  Scopes:
        * default: every live worker on this node;
        * task_id (hex prefix): only the worker(s) executing that task;
        * cluster=True (multinode): fan out to every alive peer and
          merge — the documented "every live worker" behavior.
        samples>0 turns one-shot dumps into low-rate sampling (N
        samples, interval_s apart, per worker) whose merged
        folded-stack counts come back under "folded" (flamegraphs)."""
        timeout = m.get("timeout", 10.0)
        samples = int(m.get("samples") or 0)
        interval_s = float(m.get("interval_s") or 0.02)
        task_id = m.get("task_id")
        want_cluster = bool(m.get("cluster")) and self.multinode
        with self.lock:
            if task_id:
                workers = self._task_workers_locked(task_id)
            else:
                workers = [w for w in self.workers.values()
                           if w.conn_send is not None
                           and w.state != "dead"]
        # Sampling keeps workers capturing for samples*interval — give
        # replies room beyond the nominal timeout.
        wait_s = timeout + (samples * interval_s if samples else 0.0)
        merged = {"stacks": {}, "folded": {}}
        merge_lock = threading.Lock()
        remaining = [2 if want_cluster else 1]

        def merge_part(stacks: dict, folded: dict) -> None:
            with merge_lock:
                merged["stacks"].update(stacks)
                for k, v in folded.items():
                    merged["folded"][k] = merged["folded"].get(k, 0) + v
                remaining[0] -= 1
                if remaining[0] > 0:
                    return
            reply = {"stacks": merged["stacks"]}
            if samples:
                reply["folded"] = merged["folded"]
            ctx.reply(m, reply)

        if want_cluster:
            def fanout() -> None:
                sub: Dict[str, Any] = {"type": "stack_dump",
                                       "cluster": False,
                                       "timeout": timeout}
                if task_id:
                    sub["task_id"] = task_id
                if samples:
                    sub["samples"] = samples
                    sub["interval_s"] = interval_s
                replies, _ = self._fanout_peers(sub,
                                                timeout=wait_s + 5.0)
                stacks: Dict[str, str] = {}
                folded: Dict[str, int] = {}
                for n, rep in replies:
                    # Namespace remote pids: across hosts they collide.
                    tag = n["node_id"].hex()[:12]
                    for pid, text in (rep.get("stacks") or {}).items():
                        stacks[f"{pid}@{tag}"] = text
                    for k, v in (rep.get("folded") or {}).items():
                        folded[k] = folded.get(k, 0) + v
                merge_part(stacks, folded)

            threading.Thread(target=fanout, daemon=True,
                             name="rtpu-stack-fanout").start()

        self._request_worker_stacks(workers, wait_s, merge_part,
                                    samples=samples,
                                    interval_s=interval_s)

    def _h_stacks_reply(self, ctx: _ConnCtx, m: dict) -> None:
        with self.lock:
            rec = self._stack_dumps.get(m["token"])
            if rec is None or rec["done"]:
                return
            if m.get("text"):
                rec["stacks"][m["pid"]] = m["text"]
            for k, v in (m.get("folded") or {}).items():
                rec["folded"][k] = rec["folded"].get(k, 0) + v
            rec["pending"].discard(m["pid"])
            if rec["pending"]:
                return
            rec["done"] = True
            self._stack_dumps.pop(m["token"], None)
        try:
            rec["cb"](rec["stacks"], rec["folded"])
        except Exception:
            pass

    def _h_kv_del(self, ctx: _ConnCtx, m: dict) -> None:
        self._gcs_proxy(ctx, m, lambda: {
            "ok": self.gcs.kv_del(m["ns"], m["key"])})

    def _h_kv_keys(self, ctx: _ConnCtx, m: dict) -> None:
        self._gcs_proxy(ctx, m, lambda: {
            "keys": self.gcs.kv_keys(m["ns"], m.get("prefix", b""))})

    def _h_fn_register(self, ctx: _ConnCtx, m: dict) -> None:
        def job():
            self.gcs.register_function(m["function_id"], m["blob"])
            return {"ok": True}
        self._gcs_proxy(ctx, m, job)

    def _h_fn_fetch(self, ctx: _ConnCtx, m: dict) -> None:
        self._gcs_proxy(ctx, m, lambda: {
            "blob": self.gcs.fetch_function(m["function_id"])})

    # -- actors ------------------------------------------------------------
    def _h_create_actor(self, ctx: _ConnCtx, m: dict) -> None:
        spec = m["spec"]
        actor_id = spec["actor_id"]
        pgspec = spec.get("pg")
        if pgspec is not None:
            key = (pgspec["id"], pgspec["bundle"])
            with self.lock:
                bundle_here = key in self.bundles
            if not bundle_here:
                # Await PG readiness + route to the bundle's node on a
                # side thread (never block this conn's dispatch loop).
                threading.Thread(target=self._create_actor_with_pg,
                                 args=(ctx, m), daemon=True,
                                 name="rtpu-pg-actor").start()
                return
        aff = spec.get("affinity")
        if (pgspec is None and aff is not None
                and aff["node_id"] != self.node_id):
            ninfo = (self._cluster_node(aff["node_id"])
                     if self.multinode else None)
            if ninfo is None or (aff.get("soft")
                                 and ninfo.get("state") != "alive"):
                if not aff.get("soft"):
                    ctx.reply(m, {"__error__": exc.NodeAffinityError(
                        f"affinity node {aff['node_id'].hex()[:12]} is "
                        f"not alive (soft=False)")})
                    return
                # Soft affinity to a dead/unknown/DRAINING node: fall
                # back to normal placement (spill targets included) —
                # same semantics as the task path clearing rec
                # affinity.  An actor placed on a departing node would
                # need an immediate second migration.
                spec = dict(spec)
                spec["affinity"] = None
                aff = None
        if self.multinode and pgspec is None:
            # Placement: keep the actor local when this node's totals can
            # ever run it; otherwise forward the whole creation to a peer
            # that can (reference: GCS actor scheduling picks a node).
            res = spec.get("resources") or {}
            with self.lock:
                local_ok = (self._local_totals_satisfy(res)
                            if aff is None
                            or aff["node_id"] == self.node_id
                            or self._cluster_node(aff["node_id"]) is None
                            else False)
                if self.draining and local_ok and (
                        aff is None or aff["node_id"] != self.node_id):
                    # Draining: a brand-new actor would outlive the
                    # node only via a second migration — place it on a
                    # healthy peer up front (the actor-migration phase
                    # only covers actors that exist when it runs).
                    # Hard affinity HERE still creates locally and
                    # rides the grace.
                    local_ok = False
            if not local_ok:
                if aff is not None:
                    target = self._cluster_node(aff["node_id"])
                    if (target is not None
                            and target["node_id"] == self.node_id):
                        # Pinned HERE but can't run yet: wait as pending
                        # like the task path — self-forwarding would
                        # recurse into our own create_actor forever.
                        target = None
                else:
                    target = (self._pick_spill_target(res,
                                                      need_avail=True)
                              or self._pick_spill_target(
                                  res, need_avail=False))
                if target is not None:
                    self._actor_homes[actor_id] = target["node_id"]
                    # Track the creation like any forwarded task so this
                    # node's embedded arg holds are released when the
                    # remote creation completes (forward_done) or its
                    # node dies — otherwise the constructor args leak
                    # here forever.
                    spec = dict(spec)
                    spec["creation_task"] = dict(spec["creation_task"])
                    spec["creation_task"]["owner_node"] = self.node_id
                    crec = TaskRecord(spec["creation_task"])
                    with self.lock:
                        self.forwarded[crec.task_id] = (crec,
                                                        target["node_id"])
                    try:
                        conn = self._peer_conn_to(target)
                        conn.call({"type": "create_actor", "spec": spec},
                                  timeout=30.0)
                        ctx.reply(m, {"ok": True})
                    except Exception as e:
                        self._actor_homes.pop(actor_id, None)
                        with self.lock:
                            self.forwarded.pop(crec.task_id, None)
                        ctx.reply(m, {"__error__": e})
                    return
        # Name reservation happens OUTSIDE the state lock: in multinode
        # mode this is a blocking RPC to the GCS process, and blocking
        # gcs.call() under self.lock can deadlock against GCS pushes.
        if spec.get("name") and (spec.get("pg") is not None
                or self._autoscaler_live()
                or self._infeasible_reason(spec.get("resources")) is None):
            ns = spec.get("namespace", "default")
            ok = self.gcs.register_named_actor(ns, spec["name"], actor_id)
            if not ok and self.gcs.lookup_named_actor(
                    ns, spec["name"]) == actor_id:
                # The SAME actor re-registering its own name: a drain
                # migration replays the creation spec on a new node
                # while the GCS registration survives — idempotent.
                ok = True
            if not ok:
                ctx.reply(m, {"__error__": ValueError(
                    f"actor name {spec['name']!r} already taken")})
                return
        with self.lock:
            # Same autoscaler gating as the task path: a live autoscaler
            # may provision the resource, so the actor waits as demand.
            reason = (None if spec.get("pg") is not None
                      or self._autoscaler_live()
                      else self._infeasible_reason(spec.get("resources")))
            if reason is not None:
                actor = ActorRecord(actor_id, spec)
                self.actors[actor_id] = actor
                rec = TaskRecord(spec["creation_task"])
                self.tasks[rec.task_id] = rec
                for oid in rec.spec["return_ids"]:
                    self.objects.setdefault(oid, ObjectEntry())
                self._fail_task_returns(rec, exc.InfeasibleResourceError(
                    f"actor {spec.get('name') or actor_id.hex()} is "
                    f"infeasible: {reason}"))
                # _fail_task_returns skips embedded decrefs for creation
                # tasks (restart replay); this actor will never restart —
                # _mark_actor_dead releases the holds and drops any
                # reserved name (idempotent for unnamed actors).
                self._mark_actor_dead(actor, f"infeasible: {reason}",
                                      teardown_worker=False)
                ctx.reply(m, {"ok": True})
                return
            actor = ActorRecord(actor_id, spec)
            self.actors[actor_id] = actor
            rec = TaskRecord(spec["creation_task"])
            self.tasks[rec.task_id] = rec
            for oid in rec.spec["return_ids"]:
                e = self.objects.setdefault(oid, ObjectEntry())
                e.producing_task = rec.task_id
            rec.deps = {d for d in rec.deps if not self._object_ready(d)}
            if rec.had_deps and not rec.deps:
                rec.stages.setdefault("deps_fetched", time.time())
            for d in rec.deps:
                self._ensure_pull(d)
            if rec.deps and self.multinode:
                rec.stages.setdefault("pull_wait", time.time())
            self.pending_queue.append(rec)
            self._schedule()
        if self.multinode:
            try:
                self.gcs.set_actor_node(actor_id, self.node_id)
            except Exception:
                pass
        ctx.reply(m, {"ok": True})

    def _on_actor_created(self, rec: TaskRecord, failed: bool) -> None:
        """Caller holds self.lock."""
        actor = self.actors.get(rec.actor_id)
        if actor is None:
            return
        if actor.state == "dead":
            # kill() raced creation: do not resurrect — tear the worker
            # down instead of letting a killed actor serve calls.
            if rec.worker is not None:
                self._teardown_worker(rec.worker)
            return
        if failed:
            # Worker death runs through _handle_worker_death (it owns
            # retry/requeue bookkeeping a plain teardown skips).
            self._mark_actor_dead(actor, "creation task failed",
                                  teardown_worker=False)
            if actor.worker is not None:
                self._handle_worker_death(actor.worker, "creation failed",
                                          actor_already_handled=True)
            return
        actor.state = "alive"
        actor.worker = rec.worker
        if rec.worker is not None:
            rec.worker.actor_id = actor.actor_id
            rec.worker.current_task = None
        self._drain_actor_queue(actor)
        # A handle-GC release that arrived during creation waited for
        # this moment (releasing earlier would have dropped the
        # creation args before the constructor ran).
        self._maybe_release_actor(actor)

    def _enqueue_actor_task(self, rec: TaskRecord) -> None:
        """Caller holds self.lock."""
        actor = self.actors.get(rec.actor_id)
        if actor is None and self.multinode:
            # A call routed here on a stale home hint after the actor
            # migrated off this (draining) node: redirect to its new
            # home instead of failing.  Foreign-owned calls hand BACK
            # to their owner (re-forwarding onward would re-own them
            # to this exiting node, and the owner's node-death sweep
            # would fail or double-run a call executing fine at the
            # new home — same rule as _drain_migrate_one).
            home = self._migrated_actors.get(rec.actor_id)
            ninfo = self._cluster_node(home) if home else None
            if ninfo is not None and ninfo.get("state") == "alive":
                owner = rec.spec.get("owner_node")
                if owner not in (None, self.node_id) \
                        and self._cluster_node(owner) is not None:
                    self.tasks.pop(rec.task_id, None)
                    rec.state = "handed_back"
                    self._peer_notify(owner, {"type": "drain_handback",
                                              "spec": rec.spec,
                                              "from": self.node_id})
                else:
                    self._forward_task(rec, ninfo)
                return
        if actor is None or actor.state == "dead":
            reason = actor.death_reason if actor else "unknown actor"
            self._fail_task_returns(rec, exc.ActorDiedError(
                rec.actor_id.hex(), reason, task_started=False))
            return
        actor.queue.append(rec)
        self._drain_actor_queue(actor)

    def _drain_actor_queue(self, actor: ActorRecord) -> None:
        if actor.state != "alive" or actor.worker is None:
            return
        if actor.hold_queue:
            # Node drain is migrating this actor: no new dispatch —
            # queued calls forward to the new home once in-flight ones
            # finish (node_drain._drain_migrate_one).
            return
        # Head-of-line blocking on unmet deps preserves the sync-actor
        # strict submission-order guarantee (a later no-dep call must not
        # overtake an earlier call waiting on its argument).
        while actor.queue and not actor.queue[0].deps:
            rec = actor.queue.popleft()
            rec.state = "dispatched"
            now = time.time()
            if rec.had_deps:
                rec.stages.setdefault("deps_fetched", now)
            rec.stages["worker_assigned"] = now
            # Fresh attempt (restart replays reuse the rec): re-arm
            # the stall sentinel, drop the stale executing checkpoint.
            rec.stall_reported = False
            rec.stages.pop("executing", None)
            actor.in_flight[rec.task_id] = rec
            actor.worker.conn_send({"type": "execute_task",
                                    "spec": rec.spec})
            self._chaos_kill_dispatch(actor.worker)

    def _release_actor_holds(self, actor: ActorRecord) -> None:
        """Release the creation-task embedded ref holds exactly once, at
        permanent actor death (they must outlive restarts: the creation
        spec and its arg blob are replayed)."""
        if actor.holds_released:
            return
        actor.holds_released = True
        for dep in actor.spec["creation_task"].get("embedded") or []:
            self._decref(dep)

    def _fail_actor_queue(self, actor: ActorRecord) -> None:
        # task_started distinguishes queued (never ran — safe for a
        # caller to retry elsewhere, e.g. Serve failover) from
        # in-flight calls (a retry could double side effects).
        while actor.queue:
            self._fail_task_returns(
                actor.queue.popleft(),
                exc.ActorDiedError(actor.actor_id.hex(),
                                   actor.death_reason,
                                   task_started=False))
        for rec in list(actor.in_flight.values()):
            self._fail_task_returns(
                rec, exc.ActorDiedError(actor.actor_id.hex(),
                                        actor.death_reason,
                                        task_started=rec.started))
        actor.in_flight.clear()

    def _h_actor_release_scope(self, ctx: _ConnCtx, m: dict) -> None:
        """Driver GC: the last in-scope handle to a non-detached,
        unnamed actor was collected.  The actor dies once its queued
        and in-flight work drains (reference: actor handle reference
        counting — out-of-scope actors terminate after pending tasks
        complete)."""
        with self.lock:
            actor = self.actors.get(m["actor_id"])
        if actor is None and self.multinode:
            # The actor lives on its home node: one-way forward (the
            # handler never replies, so a call would park a dispatch
            # thread until timeout).
            home = self._actor_homes.get(m["actor_id"])
            if home is None:
                try:
                    home = self.gcs.get_actor_node(m["actor_id"])
                except Exception:
                    home = None
            if home is not None and home != self.node_id:
                self._peer_notify(home, {
                    "type": "actor_release_scope",
                    "actor_id": m["actor_id"]})
            return
        with self.lock:
            actor = self.actors.get(m["actor_id"])
            if actor is None or actor.state == "dead":
                return
            actor.release_on_drain = True
            actor.restarts_left = 0
            self._maybe_release_actor(actor)

    def _mark_actor_dead(self, actor: ActorRecord, reason: str,
                         teardown_worker: bool = True) -> None:
        """Caller holds the lock: THE actor-death bookkeeping sequence
        (state flip, name drop, hold release, queue failure, worker
        teardown) — every death path funnels here so the steps can
        never diverge by cause of death."""
        actor.state = "dead"
        actor.death_reason = reason
        try:
            self.gcs.drop_named_actor(actor.actor_id)
        except Exception:
            # Best-effort cleanup: at shutdown the GCS connection may
            # already be closed when a worker disconnect lands here.
            pass
        self._release_actor_holds(actor)
        self._fail_actor_queue(actor)
        if teardown_worker and actor.worker is not None:
            self._teardown_worker(actor.worker)

    def _maybe_release_actor(self, actor: ActorRecord) -> None:
        """Caller holds the lock: tear the actor down if its release
        was requested and no work remains.  Only a LIVE actor is
        eligible — a pending/restarting actor's creation task rides
        the node's pending_queue (not actor.in_flight), and releasing
        then would decref the creation args before the constructor
        ever ran; _on_actor_created re-checks once alive."""
        if not actor.release_on_drain or actor.state != "alive":
            return
        if actor.in_flight or actor.queue:
            return
        self._mark_actor_dead(actor, "all handles out of scope")

    def _h_actor_exiting(self, ctx: _ConnCtx, m: dict) -> None:
        """Worker announces an INTENTIONAL exit (ray_tpu.exit_actor())
        before its process dies: zero the restart budget so the
        imminent worker death is permanent, and record the reason so
        callers see 'exited' rather than a crash (reference:
        ray.actor.exit_actor semantics)."""
        with self.lock:
            actor = self.actors.get(m["actor_id"])
            if actor is not None and actor.state != "dead":
                actor.restarts_left = 0
                actor.intentional_exit = True
                actor.death_reason = "exited via exit_actor()"

    def _h_kill_actor(self, ctx: _ConnCtx, m: dict) -> None:
        with self.lock:
            actor = self.actors.get(m["actor_id"])
        if actor is None and self.multinode:
            fwd = self._forward_actor_rpc(m["actor_id"], {
                "type": "kill_actor", "actor_id": m["actor_id"],
                "no_restart": m.get("no_restart", True)})
            if fwd is not None:
                if m.get("no_restart", True):
                    # A restartable kill leaves the actor alive on its
                    # home node — no tombstone.
                    with self.lock:
                        self._remote_actor_tombstones[m["actor_id"]] = \
                            "killed via kill()"
                ctx.reply(m, fwd)
                return
        with self.lock:
            actor = self.actors.get(m["actor_id"])
            if actor is None:
                ctx.reply(m, {"ok": False})
                return
            if m.get("no_restart", True):
                actor.restarts_left = 0
            self._mark_actor_dead(actor, "killed via kill()")
        ctx.reply(m, {"ok": True})

    def _forward_actor_rpc(self, actor_id: bytes,
                           msg: dict) -> Optional[dict]:
        """Call an actor RPC on the actor's home node; None if the home
        is unknown/unreachable.  Never called under self.lock."""
        home = self._actor_homes.get(actor_id)
        if home is None:
            try:
                home = self.gcs.get_actor_node(actor_id)
            except Exception:
                home = None
        if home is None or home == self.node_id:
            return None
        ninfo = self._node_info(home)
        if ninfo is None:
            return None
        try:
            conn = self._peer_conn_to(ninfo)
            return conn.call(dict(msg), timeout=30.0)
        except Exception:
            return None

    def _h_actor_state(self, ctx: _ConnCtx, m: dict) -> None:
        with self.lock:
            a = self.actors.get(m["actor_id"])
            if a is not None:
                ctx.reply(m, {"state": a.state, "reason": a.death_reason})
                return
            tomb = self._remote_actor_tombstones.get(m["actor_id"])
        if tomb is not None:
            ctx.reply(m, {"state": "dead", "reason": tomb})
            return
        if self.multinode:
            fwd = self._forward_actor_rpc(m["actor_id"], {
                "type": "actor_state", "actor_id": m["actor_id"]})
            if fwd is not None:
                ctx.reply(m, {"state": fwd["state"],
                              "reason": fwd["reason"]})
                return
        ctx.reply(m, {"state": "unknown", "reason": ""})

    def _h_lookup_named_actor(self, ctx: _ConnCtx, m: dict) -> None:
        def job():
            aid = self.gcs.lookup_named_actor(m["namespace"], m["name"])
            spec = None
            with self.lock:
                if aid is not None and aid in self.actors:
                    spec = {k: v for k, v in self.actors[aid].spec.items()
                            if k != "creation_task"}
            if spec is None and aid is not None and self.multinode:
                fwd = self._forward_actor_rpc(aid, {"type": "actor_spec",
                                                    "actor_id": aid})
                if fwd is not None:
                    spec = fwd.get("spec")
            return {"actor_id": aid, "spec": spec}
        self._gcs_proxy(ctx, m, job)

    def _h_list_named_actors(self, ctx: _ConnCtx, m: dict) -> None:
        self._gcs_proxy(ctx, m, lambda: {
            "names": self.gcs.list_named_actors(m.get("namespace"))})

    # -- cluster info ------------------------------------------------------
    def _h_cluster_resources(self, ctx: _ConnCtx, m: dict) -> None:
        if self.multinode:
            try:
                # Bounded: a conn thread serves every rpc from its
                # client serially — a GCS outage must degrade this to
                # the cached cluster view, not park the connection
                # (and everything queued behind it) for the wait.
                self._cluster_view = self.gcs.nodes(max_wait_s=2.0)
            except Exception:
                pass
            total: Dict[str, float] = {}
            avail: Dict[str, float] = {}
            with self.lock:
                mine_t = dict(self.resources_total)
                mine_a = dict(self.resources_avail)
            for n in self._cluster_view:
                src_t = (mine_t if n["node_id"] == self.node_id
                         else n["resources_total"])
                src_a = (mine_a if n["node_id"] == self.node_id
                         else n["resources_avail"])
                for k, v in src_t.items():
                    total[k] = total.get(k, 0.0) + v
                for k, v in src_a.items():
                    avail[k] = avail.get(k, 0.0) + v
            ctx.reply(m, {"total": total, "available": avail,
                          "nodes": self._cluster_view})
            return
        with self.lock:
            ctx.reply(m, {"total": dict(self.resources_total),
                          "available": dict(self.resources_avail)})

    def _h_store_stats(self, ctx: _ConnCtx, m: dict) -> None:
        ctx.reply(m, {"stats": self._store().stats()})

    def _h_node_info(self, ctx: _ConnCtx, m: dict) -> None:
        ctx.reply(m, {"node_id": self.node_id,
                      "session_dir": self.session_dir,
                      "multinode": self.multinode,
                      "gcs_address": self.gcs_address,
                      "host": getattr(self, "host", "127.0.0.1"),
                      "control_port": self.control_port})

    # ------------------------------------------------------------------
    # observability: state dump + metrics (reference: util/state/api.py,
    # _private/metrics_agent.py)
    # ------------------------------------------------------------------
    def _actor_pinned_oids_locked(self) -> set:
        """Objects a live actor on this node holds: creation-spec
        embedded refs (held across restarts) plus the arg/embedded refs
        of its queued and in-flight calls.  Feeds the pinned_by_actor
        reference kind of the memory plane.  Caller holds self.lock."""
        pinned: set = set()
        for a in self.actors.values():
            if a.state == "dead":
                continue
            ct = a.spec.get("creation_task") or {}
            pinned.update(ct.get("embedded") or [])
            for rec in list(a.queue) + list(a.in_flight.values()):
                for arg in rec.spec.get("args") or []:
                    if arg and arg[0] == "ref":
                        pinned.add(arg[1])
                pinned.update(rec.spec.get("embedded") or [])
        return pinned

    def _memory_kind_bytes_locked(self) -> Dict[str, Dict[str, float]]:
        """Per-reference-kind {bytes, count} over this node's READY
        object directory — the ray_tpu_object_store_bytes{kind} gauge
        source.  Cached for a few seconds: the walk is O(objects +
        actor queues) under the lock, and scrapes arrive on a clock.
        Caller holds self.lock."""
        ts, cached = self._mem_kind_cache
        now = time.time()
        if now - ts < 5.0:
            return cached
        pinned = self._actor_pinned_oids_locked()
        out: Dict[str, Dict[str, float]] = {}
        for oid, e in self.objects.items():
            if e.state != READY:
                continue
            kind = _reference_kind(e, oid in pinned)
            cell = out.setdefault(kind, {"bytes": 0.0, "count": 0.0})
            cell["bytes"] += float(e.size or 0)
            cell["count"] += 1.0
        self._mem_kind_cache = (now, out)
        return out

    def _local_state_dump(self) -> dict:
        """Snapshot of this node's runtime state.  Caller must NOT hold
        the lock."""
        with self.lock:
            tasks = []
            for rec in self.tasks.values():
                tasks.append({
                    "task_id": rec.task_id.hex(),
                    "name": rec.spec.get("name", ""),
                    "state": rec.state,
                    "actor_id": (rec.actor_id.hex()
                                 if rec.actor_id else None),
                    "is_actor_creation": rec.is_actor_creation,
                    "retries_left": rec.retries_left,
                    "pid": rec.worker.pid if rec.worker else None,
                    "node_id": self.node_id.hex(),
                })
            actors = []
            for a in self.actors.values():
                actors.append({
                    "actor_id": a.actor_id.hex(),
                    "name": a.name,
                    "namespace": a.namespace,
                    "class_name": (a.spec.get("class_name")
                                   or a.spec.get("creation_task", {})
                                   .get("name", "").removesuffix(
                                       ".__init__")),
                    "state": a.state,
                    "pid": a.worker.pid if a.worker else None,
                    "restarts_left": a.restarts_left,
                    "detached": a.detached,
                    "queued": len(a.queue),
                    "in_flight": len(a.in_flight),
                    "death_reason": a.death_reason,
                    "node_id": self.node_id.hex(),
                })
            workers = []
            for w in self.workers.values():
                workers.append({
                    "worker_id": w.worker_id.hex(),
                    "pid": w.pid,
                    "state": w.state,
                    "tpu": w.tpu,
                    "task": (w.current_task.spec.get("name")
                             if w.current_task else None),
                    "actor_id": (w.actor_id.hex()
                                 if w.actor_id else None),
                    "node_id": self.node_id.hex(),
                })
            objects = []
            pinned = self._actor_pinned_oids_locked()
            now = time.time()
            my_hex = self.node_id.hex()
            for oid, e in self.objects.items():
                kind = _reference_kind(e, oid in pinned)
                objects.append({
                    "object_id": oid.hex(),
                    "state": ("failed" if e.state == FAILED else
                              "ready" if e.state == READY else "pending"),
                    "loc": e.loc,
                    "size": e.size,
                    "size_bytes": e.size,
                    "refcount": e.refcount,
                    "foreign": e.foreign,
                    "reference_kind": kind,
                    "owner": e.owner.hex() if e.owner else None,
                    "age_s": round(now - e.created_ts, 3),
                    "created_ts": e.created_ts,
                    # Local view; the cluster merge in _h_state_dump
                    # rebuilds this across every node's copies.
                    "holder_nodes": ([my_hex] if e.state == READY
                                     and e.loc in ("inline", "shm",
                                                   "spilled") else []),
                    "has_lineage": e.lineage is not None,
                    "node_id": my_hex,
                })
            # Live client ids (driver + workers): memory_summary uses
            # this to flag owned objects whose owner process is gone.
            clients = {w.worker_id.hex() for w in self.workers.values()
                       if w.state != "dead"}
            for c in self._conns:
                if c.client_id is not None:
                    clients.add(c.client_id.hex())
            pgs = []
            for pgid, pg in self.pgs.items():
                pgs.append({
                    "pg_id": pgid.hex(),
                    "name": pg.get("name"),
                    "strategy": pg.get("strategy"),
                    "state": pg.get("state"),
                    "bundles": pg.get("bundles"),
                    "node_id": self.node_id.hex(),
                })
            pending = len(self.pending_queue)
            sched = self._sched_summary_locked()
        store = self._store().stats()
        return {"tasks": tasks, "actors": actors, "workers": workers,
                "objects": objects, "placement_groups": pgs,
                "clients": sorted(clients),
                "node_id": self.node_id.hex(),
                "pending_tasks": pending,
                "store": store,
                "stores": {self.node_id.hex(): store},
                "dag_channel_items": {
                    self.node_id.hex(): dict(self._dag_items)},
                "scheduling": {
                    self.node_id.hex(): sched}}

    def _fanout_peers(self, request: dict, timeout: float = 2.0
                      ) -> Tuple[List[Tuple[dict, dict]], List[str]]:
        """Issue one RPC to every alive peer IN PARALLEL; returns
        ([(node_info, reply)...], [unreachable node id hexes]).  Serial
        per-peer timeouts would stack past the caller's deadline on big
        clusters."""
        from concurrent.futures import ThreadPoolExecutor

        # Draining nodes are still reachable and still hold state worth
        # observing (their tasks/objects appear in dumps until they go).
        peers = [n for n in self._cluster_view
                 if n["node_id"] != self.node_id
                 and n.get("state") in ("alive", "draining")]
        if not peers:
            return [], []
        results: List[Tuple[dict, dict]] = []
        unreachable: List[str] = []

        def one(n):
            try:
                conn = self._peer_conn_to(n)
                return n, conn.call(dict(request), timeout=timeout)
            except Exception:
                return n, None

        with ThreadPoolExecutor(max_workers=min(8, len(peers))) as ex:
            for n, reply in ex.map(one, peers):
                if reply is None:
                    unreachable.append(n["node_id"].hex())
                else:
                    results.append((n, reply))
        return results, unreachable

    def _h_state_dump(self, ctx: _ConnCtx, m: dict) -> None:
        dump = self._local_state_dump()
        if m.get("cluster") and self.multinode:
            merged = {k: list(dump[k]) for k in
                      ("tasks", "actors", "workers", "objects",
                       "placement_groups")}
            replies, unreachable = self._fanout_peers(
                {"type": "state_dump", "cluster": False})
            clients = set(dump.get("clients") or [])
            stores = dict(dump.get("stores") or {})
            dag_items = dict(dump.get("dag_channel_items") or {})
            scheduling = dict(dump.get("scheduling") or {})
            for _, peer in replies:
                for k in merged:
                    merged[k].extend(peer["dump"].get(k, []))
                clients.update(peer["dump"].get("clients") or [])
                stores.update(peer["dump"].get("stores") or {})
                dag_items.update(
                    peer["dump"].get("dag_channel_items") or {})
                scheduling.update(
                    peer["dump"].get("scheduling") or {})
            # Holder sets are a cluster-level fact: rebuild them from
            # every node's local copies so list_objects/memory_summary
            # show where each object's replicas actually live.
            holders: Dict[str, set] = {}
            for row in merged["objects"]:
                for h in row.get("holder_nodes") or []:
                    holders.setdefault(row["object_id"], set()).add(h)
            for row in merged["objects"]:
                row["holder_nodes"] = sorted(
                    holders.get(row["object_id"], ()))
            merged["nodes"] = list(self._cluster_view)
            # Partial snapshots must say so — silently missing nodes
            # send operators debugging the wrong thing.
            merged["unreachable_nodes"] = unreachable
            merged["node_id"] = dump["node_id"]
            merged["pending_tasks"] = dump["pending_tasks"]
            merged["store"] = dump["store"]
            merged["stores"] = stores
            merged["clients"] = sorted(clients)
            merged["dag_channel_items"] = dag_items
            merged["scheduling"] = scheduling
            ctx.reply(m, {"dump": merged})
            return
        ctx.reply(m, {"dump": dump})

    # ------------------------------------------------------------------
    # metrics history ring + doctor probe (control-plane observability)
    # ------------------------------------------------------------------
    def _history_sample_tick(self) -> None:
        """Monitor-loop job: append one (ts, value) sample per tracked
        series to the bounded history rings (counters sample their
        running total, gauges their last value, histograms their
        observation count) plus a few runtime built-ins — the data
        behind state.metric_history() / /api/metrics/history /
        `ray_tpu top`."""
        now = time.time()
        res_s = max(config.metrics_history_resolution_s, 0.05)
        cap = max(int(config.metrics_history_window_s / res_s), 2)
        max_series = config.metrics_history_max_series
        try:
            store_used = float(
                self._store().stats().get("used_bytes", 0))
        except Exception:
            store_used = 0.0
        with self._rpc_lock:
            rpc_counts = [(m, float(st["count"]), float(st["inflight"]))
                          for m, st in self._rpc_stats.items()]
        with self.lock:
            rows = []
            for key, s in self._metrics.items():
                if s["kind"] == "histogram":
                    rows.append((key, float(s.get("count") or 0.0)))
                else:
                    rows.append((key, float(s.get("value") or 0.0)))
            from ray_tpu.util.metrics import (RPC_INFLIGHT_METRIC,
                                              RPC_SERVER_SECONDS_METRIC)
            for method, count, inflight in rpc_counts:
                mt = (("method", method),)
                rows.append(((RPC_SERVER_SECONDS_METRIC, "histogram",
                              mt), count))
                rows.append(((RPC_INFLIGHT_METRIC, "gauge", mt),
                             inflight))
            rows.extend((
                (("ray_tpu_tasks_pending", "gauge", ()),
                 float(len(self.pending_queue))),
                (("ray_tpu_tasks_total", "gauge", ()),
                 float(len(self.tasks))),
                (("ray_tpu_actors_alive", "gauge", ()),
                 float(sum(1 for a in self.actors.values()
                           if a.state == "alive"))),
                (("ray_tpu_workers", "gauge", ()),
                 float(len(self.workers))),
                (("ray_tpu_objects_local", "gauge", ()),
                 float(len(self.objects))),
                (("ray_tpu_object_store_bytes_used", "gauge", ()),
                 store_used),
            ))
            hist = self._metrics_history
            for key, val in rows:
                ring = hist.get(key)
                if ring is None:
                    if len(hist) >= max_series:
                        continue   # cardinality cap: drop new series
                    ring = deque(maxlen=cap)
                    hist[key] = ring
                elif ring.maxlen != cap:
                    # Window/resolution knobs changed at runtime:
                    # re-bound the ring, keeping the newest samples.
                    ring = deque(ring, maxlen=cap)
                    hist[key] = ring
                ring.append((now, val))

    def _h_metric_history(self, ctx: _ConnCtx, m: dict) -> None:
        """Per-series history samples, optionally cluster-merged (each
        row carries its node_id — the merge is a concat, not a sum)."""
        name = m.get("name") or None
        with self.lock:
            series = []
            for (n, kind, tags), ring in self._metrics_history.items():
                if name and n != name:
                    continue
                series.append({
                    "name": n, "kind": kind, "tags": dict(tags),
                    "node_id": self.node_id.hex(),
                    "samples": [[round(ts, 3), v] for ts, v in ring]})
        if m.get("cluster") and self.multinode:
            replies, unreachable = self._fanout_peers(
                {"type": "metric_history", "name": name,
                 "cluster": False})
            for _, peer in replies:
                series.extend(peer.get("series") or [])
            ctx.reply(m, {"series": series,
                          "unreachable_nodes": unreachable})
            return
        ctx.reply(m, {"series": series, "unreachable_nodes": []})

    def _h_health_probe(self, ctx: _ConnCtx, m: dict) -> None:
        """Doctor's per-node health card: GCS liveness age, GCS status
        card, event-ring drops, slow-RPC tallies, scheduler outcome
        counts — fanned out cluster-wide for state.doctor()."""
        from ray_tpu.util.metrics import EVENTS_DROPPED_METRIC
        now = time.time()
        with self.lock:
            cell = self._metrics.get(
                (EVENTS_DROPPED_METRIC, "counter", ()))
            info = {
                "node_id": self.node_id.hex(),
                "multinode": self.multinode,
                "gcs_last_ok_age_s": round(now - self._gcs_last_ok, 3),
                "gcs_status": dict(self._gcs_status or {}),
                "events_dropped": float(cell["value"]) if cell else 0.0,
                "pending_tasks": len(self.pending_queue),
                "workers": len(self.workers),
                "draining": bool(self.draining),
                "sched_outcomes": dict(self._sched_outcomes),
            }
        with self._rpc_lock:
            info["slow_rpcs"] = {meth: st["slow"]
                                 for meth, st in self._rpc_stats.items()
                                 if st["slow"]}
        if m.get("cluster") and self.multinode:
            replies, unreachable = self._fanout_peers(
                {"type": "health_probe", "cluster": False})
            nodes = [info] + [r.get("info") for _, r in replies
                              if r.get("info")]
            ctx.reply(m, {"info": info, "nodes": nodes,
                          "unreachable_nodes": unreachable})
            return
        ctx.reply(m, {"info": info, "nodes": [info],
                      "unreachable_nodes": []})

    # ------------------------------------------------------------------
    # task-lifecycle tracing (reference: task events + state-API task
    # summaries; chrome-trace via ray.timeline)
    # ------------------------------------------------------------------
    def _emit_event(self, ev: dict) -> None:
        """Append one event to the bounded per-node ring, counting the
        eviction the append forces when the ring is full — a silently
        rolling ring hides lifecycle history from summarize_tasks()
        and the timeline.  Safe with or without self.lock held (RLock)."""
        from ray_tpu.util.metrics import EVENTS_DROPPED_METRIC
        with self.lock:
            if (self._events.maxlen is not None
                    and len(self._events) >= self._events.maxlen):
                self._inc_counter(
                    EVENTS_DROPPED_METRIC, {},
                    "lifecycle/profile events evicted from the "
                    "bounded per-node event ring")
            self._events.append(ev)

    def _emit_lifecycle(self, rec: TaskRecord, prof: Optional[dict],
                        failed: bool) -> None:
        """Record the task's stage-transition record into the event
        ring and fold stage durations into the per-stage histograms.
        Caller holds self.lock."""
        from ray_tpu._private import tracing
        st = dict(rec.stages)
        now = time.time()
        if prof is not None:
            st.setdefault("executing", prof["start"])
            st["finished"] = prof["end"]
        else:
            st.setdefault("finished", now)
        base = rec.spec.get("name") or "<task>"
        tc = rec.spec.get("trace_ctx") or {}
        # Actor dispatch never sets rec.worker (the call rides the
        # actor's resident worker) — resolve the pid through the actor
        # record so the timeline row matches the execute span's.
        pid = rec.worker.pid if rec.worker else 0
        if not pid and rec.actor_id is not None:
            actor = self.actors.get(rec.actor_id)
            if actor is not None and actor.worker is not None:
                pid = actor.worker.pid
        ev = {
            "kind": "lifecycle",
            # ":lifecycle" suffix keeps the record distinct from the
            # worker's execute span of the same task name.
            "name": base + ":lifecycle",
            "task_name": base,
            "task_id": rec.task_id.hex(),
            "trace_id": tracing.task_trace_id(rec.spec),
            "span_id": tracing.lifecycle_span_id(rec.task_id),
            "parent_span_id": tc.get("parent_span_id"),
            "start": st.get("submitted", now),
            "end": st["finished"],
            "stages": st,
            "failed": failed,
            "actor": rec.actor_id is not None,
            "pid": pid,
            "node_id": self.node_id.hex(),
        }
        self._emit_event(ev)
        self._observe_stage_metrics(st)

    def _observe_stage_metrics(self, stages: Dict[str, float]) -> None:
        """Fold one task's stage durations into the auto-registered
        per-stage histograms (ray_tpu_task_stage_duration_seconds,
        declared in util/metrics.py) so a Prometheus scrape exposes
        scheduling delay and queue wait without any user code.  Merged
        directly into the node's aggregate table — same cell layout as
        _h_metrics_push.  Caller holds self.lock."""
        from ray_tpu._private.tracing import stage_durations
        from ray_tpu.util.metrics import (TASK_STAGE_BUCKETS,
                                          TASK_STAGE_METRIC)
        for stage, dur in stage_durations(stages).items():
            self._observe_hist(TASK_STAGE_METRIC, {"stage": stage},
                               dur, TASK_STAGE_BUCKETS,
                               "task lifecycle stage duration")

    def _observe_hist(self, name: str, tags: Dict[str, str],
                      value: float, buckets, description: str = ""
                      ) -> None:
        """Fold one observation into a node-side auto-registered
        histogram cell (same table as _h_metrics_push).  Prefills every
        boundary (like Histogram._new_cell) so each scrape exposes a
        stable, uniform bucket set.  Caller holds self.lock."""
        key = (name, "histogram", tuple(sorted(tags.items())))
        cur = self._metrics.get(key)
        if cur is None:
            cur = {"name": name, "kind": "histogram",
                   "tags": dict(tags), "value": 0.0,
                   "buckets": {str(b): 0 for b in buckets},
                   "sum": 0.0, "count": 0.0,
                   "description": description}
            self._metrics[key] = cur
        for b in buckets:
            if value <= b:
                k = str(b)
                cur["buckets"][k] = cur["buckets"].get(k, 0) + 1
                break
        cur["sum"] += value
        cur["count"] += 1

    def _inc_counter(self, name: str, tags: Dict[str, str],
                     description: str = "",
                     value: float = 1.0) -> None:
        """Bump a node-side auto-registered counter cell (same table
        the stage histograms land in).  Caller holds self.lock."""
        key = (name, "counter", tuple(sorted(tags.items())))
        cur = self._metrics.get(key)
        if cur is None:
            cur = {"name": name, "kind": "counter", "tags": dict(tags),
                   "value": 0.0, "buckets": {}, "sum": 0.0,
                   "count": 0.0, "description": description}
            self._metrics[key] = cur
        cur["value"] += value

    # ------------------------------------------------------------------
    # retry scheduling: exponential backoff with jitter
    # (reference role: task resubmit backoff; the jitter stream is
    # seeded alongside the chaos RNG so a chaos schedule replays)
    # ------------------------------------------------------------------
    def _retry_delay_s(self, rec: TaskRecord) -> float:
        base = max(config.task_retry_delay_ms, 0) / 1000.0
        cap = max(config.task_retry_max_delay_ms, 0) / 1000.0
        attempt = max(rec.spec.get("retries", 0) - rec.retries_left, 1)
        delay = min(cap, base * (2 ** (attempt - 1)))
        # Full-ish jitter in [0.5x, 1x]: staggers a thundering herd of
        # simultaneous retries without ever *extending* the cap.
        return delay * (0.5 + 0.5 * chaos.jitter())

    def _schedule_retry(self, rec: TaskRecord, reason_tag: str,
                        reason: str) -> None:
        """Re-run `rec` after an exponential-backoff delay.  Decrements
        the retry budget, emits the retry lifecycle event + counter,
        and parks the resubmission on the monitor's deadline list.
        Caller holds self.lock and has already verified
        rec.retries_left > 0."""
        rec.retries_left -= 1
        rec.state = "retry_backoff"
        rec.worker = None
        rec.locality_deadline = None
        rec.spec.pop("spilled", None)
        self.tasks[rec.task_id] = rec
        delay = self._retry_delay_s(rec)
        now = time.time()
        self._emit_retry(rec, reason_tag, reason, delay)

        def fire() -> None:
            with self.lock:
                if rec.state != "retry_backoff" or self._shutdown:
                    return      # cancelled / failed during backoff
                rec.state = "pending"
                rec.stages["queued"] = time.time()
                self.pending_queue.append(rec)
                self._schedule()

        self._add_deadline_waiter(now + delay, fire)

    def _requeue_as_reconstruction(self, rec: TaskRecord,
                                   reason: str) -> bool:
        """Re-run a forwarded plain task lost to a node death under the
        object-reconstruction budget.  Caller holds self.lock; returns
        False when the budget is spent (caller fails the returns)."""
        if rec.is_actor_creation or rec.cancelled:
            return False
        entries = []
        for oid in rec.spec["return_ids"]:
            e = self.objects.setdefault(oid, ObjectEntry())
            if e.reconstructions >= config.max_object_reconstructions:
                return False
            entries.append((oid, e))
        for oid, e in entries:
            e.reconstructions += 1
            e.state = PENDING
            e.loc = None
            e.data = None
            e.producing_task = rec.task_id
        rec.state = "pending"
        rec.worker = None
        rec.spec.pop("spilled", None)
        rec.deps = {a[1] for a in rec.spec["args"] if a[0] == "ref"
                    and not self._object_ready(a[1])}
        for d in rec.deps:
            self._ensure_pull(d)
        self.tasks[rec.task_id] = rec
        self.pending_queue.append(rec)
        self._emit_retry(rec, "node_death",
                         f"reconstructing results lost with node: "
                         f"{reason}", 0.0)
        return True

    def _emit_retry(self, rec: TaskRecord, reason_tag: str,
                    reason: str, delay_s: float) -> None:
        """Retry observability, shared by every retry path: the
        counter cell plus one lifecycle event carrying the backoff
        delay and reason.  Caller holds self.lock and has already
        decremented the budget."""
        from ray_tpu.util.metrics import TASK_RETRIES_METRIC
        self._inc_counter(
            TASK_RETRIES_METRIC, {"reason": reason_tag},
            "task retries, by failure reason")
        now = time.time()
        self._emit_event({
            "kind": "retry",
            "name": (rec.spec.get("name") or "<task>") + ":retry",
            "task_id": rec.task_id.hex(),
            "reason": reason,
            "reason_tag": reason_tag,
            "delay_s": delay_s,
            "attempt": rec.spec.get("retries", 0) - rec.retries_left,
            "start": now, "end": now,
            "pid": 0,
            "node_id": self.node_id.hex(),
        })

    # ------------------------------------------------------------------
    # scheduling
    # ------------------------------------------------------------------
    def _take(self, res: Dict[str, float], allow_negative: bool = False) -> bool:
        for k, v in res.items():
            if not allow_negative and self.resources_avail.get(k, 0.0) < v - 1e-9:
                return False
        for k, v in res.items():
            self.resources_avail[k] = self.resources_avail.get(k, 0.0) - v
        return True

    def _give_back(self, res: Dict[str, float]) -> None:
        for k, v in res.items():
            self.resources_avail[k] = self.resources_avail.get(k, 0.0) + v

    def _schedule_reap(self, w: WorkerHandle) -> None:
        """Reclaim a dead worker's shm pins (read pins + unadopted
        creator pins) — but only once its PROCESS is actually gone:
        reaping a live process (connection lost, SIGTERM still in
        flight) would release pins it is still using.  Caller holds the
        lock."""
        if w.proc is not None and w.proc.poll() is None:
            self._pending_reaps.append((w, time.time() + 2.0))
            return
        self._reap_exited(w)

    def _reap_exited(self, w: WorkerHandle) -> bool:
        """`w`'s process is gone: reclaim its shm pins and repay its
        chip lease — not before, because libtpu gives a chip to one
        process at a time and a successor spawned while the old holder
        was still exiting could not claim it.  True if chips came back
        (a queued TPU task may now be runnable).  Caller holds the
        lock."""
        if w.pid:
            try:
                self._store().reap_client(w.pid)
            except Exception:
                pass
        return self._chip_alloc.release(w.worker_id)

    def _teardown_worker(self, w: WorkerHandle) -> None:
        """Forcibly stop a worker (kill_actor / kill-race paths).
        Caller holds the lock."""
        if w.state == "dead":
            return
        w.state = "dead"
        self._release_held(w)
        w.resources_held = {}
        w.bundle_key = None
        if w.conn_send:
            try:
                w.conn_send({"type": "exit"})
            except Exception:
                pass
        if w.proc is not None:
            w.proc.terminate()
        self.workers.pop(w.worker_id, None)
        self._schedule_reap(w)

    def _release_worker(self, w: WorkerHandle) -> None:
        self._release_held(w)
        w.resources_held = {}
        w.bundle_key = None
        w.current_task = None
        w.state = "idle"
        w.last_idle_time = time.time()

    def _cluster_node(self, nid: bytes) -> Optional[dict]:
        """_cluster_view lookup WITHOUT any GCS round-trip (lock-safe)."""
        for n in self._cluster_view:
            if n["node_id"] == nid:
                return n
        return None

    def _sched_note(self, rec: TaskRecord, outcome: str,
                    **detail) -> None:
        """Record one scheduler placement decision: outcome counter,
        placement-latency histogram (terminal outcomes), the bounded
        recent-decision ring behind state.summarize_scheduling(), and
        the rate-limited `sched.decide` span accumulator.  Caller
        holds self.lock.  Non-terminal outcomes (queue /
        drain_handback) count once per queue episode, not once per
        scheduling pass — _schedule revisits the queue on every
        resource change."""
        from ray_tpu.util.metrics import (SCHED_DECISIONS_METRIC,
                                          SCHED_PLACEMENT_BUCKETS,
                                          SCHED_PLACEMENT_SECONDS_METRIC)
        terminal = outcome in ("local", "forward", "spill",
                               "infeasible")
        if not terminal:
            if rec.task_id in self._sched_noted:
                return
            if len(self._sched_noted) > 100_000:
                # Cancelled-while-queued strays: intersect with live
                # tasks instead of growing forever.
                self._sched_noted &= set(self.tasks)
            self._sched_noted.add(rec.task_id)
        else:
            self._sched_noted.discard(rec.task_id)
        self._inc_counter(SCHED_DECISIONS_METRIC, {"outcome": outcome},
                          "scheduler placement decisions by outcome")
        self._sched_outcomes[outcome] = \
            self._sched_outcomes.get(outcome, 0) + 1
        if outcome in ("local", "forward", "spill"):
            t0 = rec.stages.get("submitted")
            if t0 is not None:
                self._observe_hist(
                    SCHED_PLACEMENT_SECONDS_METRIC,
                    {"outcome": outcome}, time.time() - t0,
                    SCHED_PLACEMENT_BUCKETS,
                    "task submit->placement latency by outcome")
        row = {"task": rec.spec.get("name") or "<task>",
               "task_id": rec.task_id.hex()[:16],
               "outcome": outcome, "ts": time.time()}
        row.update(detail)
        self._sched_recent.append(row)
        if not self._sched_span:
            self._sched_span_t0 = time.time()
        self._sched_span[outcome] = \
            self._sched_span.get(outcome, 0) + 1

    def _flush_sched_span_locked(self) -> None:
        """Emit the accumulated decision counts as ONE sampled
        `sched.decide` timeline span, at most once per
        sched_span_min_interval_s (per-decision spans would be the
        PR-8 hot-path trap at 10k placements/s).  Caller holds
        self.lock."""
        if not self._sched_span:
            return
        now = time.time()
        min_iv = config.sched_span_min_interval_s
        if min_iv > 0 and now < self._next_sched_span:
            return
        self._next_sched_span = now + max(min_iv, 0.0)
        counts, self._sched_span = self._sched_span, {}
        self._emit_event({
            "kind": "sched",
            "name": "sched.decide",
            "outcomes": counts,
            "decisions": sum(counts.values()),
            "pid": os.getpid(),
            "start": self._sched_span_t0 or now, "end": now,
            "node_id": self.node_id.hex(),
        })

    def _sched_summary_locked(self) -> dict:
        """This node's scheduler-decision summary (cumulative outcome
        counts + the recent-decision ring).  Caller holds self.lock."""
        return {"outcomes": dict(self._sched_outcomes),
                "pending": len(self.pending_queue),
                "recent": list(self._sched_recent)}

    def _schedule(self) -> None:
        """Dispatch every runnable pending task. Caller holds self.lock."""
        if self._shutdown:
            return
        progressed = True
        while progressed:
            progressed = False
            for rec in list(self.pending_queue):
                if rec.deps:
                    continue
                if (self.draining and self.multinode
                        and rec.actor_id is None
                        and not rec.is_actor_creation
                        and rec.spec.get("pg") is None
                        and not rec.drain_keep):
                    # Draining: no new leases for movable work — the
                    # handback sweep (node_drain) forwards it to a
                    # healthy peer or marks it drain_keep when nothing
                    # can take it (then it runs here within the grace).
                    self._sched_note(rec, "drain_handback")
                    continue
                res = dict(rec.spec.get("resources") or {})
                chips = chips_for(res)
                if 0 < chips < self._chip_alloc.num_chips \
                        and not self._chip_alloc.leasable(chips):
                    # Fits this host's chips but is no sub-slice libtpu
                    # can carve (a larger request is left to the
                    # resource check: another node may hold it).
                    self.pending_queue.remove(rec)
                    self.tasks.pop(rec.task_id, None)
                    self._sched_note(rec, "infeasible",
                                     reason="chip_lease_shape")
                    self._fail_task_returns(rec, ValueError(
                        f"{rec.spec.get('name')!r} asks for TPU={chips} "
                        f"on a {self._chip_alloc.num_chips}-chip host: "
                        f"one worker can lease 1 chip, an aligned pair, "
                        f"or the whole host"))
                    progressed = True
                    continue
                aff = rec.spec.get("affinity")
                if aff is not None and aff["node_id"] != self.node_id:
                    # Node affinity: route to the pinned node; hard
                    # affinity to a dead node fails, soft falls back
                    # (reference: NodeAffinitySchedulingStrategy).
                    # A DRAINING target counts as gone for SOFT
                    # affinity (chasing it would ping-pong with its
                    # handback sweep); hard pins still forward — the
                    # node can run the task within its drain grace.
                    ninfo = (self._cluster_node(aff["node_id"])
                             if self.multinode else None)
                    if ninfo is not None and (
                            ninfo.get("state") == "alive"
                            or not aff.get("soft")):
                        self._forward_task(rec, ninfo)
                        self._sched_note(
                            rec, "forward", reason="affinity",
                            target=ninfo["node_id"].hex()[:12])
                        progressed = True
                        continue
                    if aff.get("soft"):
                        rec.spec["affinity"] = None
                    else:
                        self.pending_queue.remove(rec)
                        self.tasks.pop(rec.task_id, None)
                        self._sched_note(
                            rec, "infeasible", reason="affinity_dead",
                            target=aff["node_id"].hex()[:12])
                        self._fail_task_returns(
                            rec, exc.NodeAffinityError(
                                f"affinity node "
                                f"{aff['node_id'].hex()[:12]} is not "
                                f"alive (soft=False)"))
                        progressed = True
                        continue
                pg = rec.spec.get("pg")
                bundle = None
                key = None
                if pg is not None:
                    key = (pg["id"], pg["bundle"])
                    bundle = self.bundles.get(key)
                    if bundle is None:
                        # Not our bundle: route to its home node (known
                        # once the PG committed); wait while pending.
                        target = self._pg_bundle_node(pg)
                        if (self.multinode and target is not None
                                and target != self.node_id):
                            ninfo = self._cluster_node(target)
                            if ninfo is not None:
                                self._forward_task(rec, ninfo)
                                self._sched_note(
                                    rec, "forward", reason="pg_home",
                                    target=target.hex()[:12])
                                progressed = True
                        continue
                    if not _fits(bundle.free, res):
                        # bundle busy: wait for a pg task end
                        self._sched_note(rec, "queue",
                                         reason="pg_bundle_busy")
                        continue
                    _charge(bundle.free, res)
                elif not self._take(res):
                    # Affinity-pinned work must wait here, not spill.
                    # Streaming generators also stay local: their item
                    # stream lives in THIS node's table, and a peer
                    # executing the task would yield into the wrong one.
                    if (self.multinode
                            and rec.spec.get("affinity") is None
                            and not rec.spec.get("streaming")
                            and self._try_spill(rec, res)):
                        progressed = True
                    else:
                        self._sched_note(rec, "queue",
                                         reason="resources_busy")
                    continue
                from ray_tpu._private.container import image_of
                image = image_of(rec.spec.get("runtime_env"))
                w = self._find_idle_worker(tpu=chips, image=image)
                if w is None:
                    if bundle is not None:
                        _uncharge(bundle.free, res)
                    else:
                        self._give_back(res)
                    self._maybe_spawn(tpu=chips, image=image)
                    self._sched_note(rec, "queue",
                                     reason="no_idle_worker")
                    continue
                self.pending_queue.remove(rec)
                rec.state = "dispatched"
                now = time.time()
                if rec.had_deps:
                    rec.stages.setdefault("deps_fetched", now)
                rec.stages["worker_assigned"] = now
                # Fresh execution attempt: re-arm the stall sentinel
                # and drop the dead attempt's executing checkpoint
                # (task_started's setdefault could never refresh it).
                rec.stall_reported = False
                rec.stages.pop("executing", None)
                rec.worker = w
                w.state = "busy"
                w.current_task = rec
                w.resources_held = res
                w.bundle_key = key if bundle is not None else None
                w.conn_send({"type": "execute_task", "spec": rec.spec})
                self._sched_note(rec, "local", worker_pid=w.pid)
                self._chaos_kill_dispatch(w)
                progressed = True
        self._flush_sched_span_locked()

    def _chaos_kill_dispatch(self, w: WorkerHandle) -> None:
        """Chaos kind=kill_worker at site 'dispatch': SIGKILL the worker
        a task was just handed to — the monitor's death sweep then
        drives the crash-retry path.  No-op unless a chaos schedule
        arms it."""
        if not chaos.fire("dispatch", "kill_worker"):
            return
        try:
            if w.proc is not None:
                w.proc.kill()
        except Exception:
            pass

    def _release_held(self, w: WorkerHandle) -> None:
        """Return a worker's held resources to their source pool: the pg
        bundle they came from if it still exists, else the node pool.
        Caller holds self.lock."""
        b = self.bundles.get(w.bundle_key) if w.bundle_key else None
        if b is not None:
            _uncharge(b.free, w.resources_held)
        else:
            self._give_back(w.resources_held)

    def _find_idle_worker(self, tpu: int,
                          image: Optional[str] = None
                          ) -> Optional[WorkerHandle]:
        """An idle pooled worker holding exactly `tpu` chips (0 = a CPU
        worker).  Caller holds self.lock."""
        for w in self.workers.values():
            if (w.state == "idle" and w.tpu == tpu
                    and w.actor_id is None and w.image == image):
                return w
        return None

    def _maybe_spawn(self, tpu: int,
                     image: Optional[str] = None) -> None:
        """Caller holds self.lock."""
        from ray_tpu._private.container import image_of
        starting = sum(1 for w in self.workers.values()
                       if w.state == "starting" and w.tpu == tpu
                       and w.image == image)
        if self._spawn_failures >= self._spawn_failure_limit:
            return
        demand = sum(
            1 for r in self.pending_queue
            if not r.deps
            and chips_for(r.spec.get("resources")) == tpu
            and image_of(r.spec.get("runtime_env")) == image
        ) or 1
        # The cap bounds the POOL: workers a task can still be handed
        # to, start-ups included.  A worker bound to an actor has left
        # the pool for the actor's lifetime and is bounded by what the
        # actor holds (reference: the raylet's soft limit counts pooled
        # workers, not actor processes) — counted here, _max_workers
        # zero-CPU actors would starve every later task and actor on
        # this node for ever.
        pooled = sum(1 for w in self.workers.values()
                     if w.state != "dead" and w.actor_id is None)
        want = min(demand - starting, self._max_workers - pooled)
        for _ in range(max(want, 0)):
            self._spawn_worker(tpu, image=image)

    def _spawn_worker(self, tpu: int,
                      image: Optional[str] = None
                      ) -> Optional[WorkerHandle]:
        """Spawn a worker process leasing `tpu` chips (0 = a CPU
        worker); None if it could not start.  Caller holds self.lock."""
        worker_id = os.urandom(16)
        env = dict(os.environ)
        if tpu:
            # One process per chip: the worker gets exactly the chips
            # its task asked for, or is not started until they are
            # free (the lease is repaid when its holder's process has
            # exited, which re-runs the scheduler).  Idle pooled
            # workers holding some other lease are asked to leave so
            # this one can be granted.
            chips = self._chip_alloc.acquire(worker_id, tpu)
            if chips is None:
                for w in list(self.workers.values()):
                    if w.state == "idle" and w.tpu and w.actor_id is None:
                        self._teardown_worker(w)
                return None
            for k, v in self._chip_alloc.visible_env(chips).items():
                if v is None:
                    env.pop(k, None)
                else:
                    env[k] = v
            use_compile_cache(env)
        self._next_worker_seq += 1
        env["RAY_TPU_WORKER_ID"] = worker_id.hex()
        env["RAY_TPU_NODE_SOCKET"] = self.socket_path
        env["RAY_TPU_STORE_PATH"] = self.store_path
        env["RAY_TPU_SESSION_DIR"] = self.session_dir
        # Workers inherit the driver's import environment: the ray_tpu
        # package location plus every driver sys.path entry (so functions
        # pickled by reference from driver-importable modules resolve —
        # the local-cluster behavior the reference gets from its default
        # working_dir runtime env).
        import sys as _sys
        import ray_tpu
        pkg_parent = os.path.dirname(os.path.dirname(
            os.path.abspath(ray_tpu.__file__)))
        existing = env.get("PYTHONPATH", "").split(os.pathsep)
        extra = [pkg_parent] + [p for p in _sys.path
                                if p and os.path.isdir(p)]
        merged = []
        for p in extra + [e for e in existing if e]:
            if p not in merged:
                merged.append(p)
        env["PYTHONPATH"] = os.pathsep.join(merged)
        # Only a worker that leased chips may touch them, and it must
        # not quietly compute on the CPU when it cannot claim them: with
        # the platform pinned, jax fails at start-up instead.
        env["JAX_PLATFORMS"] = "tpu" if tpu else "cpu"
        # Capture worker output into a per-worker log file; the tailer
        # thread forwards appended lines to the driver console when
        # config.log_to_driver (reference: worker logs under
        # session/logs/worker-*.out + log monitor tailing).
        log_path = os.path.join(
            self._log_dir,
            f"worker-{self._next_worker_seq:04d}-{worker_id.hex()[:8]}.log")
        log_f = open(log_path, "ab", buffering=0)
        if image is not None:
            # Containerized worker (runtime_env image_uri): same worker
            # program inside the image, session/state paths mounted
            # (reference: _private/runtime_env/image_uri.py).
            from ray_tpu._private import container
            argv = container.build_worker_argv(
                image, env,
                mounts=[self.session_dir,
                        os.path.dirname(self.socket_path),
                        os.path.dirname(self.store_path)])
        else:
            argv = [sys.executable, "-m", "ray_tpu._private.worker_main"]
        try:
            try:
                proc = subprocess.Popen(
                    argv, env=env, cwd=os.getcwd(),
                    stdout=log_f, stderr=subprocess.STDOUT)
            except OSError as e:
                # Missing container runtime / bad binary: count it
                # against the spawn circuit breaker instead of blowing
                # up the scheduling pass (and every background caller
                # of _schedule) with FileNotFoundError.
                self._spawn_failures += 1
                log_f.write(
                    f"worker spawn failed: {e} (argv[0]={argv[0]})\n"
                    .encode())
                if tpu:
                    self._chip_alloc.release(worker_id)
                return None
        finally:
            log_f.close()
        w = WorkerHandle(worker_id, proc, tpu, image=image)
        self.workers[worker_id] = w
        return w

    def _log_tail_loop(self) -> None:
        """Forward new worker-log lines to this process's stderr with a
        `(worker pid=N)` prefix — the driver console on a head node."""
        import glob as _glob
        while not self._shutdown:
            time.sleep(0.25)
            try:
                for path in _glob.glob(os.path.join(self._log_dir,
                                                    "worker-*.log")):
                    off = self._log_offsets.get(path, 0)
                    try:
                        size = os.path.getsize(path)
                    except OSError:
                        continue
                    if size <= off:
                        continue
                    with open(path, "rb") as f:
                        f.seek(off)
                        chunk = f.read(size - off)
                    # Only forward complete lines; carry the remainder.
                    cut = chunk.rfind(b"\n")
                    if cut < 0:
                        continue
                    self._log_offsets[path] = off + cut + 1
                    tag = os.path.basename(path)[:-4]
                    for line in chunk[:cut].splitlines():
                        try:
                            sys.stderr.write(
                                f"({tag}) "
                                f"{line.decode(errors='replace')}\n")
                        except Exception:
                            pass
            except Exception:
                pass

    def _handle_worker_death(self, w: WorkerHandle, reason: str,
                             actor_already_handled: bool = False,
                             oom: bool = False) -> None:
        """Caller holds self.lock."""
        if w.state == "dead":
            return
        if w.state == "starting":
            self._spawn_failures += 1
            if self._spawn_failures >= self._spawn_failure_limit:
                err = exc.WorkerCrashedError(
                    f"{self._spawn_failures} consecutive workers died "
                    f"before registering (last: {reason}); worker "
                    "environment is broken — failing pending tasks")
                for rec in list(self.pending_queue):
                    self._fail_task_returns(rec, err)
                self.pending_queue.clear()
        if w.state == "busy":
            # ("blocked" workers already returned their resources when
            # they blocked — giving back again would double-credit.)
            self._release_held(w)
        w.state = "dead"
        self.workers.pop(w.worker_id, None)
        self._schedule_reap(w)
        rec = w.current_task
        if rec is not None and rec.state == "dispatched":
            if rec.retries_left > 0 and not rec.is_actor_creation \
                    and not rec.cancelled:
                self._schedule_retry(rec, "worker_crash", reason)
            else:
                err_cls = (exc.TaskCancelledError if rec.cancelled
                           else exc.OutOfMemoryError if oom
                           else exc.WorkerCrashedError)
                self._fail_task_returns(
                    rec, err_cls(
                        f"worker died while running "
                        f"{rec.spec.get('name')}: {reason}"))
                if rec.is_actor_creation and rec.actor_id is not None:
                    # A crash during __init__ must not strand the actor
                    # in 'pending' (method calls would hang forever) —
                    # restart or declare it dead.
                    actor = self.actors.get(rec.actor_id)
                    if actor is not None and actor.state != "dead":
                        self._on_actor_worker_death(
                            actor, f"worker died during creation: {reason}")
        if w.actor_id is not None and not actor_already_handled:
            actor = self.actors.get(w.actor_id)
            if actor is not None and actor.state != "dead":
                self._on_actor_worker_death(actor, reason)

    def _on_actor_worker_death(self, actor: ActorRecord, reason: str) -> None:
        """Caller holds self.lock."""
        # Fail or retry in-flight calls; restart if budget remains.  An
        # exit announced via exit_actor() keeps its intentional reason.
        if actor.intentional_exit:
            reason = actor.death_reason
        will_restart = (actor.restarts_left != 0
                        and not actor.intentional_exit)
        retried: List[TaskRecord] = []
        for rec in list(actor.in_flight.values()):
            if rec.cancelled:
                # Unreachable today (_h_cancel_task rejects actor
                # tasks) but load-bearing if cancellation ever extends
                # to them: a cancelled call must surface as cancelled,
                # never as a retryable/transient failure.
                self._fail_task_returns(rec, exc.TaskCancelledError(
                    f"task {rec.spec.get('name')!r} was cancelled"))
            elif will_restart and not rec.started:
                # Never began executing (sat in the dead worker's
                # queue): requeue for FREE — nothing ran, so nothing
                # can double, and no retry budget is owed.
                rec.state = "pending"
                rec.worker = None
                retried.append(rec)
            elif will_restart and rec.retries_left > 0:
                # max_task_retries: a STARTED call rides the restart —
                # back onto the head of the actor queue, re-dispatched
                # once the replacement worker is alive.
                rec.retries_left -= 1
                rec.state = "pending"
                rec.worker = None
                rec.started = False
                retried.append(rec)
                # delay 0: the resubmission is gated on the restart
                # itself, not a timer.
                self._emit_retry(rec, "actor_restart",
                                 f"actor restarting: {reason}", 0.0)
            elif will_restart:
                # The actor comes back but this started call's budget
                # is spent: typed TRANSIENT error (task_started=True —
                # a re-route could double its side effects; callers
                # decide).
                self._fail_task_returns(rec, exc.ActorUnavailableError(
                    actor.actor_id.hex(),
                    f"restarting after: {reason}",
                    task_started=True))
            else:
                self._fail_task_returns(rec, exc.ActorDiedError(
                    actor.actor_id.hex(), reason,
                    task_started=rec.started))
        actor.in_flight.clear()
        # Retried calls precede everything already queued, in their
        # original dispatch order.
        for rec in reversed(retried):
            actor.queue.appendleft(rec)
        actor.worker = None
        if actor.restarts_left != 0:
            if actor.restarts_left > 0:
                actor.restarts_left -= 1
            actor.state = "restarting"
            from ray_tpu.util.metrics import ACTOR_RESTARTS_METRIC
            self._inc_counter(ACTOR_RESTARTS_METRIC, {},
                              "actor restarts after worker death")
            creation = dict(actor.spec["creation_task"])
            creation["task_id"] = os.urandom(16)
            # Fresh return object for the restart's creation result.
            creation["return_ids"] = [os.urandom(16)]
            rec = TaskRecord(creation)
            # Init args produced before the first creation are READY now;
            # without pruning, stale deps would block the restart forever.
            rec.deps = {d for d in rec.deps if not self._object_ready(d)}
            if rec.had_deps and not rec.deps:
                rec.stages.setdefault("deps_fetched", time.time())
            self.tasks[rec.task_id] = rec
            for oid in creation["return_ids"]:
                e = self.objects.setdefault(oid, ObjectEntry())
                e.producing_task = rec.task_id
            self.pending_queue.append(rec)
            self._schedule()
        else:
            # Worker is already gone on this path (actor.worker was
            # cleared above); no teardown to do.
            self._mark_actor_dead(actor, reason,
                                  teardown_worker=False)

    def _fail_task_returns(self, rec: TaskRecord, error: Exception) -> None:
        """Caller holds self.lock."""
        blob = ser.dumps(error)
        rec.state = "done"
        self._emit_lifecycle(rec, prof=None, failed=True)
        self.tasks.pop(rec.task_id, None)
        try:
            self.pending_queue.remove(rec)
        except ValueError:
            pass
        for oid in rec.spec["return_ids"]:
            self._register_object(oid, "error", blob, len(blob),
                                  state=FAILED)
            if oid in self._streams:
                self.finish_stream(oid)   # wake parked consumers
        foreign_task = rec.spec.get("owner_node") not in (None,
                                                          self.node_id)
        if not rec.is_actor_creation and not foreign_task:
            for dep in rec.spec.get("embedded") or []:
                self._decref(dep)

    # ------------------------------------------------------------------
    # OOM defense (reference: src/ray/common/memory_monitor.h:52 +
    # raylet worker-killing policies, worker_killing_policy.h:34 /
    # worker_killing_policy_retriable_fifo.h:31)
    # ------------------------------------------------------------------
    @staticmethod
    def _host_memory_used_fraction() -> float:
        try:
            total = avail = None
            with open("/proc/meminfo") as f:
                for line in f:
                    if line.startswith("MemTotal:"):
                        total = float(line.split()[1])
                    elif line.startswith("MemAvailable:"):
                        avail = float(line.split()[1])
                    if total is not None and avail is not None:
                        break
            if not total or avail is None:
                # No MemAvailable (exotic kernel): better a disabled
                # monitor than a kill-storm from reading "100% used".
                return 0.0
            return 1.0 - avail / total
        except OSError:
            return 0.0

    @staticmethod
    def _rss_mb(pid: int) -> float:
        try:
            with open(f"/proc/{pid}/statm") as f:
                pages = int(f.read().split()[1])
            return pages * os.sysconf("SC_PAGE_SIZE") / 1e6
        except (OSError, ValueError, IndexError):
            return 0.0

    def _check_memory_pressure(self) -> None:
        """Kill one worker per check while the host is above the memory
        threshold.  Victim policy (reference retriable-FIFO +
        group-by-owner, simplified): retriable non-actor tasks first
        (their retry makes the kill recoverable), then non-retriable
        tasks, actors last; within a class, the newest-started first
        (least progress lost).  The killed task fails with a typed
        OutOfMemoryError that counts against its retries."""
        threshold = config.memory_usage_threshold
        if threshold >= 1.0:
            return
        used = self._host_memory_used_fraction()
        if used < threshold:
            return
        min_rss = config.memory_monitor_min_rss_mb
        with self.lock:
            candidates = []
            for w in self.workers.values():
                if w.state not in ("busy", "blocked"):
                    continue
                rss = self._rss_mb(w.pid)
                if rss < min_rss:
                    continue
                rec = w.current_task
                retriable = (rec is not None and rec.retries_left > 0
                             and not rec.is_actor_creation)
                is_actor = w.actor_id is not None
                klass = 0 if retriable and not is_actor else \
                    (1 if not is_actor else 2)
                candidates.append((klass, -w.last_idle_time, rss, w))
            if not candidates:
                return
            candidates.sort(key=lambda t: (t[0], t[1]))
            _, _, rss, victim = candidates[0]
            reason = (f"killed by the memory monitor: host memory at "
                      f"{used:.0%} >= threshold {threshold:.0%} "
                      f"(worker RSS {rss:.0f} MB)")
            try:
                if victim.proc is not None:
                    victim.proc.kill()
            except Exception:
                pass
            self._handle_worker_death(victim, reason, oom=True)
            self._schedule()

    def _recheck_infeasible(self) -> None:
        """Tasks admitted as pending demand while an autoscaler lease
        was fresh are re-checked when the lease expires: if the shape
        is unsatisfiable by any alive node's totals and nobody will
        ever provision it, fail it with the reason instead of leaving
        it pending forever (advisor round-2 finding)."""
        if self._autoscaler_live():
            return
        with self.lock:
            stale = []
            for rec in list(self.pending_queue):
                spec = rec.spec
                if spec.get("pg") is not None:
                    continue
                reason = self._infeasible_reason(spec.get("resources"))
                if reason is not None:
                    stale.append((rec, reason))
            for rec, reason in stale:
                if rec.is_actor_creation:
                    actor = self.actors.get(rec.actor_id)
                    if actor is not None:
                        # Queue failure matters here too: method calls
                        # queued while the actor was pending demand
                        # would otherwise hang their callers forever.
                        self._mark_actor_dead(
                            actor, f"infeasible: {reason}",
                            teardown_worker=False)
                self._fail_task_returns(rec, exc.InfeasibleResourceError(
                    f"task {rec.spec.get('name')!r} is infeasible and "
                    f"no autoscaler is alive to provision it: {reason}"))

    # ------------------------------------------------------------------
    # monitor: deadlines, dead procs, idle reaping
    # ------------------------------------------------------------------
    def _add_deadline_waiter(self, deadline: float,
                             cb: Callable[[], None]) -> None:
        """Register a timeout callback for the monitor to fire.  Wakes
        the monitor when the deadline lands inside the current tick so
        sub-50ms get/wait timeouts are honored precisely.

        Takes self.lock itself (reentrant — most callers already hold
        it): the monitor REBINDS _deadline_waiters under the lock each
        sweep, so an unlocked append can land on the superseded list
        and silently never fire (an RT010 self-finding)."""
        with self.lock:
            self._deadline_waiters.append((deadline, cb))
        if deadline - time.time() < 0.05:
            self._monitor_wake.set()

    # ------------------------------------------------------------------
    # stall sentinel (reference role: the dashboard reporter's py-spy
    # integration made automatic — stragglers get a targeted stack
    # capture recorded as a `stall` lifecycle event)
    # ------------------------------------------------------------------
    @staticmethod
    def _hist_quantile(cell: dict, q: float) -> float:
        """Upper-bound estimate of quantile `q` from an aggregated
        histogram cell — delegates to the shared implementation in
        util/metrics.py (one definition of "p95" for the stall
        sentinel, the slow-RPC sentinel, and the state APIs)."""
        from ray_tpu.util.metrics import hist_quantile
        return hist_quantile(cell, q)

    def _stall_threshold_locked(self) -> float:
        """max(stall_min_seconds, stall_p95_multiple * executing-stage
        p95) — the floor alone until enough tasks completed to make
        the histogram meaningful.  Caller holds self.lock."""
        from ray_tpu.util.metrics import TASK_STAGE_METRIC
        floor = config.stall_min_seconds
        key = (TASK_STAGE_METRIC, "histogram",
               (("stage", "executing"),))
        cell = self._metrics.get(key)
        if cell is None or (cell.get("count") or 0) \
                < config.stall_min_samples:
            return floor
        p95 = self._hist_quantile(cell, 0.95)
        return max(floor, config.stall_p95_multiple * p95)

    def _executing_tasks_locked(self):
        """(TaskRecord, WorkerHandle) pairs for everything currently
        executing user code on this node.  Caller holds self.lock."""
        for w in self.workers.values():
            rec = w.current_task
            if (rec is not None and w.state in ("busy", "blocked")
                    and rec.state == "dispatched"):
                yield rec, w
        for a in self.actors.values():
            if a.worker is None or a.worker.state == "dead":
                continue
            for rec in a.in_flight.values():
                # Dispatched-but-unstarted actor calls sit in the
                # worker's queue — queued, not stalled.
                if rec.started and rec.worker is None:
                    yield rec, a.worker

    def _stall_sentinel_tick(self) -> None:
        if not config.stall_detection_enabled \
                or config.stall_min_seconds <= 0:
            return
        now = time.time()
        flagged = []
        with self.lock:
            threshold = self._stall_threshold_locked()
            for rec, w in self._executing_tasks_locked():
                if rec.stall_reported:
                    continue
                start = (rec.stages.get("executing")
                         or rec.stages.get("worker_assigned"))
                if start is None or now - start < threshold:
                    continue
                rec.stall_reported = True
                flagged.append((rec, w, now - start, threshold))
        for rec, w, elapsed, threshold in flagged:
            self._capture_stall(rec, w, elapsed, threshold)

    def _capture_stall(self, rec: TaskRecord, w: WorkerHandle,
                       elapsed: float, threshold: float) -> None:
        """Targeted stack capture of the straggler's worker, recorded
        into the event ring as a `stall` lifecycle event (surfaced in
        summarize_tasks() and the chrome timeline)."""
        from ray_tpu.util.metrics import TASK_STALLS_METRIC
        name = rec.spec.get("name") or "<task>"

        def finish(stacks: dict, folded: dict) -> None:
            now = time.time()
            text = "\n".join(str(v) for v in stacks.values())
            with self.lock:
                self._inc_counter(
                    TASK_STALLS_METRIC, {},
                    "executing tasks flagged by the stall sentinel")
            self._emit_event({
                "kind": "stall",
                "name": name + ":stall",
                "task_name": name,
                "task_id": rec.task_id.hex(),
                "actor": rec.actor_id is not None,
                "elapsed_s": round(elapsed, 3),
                "threshold_s": round(threshold, 3),
                "stack": text,
                "pid": w.pid,
                "start": now, "end": now,
                "node_id": self.node_id.hex(),
            })

        self._request_worker_stacks([w], timeout=5.0, cb=finish)

    def _monitor_loop(self) -> None:
        # Event wait, not a fixed sleep (an RT005-class self-finding of
        # devtools/lint): shutdown() and a newly-registered near
        # deadline wake the loop immediately, so get/wait timeouts fire
        # on time instead of quantized to the next 50ms tick, and
        # shutdown never pays a last stale sleep.
        next_spill = next_infeasible = next_mem = next_scan = 0.0
        next_drain = next_stall = 0.0
        next_slow_rpc = next_hist = 0.0
        while not self._shutdown:
            with self.lock:
                nearest = min(
                    (d for d, _ in self._deadline_waiters),
                    default=None)
            timeout = 0.05
            if nearest is not None:
                timeout = max(0.0, min(timeout, nearest - time.time()))
            self._monitor_wake.wait(timeout)
            self._monitor_wake.clear()
            if self._shutdown:
                break
            now = time.time()
            # Periodic jobs are wall-clock scheduled (event wakes can
            # arrive much faster than the 50ms tick ever did).
            if now >= next_spill:     # ~1s: spill-threshold watchdog
                next_spill = now + 1.0
                try:
                    self._maybe_proactive_spill()
                except Exception:
                    pass
            if now >= next_infeasible:   # ~2s: infeasible recheck
                next_infeasible = now + 2.0
                try:
                    self._recheck_infeasible()
                except Exception:
                    pass
            if now >= next_drain:    # ~0.25s: preemption notice /
                next_drain = now + 0.25   # chaos preempt / drain sweep
                try:
                    self._drain_monitor_tick()
                except Exception:
                    pass
            if now >= next_stall:    # stall sentinel sweep
                next_stall = now + max(config.stall_check_interval_s,
                                       0.1)
                try:
                    self._stall_sentinel_tick()
                except Exception:
                    pass
            if now >= next_slow_rpc:   # slow-RPC sentinel sweep
                next_slow_rpc = now + max(
                    config.slow_rpc_check_interval_s, 0.1)
                try:
                    self._slow_rpc_tick()
                except Exception:
                    pass
            if now >= next_hist:     # metrics history ring sampler
                next_hist = now + max(
                    config.metrics_history_resolution_s, 0.05)
                try:
                    self._history_sample_tick()
                except Exception:
                    pass
            refresh_ms = config.memory_monitor_refresh_ms
            if refresh_ms > 0 and now >= next_mem:
                next_mem = now + refresh_ms / 1000.0
                try:
                    self._check_memory_pressure()
                except Exception:
                    pass
            # Deadline firing runs on EVERY wake (that is the point of
            # the event); the O(workers) death/idle/reap scans keep
            # their 50ms wall-clock cadence so a stream of sub-tick
            # timeouts can't turn them into wake-rate lock traffic.
            scan = now >= next_scan
            if scan:
                next_scan = now + 0.05
            fire = []
            with self.lock:
                remaining = []
                for deadline, cb in self._deadline_waiters:
                    if getattr(cb, "cancelled", False):
                        continue        # satisfied early: drop now
                    if now >= deadline:
                        fire.append(cb)
                    else:
                        remaining.append((deadline, cb))
                self._deadline_waiters = remaining
                if scan:
                    self._monitor_scan_locked(now)
            for cb in fire:
                try:
                    cb()
                except Exception:
                    pass

    def _monitor_scan_locked(self, now: float) -> None:
        """Worker-death / idle-reap / pending-reap sweep (caller holds
        self.lock; runs at the 50ms scan cadence, not per wake)."""
        for w in list(self.workers.values()):
            if (w.proc is not None and w.proc.poll() is not None
                    and w.state != "dead"):
                self._handle_worker_death(
                    w, f"worker process exited "
                       f"(code {w.proc.returncode})")
                self._schedule()
        idle_timeout = config.worker_idle_timeout_s
        for w in list(self.workers.values()):
            if (w.state == "idle" and w.actor_id is None
                    and now - w.last_idle_time > idle_timeout):
                w.state = "dead"
                self.workers.pop(w.worker_id, None)
                if w.conn_send:
                    w.conn_send({"type": "exit"})
                self._schedule_reap(w)
        pending, self._pending_reaps = self._pending_reaps, []
        chips_back = False
        for w, deadline in pending:
            if w.proc.poll() is not None:
                chips_back |= self._reap_exited(w)
            elif now >= deadline:
                w.proc.kill()
                self._pending_reaps.append((w, now + 2.0))
            else:
                self._pending_reaps.append((w, deadline))
        if chips_back:
            self._schedule()


def main() -> None:
    """Standalone node entry: one raylet-role process joining a cluster.

    python -m ray_tpu._private.node_service --gcs-host H --gcs-port P \
        [--resources '{"CPU": 4, "remote": 1}'] [--store-capacity BYTES]
    Prints NODE_READY=<node_id_hex> once serving (the Cluster fixture
    scrapes it).  Reference: raylet main (src/ray/raylet/main.cc)."""
    import argparse
    import json
    import signal

    ap = argparse.ArgumentParser()
    ap.add_argument("--gcs-host", required=True)
    ap.add_argument("--gcs-port", type=int, required=True)
    ap.add_argument("--resources", default="{}")
    ap.add_argument("--store-capacity", type=int, default=0)
    ap.add_argument("--session-prefix", default="")
    args = ap.parse_args()

    res = {k: float(v) for k, v in json.loads(args.resources).items()}
    res.setdefault("CPU", float(os.cpu_count() or 1))
    prefix = args.session_prefix or config.session_dir_prefix
    session_dir = os.path.join(
        prefix, f"node_{int(time.time()*1000)}_{os.getpid()}")
    os.makedirs(session_dir, exist_ok=True)
    store_path = f"/dev/shm/rtpu_node_{os.getpid()}"
    capacity = args.store_capacity or config.object_store_memory
    node = NodeService(session_dir, res, store_path, capacity,
                       gcs_address=(args.gcs_host, args.gcs_port))
    node.start()
    print(f"NODE_READY={node.node_id.hex()}", flush=True)

    stop = threading.Event()
    # Drain completion (clean or deadline-expired) ends the process.
    node._drain_exit_cb = stop.set

    def _on_sigterm(*_a) -> None:
        # First SIGTERM = preemption/maintenance notice: drain
        # gracefully (hand back work, migrate actors, re-replicate
        # sole object copies), then exit.  A second SIGTERM — or one
        # arriving mid-drain — forces an immediate stop.
        if node.draining:
            stop.set()
            return
        threading.Thread(
            target=node._begin_drain,
            args=("sigterm", "SIGTERM (drain requested)"),
            daemon=True, name="rtpu-sigterm-drain").start()

    signal.signal(signal.SIGTERM, _on_sigterm)
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    while not stop.is_set():
        stop.wait(0.5)
    node.shutdown()


if __name__ == "__main__":
    main()
