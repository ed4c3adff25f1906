"""Serve control plane: the controller actor.

Analog of the reference's detached ServeController
(serve/_private/controller.py:84) + deployment_state reconciler
(deployment_state.py:1232): holds the target state for every deployment
and reconciles actual replica actors toward it.  Reconciliation runs
inside control calls and from the router's failure reports — no
standing poll loop is needed at this scale (the reference's controller
loops because it also drives autoscaling/long-poll broadcast).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

CONTROLLER_NAME = "SERVE_CONTROLLER"


def _differs(old: Any, new: Any) -> bool:
    """Inequality that tolerates array-valued init args (plain != on a
    tuple holding numpy/jax arrays raises 'truth value is ambiguous');
    any comparison failure counts as a change."""
    try:
        return bool(old != new)
    except Exception:
        return True


class ServeController:
    """Named actor owning deployment target state + replica registry."""

    def __init__(self) -> None:
        import threading
        # name -> {"blob", "init_args", "init_kwargs", "num_replicas",
        #          "max_concurrent_queries", "version",
        #          "replicas": [ActorHandle], "autoscaling": dict|None}
        self._deployments: Dict[str, dict] = {}
        self._version = 0
        self._autoscale_thread = None
        # Loop-thread stop flag: the health/drain/autoscale daemons
        # wait on it instead of sleeping, so shutdown_all can stop and
        # JOIN them — a daemon loop still probing replicas through
        # interpreter teardown is the PR-9 stop()-segfault class.
        self._loops_stop = threading.Event()
        # Guards deployment state: the autoscale daemon thread mutates
        # it concurrently with actor-method execution.
        self._state_lock = threading.RLock()
        # actor_id -> per-engine KV gauge tags, cached by the health
        # sweep while the replica is healthy so its series can be
        # zeroed after an UNCLEAN death (the process that wrote them
        # is gone).  Guarded by _state_lock.
        self._engine_tags: Dict[bytes, list] = {}
        # Construct the shared serve gauges HERE, outside any lock:
        # the first shared_gauge() call registers the metric and
        # starts the metrics flusher thread — a Thread.start under
        # _state_lock is the PR-10 locksan handshake trap.  Later
        # _update_serve_gauges_locked calls are pure cell writes.
        try:
            from ray_tpu.util.metrics import (SERVE_QUEUE_DEPTH_METRIC,
                                              SERVE_REPLICAS_METRIC,
                                              shared_gauge)
            shared_gauge(
                SERVE_REPLICAS_METRIC,
                description="serve replicas per deployment by state "
                            "(running | draining | target)",
                tag_keys=("deployment", "state"))
            shared_gauge(
                SERVE_QUEUE_DEPTH_METRIC,
                description="total outstanding requests per "
                            "deployment (autoscaler's last poll)",
                tag_keys=("deployment",))
        except Exception:
            pass
        # route prefix -> root deployment (reference: route_prefix on
        # the ingress deployment, serve/_private/proxy.py routing)
        self._routes: Dict[str, str] = {}
        # Long-poll push (reference: serve/_private/long_poll.py:64):
        # routers park wait_for_update calls on this condition; every
        # version bump notifies them.  Requires the controller actor to
        # run with max_concurrency > 1 (serve.__init__ sets it).
        self._update_cond = threading.Condition(self._state_lock)

    # -- control ----------------------------------------------------------
    def deploy(self, name: str, cls_blob: bytes, init_args: tuple,
               init_kwargs: dict, num_replicas: int,
               max_concurrent_queries: int,
               actor_options: Optional[Dict[str, Any]] = None,
               autoscaling_config: Optional[Dict[str, Any]] = None,
               health_check_period_s: float = 10.0,
               health_check_timeout_s: float = 30.0,
               user_config: Any = None,
               admission_config: Optional[Dict[str, Any]] = None
               ) -> int:
        """Create or update a deployment; reconciles synchronously and
        returns the new version.  Changed code/args/options replace
        every running replica (the reference's version-driven replica
        rollout, deployment_state.py); a changed user_config alone is
        pushed live via reconfigure() with NO replica restart."""
        self._state_lock.acquire()
        try:
            version, push = self._deploy_locked(
                name, cls_blob, init_args, init_kwargs, num_replicas,
                max_concurrent_queries, actor_options,
                autoscaling_config, health_check_period_s,
                health_check_timeout_s, user_config,
                admission_config)
        finally:
            self._state_lock.release()
        if push:
            # Synchronous config push OUTSIDE the lock (it blocks on
            # replica RPCs; holding _state_lock here would stall
            # health checks, failure reports, and other deploys).
            import ray_tpu
            try:
                ray_tpu.get([r.reconfigure.remote(user_config)
                             for r in push], timeout=60)
            except Exception:
                # Partial application would leave MIXED configs under
                # one version: roll every replica — fresh ones build
                # with the recorded (new) user_config, where a failure
                # is attributable — then surface the push error.
                with self._state_lock:
                    d = self._deployments.get(name)
                    if d is not None:
                        old, d["replicas"] = d["replicas"], []
                        self._stop_replicas(old)
                        self._reconcile(name)
                        self._notify_update()
                raise
        return version

    def _deploy_locked(self, name, cls_blob, init_args, init_kwargs,
                       num_replicas, max_concurrent_queries,
                       actor_options, autoscaling_config,
                       health_check_period_s=10.0,
                       health_check_timeout_s=30.0,
                       user_config=None, admission_config=None) -> int:
        d = self._deployments.get(name)
        if d is None:
            d = {"replicas": [], "version": 0, "draining": []}
            self._deployments[name] = d
        new_state = dict(blob=cls_blob, init_args=init_args,
                         init_kwargs=init_kwargs,
                         max_concurrent_queries=max_concurrent_queries,
                         actor_options=dict(actor_options or {}))
        changed = any(_differs(d.get(k), v)
                      for k, v in new_state.items())
        asc = None
        if autoscaling_config:
            # SLO-aware autoscaling policy knobs.  target_queue_depth
            # is the preferred name for per-replica queue pressure
            # (target_ongoing_requests kept as the reference-compatible
            # alias); target_ttft_ms / target_itl_ms scale on the
            # latency SLOs the replicas report through slo_stats()
            # (0 = that SLO signal off).  The delays are the
            # hysteresis: pressure must HOLD for the delay before the
            # controller acts, so bursty traffic doesn't flap.
            asc = {"min_replicas": 1, "max_replicas": 8,
                   "target_ongoing_requests": 2.0,
                   "target_queue_depth": None,
                   "target_ttft_ms": 0.0,
                   "target_itl_ms": 0.0,
                   "downscale_slo_fraction": 0.5,
                   "upscale_delay_s": 0.5, "downscale_delay_s": 5.0,
                   "interval_s": 0.5}
            unknown = set(autoscaling_config) - set(asc)
            if unknown:
                raise ValueError(
                    f"unknown autoscaling_config keys "
                    f"{sorted(unknown)}; known: {sorted(asc)}")
            asc.update(autoscaling_config)
            # Value sanity alongside the key check: a zero target or
            # interval would ZeroDivision/spin inside the policy loop,
            # where the error is unattributable.
            if (asc["target_queue_depth"] or
                    asc["target_ongoing_requests"]) <= 0:
                raise ValueError(
                    "autoscaling target_queue_depth/"
                    "target_ongoing_requests must be > 0")
            if asc["interval_s"] <= 0:
                raise ValueError("autoscaling interval_s must be > 0")
            if not 1 <= asc["min_replicas"] <= asc["max_replicas"]:
                raise ValueError(
                    "autoscaling needs 1 <= min_replicas <= "
                    "max_replicas")
            num_replicas = max(asc["min_replicas"],
                               min(d.get("num_replicas",
                                         asc["min_replicas"]),
                                   asc["max_replicas"]))
        old_user_config = d.get("user_config")
        cfg_changed = _differs(old_user_config, user_config)
        # Admission is router-enforced: a change only needs to reach
        # the routers (the unconditional version bump below pushes the
        # fresh config through every long-poll); no replica restart.
        d.update(new_state, num_replicas=num_replicas,
                 autoscaling=asc,
                 admission=(dict(admission_config)
                            if admission_config else None),
                 user_config=user_config,
                 health_check_period_s=health_check_period_s,
                 health_check_timeout_s=health_check_timeout_s,
                 _scale_pressure_since=None)
        d.setdefault("draining", [])
        if asc is not None:
            self._ensure_autoscale_loop()
        if health_check_period_s:
            self._ensure_health_loop()
        self._ensure_drain_loop()
        if cfg_changed and user_config is None:
            # Clearing user_config has no live representation (there
            # is nothing to reconfigure TO): roll the replicas so
            # every one serves the class's __init__ state — mixed
            # configs across one version would be worse.
            changed = True
        push: list = []
        if changed and d["replicas"]:
            old, d["replicas"] = d["replicas"], []
            self._stop_replicas(old)
        elif cfg_changed and d["replicas"]:
            # user_config-only update: live reconfigure, no restart.
            # The blocking push happens in deploy() AFTER the lock is
            # released.
            push = list(d["replicas"])
        d["version"] += 1
        self._version += 1
        self._reconcile(name)
        self._notify_update()
        return d["version"], push

    def set_route(self, prefix: str, name: str) -> None:
        if not prefix.startswith("/"):
            raise ValueError("route_prefix must start with '/'")
        with self._state_lock:
            # One prefix per app root: re-running with a new prefix
            # must retire the old one, or clients on the stale path
            # would silently reach the new code.
            self._drop_routes_locked(name)
            self._routes[prefix.rstrip("/") or "/"] = name
            self._version += 1
            self._notify_update()

    def get_routes(self) -> Dict[str, str]:
        with self._state_lock:
            return dict(self._routes)

    def delete(self, name: str) -> bool:
        with self._state_lock:
            d = self._deployments.get(name)
            gone = ([r._actor_id for r in d["replicas"]]
                    + [r._actor_id for r in (d.get("draining") or [])]
                    if d else [])
            out = self._delete_locked(name)
        # Gauge cleanup OUTSIDE the lock (first call may construct the
        # shared gauges / start the metrics flusher).
        for actor_id in gone:
            self._clear_replica_kv_gauges(actor_id)
        if out:
            self._drop_serve_gauges(name)
        return out

    def _drop_routes_locked(self, name: str) -> None:
        for prefix in [p for p, n in self._routes.items() if n == name]:
            del self._routes[prefix]

    def _delete_locked(self, name: str) -> bool:
        d = self._deployments.pop(name, None)
        if d is None:
            return False
        self._drop_routes_locked(name)
        self._stop_replicas(d["replicas"] + list(d.get("draining")
                                                 or []))
        self._version += 1
        self._notify_update()
        return True

    def shutdown_all(self) -> None:
        import threading
        with self._state_lock:
            names = list(self._deployments)
        for name in names:
            self.delete(name)
        # Stop + join the daemon loops (bounded: they wake on the
        # event).  Controller teardown with loops mid-probe otherwise
        # races interpreter shutdown.  Swap the event and detach the
        # threads UNDER the lock (see _loop_needs_start), then signal
        # and join outside it.
        with self._state_lock:
            stop, self._loops_stop = self._loops_stop, \
                threading.Event()
            threads = [getattr(self, a, None) for a in
                       ("_health_thread", "_drain_thread",
                        "_autoscale_thread")]
            for a in ("_health_thread", "_drain_thread",
                      "_autoscale_thread"):
                setattr(self, a, None)
        stop.set()
        from ray_tpu.devtools import leaksan
        for t in threads:
            if t is not None:
                if t.is_alive():
                    t.join(timeout=5.0)
                # A timed-out join leaves the thread in the ledger on
                # purpose: a wedged loop is exactly what it tracks.
                if not t.is_alive():
                    leaksan.discharge_thread(t)

    # -- data-plane queries ------------------------------------------------
    def get_replicas(self, name: str) -> dict:
        with self._state_lock:
            d = self._deployments.get(name)
            if d is None:
                return {"replicas": [], "version": -1,
                        "max_concurrent_queries": 1, "admission": None}
            # Draining replicas are deliberately ABSENT from the list:
            # the routers' next pick excludes them (the scale-down
            # mask) while their in-flight requests finish on refs
            # already held.
            return {"replicas": list(d["replicas"]),
                    "version": d["version"],
                    "max_concurrent_queries":
                        d["max_concurrent_queries"],
                    "admission": d.get("admission")}

    def version(self) -> int:
        with self._state_lock:
            return self._version

    def wait_for_update(self, name: str, known_version: int,
                        timeout: float = 60.0) -> Optional[dict]:
        """Long-poll (reference: long_poll.py:177 listen_for_change):
        parks until deployment `name`'s version advances past
        `known_version`, then returns the fresh replica listing; None on
        timeout (the client re-arms).  Deleted deployments answer with
        version -1 immediately."""
        import time
        deadline = time.time() + timeout
        with self._update_cond:
            while True:
                d = self._deployments.get(name)
                cur = d["version"] if d is not None else -1
                if cur != known_version:
                    return self.get_replicas(name)
                remaining = deadline - time.time()
                if remaining <= 0:
                    return None
                self._update_cond.wait(remaining)

    def _notify_update(self) -> None:
        """Caller holds _state_lock."""
        self._update_cond.notify_all()

    def status(self) -> Dict[str, dict]:
        import ray_tpu
        with self._state_lock:
            snap = {name: (list(d["replicas"]),
                           list(d.get("draining") or []),
                           d["num_replicas"], d["version"],
                           dict(d.get("_autoscale_last") or {}),
                           bool(d.get("autoscaling")))
                    for name, d in self._deployments.items()}
        out = {}
        for name, (reps, draining, target, version, last,
                   autoscaled) in snap.items():
            states = []
            for r in reps:
                try:
                    states.append(
                        ray_tpu._ensure_connected().actor_state(
                            r._actor_id)["state"])
                except Exception:
                    states.append("unknown")
            out[name] = {"target_replicas": target,
                         "replica_states": states,
                         "draining_replicas": len(draining),
                         "version": version}
            if autoscaled:
                out[name]["autoscale"] = last or None
        return out

    def overload_status(self) -> Dict[str, dict]:
        """Rich status for `ray_tpu serve status`: replicas by state,
        LIVE queue depths / SLO readings (polled here, off the control
        hot path), admission config, and the autoscaler's last
        decision + recent scale events."""
        import ray_tpu
        with self._state_lock:
            snap = {
                name: {
                    "replicas": list(d["replicas"]),
                    "draining": len(d.get("draining") or []),
                    "target_replicas": d["num_replicas"],
                    "version": d["version"],
                    "autoscaling": (dict(d["autoscaling"])
                                    if d.get("autoscaling") else None),
                    "admission": (dict(d["admission"])
                                  if d.get("admission") else None),
                    "autoscale_last": dict(d.get("_autoscale_last")
                                           or {}) or None,
                    "autoscale_events": list(
                        d.get("_autoscale_events") or [])[-10:],
                } for name, d in self._deployments.items()}
        out = {}
        for name, s in snap.items():
            reps = s.pop("replicas")
            qs, ttfts, itls = [], [], []
            for st in self._poll_slo_stats(reps).values():
                if st is None:
                    continue
                qs.append(float(st.get("qlen") or 0.0))
                if st.get("ttft_p95_ms") is not None:
                    ttfts.append(float(st["ttft_p95_ms"]))
                if st.get("itl_p95_ms") is not None:
                    itls.append(float(st["itl_p95_ms"]))
            s.update(running=len(reps),
                     queue_depth=sum(qs),
                     ttft_p95_ms=max(ttfts) if ttfts else None,
                     itl_p95_ms=max(itls) if itls else None)
            out[name] = s
        return out

    def report_replica_failure(self, name: str, actor_id: bytes) -> None:
        """Router saw a replica die: drop it and backfill.  The death
        was UNCLEAN by definition (a clean stop zeroes its own
        series), so also zero the replica's per-engine KV gauges —
        outside the lock, the first call may construct the gauges."""
        with self._state_lock:
            self._report_replica_failure_locked(name, actor_id)
        self._clear_replica_kv_gauges(actor_id)

    def _report_replica_failure_locked(self, name: str,
                                       actor_id: bytes) -> None:
        d = self._deployments.get(name)
        if d is None:
            return
        before = len(d["replicas"])
        d["replicas"] = [r for r in d["replicas"]
                         if r._actor_id != actor_id]
        # A draining replica that dies mid-drain needs no backfill
        # (it was leaving anyway) — just stop tracking it.
        drn = d.get("draining") or []
        d["draining"] = [r for r in drn if r._actor_id != actor_id]
        if len(d["replicas"]) != before:
            d["version"] += 1
            self._version += 1
        self._reconcile(name)
        self._notify_update()

    # -- reconciliation ----------------------------------------------------
    @staticmethod
    def _spawn_replica(name: str, d: dict):
        """One replica actor with the deployment's options — THE spawn
        expression, shared by reconcile and drain migration so their
        replicas can never diverge.  Caller holds _state_lock."""
        import ray_tpu
        from ray_tpu.serve._replica import Replica
        cls = ray_tpu.remote(Replica)
        opts = {k: v for k, v in d["actor_options"].items()
                if k in ("num_cpus", "num_tpus", "resources")
                and v is not None}
        return cls.options(
            # +3 headroom over the router's request cap: the
            # controller's check_health/queue_len/slo_stats probes
            # must never queue behind a saturated request pool, or
            # a fully-loaded healthy replica would miss its
            # health deadline and be killed at peak load.
            max_concurrency=max(d["max_concurrent_queries"], 1) + 3,
            max_restarts=2, **opts,
        ).remote(name, d["blob"], d["init_args"],
                 d["init_kwargs"], d.get("user_config"))

    def _reconcile(self, name: str,
                   load: Optional[Dict[bytes, float]] = None) -> None:
        """Caller holds _state_lock.  `load` (actor_id -> queue depth,
        the autoscaler's freshly polled map) steers scale-down victim
        choice toward the least-loaded replicas."""
        d = self._deployments.get(name)
        if d is None:
            return
        want, have = d["num_replicas"], len(d["replicas"])
        if have < want:
            for i in range(want - have):
                d["replicas"].append(self._spawn_replica(name, d))
            d["version"] += 1
            self._version += 1
            self._notify_update()
        elif have > want:
            # Graceful scale-down: mask the victims from routing NOW
            # (they leave the get_replicas listing, the version bump
            # pushes that through every router long-poll), then hand
            # them to the release worker, which waits for their
            # in-flight queue to drain (paged decodes finish) before
            # the kill.  Contrast with the old kill-at-reconcile,
            # which turned every downscale under load into failover
            # retries.
            if load:
                order = sorted(d["replicas"],
                               key=lambda r: load.get(r._actor_id,
                                                      0.0))
                victims = order[:have - want]
            else:
                victims = d["replicas"][want:]
            vic_ids = {r._actor_id for r in victims}
            d["replicas"] = [r for r in d["replicas"]
                             if r._actor_id not in vic_ids]
            d.setdefault("draining", []).extend(victims)
            d["version"] += 1
            self._version += 1
            self._notify_update()
            self._start_release_thread(name, victims)
        self._update_serve_gauges_locked(name)

    def _start_release_thread(self, name: str, victims: list) -> None:
        """Caller holds _state_lock (the stop event must be the one
        live at decision time — shutdown_all swaps it)."""
        import threading
        stop = self._loops_stop
        threading.Thread(
            target=self._release_replicas, args=(name, victims, stop),
            daemon=True, name="rtpu-serve-release").start()

    def _release_replicas(self, name: str, victims: list,
                          stop) -> None:
        """Release worker: wait until each masked replica's queue
        drains (two consecutive zero readings — one could race a
        router that had not yet applied the mask), then kill it and
        zero its engine gauges.  Past the deadline stragglers are cut
        loose anyway: their in-flight requests ride the PR-3
        retry/failover path, which is the pre-existing contract for a
        replica that will not finish."""
        import time

        import ray_tpu
        from ray_tpu import exceptions as exc
        deadline = time.time() + 60.0
        zero_seen: dict = {}
        pending = list(victims)
        # Let the version push land before the first queue reading:
        # a router mid-pick can still assign for a few milliseconds.
        stop.wait(0.2)
        while pending and not stop.is_set() \
                and time.time() < deadline:
            still = []
            for r in pending:
                try:
                    q = ray_tpu.get(r.queue_len.remote(), timeout=5)
                except (exc.ActorDiedError,
                        exc.WorkerCrashedError):
                    q = 0    # provably gone: finalize below
                except Exception:
                    # Transient (probe timeout, restarting, control-
                    # plane hiccup): a BUSY replica's probe can time
                    # out too — treating it as drained would kill it
                    # mid-request, the exact failure this worker
                    # exists to prevent.  Keep waiting; the 60 s
                    # deadline still bounds a wedged replica.
                    q = 1
                if q == 0 and zero_seen.get(r._actor_id):
                    self._finalize_release(name, r)
                else:
                    zero_seen[r._actor_id] = (q == 0)
                    still.append(r)
            pending = still
            if pending and stop.wait(0.1):
                return
        for r in pending:
            self._finalize_release(name, r)

    def _finalize_release(self, name: str, replica) -> None:
        import ray_tpu
        try:
            ray_tpu.kill(replica)
        except Exception:
            pass
        with self._state_lock:
            d = self._deployments.get(name)
            if d is not None:
                d["draining"] = [r for r in (d.get("draining") or [])
                                 if r._actor_id != replica._actor_id]
                self._update_serve_gauges_locked(name)
        self._clear_replica_kv_gauges(replica._actor_id)

    # -- serve metric plane ------------------------------------------------
    def _update_serve_gauges_locked(self, name: str) -> None:
        """ray_tpu_serve_replicas{deployment,state} from the current
        target state.  Caller holds _state_lock (Gauge.set is a dict
        write under the metrics registry lock — never blocks)."""
        d = self._deployments.get(name)
        if d is None:
            return
        try:
            from ray_tpu.util.metrics import (SERVE_REPLICAS_METRIC,
                                              shared_gauge)
            g = shared_gauge(
                SERVE_REPLICAS_METRIC,
                description="serve replicas per deployment by state "
                            "(running | draining | target)",
                tag_keys=("deployment", "state"))
            g.set(len(d["replicas"]),
                  tags={"deployment": name, "state": "running"})
            g.set(len(d.get("draining") or ()),
                  tags={"deployment": name, "state": "draining"})
            g.set(d["num_replicas"],
                  tags={"deployment": name, "state": "target"})
        except Exception:
            pass

    def _drop_serve_gauges(self, name: str) -> None:
        """Deployment deleted: remove its controller-written series."""
        try:
            from ray_tpu.util.metrics import (SERVE_QUEUE_DEPTH_METRIC,
                                              SERVE_REPLICAS_METRIC,
                                              shared_gauge)
            g = shared_gauge(SERVE_REPLICAS_METRIC,
                             tag_keys=("deployment", "state"))
            for state in ("running", "draining", "target"):
                g.remove(tags={"deployment": name, "state": state},
                         force=True)
            shared_gauge(SERVE_QUEUE_DEPTH_METRIC,
                         tag_keys=("deployment",)).remove(
                             tags={"deployment": name}, force=True)
        except Exception:
            pass

    def _clear_replica_kv_gauges(self, actor_id: bytes) -> None:
        """Zero a dead replica's per-engine ray_tpu_kv_blocks{state}
        series node-side (the PR-9 known limitation: an uncleanly
        killed replica's last gauge samples persist until node
        restart — push-model series are never deleted there).  The
        controller learns of replica death first, so it owns the
        sweep: the engine tags were cached from the replica while it
        was healthy, and remove(force=True) pushes the zero even
        though THIS process never wrote the series."""
        with self._state_lock:
            tags = self._engine_tags.pop(actor_id, None)
        if not tags:
            return
        try:
            from ray_tpu.serve.llm import _get_kv_metrics
            km = _get_kv_metrics()
            if km is None:
                return
            for tag in tags:
                for state in ("used", "cached", "free"):
                    km["blocks"].remove(
                        tags={"state": state, "engine": tag},
                        force=True)
        except Exception:
            pass

    # -- replica autoscaling ----------------------------------------------
    # Reference: replicas report ongoing-request metrics, the controller
    # runs the autoscaling policy (serve/_private/autoscaling_state.py,
    # serve/autoscaling_policy.py): desired = total_ongoing / target,
    # clamped to [min, max], with upscale/downscale smoothing delays.
    def _start_loop(self, attr: str, name: str, make_loop) -> None:
        """Start the named daemon loop unless it is already running —
        check, claim (attr assignment), and start all happen UNDER
        _state_lock, because the controller actor runs with
        max_concurrency > 1 and two concurrent deploy()s must not
        both start a loop.  `make_loop(stop)` builds the loop body
        around the stop Event captured under the same lock:
        shutdown_all SWAPS in a fresh Event rather than anyone ever
        clear()ing a shared one, so a loop started concurrently with
        a shutdown either runs on the new event (untouched by the old
        set()) or on the old one (and exits with the rest).  A
        deploy() after shutdown_all() therefore gets live loops again
        instead of stale dead threads."""
        import threading

        from ray_tpu.devtools import leaksan
        with self._state_lock:
            t = getattr(self, attr, None)
            if t is not None and t.is_alive():
                return
            t = threading.Thread(target=make_loop(self._loops_stop),
                                 daemon=True, name=name)
            setattr(self, attr, t)
            t.start()
            leaksan.track_thread(t)

    def _ensure_health_loop(self) -> None:
        """Active replica health probing (reference:
        deployment_state.py health checking: the controller calls
        check_health on every replica each period; a probe that errors
        or times out replaces the replica)."""
        def make_loop(stop):
            def loop() -> None:
                import ray_tpu
                # (name, actor_id) -> (probe ref, deadline, replica)
                pending: dict = {}
                # (name, actor_id) -> one-shot kv_engine_tags probe
                tags_pending: dict = {}
                while not stop.is_set():
                    try:
                        self._health_tick(pending, tags_pending)
                    except Exception:
                        pass   # transient error: keep probing
                    stop.wait(self._health_period())
            return loop

        self._start_loop("_health_thread", "rtpu-serve-health",
                         make_loop)

    def _health_period(self) -> float:
        with self._state_lock:
            periods = [d.get("health_check_period_s")
                       for d in self._deployments.values()
                       if d.get("health_check_period_s")]
        return min(periods) if periods else 10.0

    def _health_tick(self, pending: dict,
                     tags_pending: Optional[dict] = None) -> None:
        """One probe round: launch check_health on unprobed replicas,
        harvest completions, replace failures/timeouts.  Piggybacked:
        a one-shot kv_engine_tags probe per replica caches its
        per-engine gauge tags, so the death sweep can zero the series
        of a replica whose process died without running stop()."""
        import time

        import ray_tpu
        with self._state_lock:
            targets = []
            for name, d in self._deployments.items():
                if not d.get("health_check_period_s"):
                    continue
                for r in d["replicas"]:
                    targets.append(
                        (name, r,
                         d.get("health_check_timeout_s", 30.0)))
            known_tags = set(self._engine_tags)
        now = time.time()
        for name, r, tmo in targets:
            key = (name, r._actor_id)
            if key not in pending:
                # The probe deadline applies once a replica has
                # answered anything (its engine-tags probe): until then
                # it is still constructing — an LLM replica claims its
                # chip and builds its weights first, well past any
                # steady-state timeout — and a constructor that dies is
                # reported through the probe's ref by the actor runtime.
                deadline = (now + tmo if r._actor_id in known_tags
                            else float("inf"))
                try:
                    pending[key] = (r.check_health.remote(), deadline, r)
                except Exception:
                    self.report_replica_failure(name, r._actor_id)
            if tags_pending is not None \
                    and r._actor_id not in known_tags \
                    and key not in tags_pending:
                try:
                    tags_pending[key] = r.kv_engine_tags.remote()
                except Exception:
                    pass
        for key in list(pending):
            ref, deadline, r = pending[key]
            ready, _ = ray_tpu.wait([ref], timeout=0)
            if ready:
                del pending[key]
                try:
                    ok = ray_tpu.get(ref)
                except Exception:
                    ok = False
                if not ok:
                    self._replace_unhealthy(key[0], r)
            elif time.time() > deadline:
                del pending[key]
                self._replace_unhealthy(key[0], r)
        for key in list(tags_pending or ()):
            ref = tags_pending[key]
            ready, _ = ray_tpu.wait([ref], timeout=0)
            if not ready:
                continue
            del tags_pending[key]
            try:
                tags = list(ray_tpu.get(ref) or [])
            except Exception:
                continue        # dead before answering: nothing cached
            with self._state_lock:
                # Cache even an empty list: non-engine replicas must
                # not be re-probed every tick.
                self._engine_tags[key[1]] = tags

    # -- graceful node drain (pre-failure signal) -----------------------
    # Reference role: the controller treating a draining node as a
    # pre-failure — start replacement replicas FIRST, flip the router
    # mask once they are ready, then release the old ones.  Contrast
    # with the reactive path (report_replica_failure after a request
    # already died): a drain produces zero user-visible errors.
    def _ensure_drain_loop(self) -> None:
        def make_loop(stop):
            def loop() -> None:
                import ray_tpu
                try:
                    # Single-node sessions have no node to drain: exit
                    # instead of polling the control plane once a
                    # second for the controller's whole lifetime.
                    if not ray_tpu._ensure_connected().node_info().get(
                            "multinode"):
                        return
                except Exception:
                    pass
                while not stop.is_set():
                    try:
                        self._drain_tick()
                    except Exception:
                        pass
                    stop.wait(1.0)
            return loop

        self._start_loop("_drain_thread", "rtpu-serve-drain",
                         make_loop)

    def _drain_tick(self) -> None:
        """Find replicas homed on DRAINING nodes and proactively move
        them (migrations run synchronously on this thread; a failed
        one is simply retried next tick)."""
        import ray_tpu
        try:
            node_list = ray_tpu.nodes()
        except Exception:
            return
        draining = {n["node_id"] for n in node_list
                    if n.get("state") == "draining"}
        if not draining:
            return
        client = ray_tpu._ensure_connected()
        with self._state_lock:
            candidates = [(name, r)
                          for name, d in self._deployments.items()
                          for r in d["replicas"]]
        for name, r in candidates:
            try:
                home = client.actor_node(r._actor_id)
            except Exception:
                continue
            if home not in draining:
                continue
            self._migrate_replica(name, r)

    def _migrate_replica(self, name: str, old) -> bool:
        """Start a replacement replica, wait for it to come up, swap it
        into the routing set (version bump pushes the new list to every
        router long-poll), then release the old replica once its
        in-flight requests drain — requests in flight on the draining
        node are never dropped."""
        import time

        import ray_tpu
        with self._state_lock:
            d = self._deployments.get(name)
            if d is None or all(r._actor_id != old._actor_id
                                for r in d["replicas"]):
                return True     # already gone: nothing left to migrate
            h = self._spawn_replica(name, d)
        # Readiness gate OUTSIDE the lock: the replacement must serve
        # before the old one leaves the mask.
        try:
            ray_tpu.get(h.check_health.remote(), timeout=60)
        except Exception:
            try:
                ray_tpu.kill(h)
            except Exception:
                pass
            return False
        with self._state_lock:
            d = self._deployments.get(name)
            if d is None:
                try:
                    ray_tpu.kill(h)
                except Exception:
                    pass
                return True     # deployment deleted mid-migration
            d["replicas"] = [r for r in d["replicas"]
                             if r._actor_id != old._actor_id]
            d["replicas"].append(h)
            d["version"] += 1
            self._version += 1
            self._notify_update()
        # Old replica: wait for its outstanding requests, then release.
        deadline = time.time() + 30.0
        while time.time() < deadline:
            try:
                if ray_tpu.get(old.queue_len.remote(), timeout=5) == 0:
                    break
            except Exception:
                break       # already gone (node exited / migrated away)
            time.sleep(0.2)
        try:
            ray_tpu.kill(old)
        except Exception:
            pass
        self._clear_replica_kv_gauges(old._actor_id)
        return True

    def _replace_unhealthy(self, name: str, replica) -> None:
        """Failed health probe: the actor may still be alive (hung or
        self-reported unhealthy) — kill it so the replacement does not
        share the chip/port, then backfill."""
        import ray_tpu
        try:
            ray_tpu.kill(replica)
        except Exception:
            pass
        self.report_replica_failure(name, replica._actor_id)

    def _ensure_autoscale_loop(self) -> None:
        def make_loop(stop):
            def loop() -> None:
                while not stop.is_set():
                    intervals = []
                    try:
                        with self._state_lock:
                            targets = [
                                (name, d) for name, d
                                in self._deployments.items()
                                if d.get("autoscaling")]
                        for name, d in targets:
                            intervals.append(
                                d["autoscaling"]["interval_s"])
                            try:
                                self._autoscale_tick(name, d)
                            except Exception:
                                # Per-deployment isolation: one
                                # misbehaving tick must not starve
                                # every other deployment's policy.
                                pass
                    except Exception:
                        pass
                    stop.wait(min(intervals) if intervals else 0.5)
            return loop

        self._start_loop("_autoscale_thread", "rtpu-serve-autoscale",
                         make_loop)

    def _autoscale_tick(self, name: str, d: dict) -> None:
        """One policy round: poll every replica's slo_stats (queue
        depth + TTFT/inter-token p95), derive the desired replica
        count from queue pressure AND the latency SLOs, then apply it
        through the hysteresis delays.  Scale-up triggers on EITHER
        signal (deep queues or a violated SLO); scale-down requires
        the queue to justify it AND the SLOs to be comfortably met
        (downscale_slo_fraction of target), so a deployment running
        hot on latency never shrinks into violation."""
        import math
        import time

        import ray_tpu
        asc = d["autoscaling"]
        with self._state_lock:
            replicas = list(d["replicas"])
        if not replicas:
            return
        # Metric poll OUTSIDE the lock (it blocks on replica RPCs).  An
        # unreachable replica is counted at the per-replica target — a
        # saturated replica whose probe times out must read as "busy",
        # not zero, or peak load would trigger a downscale.
        tq = float(asc["target_queue_depth"]
                   or asc["target_ongoing_requests"])
        total = 0.0
        load: Dict[bytes, float] = {}
        ttfts: list = []
        itls: list = []
        for r, st in self._poll_slo_stats(replicas).items():
            if st is None:
                q = tq
            else:
                q = float(st.get("qlen") or 0.0)
                if st.get("ttft_p95_ms") is not None:
                    ttfts.append(float(st["ttft_p95_ms"]))
                if st.get("itl_p95_ms") is not None:
                    itls.append(float(st["itl_p95_ms"]))
            load[r] = q
            total += q
        ttft_p95 = max(ttfts) if ttfts else None
        itl_p95 = max(itls) if itls else None
        t_ttft = float(asc["target_ttft_ms"] or 0.0)
        t_itl = float(asc["target_itl_ms"] or 0.0)
        frac = float(asc["downscale_slo_fraction"])
        metrics = {"queue_depth": total, "ttft_p95_ms": ttft_p95,
                   "itl_p95_ms": itl_p95}
        with self._state_lock:
            if self._deployments.get(name) is not d:
                return          # deleted/replaced while polling
            # Gauge set AFTER the staleness check and under the lock:
            # set racing a delete() would otherwise re-create the
            # series _drop_serve_gauges just zeroed (push-model series
            # are never deleted node-side).  Pure cell write — the
            # gauge was constructed in __init__, never here.
            self._set_queue_depth_gauge(name, total)
            desired = int(math.ceil(total / tq)) or asc["min_replicas"]
            current = d["num_replicas"]
            reason = (f"queue_depth {total:g} at target {tq:g}/replica"
                      f" -> {desired}")
            hot = []
            if t_ttft and ttft_p95 is not None and ttft_p95 > t_ttft:
                hot.append(f"ttft_p95 {ttft_p95:.0f}ms > "
                           f"target {t_ttft:g}ms")
            if t_itl and itl_p95 is not None and itl_p95 > t_itl:
                hot.append(f"itl_p95 {itl_p95:.1f}ms > "
                           f"target {t_itl:g}ms")
            if hot and desired <= current:
                # A violated latency SLO scales up one step per
                # held-delay window even when queues look shallow
                # (the LLM case: decode saturation shows up as ITL,
                # not queue depth).
                desired = current + 1
                reason = "; ".join(hot)
            elif desired < current:
                slo_ok = ((not t_ttft or ttft_p95 is None
                           or ttft_p95 < frac * t_ttft)
                          and (not t_itl or itl_p95 is None
                               or itl_p95 < frac * t_itl))
                if not slo_ok:
                    desired = current
                    reason = ("downscale vetoed: latency within "
                              f"{frac:g} of SLO target")
            desired = max(asc["min_replicas"],
                          min(desired, asc["max_replicas"]))
            if desired == current:
                d["_scale_pressure_since"] = None
                self._record_decision_locked(d, "hold", current,
                                             desired, reason, metrics)
                return
            now = time.time()
            since = d.get("_scale_pressure_since")
            if since is None or since[0] != (desired > current):
                d["_scale_pressure_since"] = (desired > current, now)
                self._record_decision_locked(d, "pending", current,
                                             desired, reason, metrics)
                return
            delay = (asc["upscale_delay_s"] if desired > current
                     else asc["downscale_delay_s"])
            if now - since[1] < delay:
                self._record_decision_locked(d, "pending", current,
                                             desired, reason, metrics)
                return
            d["num_replicas"] = desired
            d["_scale_pressure_since"] = None
            action = ("scale_up" if desired > current
                      else "scale_down")
            self._record_decision_locked(d, action, current, desired,
                                         reason, metrics)
            self._reconcile(name, load=load)

    @staticmethod
    def _record_decision_locked(d: dict, action: str, current: int,
                                desired: int, reason: str,
                                metrics: dict) -> None:
        """Last decision + a bounded scale-event log (what `ray_tpu
        serve status` and the bursty bench read).  Caller holds
        _state_lock."""
        import time
        dec = {"at": time.time(), "action": action,
               "current": current, "desired": desired,
               "reason": reason, "metrics": metrics}
        d["_autoscale_last"] = dec
        if action in ("scale_up", "scale_down"):
            ev = d.setdefault("_autoscale_events", [])
            ev.append(dec)
            del ev[:-100]

    @staticmethod
    def _poll_slo_stats(replicas) -> Dict[bytes, Optional[dict]]:
        """actor_id -> slo_stats dict (None = unreachable).  Launches
        every probe, then collects with ONE bounded wait — the old
        serial get(timeout=5) per replica let a few wedged replicas
        stall a policy tick (or `serve status`) for 5 s EACH."""
        import ray_tpu
        out: Dict[bytes, Optional[dict]] = {}
        refs = {}
        for r in replicas:
            try:
                refs[r._actor_id] = r.slo_stats.remote()
            except Exception:
                out[r._actor_id] = None
        if refs:
            try:
                ray_tpu.wait(list(refs.values()),
                             num_returns=len(refs), timeout=5)
            except Exception:
                pass
            for aid, ref in refs.items():
                try:
                    out[aid] = ray_tpu.get(ref, timeout=0.1)
                except Exception:
                    out[aid] = None
        return out

    def _set_queue_depth_gauge(self, name: str, total: float) -> None:
        try:
            from ray_tpu.util.metrics import (SERVE_QUEUE_DEPTH_METRIC,
                                              shared_gauge)
            shared_gauge(
                SERVE_QUEUE_DEPTH_METRIC,
                description="total outstanding requests per "
                            "deployment (autoscaler's last poll)",
                tag_keys=("deployment",)).set(
                    total, tags={"deployment": name})
        except Exception:
            pass

    @staticmethod
    def _stop_replicas(replicas: List[Any]) -> None:
        import ray_tpu
        for r in replicas:
            try:
                ray_tpu.kill(r)
            except Exception:
                pass
