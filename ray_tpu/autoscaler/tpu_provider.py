"""Concrete TPU-slice provisioning: a QueuedResources-style cloud API
client + a v2-style reconciler that converges desired <-> actual slices.

Reference analogs:
* python/ray/autoscaler/v2/instance_manager/reconciler.py — the
  Reconciler diffs desired instances against cloud reality every tick
  and issues create/terminate/retry transitions;
* the GCP TPU QueuedResources flow the reference's TPU pod docs target:
  an async create request moves QUEUED -> PROVISIONING -> ACTIVE (or
  FAILED), a slice is atomic (all hosts or nothing), and preemption
  kills the whole slice.

`QueuedResourcesApi` is the mockable seam: `LocalQueuedResourcesApi`
"provisions" slice hosts as local node-service subprocesses (the CI
fake — same mechanics as a real slice modulo the machines being
remote), with failure injection for chaos tests.  A GKE/GCP
implementation plugs in by implementing the full seam over HTTP: the
four queued-resource calls (create/get/delete/list) plus the host
surface (`non_terminated_nodes`, `node_cluster_id`, `shutdown`) the
autoscaler polls every reconcile tick.

`QueuedResourcesSliceProvider` implements the autoscaler's
TpuSliceProvider contract on top of the API: `create_slice` records
DESIRED state and returns immediately; the reconciler thread drives
cloud reality toward it — retrying failed creates with fresh attempt
names, and declaring a slice dead (then re-provisioning it) when any
host process dies, because a TPU slice with a dead host is useless as
a whole (ICI is cut).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional

from ray_tpu.autoscaler.node_provider import (LocalNodeProvider,
                                              TpuSliceProvider)

QUEUED = "QUEUED"
PROVISIONING = "PROVISIONING"
ACTIVE = "ACTIVE"
FAILED = "FAILED"


class QueuedResourcesApi:
    """The cloud seam.  Names are caller-chosen and unique per attempt;
    `get` returns None for unknown names.  Implementations must also
    provide the host surface (non_terminated_nodes / node_cluster_id /
    shutdown) — the autoscaler reads it every tick."""

    def create_queued_resource(self, name: str, slice_type: str,
                               num_hosts: int) -> None:
        raise NotImplementedError

    def get(self, name: str) -> Optional[dict]:
        """-> {"state": ..., "hosts": [provider node names]} or None."""
        raise NotImplementedError

    def delete(self, name: str) -> None:
        raise NotImplementedError

    def list_names(self) -> List[str]:
        raise NotImplementedError

    # -- host surface ------------------------------------------------------
    def non_terminated_nodes(self) -> List[str]:
        """Provider node names of every live slice host."""
        raise NotImplementedError

    def node_cluster_id(self, node_name: str):
        """GCS node_id of a host once registered, else None."""
        raise NotImplementedError

    def shutdown(self) -> None:
        """Release every host this API provisioned."""
        raise NotImplementedError


class LocalQueuedResourcesApi(QueuedResourcesApi):
    """Slice hosts as local node-service subprocesses (CI fake).

    Each host registers with the GCS advertising the TPU gang shape
    (`{"TPU": chips, "TPU-<type>-head": 1}` on host 0) so
    tpu_slice_bundles placement groups land on exactly one slice.

    Failure injection:
      fail_next_creates(n)  — the next n creates land in FAILED;
      kill_slice(name)      — SIGKILL every host (preemption).
    """

    def __init__(self, gcs_address: tuple,
                 chips_per_host: int = 4,
                 host_resources: Optional[Dict[str, float]] = None
                 ) -> None:
        self._local = LocalNodeProvider(gcs_address)
        self._chips = chips_per_host
        self._extra = dict(host_resources or {"CPU": 1.0})
        self._state: Dict[str, dict] = {}
        self._fail_budget = 0
        self._lock = threading.Lock()

    # -- failure injection -------------------------------------------------
    def fail_next_creates(self, n: int) -> None:
        with self._lock:
            self._fail_budget += n

    def kill_slice(self, name: str) -> None:
        info = self._state.get(name)
        if not info:
            return
        for node in info["hosts"]:
            self._local.terminate_node(node, force=True)

    # -- QueuedResourcesApi ------------------------------------------------
    def create_queued_resource(self, name: str, slice_type: str,
                               num_hosts: int) -> None:
        with self._lock:
            if name in self._state:
                raise ValueError(f"duplicate queued resource {name!r}")
            if self._fail_budget > 0:
                self._fail_budget -= 1
                self._state[name] = {"state": FAILED, "hosts": [],
                                     "slice_type": slice_type}
                return
            self._state[name] = {"state": PROVISIONING, "hosts": [],
                                 "slice_type": slice_type}
        hosts = []
        try:
            for i in range(num_hosts):
                res = dict(self._extra)
                res["TPU"] = float(self._chips)
                if i == 0:
                    res[f"TPU-{slice_type}-head"] = 1.0
                hosts.append(self._local.create_node(res))
        except Exception:
            for h in hosts:
                self._local.terminate_node(h)
            self._state[name] = {"state": FAILED, "hosts": [],
                                 "slice_type": slice_type}
            return
        self._state[name] = {"state": ACTIVE, "hosts": hosts,
                             "slice_type": slice_type}

    def get(self, name: str) -> Optional[dict]:
        info = self._state.get(name)
        if info is None:
            return None
        out = dict(info)
        if info["state"] == ACTIVE:
            alive = set(self._local.non_terminated_nodes())
            if any(h not in alive for h in info["hosts"]):
                # Preempted/crashed host: cloud reports SUSPENDED-like
                # failure for the whole slice.
                out["state"] = FAILED
        return out

    def delete(self, name: str) -> None:
        info = self._state.pop(name, None)
        if info:
            for h in info["hosts"]:
                self._local.terminate_node(h)

    def list_names(self) -> List[str]:
        return list(self._state)

    # helpers for the provider
    def node_cluster_id(self, node_name: str):
        return self._local.node_cluster_id(node_name)

    def non_terminated_nodes(self) -> List[str]:
        return self._local.non_terminated_nodes()

    def shutdown(self) -> None:
        self._local.shutdown()


class QueuedResourcesSliceProvider(TpuSliceProvider):
    """TpuSliceProvider over a QueuedResourcesApi with a reconciler.

    Desired state: slice name -> (slice_type, num_hosts).  Actual
    state: the API's queued resources, one per attempt, named
    `<slice>--a<N>`.  `reconcile_once()` (also run by the background
    thread) converges:

      desired, no attempt        -> create attempt 1
      attempt FAILED             -> delete it, create attempt N+1
                                    (up to max_retries, then give up
                                    and drop the desired entry)
      attempt ACTIVE, host dead  -> delete it, create attempt N+1
      attempt exists, undesired  -> delete it

    (reference: autoscaler/v2/instance_manager/reconciler.py
    _step_next — the same diff-and-transition loop over instances).
    """

    def __init__(self, api: QueuedResourcesApi, max_retries: int = 3,
                 on_give_up: Optional[Callable[[str], None]] = None
                 ) -> None:
        self.api = api
        self.max_retries = max_retries
        self.on_give_up = on_give_up
        self._desired: Dict[str, dict] = {}   # name -> spec + attempt
        self._lock = threading.RLock()
        # Serializes whole reconcile passes: create_slice/delete_slice
        # call reconcile_once synchronously while the background loop
        # also runs it; overlapping passes would double-create attempts.
        self._reconcile_lock = threading.Lock()
        self._seq = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle ---------------------------------------------------------
    def start(self, interval_s: float = 1.0
              ) -> "QueuedResourcesSliceProvider":
        def loop():
            while not self._stop.is_set():
                try:
                    self.reconcile_once()
                except Exception:
                    pass
                self._stop.wait(interval_s)
        self._thread = threading.Thread(
            target=loop, daemon=True, name="rtpu-slice-reconciler")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=5)

    # -- TpuSliceProvider contract ----------------------------------------
    def create_slice(self, slice_type: str, num_hosts: int) -> str:
        with self._lock:
            self._seq += 1
            name = f"slice-{self._seq}"
            self._desired[name] = {"slice_type": slice_type,
                                   "num_hosts": num_hosts,
                                   "attempt": 0}
        # Kick convergence, but never let a transient API error escape
        # AFTER desired state is recorded: the caller must get the name
        # (and record its gang pin) or the background loop's eventual
        # success would double-provision the gang.
        try:
            self.reconcile_once()
        except Exception:
            pass
        return name

    def delete_slice(self, name: str) -> None:
        with self._lock:
            self._desired.pop(name, None)
        try:
            self.reconcile_once()
        except Exception:
            pass

    def list_slices(self) -> List[str]:
        with self._lock:
            return list(self._desired)

    def slice_nodes(self, name: str) -> List[str]:
        with self._lock:
            d = self._desired.get(name)
            if d is None or not d["attempt"]:
                return []
            attempt_name = f"{name}--a{d['attempt']}"
        info = self.api.get(attempt_name)
        return list(info["hosts"]) if info else []

    # inherited NodeProvider surface
    def create_node(self, resources):
        raise NotImplementedError(
            "pure-TPU pool: per-host create is not supported; demand "
            "whole slices via TPU-<type>-head gang bundles")

    def terminate_node(self, name: str) -> None:
        raise NotImplementedError(
            "TPU slices are atomic; use delete_slice")

    def non_terminated_nodes(self) -> List[str]:
        return self.api.non_terminated_nodes()

    def node_cluster_id(self, name: str):
        return self.api.node_cluster_id(name)

    def shutdown(self) -> None:
        self.stop()
        with self._lock:
            self._desired.clear()
        for qr in self.api.list_names():
            self.api.delete(qr)

    # -- the v2-style convergence step ------------------------------------
    def reconcile_once(self) -> dict:
        with self._reconcile_lock:
            return self._reconcile_locked()

    def _reconcile_locked(self) -> dict:
        actions = {"created": 0, "retried": 0, "cleaned": 0,
                   "gave_up": 0}
        with self._lock:
            desired = {n: dict(d) for n, d in self._desired.items()}
        # 1) drive each desired slice toward one ACTIVE attempt
        for name, d in desired.items():
            attempt = d["attempt"]
            attempt_name = f"{name}--a{attempt}" if attempt else None
            info = self.api.get(attempt_name) if attempt_name else None
            if info is not None and info["state"] in (QUEUED,
                                                      PROVISIONING,
                                                      ACTIVE):
                continue
            if info is not None:           # FAILED (incl. dead host)
                self.api.delete(attempt_name)
            if attempt >= self.max_retries:
                # Give-up is terminal FOR THIS SLICE NAME: drop the
                # desired entry entirely (no leak; attempts are reaped
                # below).  If the gang is still pending, the autoscaler
                # sees the name vanish from list_slices, clears its
                # pin, and re-provisions at its launch-cooldown pace —
                # retry-while-demand-exists with pacing, the reference
                # v1 failed-launch behavior.  on_give_up is the hook
                # for callers that want to fail the gang instead.
                with self._lock:
                    self._desired.pop(name, None)
                actions["gave_up"] += 1
                if self.on_give_up:
                    try:
                        self.on_give_up(name)
                    except Exception:
                        pass
                continue
            with self._lock:
                if name not in self._desired:
                    continue               # deleted concurrently
                self._desired[name]["attempt"] = attempt + 1
            self.api.create_queued_resource(
                f"{name}--a{attempt + 1}", d["slice_type"],
                d["num_hosts"])
            actions["retried" if attempt else "created"] += 1
        # 2) reap attempts no longer desired (stale retries, deletes)
        with self._lock:
            live = {f"{n}--a{d['attempt']}"
                    for n, d in self._desired.items() if d["attempt"]}
        for qr in self.api.list_names():
            if qr not in live:
                self.api.delete(qr)
                actions["cleaned"] += 1
        return actions
