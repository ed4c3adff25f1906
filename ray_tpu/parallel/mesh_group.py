"""MeshGroup: gang-scheduled multi-host SPMD over a placement group.

The multi-host bring-up the reference gets from Train's backend executor
(python/ray/train/_internal/backend_executor.py:135 gang-spawns one
worker group per node, worker_group.py:102), rebuilt TPU-first:

  1. a placement group reserves one bundle per host (STRICT_SPREAD on a
     real cluster; PACK for single-machine simulation),
  2. one `_MeshHostWorker` actor is created per bundle,
  3. every worker calls `jax.distributed.initialize` (coordinator =
     rank 0), after which `jax.devices()` spans all hosts,
  4. `run(fn)` broadcasts an SPMD closure: each host executes the same
     program over the GLOBAL mesh, and XLA lays collectives over
     ICI/DCN.

This makes real the promise at parallel/mesh.py:17 ("handled by
parallel/mesh_group.py actors").
"""

from __future__ import annotations

import socket
from typing import Any, Callable, Dict, List, Optional, Sequence

import ray_tpu
from ray_tpu.util.placement_group import (PlacementGroup, placement_group,
                                          remove_placement_group)


class _MeshHostWorker:
    """One actor per host: owns that host's JAX runtime + local devices.

    Lives in its own worker process, so jax configuration (platform,
    device count, distributed init) is private to the gang.
    """

    def __init__(self, rank: int, world: int, platform: str,
                 local_devices: int) -> None:
        self.rank = rank
        self.world = world
        if platform == "cpu":
            n = max(local_devices, 1)
            # XLA_FLAGS is read at backend init, and this process has
            # not touched devices yet (a fresh gang worker has not); an
            # inherited device count (the test suite's) is replaced.
            import os
            import re
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", "",
                os.environ.get("XLA_FLAGS", ""))
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n}"
            ).strip()
            import jax
            jax.config.update("jax_platforms", "cpu")
            jax.config.update("jax_num_cpu_devices", n)

    def choose_coordinator(self) -> str:
        """Rank 0 picks the coordinator address ON ITS OWN HOST — the
        jax coordinator service binds in rank 0's process, so the
        address must be this machine's, not the driver's."""
        ip = _local_ip()
        return f"{ip}:{_free_port(ip)}"

    def setup(self, coordinator: str) -> int:
        """Join the gang; returns once every rank has connected."""
        import jax
        if self.world > 1:
            jax.distributed.initialize(coordinator_address=coordinator,
                                       num_processes=self.world,
                                       process_id=self.rank)
        return self.rank

    def device_counts(self) -> Dict[str, int]:
        import jax
        return {"local": jax.local_device_count(),
                "global": jax.device_count(), "rank": self.rank}

    def run(self, fn: Callable, *args, **kwargs) -> Any:
        """Execute fn(rank, *args, **kwargs) in this host's process.
        fn sees the multi-host JAX runtime (global jax.devices())."""
        return fn(self.rank, *args, **kwargs)

    def ping(self) -> int:
        return self.rank


def _local_ip() -> str:
    """This machine's reachable IP (UDP connect() sends no packets)."""
    try:
        # Context manager: an unroutable host raising mid-probe must
        # not leak the socket until GC (RT013 self-finding).
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.connect(("8.8.8.8", 80))
            return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"


def _free_port(host: str = "127.0.0.1") -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((host, 0))
        return s.getsockname()[1]


class MeshGroup:
    """A gang of per-host JAX runtimes forming one global device mesh.

    Usage:
        mg = MeshGroup(num_hosts=2, devices_per_host=4)   # CPU simulate
        counts = mg.device_counts()      # every host sees global=8
        results = mg.run(train_fn, cfg)  # SPMD: same fn on every host
        mg.shutdown()
    """

    def __init__(self, num_hosts: int,
                 devices_per_host: int = 0,
                 platform: str = "cpu",
                 resources_per_host: Optional[Dict[str, float]] = None,
                 strategy: str = "PACK",
                 name: Optional[str] = None,
                 slice_type: Optional[str] = None,
                 pg_timeout_s: float = 60.0) -> None:
        if platform not in ("cpu", "tpu"):
            raise ValueError("platform must be 'cpu' or 'tpu'")
        self.num_hosts = num_hosts
        if slice_type is not None:
            # Gang the group onto ONE whole TPU slice: tpu_slice_bundles
            # marks bundle 0 with the TPU-<type>-head resource, which is
            # both the one-gang-per-slice exclusivity claim and the
            # demand signal a slice-provider autoscaler provisions from
            # (autoscaler/autoscaler.py TPU-head gang path).
            from ray_tpu.util.placement_group import tpu_slice_bundles
            bundles = tpu_slice_bundles(
                slice_type, num_hosts,
                chips_per_host=devices_per_host or 4)
            res = dict(bundles[1] if num_hosts > 1 else bundles[0])
            # One rank per host is the gang's whole point: PACK would
            # happily co-locate two bundles on one host (only bundle 0
            # carries the slice-head pin), splitting the ICI ring.
            strategy = "STRICT_SPREAD"
        else:
            res = dict(resources_per_host
                       or ({"CPU": 1} if platform == "cpu"
                           else {"TPU": float(devices_per_host or 4)}))
            bundles = [dict(res) for _ in range(num_hosts)]
        self.pg: PlacementGroup = placement_group(
            bundles, strategy=strategy, name=name)
        if not self.pg.wait(timeout_seconds=pg_timeout_s):
            remove_placement_group(self.pg)
            raise TimeoutError(
                f"MeshGroup placement group ({num_hosts} x {res}, "
                f"{strategy}) did not become ready")
        self._res = res
        self._platform = platform
        self._devices_per_host = devices_per_host
        self.restarts = 0
        # The PG was sized for num_hosts bundles; resize() can shrink
        # below and grow back up to this, never beyond.
        self.max_hosts = num_hosts
        self.resizes = 0
        self._spawn_gang()

    def _spawn_gang(self) -> None:
        cls = ray_tpu.remote(_MeshHostWorker)
        res, platform = self._res, self._platform
        tpus = res.get("TPU", 0) if platform == "tpu" else 0
        self.workers = [
            cls.options(num_cpus=res.get("CPU", 0), num_tpus=tpus,
                        placement_group=self.pg,
                        placement_group_bundle_index=i).remote(
                rank=i, world=self.num_hosts, platform=platform,
                local_devices=self._devices_per_host)
            for i in range(self.num_hosts)
        ]
        # Rank 0 picks the coordinator address on ITS host (which may
        # not be the driver's machine), then every rank joins — setup
        # is a barrier: jax.distributed.initialize returns only once
        # all ranks have connected.
        coordinator = ray_tpu.get(
            self.workers[0].choose_coordinator.remote(), timeout=120)
        ray_tpu.get([w.setup.remote(coordinator) for w in self.workers],
                    timeout=300)

    # -- elasticity (reference: backend_executor.py restart paths) ------
    def rebuild(self, retry_timeout_s: float = 180.0) -> None:
        """Tear down and re-rendezvous the whole gang.  One dead member
        poisons jax.distributed for everyone (the survivors hang in
        collectives against the dead peer), so recovery is always
        all-ranks: kill, respawn on the SAME placement-group bundles,
        re-initialize.

        The respawn retries: when the gang died WITH its nodes (slice
        preemption), actor creation races node-death detection and PG
        repair — the bundle map may still point at dead nodes for a few
        heartbeats, and replacement nodes may still be provisioning."""
        import time as _time
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        self.restarts += 1
        deadline = _time.monotonic() + retry_timeout_s
        while True:
            try:
                self._spawn_gang()
                return
            except Exception:
                for w in getattr(self, "workers", []):
                    try:
                        ray_tpu.kill(w)
                    except Exception:
                        pass
                if _time.monotonic() > deadline:
                    raise
                _time.sleep(1.0)

    def resize(self, new_num_hosts: int,
               retry_timeout_s: float = 180.0) -> None:
        """Re-rendezvous the gang at a DIFFERENT world size on the
        same placement group (elastic shrink on preemption / grow-back
        on heal — the train/elastic.py resize, at the mesh layer).

        jax.distributed world membership is fixed at initialize(), so
        a resize is necessarily a full re-rendezvous: kill all ranks,
        respawn ``new_num_hosts`` of them on the first bundles, and
        re-initialize with the new world size.  State survival is the
        caller's job (reshard from an in-cluster checkpoint — the
        TpuTrainer elastic path — or re-load from disk).  Grow is
        bounded by ``max_hosts``: the placement group reserved exactly
        that many bundles at construction."""
        if not 1 <= new_num_hosts <= self.max_hosts:
            raise ValueError(
                f"new_num_hosts {new_num_hosts} not in "
                f"[1, {self.max_hosts}] (the placement group has "
                f"{self.max_hosts} bundles)")
        import time as _time
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        self.num_hosts = new_num_hosts
        self.resizes += 1
        deadline = _time.monotonic() + retry_timeout_s
        while True:
            try:
                self._spawn_gang()
                return
            except Exception:
                for w in getattr(self, "workers", []):
                    try:
                        ray_tpu.kill(w)
                    except Exception:
                        pass
                if _time.monotonic() > deadline:
                    raise
                _time.sleep(1.0)

    def run_elastic(self, fn: Callable, *args,
                    max_restarts: int = 2,
                    timeout: Optional[float] = None,
                    **kwargs) -> List[Any]:
        """run(), surviving gang-member death: on a worker failure the
        gang is rebuilt and fn re-runs from scratch on every rank — fn
        must be resumable (load its latest checkpoint at start), the
        TpuTrainer/orbax pattern.  Reference:
        train/_internal/backend_executor.py worker-group restart +
        FailureConfig."""
        import time as _time
        from ray_tpu import exceptions as exc
        attempt = 0
        while True:
            refs = [w.run.remote(fn, *args, **kwargs)
                    for w in self.workers]
            deadline = (None if timeout is None
                        else _time.monotonic() + timeout)
            failure: Optional[BaseException] = None
            checked: set = set()
            while True:
                # Poll instead of one blocking get: a dead rank leaves
                # the survivors HUNG in collectives, so their refs
                # never resolve — the dead rank's error must be
                # noticed while the others are still pending.
                done, not_done = ray_tpu.wait(
                    refs, num_returns=len(refs), timeout=1.0)
                for r in done:
                    if r.binary() in checked:
                        continue
                    checked.add(r.binary())
                    try:
                        ray_tpu.get(r)
                    except BaseException as e:   # noqa: BLE001
                        failure = e
                        break
                if failure is not None or not not_done:
                    break
                if deadline is not None and _time.monotonic() > deadline:
                    # Survivors may be hung in collectives: a leaked
                    # gang is unusable, so tear it down before raising.
                    self.rebuild()
                    raise TimeoutError(
                        f"run_elastic timed out after {timeout}s")
            if failure is None:
                return ray_tpu.get(refs)
            worker_death = isinstance(
                failure, (exc.ActorDiedError, exc.WorkerCrashedError,
                          exc.ActorUnavailableError))
            if not worker_death or attempt >= max_restarts:
                # Application error (or restart budget exhausted): the
                # other ranks are hung against the failed peer — kill
                # and respawn the gang so the MeshGroup stays usable,
                # then surface the error.
                self.rebuild()
                raise failure
            attempt += 1
            self.rebuild()

    def device_counts(self) -> List[Dict[str, int]]:
        return ray_tpu.get(
            [w.device_counts.remote() for w in self.workers], timeout=60)

    def run(self, fn: Callable, *args, timeout: Optional[float] = None,
            **kwargs) -> List[Any]:
        """Run fn(rank, *args, **kwargs) on EVERY host concurrently
        (SPMD: all ranks must execute the same jitted programs).
        Returns per-rank results ordered by rank."""
        refs = [w.run.remote(fn, *args, **kwargs) for w in self.workers]
        return ray_tpu.get(refs, timeout=timeout)

    def shutdown(self) -> None:
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        try:
            remove_placement_group(self.pg)
        except Exception:
            pass
