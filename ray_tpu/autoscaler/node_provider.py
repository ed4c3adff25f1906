"""NodeProvider: how the autoscaler actually acquires machines.

Reference: python/ray/autoscaler/node_provider.py (NodeProvider ABC;
cloud impls live per provider).  Here the in-tree implementation is
LocalNodeProvider, which "provisions" worker nodes as OS processes on
this machine (`python -m ray_tpu._private.node_service`) — the same
mechanics as a cloud provider modulo the machine actually being remote.
A TPU-pod provider would subclass NodeProvider and create/delete
GKE/QueuedResources slices instead; the autoscaler above is unchanged.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional


class NodeProvider:
    """Minimal provider contract the autoscaler needs."""

    def create_node(self, resources: Dict[str, float]) -> str:
        """Start one worker node; returns a provider-scoped node name."""
        raise NotImplementedError

    def terminate_node(self, name: str) -> None:
        raise NotImplementedError

    def non_terminated_nodes(self) -> List[str]:
        raise NotImplementedError

    def node_cluster_id(self, name: str) -> Optional[bytes]:
        """GCS node_id of a provider node once registered, else None."""
        raise NotImplementedError

    def shutdown(self) -> None:
        for name in list(self.non_terminated_nodes()):
            self.terminate_node(name)


def _drain(pipe) -> None:
    try:
        for _ in pipe:
            pass
    except (OSError, ValueError):
        pass


class LocalNodeProvider(NodeProvider):
    """Worker nodes as local node-service subprocesses."""

    def __init__(self, gcs_address: tuple,
                 env: Optional[Dict[str, str]] = None) -> None:
        self.gcs_address = gcs_address
        self._env = dict(env or {})
        self._procs: Dict[str, subprocess.Popen] = {}
        self._node_ids: Dict[str, bytes] = {}
        self._seq = 0

    def create_node(self, resources: Dict[str, float]) -> str:
        env = dict(os.environ)
        env.update(self._env)
        env.setdefault("JAX_PLATFORMS", "cpu")
        import ray_tpu
        pkg_parent = os.path.dirname(os.path.dirname(
            os.path.abspath(ray_tpu.__file__)))
        parts = [pkg_parent] + [p for p in sys.path
                                if p and os.path.isdir(p)]
        env["PYTHONPATH"] = os.pathsep.join(
            dict.fromkeys(parts + env.get("PYTHONPATH", "").split(
                os.pathsep)))
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.node_service",
             "--gcs-host", self.gcs_address[0],
             "--gcs-port", str(self.gcs_address[1]),
             "--resources", json.dumps(resources)],
            env=env, stdout=subprocess.PIPE)
        # select-based deadline: readline() could block past any wall
        # clock check if the node prints nothing.  On timeout/exit the
        # process is killed and NOT registered — a half-launched node
        # must never count toward max_workers.
        import select
        deadline = time.time() + 60.0
        buf = b""
        node_id = b""
        fd = proc.stdout.fileno()
        while True:
            remaining = deadline - time.time()
            if remaining <= 0:
                proc.kill()
                raise TimeoutError(
                    "provider node did not print NODE_READY in 60s")
            ready, _, _ = select.select([fd], [], [], remaining)
            if not ready:
                continue
            chunk = os.read(fd, 4096)
            if not chunk:
                proc.kill()
                raise RuntimeError(
                    f"provider node exited rc={proc.poll()}")
            buf += chunk
            *complete, buf = buf.split(b"\n")   # keep partial tail
            for line in complete:
                if line.startswith(b"NODE_READY="):
                    node_id = bytes.fromhex(
                        line.split(b"=", 1)[1].decode())
                    break
            if node_id:
                break
        threading.Thread(target=_drain, args=(proc.stdout,),
                         daemon=True).start()
        self._seq += 1
        name = f"local-{self._seq}"
        self._procs[name] = proc
        self._node_ids[name] = node_id
        return name

    def terminate_node(self, name: str, force: bool = False) -> None:
        """SIGTERM asks the node to drain and leave (running work may
        finish inside the grace); force=True is SIGKILL, a node lost
        without notice."""
        proc = self._procs.pop(name, None)
        self._node_ids.pop(name, None)
        if proc is None:
            return
        if proc.poll() is None:
            if not force:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                    return
                except subprocess.TimeoutExpired:
                    pass
            proc.kill()

    def non_terminated_nodes(self) -> List[str]:
        return [n for n, p in self._procs.items() if p.poll() is None]

    def node_cluster_id(self, name: str) -> Optional[bytes]:
        return self._node_ids.get(name)


class TpuSliceProvider(NodeProvider):
    """Provider contract for WHOLE-TPU-SLICE provisioning (reference
    role: the TPU pod support in autoscaler cloud providers +
    _private/accelerators/tpu.py's `TPU-<type>-head` gang resource).

    A slice is an atomic unit of num_hosts machines wired by ICI; the
    autoscaler asks for slices (never individual slice hosts) when the
    demand contains `TPU-<type>-head` gang bundles, and each launched
    host must register advertising:

        {"TPU": <chips_per_host>, "TPU-<type>-head": 1}   # host 0
        {"TPU": <chips_per_host>}                         # hosts 1..N-1

    so tpu_slice_bundles() placement groups land on exactly one slice.
    Cloud implementations map create_slice to GKE node pools or
    QueuedResources; delete_slice must release the whole slice (TPU
    slices cannot shrink).  `create_node` (inherited contract) may be
    implemented as a 1-host slice or left unsupported for pure-TPU
    pools.
    """

    def create_slice(self, slice_type: str, num_hosts: int) -> str:
        """Provision one slice; returns a provider-scoped slice name."""
        raise NotImplementedError

    def delete_slice(self, name: str) -> None:
        raise NotImplementedError

    def list_slices(self) -> List[str]:
        raise NotImplementedError

    def slice_nodes(self, name: str) -> List[str]:
        """Provider node names of every host in the slice."""
        raise NotImplementedError
