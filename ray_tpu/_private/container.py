"""Container-image isolation for workers (runtime_env image_uri).

Reference analog: the container/image_uri runtime-env plugin
(python/ray/_private/runtime_env/image_uri.py, applied by the per-node
agent at _private/runtime_env/agent/runtime_env_agent.py:161): the
worker process for a task/actor whose runtime_env names an image runs
INSIDE that image, giving multi-tenant clusters dependency isolation
without pip/conda (this repo rejects in-cluster installs by design —
image isolation is the sanctioned alternative).

The node service spawns such workers through ``build_worker_argv``:
the normal worker command wrapped in ``<runtime> run`` with the
session/state paths bind-mounted and the worker's control env passed
explicitly.  The runtime binary is a seam — ``podman`` by default
(rootless-friendly), ``RAY_TPU_CONTAINER_RUNTIME`` overrides, and CI
points it at a fake that records the image and execs the command,
which exercises every layer except the kernel namespace itself.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, List, Optional, Sequence

from ray_tpu._private.accelerators import LEASE_ENV_KEYS


def runtime_binary() -> str:
    return os.environ.get("RAY_TPU_CONTAINER_RUNTIME", "podman")


# Env vars the worker needs to find its node service + store + session,
# plus its accelerator lease (the chip pin keeps concurrent TPU workers
# on disjoint chips — dropping it would let two containerized workers
# grab the same device) and the compile cache an operator placed;
# everything else inside the container comes from the image.
_PASS_KEYS = ("RAY_TPU_WORKER_ID", "RAY_TPU_NODE_SOCKET",
              "RAY_TPU_STORE_PATH", "RAY_TPU_SESSION_DIR",
              "PYTHONPATH", "JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR",
              "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
              ) + LEASE_ENV_KEYS


def build_worker_argv(image: str, env: Dict[str, str],
                      mounts: Sequence[str],
                      python: Optional[str] = None) -> List[str]:
    """argv that runs ``python -m ray_tpu._private.worker_main`` inside
    `image`.

    --network/--ipc/--pid host: the worker speaks a unix socket to the
    node service and maps the host's /dev/shm store segment — the
    container isolates the FILESYSTEM (dependencies), not the runtime's
    data plane (same trade the reference's container plugin makes:
    image_uri.py passes the session socket dir through).
    """
    argv = [runtime_binary(), "run", "--rm",
            "--network=host", "--ipc=host", "--pid=host"]
    seen = set()
    for m in list(mounts) + ["/dev/shm"]:
        m = os.path.abspath(m)
        if m and m not in seen and os.path.exists(m):
            seen.add(m)
            argv += ["-v", f"{m}:{m}"]
    for k in _PASS_KEYS:
        if k in env:
            argv += ["--env", f"{k}={env[k]}"]
    argv += [image, python or "python3", "-m",
             "ray_tpu._private.worker_main"]
    return argv


def image_of(runtime_env: Optional[dict]) -> Optional[str]:
    """The image a task/actor's runtime env pins, if any."""
    if not runtime_env:
        return None
    return runtime_env.get("image_uri") or None
