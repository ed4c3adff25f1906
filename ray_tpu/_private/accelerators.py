"""TPU accelerator management: chip detection, typed slice resources,
and per-worker chip visibility.

Reference surface: python/ray/_private/accelerators/tpu.py —
`TPUAcceleratorManager` detects chips via device files and GCE metadata
(tpu.py:107-117), advertises the pod-slice gang resource
`TPU-{type}-head` on worker 0 (tpu.py:360-362), and pins workers to
their allocation by exporting `TPU_VISIBLE_CHIPS` plus the bounds
variables.

This build keeps the same three capabilities but node-native: the node
service owns a chip-id pool sized by the node's TPU resource; each TPU
worker process leases exactly the chips its task asked for at spawn and
the pool is repaid once that process has exited.  libtpu gives a chip
to one process at a time, so workers are the only processes that touch
it: detection reads device files and never initializes a jax backend —
a driver that did would hold the chip its own workers need.
"""

from __future__ import annotations

import glob
import math
import os
import threading
from typing import Dict, List, MutableMapping, Optional


def detect_num_chips() -> int:
    """Chip count: env override, else one per TPU device file — what an
    unpinned TPU process on this host would see.  A TPU VM exposes its
    chips either as /dev/accel<N> or as numbered VFIO groups
    /dev/vfio/<N> (next to the /dev/vfio/vfio control node, which is
    not a chip)."""
    env = os.environ.get("RAY_TPU_NUM_TPUS")
    if env is not None:
        return int(env)
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def detect_accelerator_type() -> Optional[str]:
    """Slice type, e.g. "v5litepod-8" (reference: GCE instance metadata;
    here the standard TPU VM env vars)."""
    return (os.environ.get("TPU_ACCELERATOR_TYPE")
            or os.environ.get("RAY_TPU_ACCELERATOR_TYPE"))


def tpu_resources(num_chips: float) -> Dict[str, float]:
    """The resource dict a TPU host advertises: plain TPU chips, the
    typed per-chip resource, and — on slice worker 0 — the slice-head
    gang marker."""
    if not num_chips:
        return {}
    res: Dict[str, float] = {"TPU": float(num_chips)}
    acc_type = detect_accelerator_type()
    if acc_type:
        res[f"TPU-{acc_type}"] = float(num_chips)
        if os.environ.get("TPU_WORKER_ID", "0") == "0":
            res[f"TPU-{acc_type}-head"] = 1.0
    return res


def chips_for(resources: Optional[Dict[str, float]]) -> int:
    """Whole chips a task's `TPU` resource leases (a fractional request
    still needs a chip of its own: one process per chip)."""
    return math.ceil((resources or {}).get("TPU", 0) - 1e-9)


# One fixed directory in the checkout: the path is part of how a cache
# is found again, so a session directory, a temp name or a pid would
# never hit.
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def use_compile_cache(env: MutableMapping[str, str]) -> None:
    """Give a process that compiles for the chip (`env` is its
    environment before it imports jax) jax's persistent compilation
    cache.  An operator's JAX_COMPILATION_CACHE_DIR is left alone;
    unset, the cache lives in the checkout.  The storing threshold
    drops to zero so the sub-second decode steps are kept as well."""
    env.setdefault("JAX_COMPILATION_CACHE_DIR", _DEFAULT_CACHE_DIR)
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")


# The lease as a worker sees it: its chip ids (ray_tpu.get_tpu_ids), and
# the variables libtpu carves a host into per-process sub-slices from —
# which a process that owns the whole host must not see at all.
_LEASE = "RAY_TPU_CHIPS"
_VISIBLE = "TPU_VISIBLE_CHIPS"
_CHIP_BOUNDS = "TPU_CHIPS_PER_PROCESS_BOUNDS"
_PROCESS_BOUNDS = "TPU_PROCESS_BOUNDS"
LEASE_ENV_KEYS = (_LEASE, _VISIBLE, _CHIP_BOUNDS, _PROCESS_BOUNDS)
_BOUNDS_FOR = {1: "1,1,1", 2: "1,2,1"}


class ChipAllocator:
    """Free-list of local chip ids.  A TPU worker leases, all or
    nothing, the chips its task's `TPU` resource asks for: one, an
    aligned pair, or the whole host — the sub-slices libtpu can carve."""

    def __init__(self, num_chips: int) -> None:
        self.num_chips = int(num_chips)
        self._free: List[int] = list(range(self.num_chips))
        self._held: Dict[bytes, List[int]] = {}
        self._lock = threading.Lock()

    def leasable(self, count: int) -> bool:
        """Whether a `count`-chip lease can ever be granted here."""
        return count == self.num_chips or (
            count < self.num_chips and count in _BOUNDS_FOR)

    def acquire(self, worker_id: bytes, count: int) -> Optional[List[int]]:
        """Lease `count` chips, or None while they are not free — the
        caller waits: an unpinned or undersized worker would collide
        with the live leases or starve its own mesh."""
        with self._lock:
            if count == 2 and self.num_chips > 2:
                # libtpu's "1,2,1" sub-slice is a row of the host grid.
                take = next(([c, c + 1] for c in self._free
                             if c % 2 == 0 and c + 1 in self._free), None)
            else:
                take = self._free[:count]
            if take is None or len(take) < count:
                return None
            self._free = [c for c in self._free if c not in take]
            self._held[worker_id] = take
            return take

    def release(self, worker_id: bytes) -> bool:
        """Repay a lease; True if one was held.  Called once the
        holder's process is gone — the chip is not free before that."""
        with self._lock:
            chips = self._held.pop(worker_id, None)
            if not chips:
                return False
            # Repay in sorted order so reuse is deterministic.
            self._free = sorted(self._free + chips)
            return True

    def visible_env(self, chips: List[int]) -> Dict[str, Optional[str]]:
        """Env pinning a worker to its lease (reference: tpu.py
        set_current_process_visible_accelerator_ids); None = unset.  A
        worker that leased the whole host gets none of libtpu's
        variables: its defaults describe the whole host."""
        ids = ",".join(str(c) for c in chips)
        if len(chips) == self.num_chips:
            return {_LEASE: ids, _VISIBLE: None, _CHIP_BOUNDS: None,
                    _PROCESS_BOUNDS: None}
        return {_LEASE: ids, _VISIBLE: ids,
                _CHIP_BOUNDS: _BOUNDS_FOR[len(chips)],
                _PROCESS_BOUNDS: "1,1,1"}
