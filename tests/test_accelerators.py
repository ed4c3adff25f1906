"""TPU accelerator manager: detection, typed slice resources, chip
pinning (reference: _private/accelerators/tpu.py
TPUAcceleratorManager)."""

import os

import pytest

import ray_tpu
from ray_tpu._private.accelerators import (ChipAllocator,
                                           detect_num_chips,
                                           tpu_resources,
                                           use_compile_cache)


def test_detection_env_override(monkeypatch):
    monkeypatch.setenv("RAY_TPU_NUM_TPUS", "4")
    assert detect_num_chips() == 4


def test_typed_slice_resources(monkeypatch):
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-8")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    res = tpu_resources(4)
    assert res["TPU"] == 4.0
    assert res["TPU-v5litepod-8"] == 4.0
    assert res["TPU-v5litepod-8-head"] == 1.0
    # Non-head slice workers advertise chips but no gang marker.
    monkeypatch.setenv("TPU_WORKER_ID", "1")
    res = tpu_resources(4)
    assert "TPU-v5litepod-8-head" not in res
    assert tpu_resources(0) == {}


def test_detection_counts_chips_not_directory_entries(monkeypatch):
    """A VFIO TPU VM has /dev/vfio/0..3 AND the /dev/vfio/vfio control
    node; only the numbered groups are chips."""
    import glob
    monkeypatch.delenv("RAY_TPU_NUM_TPUS", raising=False)
    files = {"/dev/accel[0-9]*": [],
             "/dev/vfio/[0-9]*": ["/dev/vfio/0", "/dev/vfio/1",
                                  "/dev/vfio/2", "/dev/vfio/3"]}
    monkeypatch.setattr(glob, "glob", lambda pat: files[pat])
    assert detect_num_chips() == 4
    files["/dev/accel[0-9]*"] = ["/dev/accel0"]
    assert detect_num_chips() == 1


def test_chip_allocator_lease_cycle():
    alloc = ChipAllocator(2)
    a = alloc.acquire(b"w1", 1)
    b = alloc.acquire(b"w2", 1)
    assert sorted(a + b) == [0, 1]
    # Exhausted pool: no lease, so no worker — never an unpinned one.
    assert alloc.acquire(b"w3", 1) is None
    # All or nothing: an undersized lease would starve the mesh the
    # worker was asked to build.
    alloc4 = ChipAllocator(4)
    assert alloc4.acquire(b"x1", 1) == [0]
    assert alloc4.acquire(b"x2", 4) is None
    # A two-chip lease is an aligned pair (a row of the host grid).
    assert alloc4.acquire(b"x3", 2) == [2, 3]
    assert alloc4.acquire(b"x4", 2) is None        # only chip 1 is free
    # Only the sub-slices libtpu can carve are leasable.
    assert [n for n in range(6) if alloc4.leasable(n)] == [1, 2, 4]
    # Death repays the lease; reuse is deterministic.
    assert alloc.release(b"w1")
    assert alloc.acquire(b"w4", 1) == a
    assert not alloc.release(b"unknown")           # no-op, never raises


def test_lease_env_carves_the_host():
    alloc = ChipAllocator(4)
    # A sub-slice gets the chip pin AND the bounds libtpu needs.
    assert alloc.visible_env([2]) == {
        "RAY_TPU_CHIPS": "2", "TPU_VISIBLE_CHIPS": "2",
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1"}
    assert alloc.visible_env([0, 1])["TPU_CHIPS_PER_PROCESS_BOUNDS"] \
        == "1,2,1"
    # The whole host: libtpu's variables are UNSET (None), so an
    # inherited pin cannot shrink it.
    assert alloc.visible_env([0, 1, 2, 3]) == {
        "RAY_TPU_CHIPS": "0,1,2,3", "TPU_VISIBLE_CHIPS": None,
        "TPU_CHIPS_PER_PROCESS_BOUNDS": None, "TPU_PROCESS_BOUNDS": None}
    # A one-chip host's only lease is the whole host.
    assert ChipAllocator(1).visible_env([0])["TPU_VISIBLE_CHIPS"] is None


def test_compile_cache_is_placed_from_outside():
    """Unset: one fixed directory in the checkout (the path is part of
    how an entry is found again).  Set: left alone."""
    env = {}
    use_compile_cache(env)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert env["JAX_COMPILATION_CACHE_DIR"] == os.path.join(
        repo, ".jax_cache")
    env = {"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}
    use_compile_cache(env)
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/elsewhere"


def _worker_env():
    return {k: os.environ.get(k) for k in (
        "RAY_TPU_CHIPS", "TPU_VISIBLE_CHIPS",
        "TPU_CHIPS_PER_PROCESS_BOUNDS", "JAX_PLATFORMS",
        "JAX_COMPILATION_CACHE_DIR")}


def test_lease_follows_requested_chips(monkeypatch):
    """The lease is the task's `TPU` resource, pinned workers never
    overlap, a TPU worker is held to the TPU platform, and a request no
    sub-slice can satisfy fails instead of running undersized."""
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "7")    # must not leak
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    ray_tpu.init(num_cpus=2, num_tpus=4)
    try:
        @ray_tpu.remote
        def env_of(delay=0.0):
            import time
            time.sleep(delay)      # hold the worker so both spawn
            return _worker_env()

        one = env_of.options(num_tpus=1)
        envs = ray_tpu.get([one.remote(0.5), one.remote(0.5)])
        assert sorted(e["TPU_VISIBLE_CHIPS"] for e in envs) == ["0", "1"]
        assert {e["JAX_PLATFORMS"] for e in envs} == {"tpu"}
        # Processes that compile for the chip share the cache; CPU
        # workers are left out of it.
        assert all(e["JAX_COMPILATION_CACHE_DIR"].endswith(".jax_cache")
                   for e in envs)
        # Four chips = the whole host: unpinned by libtpu's variables,
        # granted only after the one-chip workers' processes are gone.
        whole = ray_tpu.get(env_of.options(num_tpus=4).remote())
        assert whole["RAY_TPU_CHIPS"] == "0,1,2,3"
        assert whole["TPU_VISIBLE_CHIPS"] is None
        assert whole["TPU_CHIPS_PER_PROCESS_BOUNDS"] is None
        cpu = ray_tpu.get(env_of.remote())
        assert cpu["JAX_PLATFORMS"] == "cpu"
        assert cpu["JAX_COMPILATION_CACHE_DIR"] is None
        with pytest.raises(Exception, match="whole host"):
            ray_tpu.get(env_of.options(num_tpus=3).remote())
    finally:
        ray_tpu.shutdown()
