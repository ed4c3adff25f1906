"""Thin client: remote-process API over the TCP control endpoint
(reference: ray.util.client / ray://)."""

import os
import subprocess
import sys
import textwrap

import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_thin_client_end_to_end():
    cluster = Cluster()
    try:
        ray_tpu.init(num_cpus=2, gcs_address=cluster.gcs_address)
        node = ray_tpu._session.node_service
        addr = f"127.0.0.1:{node.control_port}"

        # A detached actor created in-cluster, visible to the client.
        @ray_tpu.remote
        class Board:
            def __init__(self):
                self.v = {}

            def set(self, k, v):
                self.v[k] = v
                return True

            def get(self, k):
                return self.v.get(k)

        board = Board.options(name="board",
                              lifetime="detached").remote()
        ray_tpu.get(board.set.remote("seed", 7))

        script = textwrap.dedent(f"""
            import sys; sys.path.insert(0, {REPO!r})
            import numpy as np
            from ray_tpu.util import client
            import ray_tpu

            ctx = client.connect({addr!r})
            assert client.is_connected()

            # tasks
            @ray_tpu.remote
            def double(x): return x * 2
            assert ray_tpu.get(double.remote(21), timeout=60) == 42

            # big result: forced through the object-transfer fetch path
            @ray_tpu.remote
            def big(): return np.arange(200_000)
            arr = ray_tpu.get(big.remote(), timeout=60)
            assert arr.sum() == sum(range(200_000))

            # put (inline-over-RPC) consumed by a task
            ref = ray_tpu.put(np.ones(50_000))
            @ray_tpu.remote
            def total(a): return float(a.sum())
            assert ray_tpu.get(total.remote(ref), timeout=60) == 50_000.0

            # named actor created by the in-cluster driver
            b = ray_tpu.get_actor("board")
            assert ray_tpu.get(b.get.remote("seed"), timeout=60) == 7
            assert ray_tpu.get(b.set.remote("from_client", 1),
                               timeout=60)
            client.disconnect()
            print("THIN_CLIENT_OK")
        """)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=180)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "THIN_CLIENT_OK" in r.stdout

        # the client's write is visible in-cluster
        assert ray_tpu.get(board.get.remote("from_client"),
                           timeout=30) == 1
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()
