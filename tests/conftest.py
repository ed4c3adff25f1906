"""Shared test fixtures.

Mirrors the reference's conftest strategy (python/ray/tests/conftest.py:419
ray_start_regular): a real single-node runtime per test (or shared), plus a
virtual 8-device CPU mesh for all sharding/parallelism tests (the TPU-build
equivalent of the reference's fake multi-node cluster_utils.Cluster).
"""

import faulthandler
import os
import signal
import sys

# Force an 8-device CPU platform for jax BEFORE jax is imported anywhere:
# sharding/pjit tests exercise real multi-device meshes this way, and the
# suite runs identically with or without a chip attached (the chip is
# checked separately: chip_smoke.py and tests_tpu/).  XLA_FLAGS and
# JAX_PLATFORMS are inherited by every worker and subprocess the tests
# spawn; the config updates cover this process if jax is already
# imported.
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8"
                               ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402

# The one time limit of every test, in seconds (the slowest takes 74 s
# alone; six xdist workers on one host may triple that).
LIMIT = 240.0


def _on_limit(signum, frame):
    faulthandler.dump_traceback(file=sys.__stderr__, all_threads=True)
    raise TimeoutError(f"test ran past {LIMIT:.0f} s; every thread's "
                       "stack is in the captured stderr")


@pytest.fixture(autouse=True)
def _time_limit():
    """A hung test becomes one failure with its stacks in the log, and
    the other fixtures' shutdown() still runs.  The timer repeats, so a
    teardown that hangs in its turn is interrupted too."""
    previous = signal.signal(signal.SIGALRM, _on_limit)
    signal.setitimer(signal.ITIMER_REAL, LIMIT, LIMIT)
    yield
    signal.setitimer(signal.ITIMER_REAL, 0)
    signal.signal(signal.SIGALRM, previous)


@pytest.fixture
def ray_start():
    """Fresh runtime per test (reference: ray_start_regular)."""
    import ray_tpu
    ray_tpu.init(num_cpus=4, _system_config={
        "worker_idle_timeout_s": 60.0,
    })
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_tpu():
    """Runtime advertising 2 fake TPU chips (chip-pinning tests; no
    hardware touched)."""
    import ray_tpu
    ray_tpu.init(num_cpus=4, num_tpus=2)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(scope="session")
def ray_shared():
    """Session-shared runtime (reference: ray_start_shared)."""
    import ray_tpu
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax
    devs = jax.devices("cpu")
    assert len(devs) >= 8, "conftest must force 8 host devices"
    return devs


@pytest.fixture(scope="session")
def lm_params():
    """make(cfg, seed) -> transformer.init_params with every parameter a
    trained model has non-zero.  init_params leaves arch "gpt2"'s biases
    at zero and its learned positions at a hundredth of the token
    embedding, where a serving path that dropped one of them would still
    agree with the full-forward oracle."""
    def make(cfg, seed=0):
        from ray_tpu.models import transformer
        params = transformer.init_params(cfg, jax.random.PRNGKey(seed))
        leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
        key = jax.random.PRNGKey(seed + 1000)
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = path[-1].key
            if name.endswith("_b") or name.startswith("b_") \
                    or name == "pos_embed":
                leaf = leaf + 0.3 * jax.random.normal(
                    jax.random.fold_in(key, i), leaf.shape, leaf.dtype)
            out.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, out)
    return make
