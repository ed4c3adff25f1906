"""On-device lane: the compiled kernels and the engines on a real chip.

    python -m pytest tests_tpu/          # on the machine with the chip

The session is ONE process that holds the chip (libtpu gives a chip to
one process at a time), so nothing here spawns a worker that needs it.
Without a TPU the lane errors — it does not skip: a lane that reports
success with nothing run has hidden a dead backend before.
"""

import os
import sys

import pytest

# Bare `pytest tests_tpu/` doesn't put the repo root on sys.path
# (tests_tpu has no __init__.py and ray_tpu isn't installed).
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from ray_tpu._private.accelerators import use_compile_cache  # noqa: E402

use_compile_cache(os.environ)       # before jax is imported


def pytest_sessionstart(session):
    import jax
    backend = jax.default_backend()     # raises if the TPU is not there
    if backend != "tpu":
        pytest.exit(f"tests_tpu/ needs a TPU backend, found {backend!r} "
                    f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')})",
                    returncode=2)
