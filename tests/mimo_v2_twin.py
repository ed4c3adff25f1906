"""The toy twin of the benchmark's MiMo-V2-Flash configuration
(tests/data/mimo_v2_tiny.json) that tests/test_mimo_v2.py (the model, the
reference, the paged layers, the ring kernels, the share) and
tests/test_mimo_v2_engine.py (everything that builds a PagedBatcher) both
run: two files, so that `--dist loadfile` can give them to two workers."""

import json
import os

import pytest

import jax
import jax.numpy as jnp

from benchmarks.lib import spec
from ray_tpu.models import transformer as tfm

KIND = spec.model_kind("sink-window-moe")
HERE = os.path.dirname(__file__)
with open(os.path.join(HERE, "data", "mimo_v2_tiny.json")) as f:
    TWIN = json.load(f)
LIMIT = KIND.TOLERANCES["logits_prefill_err"]
T = BS = 16                 # the engine's tile and the block
WINDOW = TWIN["sliding_window"]


def tiny(dtype="float32", config=None, **kw):
    kwargs = KIND.transformer_kwargs(config or TWIN, max_seq=256,
                                     param_dtype=dtype, dtype=dtype, **kw)
    for k in ("dtype", "param_dtype"):
        kwargs[k] = jnp.dtype(kwargs[k]).type
    return tfm.TransformerConfig(**kwargs)


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, tfm.init_params(cfg, jax.random.PRNGKey(0))


def tokens(n, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                              TWIN["vocab_size"]).tolist()
