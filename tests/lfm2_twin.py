"""The toy twin of the benchmark's LFM2 configuration
(tests/data/lfm2_tiny.json) that tests/test_lfm2.py (the forward pass, the
paged layers, the comparison's limits) and tests/test_lfm2_engine.py (what
builds a PagedBatcher) both run: two files, so that `--dist loadfile` can
give them to two workers."""

import json
import os

import pytest

import jax
import jax.numpy as jnp

from benchmarks.lib import spec
from ray_tpu.models import transformer as tfm

KIND = spec.model_kind("lfm2-moe")
HERE = os.path.dirname(__file__)
with open(os.path.join(HERE, "data", "lfm2_tiny.json")) as f:
    TWIN = json.load(f)
LIMIT = KIND.TOLERANCES["logits_prefill_err"]
# bf16 at this toy's width of 256 errs more than at the published 2048: its
# own bound, still well under what the wrong programs and the control read
TOY_BF16 = {"logits_prefill_err": 0.04, "logits_decode_err": 0.04,
            "conv_tail_err": 0.05, "logits_after_hit_err": 1e-6,
            "route_mismatch_share": 0.02,
            "route_own_input_mismatch_share":
                KIND.TOLERANCES["route_own_input_mismatch_share"]}
T = BS = 16                 # the engine's tile and the block


def tiny(dtype="float32", **kw):
    kwargs = KIND.transformer_kwargs(TWIN, max_seq=256, param_dtype=dtype,
                                     dtype=dtype, **kw)
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
