"""`correct` has been shown to fail: the control is the plain reference
put in the program's place and computed in the nearest precision below the
configuration's bfloat16 (q, k, v and the pool rounded to fp8), and it has
to read OVER the kind's limit where the program reads under it.  Here at a
size a test run can hold, on the CPU; on the chip at the cells' own size
the readings are in PERF.md section 2."""

import types

import numpy as np
import pytest

from benchmarks.lib import reference, spec

SEEDS = [5, 2 ** 31 + 11, 3_000_000_001]


@pytest.fixture(scope="module")
def cfg():
    import jax.numpy as jnp
    return types.SimpleNamespace(n_heads=4, kv_heads=2, head_dim=64,
                                 dtype=jnp.bfloat16)


def _pool(cfg, seed):
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    nb, bs, slots, width = 24, 16, 4, 5
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)))
    kp, vp = (jax.random.normal(k, (1, nb, cfg.kv_heads, bs, cfg.head_dim),
                                jnp.bfloat16) for k in keys)
    tables = rng.permutation(nb)[:slots * width].reshape(slots, width)
    return types.SimpleNamespace(
        kp=kp, vp=vp, block_tables=jnp.asarray(tables.astype(np.int32)),
        lengths=jnp.asarray(np.array([0, 79, 30, 5], np.int32)))


@pytest.mark.parametrize("seed", SEEDS)
def test_fp8_control_is_not_correct_where_the_program_is(cfg, seed):
    kind = spec.model_kind("dense-llama")
    limits, serve = kind.TOLERANCES, kind.CHECKS["serve"]
    program = reference.flash_parity(cfg, seed, seq=128)
    program.update(reference.paged_parity(_pool(cfg, seed), cfg, seed))
    assert reference.judge(limits, serve, program)[0] == []
    control = {"flash_err": reference.flash_control(cfg, seed, seq=128),
               "paged_err": reference.paged_control(_pool(cfg, seed), cfg,
                                                    seed)}
    faults, compared = reference.judge(limits, serve, control)
    assert len(faults) == 2, compared
    # room on both sides of the limit
    for name, (value, limit) in compared.items():
        assert value > 2 * limit and program[name] < limit / 2
