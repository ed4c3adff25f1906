"""ON-DEVICE: what writing new K/V into a block pool costs, alone, at the
serving cells' shapes: the form every program had until PR 45 (a scatter of
D-wide rows into the pool seen as [NB * Hkv * bs, D]) against
`decoding._write_rows` (whole pages, indexed by the block alone), in us a
pool a layer; and that both leave the same pool.  Printed with `-s`, kept
in chiprun_out/pr45/pool_writes.jsonl.

    python -m pytest tests_tpu/test_pool_writes_on_device.py -q -s
"""

import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decoding

BS = 16
OUT = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                   "chiprun_out", "pr45")


def _row_scatter(pool, blocks, offsets, new, prompt=(0, 1)):
    """The write as it was: one update a (position, head), a row of lanes
    each."""
    NB, hkv, bs, D = pool.shape
    blocks, offsets = blocks.reshape(-1), offsets.reshape(-1)
    at = ((blocks[:, None] * hkv + jnp.arange(hkv)) * bs
          + offsets[:, None]).reshape(-1)
    return pool.reshape(NB * hkv * bs, D).at[at].set(
        new.reshape(-1, D).astype(pool.dtype)).reshape(pool.shape)


FORMS = {"rows": _row_scatter, "pages": decoding._write_rows}

# (pool of one layer [NB, Hkv', bs, lanes], layers that share one buffer,
#  prefill rows N of 16 positions, slots with one position each)
CASES = {
    # Mistral: 16 layers' pools stacked in ONE buffer, layer i's block b at
    # i * NB + b; a decode step of 32 slots; the passes of the 256 / 640 /
    # 2,048 rungs with 32 carried slots
    "mistral.step": ((1537, 8, BS, 128), 16, 0, 32),
    "mistral.pass256": ((1537, 8, BS, 128), 16, 16, 32),
    "mistral.pass640": ((1537, 8, BS, 128), 16, 40, 32),
    "mistral.pass2048": ((1537, 8, BS, 128), 16, 128, 32),
    # Trinity-Mini (and LFM2: 8 heads of 64 side by side are its shape)
    "trinity.step": ((8193, 4, BS, 128), 1, 0, 32),
    "trinity.pass640": ((8193, 4, BS, 128), 1, 40, 32),
    # A.X-K1's one pool of latent rows
    "latent.step": ((8193, 1, BS, 640), 1, 0, 64),
    "latent.pass2048": ((8193, 1, BS, 640), 1, 128, 64),
}


def _inputs(case, seed=0):
    (NB, hkv, bs, D), layers, N, B = CASES[case]
    rng = np.random.RandomState(seed)
    ids = rng.permutation(np.arange(1, NB))[:N + B].astype(np.int32)
    # every prompt row one whole block of its own, all of it live; then a
    # slot's position somewhere in a block of its own
    blocks = np.concatenate([np.repeat(ids[:N], bs), ids[N:]])
    offsets = np.concatenate([np.tile(np.arange(bs), N),
                              rng.randint(0, bs, B)]).astype(np.int32)
    new = jax.random.normal(jax.random.PRNGKey(seed),
                            (N * bs + B, hkv, D), jnp.bfloat16)
    pool = jnp.zeros((layers * NB, hkv, bs, D), jnp.bfloat16)
    return pool, jnp.asarray(blocks), jnp.asarray(offsets), new, (N, bs)


@functools.partial(jax.jit, static_argnames=("form", "prompt", "layers",
                                             "repeats"),
                   donate_argnums=(0,))
def _chain(pool, blocks, offsets, new, *, form, prompt, layers, repeats):
    """`repeats` walks over `layers` layers that share the buffer, each a
    write at its own blocks: the pool is carried and updated in place, as
    the layer scans carry it."""
    NB = pool.shape[0] // layers

    def layer(i, pool):
        first = (i % layers) * NB
        return FORMS[form](pool, first + blocks, offsets,
                           new + i.astype(new.dtype), prompt)

    return jax.lax.fori_loop(0, layers * repeats, layer, pool)


@pytest.mark.parametrize("case", list(CASES))
def test_both_forms_leave_the_same_pool_on_tpu(case):
    pool, blocks, offsets, new, prompt = _inputs(case)
    layers = CASES[case][1]
    outs = [np.asarray(_chain(jnp.copy(pool), blocks, offsets, new,
                              form=form, prompt=prompt, layers=layers,
                              repeats=1).astype(jnp.float32))
            for form in FORMS]
    # (every written position is live here, and a slot's page held zeros
    # where the row scatter leaves zeros)
    assert np.array_equal(*outs)
    assert np.abs(outs[0]).sum() > 0


@pytest.mark.parametrize("case", list(CASES))
def test_pool_write_time_on_tpu(case):
    """us a pool a layer, by the device's own pace: a chain of `repeats`
    walks of writes in one program, the shortest of three timings."""
    pool, blocks, offsets, new, prompt = _inputs(case)
    shape, layers, N, B = CASES[case]
    repeats = max(1, 64 // layers)
    record = {"case": case, "pool": list(shape), "layers": layers,
              "prompt_rows": N, "slots": B,
              "row_updates": (N * BS + B) * shape[1],
              "page_updates": N + B}
    for form in FORMS:
        run = functools.partial(_chain, form=form, prompt=prompt,
                                layers=layers, repeats=repeats)
        pool = run(pool, blocks, offsets, new)
        pool.block_until_ready()
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(5):
                pool = run(pool, blocks, offsets, new)
            pool.block_until_ready()
            times.append((time.perf_counter() - t0) / (5 * layers * repeats))
        record[form + "_us"] = round(min(times) * 1e6, 3)
    print(json.dumps(record))
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "pool_writes.jsonl"), "a") as f:
        f.write(json.dumps(record) + "\n")
    if N:       # a pass: 16 positions a head go in one update, not 16
        assert record["pages_us"] < record["rows_us"]
