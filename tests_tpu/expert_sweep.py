"""What a whole `grouped_ffn` call takes on the chip at a cell's shapes, for
the tile the rule picks and for every other one (16 ... 256): the numbers
`ops/grouped_ffn.py` `tile_rows` is fitted from.  Used by the three
`test_*_kernels_on_device.py` files of the expert configurations; each line
is kept in chiprun_out/pr52/kernels.jsonl with the tree's name (`TREE`, set
by whoever runs a parent beside a change; `SWEEP_TILES=rule` times the rule's
own tile alone).

The picks ride in the timed loop's carry, so the plan is inside every timed
call: with the picks closed over, XLA may lift the plan out of the loop as
loop-invariant.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import grouped_ffn as gf

_HBM_BYTES_PER_S = 819e9            # TPU v5e (benchmarks/lib/peaks.py)
TILES = (16, 32, 64, 128, 256)
OUT = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                   "chiprun_out", "pr52")

# config -> held experts, router width, picks a token, hidden, expert width,
# slots; and the fused rungs every engine compiles (serve/llm.py).
CONFIGS = {
    "trinity-mini": dict(E=128, width=128, K=8, D=2048, F=1024, slots=32),
    "lfm2": dict(E=64, width=64, K=4, D=2048, F=1536, slots=32),
    "axk1": dict(E=12, width=192, K=8, D=7168, F=2048, slots=64),
    "qw3n": dict(E=128, width=512, K=10, D=2048, F=512, slots=64),
}
RUNGS = (256, 640, 896, 2048)


def keep(name, record):
    os.makedirs(OUT, exist_ok=True)
    record = dict(record, what=name, tree=os.environ.get("TREE", "change"))
    with open(os.path.join(OUT, "kernels.jsonl"), "a") as f:
        f.write(json.dumps(record) + "\n")


def timed(chain, *args, calls=20, repeats=3, launches=5):
    jax.block_until_ready(chain(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(launches):
            out = chain(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / (launches * calls))
    return min(times)


def call(config, tokens, valid_share, seed=0):
    """`tokens` tokens' picks spread evenly over the router, a share of the
    tokens valid (a pass's padding, a retired slot), the pairs on experts
    that are not held routed nowhere."""
    c = CONFIGS[config]
    E, width, K, D, F = (c[k] for k in ("E", "width", "K", "D", "F"))
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (tokens, D), jnp.bfloat16)
    picks = jnp.argsort(jax.random.uniform(ks[1], (tokens, width)),
                        axis=1)[:, :K].astype(jnp.int32)
    valid = jax.random.uniform(ks[6], (tokens,)) < valid_share
    held = (picks < E) & valid[:, None]
    idx = jnp.clip(picks, 0, E - 1)
    w = jax.nn.softmax(jax.random.normal(ks[2], (tokens, K)), axis=-1)
    wg, wu = ((jax.random.normal(k, (E, D, F), jnp.float32) * D ** -0.5
               ).astype(jnp.bfloat16) for k in ks[3:5])
    wd = (jax.random.normal(ks[5], (E, F, D), jnp.float32) * F ** -0.5
          ).astype(jnp.bfloat16)
    return x, idx, w, held, wg, wu, wd


def whole_call_us(config, args, name, tile=None):
    """One `grouped_ffn` call, plan and gathers included, at the rule's tile
    or at `tile`."""
    width = CONFIGS[config]["width"]
    rule = gf.tile_rows
    if tile is not None:
        gf.tile_rows = lambda *a, **k: tile
    try:
        @jax.jit
        def chain(x, idx, w, held, wg, wu, wd, zero):
            def one(_, c):
                x, shift = c
                # a fresh trace of the body: `tile_rows` is read here
                y, sizes = gf.grouped_ffn.__wrapped__(
                    x, idx + shift, w, held, wg, wu, wd, name=name,
                    impl="kernel", router_width=width)
                return (x + (y * 0).astype(x.dtype),
                        jnp.minimum(shift, jnp.sum(sizes)))
            return jax.lax.fori_loop(0, 20, one, (x, zero))
        return timed(chain, *args, jnp.zeros((), jnp.int32)) * 1e6
    finally:
        gf.tile_rows = rule


def sweep(config, tokens, valid_share, name):
    """Every tile at one shape; the rule's own is marked.  Returns
    {tile: us}."""
    c = CONFIGS[config]
    args = call(config, tokens, valid_share, seed=tokens)
    held = np.asarray(args[3])
    sizes = np.bincount(np.asarray(args[1])[held], minlength=c["E"])
    rule = gf.tile_rows(tokens * c["K"], c["width"])
    touched = int((sizes > 0).sum())
    least = touched * 3 * c["D"] * c["F"] * 2 / _HBM_BYTES_PER_S * 1e6
    took = {}
    only_rule = os.environ.get("SWEEP_TILES") == "rule"
    for tile in (rule,) if only_rule else TILES:
        took[tile] = whole_call_us(config, args, name, tile)
        padded = int((-(-sizes // tile) * tile).sum())
        keep("tile_sweep", dict(
            config=config, kernel=name, tokens=tokens, tile=tile,
            rule=tile == rule, rows=int(sizes.sum()), touched=touched,
            padded=padded, us=took[tile], bytes_us=least))
    print(f"\n{config} {name} {tokens} tokens ({int(sizes.sum())} rows on "
          f"{touched} of {c['E']} held, mean {tokens * c['K'] / c['width']:.1f}"
          f" pairs a router's expert; bytes {least:.0f} us): "
          + ", ".join(f"{t}: {u:.0f} us" + (" (rule)" if t == rule else "")
                      for t, u in took.items()))
    return took, rule


def check_rule(took, rule):
    best = min(took.values())
    assert took[rule] <= 1.05 * best, (rule, took)
