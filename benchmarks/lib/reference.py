"""Plain references, independent of the program: causal grouped-query
attention and attention over a paged KV pool, in straightforward float32
`jax.numpy` with no kernel, no tiling and no cache logic.  `correct` holds
the program's kernels to these, at the configuration's own head layout,
in set-up (seconds each, outside the window).

Tolerance 2e-2 on outputs of order 1: inputs and outputs are bf16 (8
mantissa bits, half an ulp near 1 is 4e-3) and the kernels accumulate in
f32 but round P to bf16 before the PV product; chip_smoke.py measured
7.8e-3 on the live pool at D=64.  A kernel that dropped a block, a head
or the causal mask errs by ~1, two orders above the tolerance.
"""

from __future__ import annotations

import math

TOLERANCE = 2e-2


def attention(q, k, v):
    """Causal GQA.  q: [B, H, S, D]; k, v: [B, Hkv, S, D] -> [B, H, S, D]
    in float32."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        g = q.shape[1] // k.shape[1]
        k, v = (jnp.repeat(a, g, axis=1) for a in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
        n = q.shape[2]
        causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def paged_attention(q, k_pool, v_pool, block_tables, context_lens):
    """One query per sequence over its cached positions.  q: [B, H, D];
    pools: [NB, Hkv, bs, D]; block_tables: [B, W] of pool ids; sequence b
    attends to its first context_lens[b] positions -> [B, H, D] float32
    (zeros where context_lens is 0)."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        B, H, D = q.shape
        hkv, bs = k_pool.shape[1], k_pool.shape[2]
        W = block_tables.shape[1]

        def rows(pool):     # [B, W, Hkv, bs, D] -> [B, Hkv, W * bs, D]
            x = pool[block_tables].astype(jnp.float32)
            return jnp.moveaxis(x, 2, 1).reshape(B, hkv, W * bs, D)

        k, v = rows(k_pool), rows(v_pool)
        g = H // hkv
        k, v = (jnp.repeat(a, g, axis=1) for a in (k, v))
        s = jnp.einsum("bhd,bhmd->bhm", q.astype(jnp.float32), k) \
            / math.sqrt(D)
        live = jnp.arange(W * bs)[None, :] < context_lens[:, None]
        s = jnp.where(live[:, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1) * live[:, None, :]
        return jnp.einsum("bhm,bhmd->bhd", p, v)


def max_abs_err(got, want) -> float:
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32))))


def flash_parity(cfg, seed: int, seq: int = 512) -> dict:
    """The program's flash forward against `attention`, at this
    configuration's heads and head size."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import attention as prog
    ks = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 3)
    q = jax.random.normal(ks[0], (1, cfg.n_heads, seq, cfg.head_dim),
                          jnp.bfloat16)
    k, v = (jax.random.normal(kk, (1, cfg.kv_heads, seq, cfg.head_dim),
                              jnp.bfloat16) for kk in ks[1:])
    auto = jax.jit(lambda *a: prog.attention(*a, impl="auto"))
    lowered = auto.lower(q, k, v).as_text()
    return {"flash_err": max_abs_err(auto(q, k, v), attention(q, k, v)),
            "flash_is_kernel": "tpu_custom_call" in lowered}


def paged_parity(caches, cfg, seed: int) -> dict:
    """The program's paged kernel against `paged_attention` over the LIVE
    pool (layer 0, the tables and lengths the warm-up traffic left)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import paged_attention as prog
    B = caches.lengths.shape[0]
    q = jax.random.normal(jax.random.PRNGKey(seed % (2 ** 31)),
                          (B, cfg.n_heads, cfg.head_dim), cfg.dtype)
    M = caches.block_tables.shape[1] * caches.kp.shape[3]
    args = (q, caches.kp[0], caches.vp[0], caches.block_tables,
            jnp.minimum(caches.lengths + 1, M))
    auto = jax.jit(lambda *a: prog.paged_attention(*a, impl="auto"))
    lowered = auto.lower(*args).as_text()
    return {"paged_err": max_abs_err(auto(*args), paged_attention(*args)),
            "paged_is_kernel": "tpu_custom_call" in lowered,
            "paged_live_positions": int(jnp.sum(args[-1]))}
