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
from typing import Any, Dict, List, Sequence, Tuple

TOLERANCE = 2e-2


def judge(tolerances: Dict[str, float], required: Sequence[str],
          checks: Dict[str, Any]
          ) -> Tuple[List[str], Dict[str, List[float]]]:
    """A kind's parity() readings against its TOLERANCES.  `required` is
    the kind's CHECKS[where]: the entries a cell of that sort must compare.
    A fault for each that parity() did not return, that has no limit, or
    that is not under its limit (a NaN is not), and for a kind that names
    none: a run that compared nothing is not correct.  Also each number
    compared beside its limit, for the result line."""
    faults, compared = [], {}
    if not required:
        faults.append("the kind names no check for this cell: nothing was "
                      "compared with the plain reference")
    for name in required:
        if name not in tolerances:
            faults.append(f"{name} has no limit in the kind's TOLERANCES")
        elif name not in checks:
            faults.append(f"{name} is missing from parity(): got "
                          f"{sorted(checks)}")
        else:
            compared[name] = [checks[name], tolerances[name]]
            if not checks[name] < tolerances[name]:
                faults.append(f"{name} {checks[name]:.3g} is not under its "
                              f"limit {tolerances[name]:g} (plain reference)")
    return faults, compared


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


def _flash_inputs(cfg, seed: int, seq: int):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 3)
    q = jax.random.normal(ks[0], (1, cfg.n_heads, seq, cfg.head_dim),
                          jnp.bfloat16)
    k, v = (jax.random.normal(kk, (1, cfg.kv_heads, seq, cfg.head_dim),
                              jnp.bfloat16) for kk in ks[1:])
    return q, k, v


def _paged_inputs(caches, cfg, seed: int):
    import jax
    import jax.numpy as jnp
    B = caches.lengths.shape[0]
    q = jax.random.normal(jax.random.PRNGKey(seed % (2 ** 31)),
                          (B, cfg.n_heads, cfg.head_dim), cfg.dtype)
    M = caches.block_tables.shape[1] * caches.kp.shape[3]
    return (q, caches.kp[0], caches.vp[0], caches.block_tables,
            jnp.minimum(caches.lengths + 1, M))


def flash_parity(cfg, seed: int, seq: int = 512) -> dict:
    """The program's flash forward against `attention`, at this
    configuration's heads and head size."""
    import jax
    from ray_tpu.ops import attention as prog
    q, k, v = _flash_inputs(cfg, seed, seq)
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
    args = _paged_inputs(caches, cfg, seed)
    auto = jax.jit(lambda *a: prog.paged_attention(*a, impl="auto"))
    lowered = auto.lower(*args).as_text()
    return {"paged_err": max_abs_err(auto(*args), paged_attention(*args)),
            "paged_is_kernel": "tpu_custom_call" in lowered,
            "paged_live_positions": int(jnp.sum(args[-1]))}


# -- the control: what `correct` has been shown to refuse ------------------
# The plain reference put in the program's place, computed in the nearest
# precision BELOW the configuration's bfloat16: q, k, v (and the pool)
# rounded to fp8 (e4m3, 3 mantissa bits), the step a later PR would be
# tempted by.  It must read over the kind's limit (tests/test_control.py;
# on the chip at the cells' own size: PERF.md section 2).
def _fp8(x):
    import jax.numpy as jnp
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def flash_control(cfg, seed: int, seq: int = 512) -> float:
    q, k, v = _flash_inputs(cfg, seed, seq)
    return max_abs_err(attention(_fp8(q), _fp8(k), _fp8(v)),
                       attention(q, k, v))


def paged_control(caches, cfg, seed: int) -> float:
    q, kp, vp, tables, lens = _paged_inputs(caches, cfg, seed)
    return max_abs_err(
        paged_attention(_fp8(q), _fp8(kp), _fp8(vp), tables, lens),
        paged_attention(q, kp, vp, tables, lens))
