"""The table of peaks, and what each kernel of the main path has to do.

Peaks are the published ones, keyed by jax's `device_kind`; a device that
is not in the table is an error, never a default.  The operation and byte
counts are what the ALGORITHM needs for one call, from its shapes: work a
kernel does beyond that (masked blocks it still computes, tiles it reads
twice) lowers its roofline share, which is the point.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

# Google Cloud documentation, "TPU v5e" (system architecture page): 197
# TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e, per chip)",
    },
}


def peaks_for(device_kind: str) -> Dict[str, Any]:
    if device_kind not in PEAKS:
        raise ValueError(
            f"no published peak for device_kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}): add it to benchmarks/lib/peaks.py with "
            f"its source")
    return PEAKS[device_kind]


def roofline_seconds(flops: float, bytes_: float,
                     device_kind: str) -> Tuple[float, str]:
    """The least time the chip could take for that work, and which peak
    bounds it."""
    p = peaks_for(device_kind)
    t_c = flops / p["bf16_flops_per_s"]
    t_m = bytes_ / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


# -- per-call work of each kernel: fn(config, shapes) -> (flops, bytes) ----
# `shapes` for the flash kernels: batch (sequences on ONE device), seq.
# Causal attention needs half of the S x S score matrix.
def _flash_dims(cfg, s):
    return (s["batch"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], s["seq"], cfg["head_dim"])


def flash_fwd(cfg, s):
    b, h, hkv, sq, d = _flash_dims(cfg, s)
    flops = 2 * 2 * b * h * sq * sq * d / 2           # QK^T, PV
    bytes_ = 2 * (2 * b * h * sq * d                  # read q, write o
                  + 2 * b * hkv * sq * d)             # read k, v (bf16)
    return flops, bytes_ + 4 * b * h * sq             # + f32 lse


def flash_bwd_dkdv(cfg, s):
    b, h, hkv, sq, d = _flash_dims(cfg, s)
    flops = 4 * 2 * b * h * sq * sq * d / 2           # S, dP, dV, dK
    bytes_ = 2 * (2 * b * h * sq * d                  # q, do
                  + 2 * b * hkv * sq * d              # k, v
                  + 2 * b * hkv * sq * d)             # dk, dv
    return flops, bytes_ + 2 * 4 * b * h * sq         # lse, delta


def flash_bwd_dq(cfg, s):
    b, h, hkv, sq, d = _flash_dims(cfg, s)
    flops = 3 * 2 * b * h * sq * sq * d / 2           # S, dP, dQ
    bytes_ = 2 * (3 * b * h * sq * d                  # q, do, dq
                  + 2 * b * hkv * sq * d)             # k, v
    return flops, bytes_ + 2 * 4 * b * h * sq


def flash_fwd_bwd_mean(cfg, s):
    """The three kernels run equally often and the trace cannot tell them
    apart by name (PERF.md, Open questions), so a call costs their mean."""
    parts = [f(cfg, s) for f in (flash_fwd, flash_bwd_dkdv, flash_bwd_dq)]
    return (sum(p[0] for p in parts) / 3, sum(p[1] for p in parts) / 3)


def paged_decode(cfg, s):
    """One call = one layer, one decode step, every slot.  `live_context`
    is the number of cached positions the live requests hold in total
    (time-averaged over the traced window, from the client's own token
    clock); `slots` the batch width."""
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    ctx = s["live_context"]
    flops = 2 * 2 * ctx * h * d
    bytes_ = 2 * (2 * ctx * hkv * d + 2 * s["slots"] * h * d)
    return flops, bytes_


COST_FNS: Dict[str, Callable] = {
    "flash_fwd": flash_fwd,
    "flash_bwd_dkdv": flash_bwd_dkdv,
    "flash_bwd_dq": flash_bwd_dq,
    "flash_fwd_bwd_mean": flash_fwd_bwd_mean,
    "paged_decode": paged_decode,
}
