"""A model kind as a later PR would add it (tests only): dense-llama's
interface with one more parity check and cost functions of its own."""
from benchmarks.lib import spec

base = spec.model_kind("dense-llama")
check, transformer_kwargs = base.check, base.transformer_kwargs
param_counts, kv_bytes_per_token = base.param_counts, base.kv_bytes_per_token
train_flops_per_token = base.train_flops_per_token
TOLERANCES = dict(base.TOLERANCES, toy_err=0.5)
CHECKS = {w: names + ("toy_err",) for w, names in base.CHECKS.items()}
COST_FNS = {"toy_cost": lambda cfg, shapes: (2.0, 4.0),
            "paged_decode": lambda cfg, shapes: (1.0, 1.0)}


def parity(where, cfg, seed, **ctx):
    return dict(base.parity(where, cfg, seed, **ctx), toy_err=0.25)
