"""Model kind `dense-llama`: a dense, llama-arch decoder (RMSNorm, rotary,
gated MLP, grouped-query attention).  Everything the harness knows about
the architecture is in this file, and the cells call only this interface
(lib/spec.py finds the file by the configuration's `"kind"`):

  check(cfg)                          refuse what the program cannot express
  transformer_kwargs(cfg, ...)        the program's TransformerConfig
  param_counts(cfg)                   parameters from the file's sizes
  train_flops_per_token(cfg, seq)     model FLOPs one trained token needs
  kv_bytes_per_token(cfg)             cache bytes one position holds
  parity(where, cfg, seed, ...)       {check: error} against this kind's
                                      plain reference; TOLERANCES judges
  CHECKS                              the entries of parity() a "train" and
                                      a "serve" cell must compare: one that
                                      is missing is a fault
  COST_FNS                            operations and bytes of kernels that
                                      only this kind runs (none: flash and
                                      paged decode are lib/peaks.py's)

A configuration of this kind is added as a JSON file alone."""

from __future__ import annotations

from typing import Any, Callable, Dict

from benchmarks.lib import reference

# Every entry of parity() named here must stay BELOW its limit, or the run
# is not correct.  How each limit was set (the program's largest reading,
# the control's smallest): PERF.md section 2.
TOLERANCES: Dict[str, float] = {
    "flash_err": reference.TOLERANCE,
    "paged_err": reference.TOLERANCE,
}

# What parity(where) has to return: a cell whose line lacks one of them is
# not correct (lib/reference.py::judge), so a kind cannot compare nothing.
CHECKS: Dict[str, tuple] = {
    "train": ("flash_err",),
    "serve": ("flash_err", "paged_err"),
}

COST_FNS: Dict[str, Callable] = {}

NOT_COMPARED = 1e9      # over any limit, and finite: the line is strict JSON


def check(cfg: Dict[str, Any]) -> None:
    if cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"]:
        # models/transformer.py derives head_dim as d_model // n_heads
        raise ValueError("head_dim x heads != hidden_size: the program "
                         "cannot express this configuration")
    if cfg["num_attention_heads"] % cfg["num_key_value_heads"]:
        raise ValueError("query heads are not a multiple of KV heads")


def transformer_kwargs(cfg: Dict[str, Any], *, max_seq: int,
                       param_dtype: str, **extra: Any) -> Dict[str, Any]:
    """Keyword arguments of ray_tpu.models.transformer.TransformerConfig.
    dtypes stay strings here (this runs in the jax-free driver too); the
    worker turns them into jnp dtypes with `with_dtypes`."""
    check(cfg)
    kw = {
        "vocab_size": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "d_ff": cfg["intermediate_size"],
        "max_seq": max_seq,
        "arch": "llama",
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "tie_embeddings": bool(cfg["tie_word_embeddings"]),
        "dtype": cfg["torch_dtype"],
        "param_dtype": param_dtype,
    }
    kw.update(extra)
    return kw


def param_counts(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters from the sizes alone (checked against the program's own
    tree in the worker)."""
    d, f, L = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    h, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    layer = (d * h * dh + 2 * d * hkv * dh + h * dh * d   # q, k, v, o
             + 3 * d * f                                    # gate, up, down
             + 2 * d)                                       # two norms
    embed = cfg["vocab_size"] * d
    head = 0 if cfg["tie_word_embeddings"] else d * cfg["vocab_size"]
    return {"total": L * layer + embed + head + d, "input_embedding": embed,
            "per_layer": layer}


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Model FLOPs one trained token requires, forward and backward, no
    recompute: 6 x every parameter that multiplies an activation (the
    input embedding is a gather, so its table is left out) +
    causal-unaware attention 12 L s d (PaLM, appendix B)."""
    n = param_counts(cfg)
    dense = n["total"] - n["input_embedding"]
    return 6.0 * dense + 12.0 * cfg["num_hidden_layers"] * seq * \
        cfg["hidden_size"]


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
            * cfg["num_hidden_layers"])


def parity(where: str, cfg, seed: int, *, seq: int = 512,
           caches=None) -> Dict[str, Any]:
    """The program's kernels against the plain reference at this
    configuration's heads and head size, in the process that holds the
    chip.  `cfg` is the program's TransformerConfig.  `where` = "train":
    the flash forward; "serve": that and the paged kernel over the live
    pool `caches`.  A comparison that compared nothing, or on a TPU not the
    kernel, reads NOT_COMPARED: the entries beside it say which."""
    import jax
    out = reference.flash_parity(cfg, seed, seq=seq)
    if where == "serve":
        out.update(reference.paged_parity(caches, cfg, seed))
        if out["paged_live_positions"] <= 0:
            out["paged_err"] = NOT_COMPARED      # an empty pool
    if jax.default_backend() == "tpu":
        for k in ("flash", "paged"):
            if not out.get(f"{k}_is_kernel", True):
                out[f"{k}_err"] = NOT_COMPARED   # 'auto' was not the kernel
    return out
