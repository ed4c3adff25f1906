"""A configuration file -> the program's TransformerConfig, and the
arithmetic that depends only on the file's sizes (parameters, model FLOPs
per trained token).  The harness knows ONE kind of model: a dense,
llama-arch decoder (RMSNorm, rotary, gated MLP, grouped-query attention).
A configuration of that kind is added as a JSON file alone."""

from __future__ import annotations

from typing import Any, Dict

KIND = "dense-llama"


def check(cfg: Dict[str, Any]) -> None:
    if cfg.get("kind") != KIND:
        raise ValueError(f"configuration {cfg.get('name')!r} is of kind "
                         f"{cfg.get('kind')!r}; this harness runs {KIND!r}")
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


def with_dtypes(kw: Dict[str, Any]) -> Dict[str, Any]:
    import jax.numpy as jnp
    out = dict(kw)
    for k in ("dtype", "param_dtype"):
        out[k] = jnp.dtype(out[k]).type
    return out


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
    input embedding is a gather, so its table is left out; bench.py's
    formula counted it) + causal-unaware attention 12 L s d (PaLM,
    appendix B)."""
    n = param_counts(cfg)
    dense = n["total"] - n["input_embedding"]
    return 6.0 * dense + 12.0 * cfg["num_hidden_layers"] * seq * \
        cfg["hidden_size"]


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
            * cfg["num_hidden_layers"])
