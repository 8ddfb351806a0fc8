"""The dense decoder family: every layer one attention block (MHA or GQA,
heads ``d // H`` wide) and one feed-forward block.  stablelm-1.6b and
internlm2-1.8b.

A family module is found by the name a configuration file gives under
``family`` (``bench/families/<family>.py``).  It holds what depends on the
block: the mapping from the file's published keys to the program's
``ArchConfig``, the operations and bytes of a layer counted from those
keys (never from the program), and the file with the widths of a
``.reduced()`` config for the CPU tests.  What every family shares (the
head, the stage split, the roofline) stays in ``benchlib/counting.py``.
"""
from __future__ import annotations

from benchlib import counting


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file.

    Widths come from the published keys; the ``serve`` group says how the
    family maps onto the program's blocks and where the exits sit.
    """
    import jax.numpy as jnp

    from repro.configs.base import ArchConfig

    s = conf["serve"]
    d, heads = conf["hidden_size"], conf["num_attention_heads"]
    return ArchConfig(
        name=conf["name"],
        family="dense",
        num_layers=conf["num_hidden_layers"],
        d_model=d,
        num_heads=heads,
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        head_dim=d // heads,
        norm=s["norm"],
        act=conf["hidden_act"],
        ffn=s["ffn"],
        qkv_bias=s["qkv_bias"],
        rope_theta=float(conf["rope_theta"]),
        period=tuple(s["period"]),
        num_stages=s["num_stages"],
        exit_stages=tuple(s["exit_stages"]),
        dtype=getattr(jnp, s["compute_dtype"]),
    )


def widths(conf: dict) -> dict:
    d = conf["hidden_size"]
    H = conf["num_attention_heads"]
    return {
        "d": d,
        "H": H,
        "KV": conf["num_key_value_heads"],
        "hd": d // H,
        "ff": conf["intermediate_size"],
        "V": conf["vocab_size"],
        "L": conf["num_hidden_layers"],
    }


def decode_attention_cost(conf: dict, lengths) -> tuple[float, float]:
    """One ``decode_attention`` call of one layer: a query token per row
    against its row's first ``length`` cached positions.

    operations: q.k and p.v over every head, 2 * 2 * H * hd per position;
    bytes: the valid K and V rows of each row's cache, the query and the
    output."""
    w = widths(conf)
    flops = bytes_ = 0.0
    for n in lengths:
        flops += 4.0 * w["H"] * w["hd"] * n
        bytes_ += 2.0 * n * w["KV"] * w["hd"] * counting.BF16 + 2.0 * w["H"] * w["hd"] * counting.BF16
    return flops, bytes_


def layer_matmul_params(conf: dict) -> int:
    """Weights one token multiplies through in one layer."""
    w = widths(conf)
    attn = w["d"] * w["H"] * w["hd"] * 2 + w["d"] * w["KV"] * w["hd"] * 2
    return attn + 3 * w["d"] * w["ff"]


def attention_flops(conf: dict, queries: int, context_end: int) -> float:
    """q.k and p.v for ``queries`` consecutive positions ending at
    ``context_end`` (causal: position p attends over p + 1 keys)."""
    w = widths(conf)
    first = context_end - queries + 1
    keys = (first + context_end) * queries / 2.0
    return 4.0 * w["H"] * w["hd"] * keys


def stage_pass_flops(conf: dict, stage: int, tokens: int, context_end: int) -> float:
    """Model operations of one row's pass through ``stage`` (1-based):
    ``tokens`` new positions ending at ``context_end`` positions."""
    n = counting.stage_layers(conf)[stage - 1]
    per_layer = 2.0 * layer_matmul_params(conf) * tokens + attention_flops(
        conf, tokens, context_end
    )
    return n * per_layer


def param_count(conf: dict, exit_heads: bool = False) -> int:
    """Parameters of the published model (exit norms only if asked)."""
    w = widths(conf)
    s = conf["serve"]
    norm = 2 if s["norm"] == "layernorm" else 1
    per_layer = layer_matmul_params(conf) + 2 * norm * w["d"]
    if s["qkv_bias"]:
        per_layer += w["H"] * w["hd"] + 2 * w["KV"] * w["hd"]
    total = w["L"] * per_layer + 2 * w["V"] * w["d"] + norm * w["d"]
    if exit_heads:
        total += len(s["exit_stages"]) * norm * w["d"]
    return total


def cpu_conf(conf: dict, cfg) -> dict:
    """The configuration file with the widths of a ``.reduced()`` config
    (CPU tests only)."""
    out = dict(conf)
    out.update(
        hidden_size=cfg.d_model,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        intermediate_size=cfg.d_ff,
        vocab_size=cfg.vocab_size,
    )
    return out
