"""Operations and bytes that the served work needs, from its shapes.

Counted from what the algorithm has to do for the real rows (padding
excluded), at the widths of the configuration file, never from what an
implementation happens to read.  A multiply-add is two operations.  The
kernels read their operands in bfloat16, as the program serves them.
"""
from __future__ import annotations

BF16 = 2


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
        "stages": conf["serve"]["num_stages"],
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
        bytes_ += 2.0 * n * w["KV"] * w["hd"] * BF16 + 2.0 * w["H"] * w["hd"] * BF16
    return flops, bytes_


def exit_confidence_cost(conf: dict, rows: int) -> tuple[float, float]:
    """One ``exit_confidence`` call: ``rows`` hidden vectors against the
    whole [d, V] head, reduced to a confidence and an argmax per row.

    operations: the logits, 2 * d * V per row (the softmax's exp and sums
    are V per row more, left out as the MXU-free part); bytes: the bf16
    head, the rows in, a float32 and an int32 per row out."""
    w = widths(conf)
    flops = 2.0 * rows * w["d"] * w["V"]
    bytes_ = w["d"] * w["V"] * BF16 + rows * w["d"] * BF16 + rows * 8.0
    return flops, bytes_


def layer_matmul_params(conf: dict) -> int:
    """Weights one token multiplies through in one layer."""
    w = widths(conf)
    attn = w["d"] * w["H"] * w["hd"] * 2 + w["d"] * w["KV"] * w["hd"] * 2
    return attn + 3 * w["d"] * w["ff"]


def stage_layers(conf: dict) -> list[int]:
    """Layers per stage: the near-even split, earlier stages take extras."""
    w = widths(conf)
    q, r = divmod(w["L"], w["stages"])
    return [q + (i < r) for i in range(w["stages"])]


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
    n = stage_layers(conf)[stage - 1]
    per_layer = 2.0 * layer_matmul_params(conf) * tokens + attention_flops(
        conf, tokens, context_end
    )
    return n * per_layer


def head_flops(conf: dict, rows: int) -> float:
    w = widths(conf)
    return 2.0 * rows * w["d"] * w["V"]


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


def roofline_s(flops: float, bytes_: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["flops_bf16"], bytes_ / peaks["hbm_bytes_per_s"])
