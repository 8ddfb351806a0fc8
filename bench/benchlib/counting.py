"""Operations and bytes that the served work needs, from its shapes.

Counted from what the algorithm has to do for the real rows (padding
excluded), at the widths of the configuration file, never from what an
implementation happens to read.  A multiply-add is two operations.  The
kernels read their operands in bfloat16, as the program serves them.

What depends on the block (a layer's weights, its attention, the decode
kernel's work, the parameter count) is counted by the configuration's
family module (``bench/families/<family>.py``); the functions of that
name here hand the call on to it.  The head, the same [d, V] for every
family, the split into stages and the roofline are counted here.
"""
from __future__ import annotations

from benchlib import spec

BF16 = 2


def decode_attention_cost(conf: dict, lengths) -> tuple[float, float]:
    """One ``decode_attention`` call of one layer over rows of the given
    valid context lengths: (operations, bytes), by the family."""
    return spec.family(conf).decode_attention_cost(conf, lengths)


def exit_confidence_cost(conf: dict, rows: int) -> tuple[float, float]:
    """One ``exit_confidence`` call: ``rows`` hidden vectors against the
    whole [d, V] head, reduced to a confidence and an argmax per row.

    operations: the logits, 2 * d * V per row (the softmax's exp and sums
    are V per row more, left out as the MXU-free part); bytes: the bf16
    head, the rows in, a float32 and an int32 per row out."""
    d, V = conf["hidden_size"], conf["vocab_size"]
    flops = 2.0 * rows * d * V
    bytes_ = d * V * BF16 + rows * d * BF16 + rows * 8.0
    return flops, bytes_


def layer_matmul_params(conf: dict) -> int:
    """Weights one token multiplies through in one layer, by the family."""
    return spec.family(conf).layer_matmul_params(conf)


def stage_layers(conf: dict) -> list[int]:
    """Layers per stage: the near-even split, earlier stages take extras."""
    q, r = divmod(conf["num_hidden_layers"], conf["serve"]["num_stages"])
    return [q + (i < r) for i in range(conf["serve"]["num_stages"])]


def stage_pass_flops(conf: dict, stage: int, tokens: int, context_end: int) -> float:
    """Model operations of one row's pass through ``stage`` (1-based):
    ``tokens`` new positions ending at ``context_end`` positions, by the
    family."""
    return spec.family(conf).stage_pass_flops(conf, stage, tokens, context_end)


def head_flops(conf: dict, rows: int) -> float:
    return 2.0 * rows * conf["hidden_size"] * conf["vocab_size"]


def param_count(conf: dict, exit_heads: bool = False) -> int:
    """Parameters of the published model (exit norms only if asked), by the
    family."""
    return spec.family(conf).param_count(conf, exit_heads)


def roofline_s(flops: float, bytes_: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["flops_bf16"], bytes_ / peaks["hbm_bytes_per_s"])
