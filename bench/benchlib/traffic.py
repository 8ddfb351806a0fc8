"""The one traffic generator: rounds of prompts from a traffic file.

A round holds ``requests_per_round`` prompts whose lengths are the file's
``prompt_lengths`` ratio expanded to that count, so every compiled shape is
the same in every round.  The order of the lengths and the engine's own
random draws (modelled arrival times, source devices, routes) come from
the deployment's seed and the round index; the run's seed draws the
tokens.  Two seeds send the same work in the same order, with other
prompts (and, through the weights, other exits where thresholds are
near the confidences).

Streams: ``WARMUP`` rounds (set-up) and ``MEASURED`` rounds
(the window) draw from different seeds, so no measured prompt was seen in
set-up.
"""
from __future__ import annotations

import numpy as np

WARMUP, MEASURED = 0, 1


def round_lengths(traffic: dict) -> list[int]:
    """The multiset of prompt lengths of one round, in a fixed order."""
    ratio = {int(k): int(v) for k, v in traffic["prompt_lengths"].items()}
    n = int(traffic["requests_per_round"])
    unit = sum(ratio.values())
    if n % unit:
        raise ValueError(f"requests_per_round {n} is not a multiple of the ratio's sum {unit}")
    out: list[int] = []
    for length, share in sorted(ratio.items()):
        out += [length] * (share * n // unit)
    return out


def lengths(traffic: dict) -> list[int]:
    return sorted({int(k) for k in traffic["prompt_lengths"]})


def max_len(traffic: dict) -> int:
    """The engine's cache length: longest prompt plus the generation."""
    return max(lengths(traffic)) + int(traffic["gen_len"])


def round_prompts(traffic: dict, vocab: int, seed: int, stream: int, index: int):
    """The prompts of round ``index`` of ``stream``: lengths in an order
    drawn from the deployment's seed, tokens from the run's ``seed``."""
    order = np.random.default_rng([int(traffic["deployment_seed"]), stream, index])
    lens = order.permutation(round_lengths(traffic))
    rng = np.random.default_rng([seed, stream, index])
    return [rng.integers(0, vocab, size=int(n)).astype(np.int32) for n in lens]


def warmup_prompts(traffic: dict, vocab: int, seed: int):
    """One prompt of each length (the longest sets the caches' length, so
    every store has its window shape): the warm-up round of set-up."""
    rng = np.random.default_rng([seed, WARMUP, 1])
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lengths(traffic)]


def engine_rng(traffic: dict, stream: int, index: int) -> np.random.Generator:
    """The engine's draws for a round: the deployment's, not the run's."""
    return np.random.default_rng([int(traffic["deployment_seed"]), stream, index])


def serve_kwargs(traffic: dict) -> dict:
    return dict(
        arrival_rate=float(traffic["arrival_rate"]),
        batch_size=int(traffic["batch_size"]),
        gen_len=int(traffic["gen_len"]),
        decode_mode=traffic["decode_mode"],
        num_slots=traffic["num_slots"],
        cache_layout=traffic["cache_layout"],
    )


def batch_buckets(batch_size: int) -> list[int]:
    """Padded batch sizes the engine uses: powers of two up to the batch."""
    out, b = [], 1
    while b < batch_size:
        out.append(b)
        b <<= 1
    return out + [batch_size]
