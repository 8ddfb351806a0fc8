"""What decides ``correct``: the timed path's tokens, confidences and exit
decisions against the plain float32 reference.

Once the window has closed, a sample of the requests it finished is drawn
from the seed, the longest among them.  The reference runs once over each
prompt followed by the tokens served for it, and is read at every position
where the program ran a head.  Three numbers, each with its limit from
``bench/limits/<cell>.json``:

  * ``token_gap``: the widest gap, in logits, by which a served token lies
    below the reference's best logit at the head that served it;
  * ``conf_gap``: the widest |ln c_program - ln c_reference| over every
    head the program ran (c the top-1 softmax probability);
  * ``exit_flips``: head readings where the program's decision to retire
    or go on contradicts the reference's, where the reference's
    confidence lies further than the ``conf_gap`` limit from the
    threshold.  An exact comparison: its limit is 0.

A request that did not finish, or whose tokens or head readings are
missing, counts under ``failed``; ``correct`` needs none.
"""
from __future__ import annotations

import dataclasses

import numpy as np

NUMBERS = ("token_gap", "conf_gap", "exit_flips")


@dataclasses.dataclass
class Served:
    """One finished request as the timed path left it."""

    prompt: np.ndarray
    tokens: tuple  # served tokens, in order
    exit_stage: int
    heads: list  # per token: {stage: (conf, tok)} as the heads returned


def sample(keys: list, lengths: dict, n: int, seed: int) -> list:
    """``n`` request keys drawn from the seed, the longest one first."""
    if not keys:
        return []
    longest = max(keys, key=lambda k: (lengths[k], k))
    rest = [k for k in keys if k != longest]
    rng = np.random.default_rng([seed, 2])
    take = rng.permutation(len(rest))[: max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(take)]


def inputs(served: list, conf: dict):
    """Reference inputs: tokens [N, L], head positions [N, G], and the
    token to read at each head {stage: [N, G]} (the served token at the
    head that served it, else the head's own argmax)."""
    s = conf["serve"]
    H = s["num_stages"]
    stages = list(s["exit_stages"]) + [H]
    N = len(served)
    L = max(len(r.prompt) + len(r.tokens) - 1 for r in served)
    G = max(len(r.tokens) for r in served)
    tokens = np.zeros((N, L), np.int32)
    positions = np.full((N, G), -1, np.int32)
    gather = {h: np.zeros((N, G), np.int32) for h in stages}
    for n, r in enumerate(served):
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        tokens[n, : len(seq)] = seq
        P = len(r.prompt)
        for g, tok in enumerate(r.tokens):
            positions[n, g] = P - 1 + g
            serving = r.exit_stage if g == len(r.tokens) - 1 else H
            for h, (_, head_tok) in r.heads[g].items():
                gather[h][n, g] = tok if h == serving else head_tok
    return tokens, positions, gather


def compare(served: list, reading: dict, conf: dict, thresholds, limits: dict) -> dict:
    """The three numbers of ``served`` against the reference's readings."""
    s = conf["serve"]
    H = s["num_stages"]
    branch = {h: b for b, h in enumerate(s["exit_stages"])}
    margin = float(limits["conf_gap"])
    token_gap = conf_gap = 0.0
    flips = 0
    for n, r in enumerate(served):
        for g, tok in enumerate(r.tokens):
            serving = r.exit_stage if g == len(r.tokens) - 1 else H
            for h, (c, _) in r.heads[g].items():
                rd = reading[h]
                ln_ref = float(rd["lmax"][n, g] - rd["lse"][n, g])
                conf_gap = max(conf_gap, abs(np.log(c) - ln_ref))
                if h == serving:
                    token_gap = max(token_gap, float(rd["lmax"][n, g] - rd["picked"][n, g]))
                if h in branch:
                    ln_thr = float(np.log(thresholds[branch[h]]))
                    retired = h == serving
                    if retired and ln_ref < ln_thr - margin:
                        flips += 1
                    if not retired and ln_ref > ln_thr + margin:
                        flips += 1
    return {"token_gap": float(token_gap), "conf_gap": float(conf_gap), "exit_flips": float(flips)}


def control_numbers(served: list, ref: dict, low: dict, conf: dict, thresholds,
                    limits: dict) -> dict:
    """The same numbers for the control: the reference computed in the
    precision below the configuration's, put where the program was.  At
    every head the program ran, it reads the token the control puts first
    (in the float32 reference's logits) and the control's confidence."""
    s = conf["serve"]
    H = s["num_stages"]
    branch = {h: b for b, h in enumerate(s["exit_stages"])}
    margin = float(limits["conf_gap"])
    token_gap = conf_gap = 0.0
    flips = 0
    for n, r in enumerate(served):
        for g in range(len(r.tokens)):
            serving = r.exit_stage if g == len(r.tokens) - 1 else H
            for h in r.heads[g]:
                ln_ref = float(ref[h]["lmax"][n, g] - ref[h]["lse"][n, g])
                ln_low = float(low[h]["lmax"][n, g] - low[h]["lse"][n, g])
                conf_gap = max(conf_gap, abs(ln_low - ln_ref))
                if h == serving:
                    token_gap = max(token_gap, float(ref[h]["lmax"][n, g] - ref[h]["picked"][n, g]))
                if h in branch:
                    ln_thr = float(np.log(thresholds[branch[h]]))
                    retired = ln_low >= ln_thr
                    if (retired and ln_ref < ln_thr - margin) or (
                        not retired and ln_ref > ln_thr + margin
                    ):
                        flips += 1
    return {"token_gap": float(token_gap), "conf_gap": float(conf_gap), "exit_flips": float(flips)}


def verdict(numbers: dict, limits: dict, failed: int) -> bool:
    return failed == 0 and all(numbers[k] <= float(limits[k]) for k in NUMBERS)
