"""Faults planted under the timed path must turn ``correct`` false.

Each drives a whole run at ``.reduced()`` widths on the CPU, with the
chip check skipped, and breaks one thing where the engine calls its stage
programs: the cache update a decode step makes, half of each batch, or the
token the final head produces.  (The cells run on one chip: there is no
exchange between chips to leave out.)  The rule that counts contradicted
exit decisions is held on hand-made readings."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import check

from conftest import WORKLOADS


def stale_cache(programs, cfg):
    """Decode steps return the replica's store as it was: the token's keys
    and values are never written."""
    inner = programs.stage_decode

    def stage_decode(stage_idx, x, store, slots):
        out, _ = inner(stage_idx, x, jax.tree.map(jnp.copy, store), slots)
        return out, store

    programs.stage_decode = stage_decode


def half_batch(programs, cfg):
    """The second half of every stage batch skips the stage."""
    for name in ("stage_prefill", "stage_decode"):
        inner = getattr(programs, name)

        def call(stage_idx, x, *args, _inner=inner):
            out = _inner(stage_idx, x, *args)
            y = out[0] if isinstance(out, tuple) else out
            keep = y.shape[0] // 2
            y = jnp.concatenate([y[:keep], jnp.asarray(x, y.dtype)[keep:]])
            return (y,) + tuple(out[1:]) if isinstance(out, tuple) else y

        setattr(programs, name, call)


def altered_token(programs, cfg):
    """The final head hands on the token after its argmax."""
    inner = programs.final_head

    def final_head(x):
        conf, tok = inner(x)
        return conf, (tok + 1) % cfg.vocab_size

    programs.final_head = final_head


@pytest.mark.parametrize("name,fault", [
    (name, fault) for name in WORKLOADS for fault in (stale_cache, half_batch, altered_token)
], ids=lambda v: getattr(v, "__name__", v))
def test_fault_fails_the_check(name, fault, small_run):
    # eight tokens: a stale cache then misses seven positions, not one
    res = small_run(name, wrap_programs=fault, gen_len=8)
    assert res["correct"] is False
    assert res["failed"] == 0  # every request finished: the numbers caught it
    over = [k for k, v in res["check"].items() if v["value"] > v["limit"]]
    assert over, res["check"]


def _served(exit_stage, conf_by_stage):
    """One served token whose heads read ``conf_by_stage`` {stage: conf}."""
    heads = [{h: (c, 0) for h, c in conf_by_stage.items()}]
    return check.Served(prompt=np.zeros(4, np.int32), tokens=(0,), exit_stage=exit_stage, heads=heads)


def _reading(ln_conf_by_stage):
    """Reference readings at one position with log-confidence ``ln``."""
    return {h: {"lmax": np.array([[0.0]]), "lse": np.array([[-ln]]), "picked": np.array([[0.0]])}
            for h, ln in ln_conf_by_stage.items()}


CONF = {"serve": {"num_stages": 4, "exit_stages": [2, 3]}}
LIMITS = {"token_gap": 0.15, "conf_gap": 0.15, "exit_flips": 0}


@pytest.mark.parametrize("exit_stage,ref_conf,flips", [
    (4, {2: 0.3, 3: 0.3, 4: 0.9}, 0),  # went on, and the reference lies below both thresholds
    (2, {2: 0.9}, 0),  # retired at 2, the reference above its threshold
    (2, {2: 0.3}, 1),  # retired at 2 though the reference lies far below the threshold
    (4, {2: 0.3, 3: 0.95, 4: 0.9}, 1),  # went past 3 though the reference lies far above it
    (4, {2: 0.3, 3: 0.72, 4: 0.9}, 0),  # near the threshold: within the conf_gap margin
])
def test_exit_decisions_are_compared(exit_stage, ref_conf, flips):
    """``exit_flips`` counts a retire-or-go-on decision that contradicts the
    reference's confidence by more than the margin (no cell on the chip
    retires a row yet, so this holds the rule on hand-made readings)."""
    served = [_served(exit_stage, ref_conf)]
    reading = _reading({h: np.log(c) for h, c in ref_conf.items()})
    numbers = check.compare(served, reading, CONF, [0.65, 0.7], LIMITS)
    assert numbers["exit_flips"] == flips
    assert numbers["conf_gap"] < 1e-6 and numbers["token_gap"] == 0
    assert check.verdict(numbers, LIMITS, 0) is (flips == 0)
