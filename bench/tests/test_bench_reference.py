"""The benchmark's float32 reference against the program's own prefill at
``.reduced()`` widths (each configuration's ``cpu``), for every
configuration of BENCHMARK.json.

With the program's matrix products switched to float32 for the test, the
two compute the same equations in the same precision and must agree to
float32 rounding: a reference that rounds to bfloat16 misses that
tolerance, so it cannot hide a wrong equation behind rounding.  The served
bfloat16 program then sits inside the cell's limits."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import spec, weights

BENCHMARK = spec.load_json(spec.ROOT / "BENCHMARK.json")
FILES = {c["name"]: spec.ROOT / c["file"] for c in BENCHMARK["configs"]}
# float32 against float32: the log-confidences and the logits agree to a
# few hundred float32 ulps of the values involved (O(10))
TIGHT = 2e-4


def setup(name, dtype):
    conf = spec.load_config(FILES[name])
    cfg = spec.arch_config(conf).reduced(**conf["cpu"])
    cfg = dataclasses.replace(cfg, dtype=dtype)
    conf = spec.family(conf).cpu_conf(conf, cfg)
    params = weights.make_params(cfg, 2**32 + 7)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (3, 24)).astype(np.int32)
    return conf, cfg, params, tokens


def program_prefill(params, cfg, tokens, f32: bool, monkeypatch):
    from repro.models import attention, layers, model as model_lib

    if f32:
        def mm(x, w, *, compute_dtype=None):
            return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32))
        monkeypatch.setattr(layers, "matmul", mm)
        monkeypatch.setattr(attention, "matmul", mm)
    with jax.default_matmul_precision("highest"):
        tok, conf, etok, _ = model_lib.prefill(params, {"tokens": jnp.asarray(tokens)}, cfg, 32)
        # the final head's confidence, as the serving path reads it
        x, _, _ = model_lib.forward_hidden(params, {"tokens": jnp.asarray(tokens)}, cfg)
        fconf, ftok = model_lib.final_confidence(params, x[:, -1:], cfg)
    return np.asarray(tok), np.asarray(conf), np.asarray(etok), np.asarray(fconf), np.asarray(ftok)


def reference_at_last(params, conf, tokens, precision):
    N, L = tokens.shape
    positions = np.full((N, 1), L - 1, np.int32)
    s = conf["serve"]
    gather = {h: np.zeros((N, 1), np.int32) for h in list(s["exit_stages"]) + [s["num_stages"]]}
    mod = spec.load_module(spec.BENCH_DIR / "references" / f"{conf['reference']}.py", "ref")
    return mod.readings(params, conf, tokens, positions, gather, precision=precision, rows=2)


def gaps(prog, ref, conf):
    _, econf, etok, fconf, ftok = prog
    s = conf["serve"]
    out = []
    for b, h in enumerate(s["exit_stages"]):
        out.append((econf[:, b], etok[:, b], ref[h]))
    out.append((fconf, ftok, ref[s["num_stages"]]))
    worst = 0.0
    same = True
    for c, t, r in out:
        ln_ref = r["lmax"][:, 0] - r["lse"][:, 0]
        worst = max(worst, float(np.max(np.abs(np.log(c) - ln_ref))))
        same &= bool(np.all(t == r["argmax"][:, 0]))
    return worst, same


@pytest.mark.parametrize("name", list(FILES))
def test_reference_matches_float32_program(name, monkeypatch):
    conf, cfg, params, tokens = setup(name, jnp.float32)
    prog = program_prefill(params, cfg, tokens, True, monkeypatch)
    worst, same = gaps(prog, reference_at_last(params, conf, tokens, "float32"), conf)
    assert same and worst <= TIGHT, worst
    worst_bf16, _ = gaps(prog, reference_at_last(params, conf, tokens, "bfloat16"), conf)
    assert worst_bf16 > 10 * TIGHT, worst_bf16


@pytest.mark.parametrize("name", list(FILES))
def test_served_precision_inside_the_cell_limits(name, monkeypatch):
    conf, cfg, params, tokens = setup(name, jnp.bfloat16)
    prog = program_prefill(params, cfg, tokens, False, monkeypatch)
    _, _, etok, _, ftok = prog
    s = conf["serve"]
    N, L = tokens.shape
    gather = {h: etok[:, b : b + 1] for b, h in enumerate(s["exit_stages"])}
    gather[s["num_stages"]] = ftok[:, None]
    mod = spec.load_module(spec.BENCH_DIR / "references" / f"{conf['reference']}.py", "ref")
    ref = mod.readings(params, conf, tokens, np.full((N, 1), L - 1, np.int32), gather, rows=2)
    conf_gap, _ = gaps(prog, ref, conf)
    token_gap = max(float(np.max(r["lmax"] - r["picked"])) for r in ref.values())
    for cell in [w["name"] for w in BENCHMARK["workloads"] if w["config"] == name]:
        limits = spec.load_json(spec.BENCH_DIR / "limits" / f"{cell}.json")
        assert conf_gap <= limits["conf_gap"], (cell, conf_gap)
        assert token_gap <= limits["token_gap"], (cell, token_gap)


def test_departure_is_what_runs(monkeypatch):
    """stablelm's file keeps the published partial RoPE; the configuration
    as run rotates the whole head, as the program does, and a reference
    that followed the published 25% would part from the program."""
    path = spec.BENCH_DIR / "configs" / "stablelm-1.6b.json"
    assert spec.load_json(path)["partial_rotary_factor"] == 0.25
    assert spec.load_config(path)["partial_rotary_factor"] == 1.0
    assert spec.load_cell("stablelm-chat").config["partial_rotary_factor"] == 1.0
    conf, cfg, params, tokens = setup("stablelm-1.6b", jnp.float32)
    prog = program_prefill(params, cfg, tokens, True, monkeypatch)
    published = dict(conf, partial_rotary_factor=0.25)
    worst, _ = gaps(prog, reference_at_last(params, published, tokens, "float32"), conf)
    assert worst > 10 * TIGHT, worst
