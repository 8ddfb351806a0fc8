"""The serving copy of the weights (``model.serving_params``).

``StagePrograms`` hands its programs a copy of the parameters in which every
leaf the programs read only through a cast to the compute dtype is held in
that dtype, so no call casts the weights again.  The rule is a predicate on
leaf names (``model.read_through_cast``); these tests hold it to what the
programs do with each leaf, check that serving from the copy gives bitwise
the outputs of programs handed the tree as given, and that the compiled
programs cast no weight.
"""
import re
from collections import defaultdict

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs
from repro.core.profiles import profile_from_arch
from repro.core.thresholds import synthetic_validation
from repro.core.topology import NetworkSpec, build_edge_network
from repro.core.types import DtoHyperParams
from repro.models import model as model_lib
from repro.serving import CollaborativeEngine, monolithic_generate, steps
from repro.serving.engine import StagePrograms

B, S, MAX_LEN = 2, 8, 16

# ops that only move a leaf's elements: a cast after them is still the
# leaf's first arithmetic
_MOVES = {"reshape", "squeeze", "expand_dims", "broadcast_in_dim", "slice",
          "dynamic_slice", "transpose"}
# ops that hand their operands to an inner jaxpr one for one
_CALLS = {"jit", "remat2", "scan"}


def _uses(jaxpr, var, out):
    """Append ``(dtype cast to | None, op)`` for every first use of ``var``,
    following it through element moves and into called jaxprs; any other
    op counts as a use that is not a cast."""
    if any(v is var for v in jaxpr.outvars):
        out.append((None, "output"))
    for eqn in jaxpr.eqns:
        for i, v in enumerate(eqn.invars):
            if v is not var:
                continue
            name = eqn.primitive.name
            if name == "convert_element_type":
                out.append((jnp.dtype(eqn.params["new_dtype"]), name))
            elif name in _MOVES:
                _uses(jaxpr, eqn.outvars[0], out)
            elif name in _CALLS:
                inner = eqn.params["jaxpr"]
                inner = getattr(inner, "jaxpr", inner)
                _uses(inner, inner.invars[i], out)
            else:
                out.append((None, name))


def _programs(cfg):
    """Every serving program of ``cfg`` with abstract arguments after the
    parameter tree: the calls ``StagePrograms`` makes."""
    x = jax.ShapeDtypeStruct((B, S, cfg.d_model), cfg.dtype)
    x1 = jax.ShapeDtypeStruct((B, 1, cfg.d_model), cfg.dtype)
    slots = jax.ShapeDtypeStruct((B,), jnp.int32)
    out = []
    if cfg.frontend == "tokens":
        out.append((steps.make_embed_step(cfg), (jax.ShapeDtypeStruct((B, S), jnp.int32),)))
    for h in range(1, cfg.num_stages + 1):
        store = jax.eval_shape(lambda: model_lib.init_stage_slot_caches(cfg, h, 3, MAX_LEN))
        pool, state = jax.eval_shape(
            lambda: model_lib.init_stage_paged_caches(cfg, h, 3, 5, 4, MAX_LEN)
        )
        tables = jax.ShapeDtypeStruct((B, MAX_LEN // 4), jnp.int32)
        out += [
            (steps.make_stage_forward(cfg, h), (x,)),
            (steps.make_stage_prefill(cfg, h, MAX_LEN), (x,)),
            (steps.make_stage_decode(cfg, h), (x1, store, slots)),
            (steps.make_paged_stage_decode(cfg, h, MAX_LEN), (x1, pool, state, tables, slots)),
        ]
        if h in cfg.exit_stages:
            out.append((steps.make_exit_head_step(cfg, h), (x1,)))
    out.append((steps.make_final_head_step(cfg), (x1,)))
    return out


@pytest.mark.parametrize("arch", list_archs())
def test_rule_matches_program_uses(arch):
    """A leaf is held in ``cfg.dtype`` exactly when every serving program
    reads it only through a cast to ``cfg.dtype``."""
    cfg = get_config(arch).reduced()
    params = model_lib.abstract_params(cfg)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    uses = defaultdict(list)
    for fn, args in _programs(cfg):
        jaxpr = jax.make_jaxpr(fn)(params, *args).jaxpr
        for (path, _), var in zip(leaves, jaxpr.invars):
            _uses(jaxpr, var, uses[jax.tree_util.keystr(path)])
    dtype = jnp.dtype(cfg.dtype)
    served = jax.eval_shape(lambda p: model_lib.serving_params(p, cfg), params)
    for (path, leaf), held in zip(leaves, jax.tree.leaves(served)):
        key = jax.tree_util.keystr(path)
        through_cast = bool(uses[key]) and all(to == dtype for to, _ in uses[key])
        assert model_lib.read_through_cast(path) == through_cast, (key, uses[key])
        assert held.dtype == (dtype if through_cast else leaf.dtype), key


def test_float32_config_serves_the_tree_as_given():
    cfg = get_config("stablelm-1.6b").reduced(dtype=jnp.float32)
    params = model_lib.init_params(jax.random.key(0), cfg)
    assert model_lib.serving_params(params, cfg) is params


# ---------------------------------------------------------------------------
# bitwise: the copy against the tree as given, and against the monolith
# ---------------------------------------------------------------------------

GEN = 3


def _engine(arch):
    cfg = get_config(arch).reduced(vocab_size=128)
    params = model_lib.init_params(jax.random.key(0), cfg)
    profile = profile_from_arch(cfg)
    topo = build_edge_network(
        seed=0, profile=profile, spec=NetworkSpec(num_eds=4, es_per_stage=(2, 2))
    )
    ep = synthetic_validation(seed=1, profile=profile)
    eng = CollaborativeEngine(params, cfg, topo, profile, ep, DtoHyperParams(rounds=20), seed=0)
    eng.configuration_phase()
    return eng


def _serve_recording_heads(engine, prompts):
    """Serve ``prompts``; return the stats and every head call's outputs.
    One row a batch, so an MoE layer's expert capacity sees the tokens the
    monolithic path gives it."""
    programs = engine.programs
    heads = []

    def record(name):
        inner = getattr(type(programs), name)

        def call(*args):
            out = inner(programs, *args)
            heads.append((name, args[:-1], np.asarray(out[0]), np.asarray(out[1])))
            return out

        return call

    programs.exit_head = record("exit_head")
    programs.final_head = record("final_head")
    try:
        engine.rng = np.random.default_rng(7)
        stats = engine.serve(prompts, arrival_rate=1e5, batch_size=1, gen_len=GEN)
    finally:
        del programs.exit_head, programs.final_head
    return stats, heads


@pytest.mark.parametrize(
    "arch",
    [
        "internlm2-20b",  # dense GQA, RMSNorm, no bias
        "qwen2.5-32b",  # GQA with QKV bias
        "mixtral-8x7b",  # MoE router and experts
        "zamba2-2.7b",  # Mamba state and conv leaves
        "deepseek-v2-lite-16b",  # MLA latents and norm_ckv
    ],
)
def test_serving_copy_is_bitwise_the_tree_as_given(arch):
    engine = _engine(arch)
    cfg = engine.cfg
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, size=6).astype(np.int32) for _ in range(3)]
    programs = engine.programs
    copy, given = programs.weights, programs.params
    assert programs.weight_bytes["held"] > programs.weight_bytes["kept"] > 0

    served, heads = _serve_recording_heads(engine, prompts)
    programs.weights = given
    as_given, heads_given = _serve_recording_heads(engine, prompts)
    programs.weights = copy

    assert served.sequences_by_rid() == as_given.sequences_by_rid()
    assert served.confidences == as_given.confidences
    assert len(heads) == len(heads_given) >= len(prompts) * (len(cfg.exit_stages) + 1)
    for (name, args, conf, tok), (name_g, args_g, conf_g, tok_g) in zip(heads, heads_given):
        assert name == name_g and args == args_g
        np.testing.assert_array_equal(conf, conf_g)
        np.testing.assert_array_equal(tok, tok_g)
    assert served.report()["weights"] == programs.weight_bytes

    reference = {
        i: (stage, tuple(toks))
        for i, p in enumerate(prompts)
        for toks, stage in [monolithic_generate(given, cfg, p, engine.thresholds, GEN)]
    }
    assert served.sequences_by_rid() == reference


_BUILDERS = ("make_embed_step", "make_stage_forward", "make_stage_prefill",
             "make_stage_decode", "make_paged_stage_decode", "make_exit_head_step",
             "make_final_head_step")


def test_every_program_is_handed_the_copy(monkeypatch):
    """Each program ``StagePrograms`` runs, in every decode mode and cache
    layout, gets the weights with the head held in the compute dtype."""
    seen = defaultdict(set)

    def recording(name, build):
        def builder(*a, **k):
            fn = build(*a, **k)

            def call(params, *args):
                seen[name].add(jnp.dtype(params["lm_head"].dtype))
                return fn(params, *args)

            return call

        return builder

    for name in _BUILDERS:
        monkeypatch.setattr(steps, name, recording(name, getattr(steps, name)))
    engine = _engine("stablelm-1.6b")
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 128, size=6).astype(np.int32) for _ in range(2)]
    for kw in ({}, {"cache_layout": "paged", "block_size": 4}, {"decode_mode": "stateless"}):
        engine.rng = np.random.default_rng(7)
        engine.serve(prompts, arrival_rate=1e5, batch_size=2, gen_len=GEN, **kw)
    assert dict(seen) == {name: {jnp.dtype(jnp.bfloat16)} for name in _BUILDERS}


def test_weight_bytes_count_the_leaves():
    cfg = get_config("stablelm-1.6b").reduced()
    params = model_lib.init_params(jax.random.key(0), cfg)
    held = kept = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if model_lib.read_through_cast(path):
            held += leaf.size * 2
        else:
            kept += leaf.size * 4
    assert held and kept
    programs = StagePrograms(params, cfg)
    assert programs.weight_bytes == {"dtype": "bfloat16", "held": held, "kept": kept}
    # setting the tree makes the copy anew
    programs.params = jax.tree.map(lambda a: a + 1, params)
    assert programs.weight_bytes == {"dtype": "bfloat16", "held": held, "kept": kept}
    np.testing.assert_array_equal(
        np.asarray(programs.weights["lm_head"], np.float32),
        np.asarray((params["lm_head"] + 1).astype(jnp.bfloat16), np.float32),
    )


# ---------------------------------------------------------------------------
# the compiled programs cast no weight
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* (\S+?)\(([^)]*)\)")


def f32_convert_shapes(hlo: str) -> set:
    """Shapes of the f32 operands of every ``convert`` in HLO text."""
    types, operands = {}, []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        name, dtype, shape, op, args = m.groups()
        types[name] = (dtype, tuple(int(d) for d in shape.split(",") if d))
        if op == "convert":
            operands.append(args.split(",")[0].strip().lstrip("%"))
    return {types[a][1] for a in operands if a in types and types[a][0] == "f32"}


def test_compiled_programs_cast_no_weight():
    cfg = get_config("stablelm-1.6b").reduced()
    params = model_lib.init_params(jax.random.key(0), cfg)
    weights = model_lib.serving_params(params, cfg)
    x1 = jax.ShapeDtypeStruct((B, 1, cfg.d_model), cfg.dtype)
    slots = jax.ShapeDtypeStruct((B,), jnp.int32)
    store = jax.eval_shape(lambda: model_lib.init_stage_slot_caches(cfg, 1, 3, MAX_LEN))
    held = [
        leaf.shape
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            {"stage": params["stages"][0], "lm_head": params["lm_head"]}
        )[0]
        if model_lib.read_through_cast(path)
    ]
    # a stage's leaves stack its periods; the loop body casts one period's
    shapes = set(held) | {s[1:] for s in held}

    def cast_shapes(tree):
        decode = steps.make_stage_decode(cfg, 1).lower(tree, x1, store, slots)
        head = steps.make_exit_head_step(cfg, cfg.exit_stages[0]).lower(tree, x1)
        return {
            name: f32_convert_shapes(lowered.compile().as_text()) & shapes
            for name, lowered in (("stage_decode", decode), ("exit_head", head))
        }

    # the f32 tree shows the casts the serving copy takes away
    assert all(cast_shapes(params).values())
    assert cast_shapes(weights) == {"stage_decode": set(), "exit_head": set()}
