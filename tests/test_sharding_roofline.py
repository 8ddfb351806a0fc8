"""Sharding specs + roofline HLO parsing."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import sharding
from repro.configs import get_config
from repro.models import model as model_lib
from repro.roofline.hlo import collective_stats
from repro.roofline import analysis, constants


@pytest.fixture
def mesh11():
    return jax.make_mesh((1, 1), ("data", "model"))


def _abstract_mesh(sizes, names):
    """AbstractMesh across the jax signature change (positional axis_sizes +
    axis_names vs. a single tuple of (name, size) pairs)."""
    from jax.sharding import AbstractMesh

    try:
        return AbstractMesh(sizes, names)
    except TypeError:
        return AbstractMesh(tuple(zip(names, sizes)))


def test_constrain_is_noop_without_mesh():
    sharding.clear_mesh()
    x = jnp.ones((4, 4))
    y = sharding.constrain(x, "batch", "seq")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_param_specs_layouts(mesh11):
    rules = sharding.set_mesh(mesh11)
    cfg = get_config("stablelm-1.6b").reduced()
    aparams = model_lib.abstract_params(cfg)
    specs = sharding.param_specs(aparams)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    # every leaf got a PartitionSpec; stacked stage weights lead with None
    for path, spec in flat:
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        assert isinstance(spec, P)
        if "stages" in pstr and len(spec) >= 1:
            assert spec[0] is None, f"{pstr} must not shard the scan dim"
    sharding.clear_mesh()


def test_cache_specs_shard_seq_on_model_axis():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    sharding.set_mesh(mesh)
    cfg = get_config("glm4-9b").reduced()
    caches = model_lib.cache_specs(cfg, batch=2, max_len=64)
    specs = sharding.cache_specs(caches)
    # with axis sizes 1 everything degrades to replication but specs exist
    for leaf in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        assert isinstance(leaf, P)
    sharding.clear_mesh()


def test_divisibility_fallback():
    from repro.sharding.specs import MeshRules, _spec_for

    mesh = _abstract_mesh((4, 2), ("data", "model"))
    rules = MeshRules.standard(mesh)
    # dim 7 not divisible by 4 / dim 3 not divisible by 2 -> replicated
    assert _spec_for((7, 3), ("batch", "seq"), rules) == P(None, None)
    # divisible dims shard
    assert _spec_for((8, 4), ("batch", "seq"), rules) == P("data", "model")


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

FAKE_HLO = """
  %all-reduce.1 = f32[16,1024]{1,0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%add
  %all-gather.2 = bf16[4,512]{1,0} all-gather(%y), replica_groups=[2,8]<=[16], dimensions={0}
  %reduce-scatter.3 = f32[128]{0} reduce-scatter(%z), replica_groups={{0,1}}, to_apply=%add
  %collective-permute.4 = bf16[64,64]{1,0} collective-permute(%w), source_target_pairs={{0,1}}
  %all-reduce.5 = f32[8]{0} all-reduce(%v), replica_groups={{0}}, to_apply=%add
"""


def test_collective_stats_parses_ops():
    st = collective_stats(FAKE_HLO, num_devices=16)
    assert st.counts["all-reduce"] == 1  # groups of 1 skipped
    assert st.counts["all-gather"] == 1
    assert st.counts["reduce-scatter"] == 1
    assert st.counts["collective-permute"] == 1
    # all-reduce: 2 * bytes * 3/4
    ar = 16 * 1024 * 4 * 2 * 3 / 4
    assert abs(st.by_op["all-reduce"] - ar) < 1
    # all-gather (iota groups of 8): bytes * 7/8
    ag = 4 * 512 * 2 * 7 / 8
    assert abs(st.by_op["all-gather"] - ag) < 1
    assert st.global_bytes == pytest.approx(st.per_device_bytes * 16)


def test_roofline_report_terms():
    rep = analysis.RooflineReport(
        arch="a",
        shape="train_4k",
        mesh="m",
        num_devices=256,
        hlo_flops=1e18,
        hlo_bytes=1e15,
        collective=collective_stats(FAKE_HLO, 256),
        model_flops=5e17,
        compute_s=1e18 / (256 * constants.peaks("TPU v5 lite").flops_bf16),
        memory_s=1e15 / (256 * constants.peaks("TPU v5 lite").hbm_bw),
        collective_s=1.0,
    )
    assert rep.dominant == "compute"  # 19.8s compute > 1s collective
    assert 0 < rep.useful_flops_ratio <= 1
    assert rep.roofline_fraction < 1


def test_model_flops_modes():
    from repro.configs import SHAPES

    cfg = get_config("stablelm-1.6b")
    n = cfg.param_count()
    train = analysis.model_flops_for(cfg, SHAPES["train_4k"])
    assert train == pytest.approx(6.0 * n * 4096 * 256, rel=1e-6)
    dec = analysis.model_flops_for(cfg, SHAPES["decode_32k"])
    assert dec == pytest.approx(2.0 * n * 128, rel=1e-6)


def test_pure_dp_policy_maps_all_axes_to_batch():
    from repro.sharding.specs import MeshRules

    mesh = _abstract_mesh((2, 4, 4), ("pod", "data", "model"))
    rules = MeshRules.pure_dp(mesh)
    assert rules.batch_axes == ("pod", "data", "model")
    assert rules.tp_axis is None
    assert rules.axis_size(rules.batch_axes) == 32


def test_cache_feature_sharding_avoids_seq_dim(monkeypatch):
    """Default KV policy shards the feature dim (local per-token writes);
    REPRO_CACHE_SHARD=seq restores the sequence layout."""
    from repro.sharding import specs as S

    mesh = _abstract_mesh((4, 4), ("data", "model"))
    rules = S.MeshRules.standard(mesh)
    cache = {
        "k": jax.ShapeDtypeStruct((2, 8, 64, 8, 128), jnp.bfloat16),
        "v": jax.ShapeDtypeStruct((2, 8, 64, 8, 128), jnp.bfloat16),
        "pos": jax.ShapeDtypeStruct((2,), jnp.int32),
    }
    monkeypatch.setenv("REPRO_CACHE_SHARD", "feature")
    spec = S.cache_specs(cache, rules)["k"]
    assert spec == P(None, "data", None, None, "model")  # hd sharded, seq local
    monkeypatch.setenv("REPRO_CACHE_SHARD", "seq")
    spec = S.cache_specs(cache, rules)["k"]
    assert spec == P(None, "data", "model", None, None)  # seq sharded


def test_constrain_like_params_noop_without_mesh():
    sharding.clear_mesh()
    tree = {"stages": [{"w_q": jnp.ones((4, 4))}]}
    out = sharding.specs.constrain_like_params(tree) if hasattr(sharding, "specs") else tree
    from repro.sharding.specs import constrain_like_params

    out = constrain_like_params(tree)
    np.testing.assert_array_equal(
        np.asarray(out["stages"][0]["w_q"]), np.ones((4, 4))
    )
