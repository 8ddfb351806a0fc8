"""A configuration file names its model family, and the harness finds the
family's module by that name (``bench/families/<family>.py``).

For the configurations of BENCHMARK.json: the ``ArchConfig`` the family
builds, written out field by field, and the counts the readers use, pinned
bitwise.  For the lookup: its errors, and a ``reduced`` entry put in place
with its published value kept.  And a family that no file under ``bench/``
holds, written for the test into a temporary directory (MLA attention and
routed experts), carried through the harness's set-up and window on the
CPU: a new architecture enters as new files, with no edit to the harness.
"""
import dataclasses
import json
import textwrap
from time import perf_counter

import jax.numpy as jnp
import pytest

from benchlib import cell as cell_lib, counting, spec

from conftest import WORKLOADS, small_cell


def conf(name):
    return spec.load_config(spec.BENCH_DIR / "configs" / f"{name}.json")


def test_every_configuration_names_a_family():
    for c in spec.load_json(spec.ROOT / "BENCHMARK.json")["configs"]:
        family = spec.load_json(spec.ROOT / c["file"])["family"]
        assert (spec.FAMILIES_DIR / f"{family}.py").is_file(), c["name"]


@pytest.mark.parametrize("name", ["stablelm-1.6b", "internlm2-1.8b"])
def test_arch_config_through_the_family(name):
    from repro.configs.base import ArchConfig

    expected = {
        "stablelm-1.6b": ArchConfig(
            name="stablelm-1.6b", family="dense", num_layers=24, d_model=2048, num_heads=32,
            num_kv_heads=32, d_ff=5632, vocab_size=100352, head_dim=64, norm="layernorm",
            act="silu", ffn="glu", qkv_bias=True, rope_theta=10000.0, sliding_window=None,
            period=("attn",), moe=None, mla=None, mamba=None, xlstm=None, frontend="tokens",
            num_stages=4, exit_stages=(2, 3), exit_loss_weight=0.3, sub_quadratic=False,
            q_chunk=1024, dtype=jnp.bfloat16, notes=""),
        "internlm2-1.8b": ArchConfig(
            name="internlm2-1.8b", family="dense", num_layers=24, d_model=2048, num_heads=16,
            num_kv_heads=8, d_ff=8192, vocab_size=92544, head_dim=128, norm="rmsnorm",
            act="silu", ffn="glu", qkv_bias=False, rope_theta=1000000.0, sliding_window=None,
            period=("attn",), moe=None, mla=None, mamba=None, xlstm=None, frontend="tokens",
            num_stages=4, exit_stages=(2, 3), exit_loss_weight=0.3, sub_quadratic=False,
            q_chunk=1024, dtype=jnp.bfloat16, notes=""),
    }[name]
    assert spec.load_json(spec.BENCH_DIR / "configs" / f"{name}.json")["family"] == "dense_decoder"
    assert spec.arch_config(conf(name)) == expected
    cells = [w["name"] for w in spec.load_json(spec.ROOT / "BENCHMARK.json")["workloads"]
             if w["config"] == name]
    assert cells and all(spec.arch_config(spec.load_cell(c).config) == expected for c in cells)


# the counts the readers use, as the harness counted them before the dense
# block's counts moved into its family module: equal to the last bit
PINNED = {
    "stablelm-1.6b": {
        "stage_pass_flops": [240393388032.0, 636174336.0, 622903296.0, 159456952320.0],
        "decode_attention_cost": (6438912.0, 6471680.0),
        "param_count_exit_heads": 1644523520,
        "attention_flops": 17031168.0,
        "exit_confidence_cost": (1233125376.0, 411054104.0),
        "head_flops": 2055208960.0,
    },
    "internlm2-1.8b": {
        "stage_pass_flops": [293543608320.0, 774586368.0, 761315328.0, 194890432512.0],
        "decode_attention_cost": (6438912.0, 3252224.0),
        "param_count_exit_heads": 1889114112,
        "attention_flops": 17031168.0,
        "exit_confidence_cost": (1137180672.0, 379072536.0),
        "head_flops": 1895301120.0,
    },
}


@pytest.mark.parametrize("name", list(PINNED))
def test_counts_unchanged_by_the_move(name):
    c, want = conf(name), PINNED[name]
    passes = [(1, 384, 384), (3, 1, 399), (4, 1, 129), (2, 256, 256)]
    assert [counting.stage_pass_flops(c, *p) for p in passes] == want["stage_pass_flops"]
    assert counting.decode_attention_cost(c, [1, 129, 257, 399]) == want["decode_attention_cost"]
    assert counting.param_count(c, exit_heads=True) == want["param_count_exit_heads"]
    assert spec.family(c).attention_flops(c, 7, 300) == want["attention_flops"]
    assert counting.exit_confidence_cost(c, 3) == want["exit_confidence_cost"]
    assert counting.head_flops(c, 5) == want["head_flops"]


def test_a_file_without_a_family_is_refused():
    c = conf("stablelm-1.6b")
    del c["family"]
    with pytest.raises(ValueError, match="names no family") as e:
        spec.arch_config(c)
    assert str(spec.FAMILIES_DIR) in str(e.value)
    with pytest.raises(ValueError, match="names no family"):
        counting.param_count(c)


def test_an_unknown_family_is_refused():
    c = dict(conf("internlm2-1.8b"), family="no_such_family")
    with pytest.raises(ValueError, match="no_such_family") as e:
        spec.arch_config(c)
    assert str(spec.FAMILIES_DIR) in str(e.value) and "dense_decoder" in str(e.value)


def test_reduced_entry_runs_and_keeps_the_published_value(tmp_path):
    raw = spec.load_json(spec.BENCH_DIR / "configs" / "internlm2-1.8b.json")
    why = "12 of the 24 layers, to fit the chip"
    raw["reduced"] = {"num_hidden_layers": {"run": 12, "why": why}}
    path = tmp_path / "internlm2-12l.json"
    path.write_text(json.dumps(raw))
    c = spec.load_config(path)
    assert c["num_hidden_layers"] == 12
    assert c["reduced"]["num_hidden_layers"] == {"run": 12, "why": why, "published": 24}
    assert spec.load_json(path)["num_hidden_layers"] == 24  # the file keeps what was published
    assert spec.arch_config(c).num_layers == 12
    assert counting.stage_layers(c) == [3, 3, 3, 3]
    # the published model's count comes from the file as published
    assert counting.param_count(raw) == raw["published_params"]
    assert counting.param_count(c) < raw["published_params"]
    # departures are still put in place the same way
    s = spec.load_config(spec.BENCH_DIR / "configs" / "stablelm-1.6b.json")
    assert s["partial_rotary_factor"] == 1.0
    assert s["departures"]["partial_rotary_factor"]["published"] == 0.25


def test_a_reduced_key_the_file_does_not_publish_is_refused(tmp_path):
    raw = spec.load_json(spec.BENCH_DIR / "configs" / "internlm2-1.8b.json")
    raw["reduced"] = {"num_layers": {"run": 12, "why": "a key of the program, not the file"}}
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(raw))
    with pytest.raises(KeyError, match="num_layers"):
        spec.load_config(path)


# A family module of the kind a new architecture brings: MLA attention and routed
# experts, mapped from DeepSeek-V2-style published keys.
MLA_MOE_FAMILY = textwrap.dedent('''
    """MLA attention and routed experts in every layer (test only)."""


    def arch_config(conf):
        import jax.numpy as jnp

        from repro.configs.base import ArchConfig
        from repro.models.attention import MlaDims
        from repro.models.moe import MoeDims

        s = conf["serve"]
        d, H = conf["hidden_size"], conf["num_attention_heads"]
        return ArchConfig(
            name=conf["name"], family="moe", num_layers=conf["num_hidden_layers"], d_model=d,
            num_heads=H, num_kv_heads=H, d_ff=conf["moe_intermediate_size"],
            vocab_size=conf["vocab_size"], head_dim=conf["v_head_dim"], norm=s["norm"],
            act=conf["hidden_act"], rope_theta=float(conf["rope_theta"]),
            period=tuple(s["period"]), num_stages=s["num_stages"],
            exit_stages=tuple(s["exit_stages"]), dtype=getattr(jnp, s["compute_dtype"]),
            mla=MlaDims(d_model=d, num_heads=H, kv_lora_rank=conf["kv_lora_rank"],
                        qk_nope_head_dim=conf["qk_nope_head_dim"],
                        qk_rope_head_dim=conf["qk_rope_head_dim"], v_head_dim=conf["v_head_dim"]),
            moe=MoeDims(d_model=d, d_ff_expert=conf["moe_intermediate_size"],
                        num_experts=conf["n_routed_experts"], top_k=conf["num_experts_per_tok"],
                        num_shared=conf["n_shared_experts"], router_norm="softmax_topk"),
        )


    def cpu_conf(conf, cfg):
        return dict(conf, hidden_size=cfg.d_model, num_hidden_layers=cfg.num_layers,
                    num_attention_heads=cfg.num_heads, vocab_size=cfg.vocab_size)
''')

MLA_MOE_CONF = {
    "name": "mla-moe-test",
    "family": "mla_moe",
    "hidden_size": 2048, "num_hidden_layers": 12, "num_attention_heads": 16,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "n_routed_experts": 64, "num_experts_per_tok": 6, "n_shared_experts": 2,
    "moe_intermediate_size": 1408, "vocab_size": 102400, "hidden_act": "silu",
    "rope_theta": 10000,
    "reduced": {"n_routed_experts": {"run": 8, "why": "the share of one chip of eight"}},
    "serve": {"norm": "rmsnorm", "period": ["moe_attn"], "num_stages": 4, "exit_stages": [2, 3],
              "compute_dtype": "bfloat16", "weight_dtype": "float32"},
    "cpu": {},
}


def test_new_family_runs_set_up_and_window_from_files_alone(tmp_path, monkeypatch, no_compile_cache):
    families = tmp_path / "families"
    families.mkdir()
    (families / "mla_moe.py").write_text(MLA_MOE_FAMILY)
    path = tmp_path / "mla-moe-test.json"
    path.write_text(json.dumps(MLA_MOE_CONF))
    monkeypatch.setattr(spec, "FAMILIES_DIR", families)
    assert not (spec.BENCH_DIR / "families" / "mla_moe.py").exists()

    c = spec.load_config(path)
    full = spec.arch_config(c)
    assert full.period == ("moe_attn",) and full.mla.kv_lora_rank == 512
    assert full.moe.num_experts == 8 and full.moe.top_k == 6  # the reduced entry runs

    cell = dataclasses.replace(small_cell(WORKLOADS[0]), name="mla-moe-chat", config=c)
    window = cell_lib.measure(cell, 2**33 + 9, 0.5, False, t_start=perf_counter(),
                              require_tpu=False, reduced=c["cpu"])
    assert window.cfg.mla is not None and window.cfg.moe is not None
    assert window.cfg.d_model == 128 and window.conf["hidden_size"] == 128
    assert window.attempted >= 8 and window.failed == 0
    assert len(window.by_key) == window.attempted
    metrics = window.result["metrics"]
    assert {"tokens_per_s", "ttft_p95_ms", "setup_s"} <= set(metrics)
    assert all(m["value"] > 0 for m in metrics.values())
