"""Operation and byte counts against hand-worked values at both
configurations' published widths, and each file's parameter count against
the published one."""
import pytest

from benchlib import counting, spec

CONFIGS = ("stablelm-1.6b", "internlm2-1.8b")


def conf(name):
    return spec.load_json(spec.BENCH_DIR / "configs" / f"{name}.json")


@pytest.mark.parametrize("name,published", [
    ("stablelm-1.6b", 1_644_515_328), ("internlm2-1.8b", 1_889_110_016)])
def test_param_count_is_published(name, published):
    c = conf(name)
    assert counting.param_count(c) == published == c["published_params"]
    assert round(published / 1e9, 3) == {"stablelm-1.6b": 1.645, "internlm2-1.8b": 1.889}[name]


@pytest.mark.parametrize("name", CONFIGS)
def test_param_count_matches_program(name):
    """The program's tree holds the published model plus one norm per exit."""
    from repro.models import model as model_lib

    c = conf(name)
    cfg = spec.arch_config(c)
    assert model_lib.count_params(cfg) == counting.param_count(c, exit_heads=True)


@pytest.mark.parametrize("name,flops,nbytes", [
    # H*hd = 2048 for both; stablelm KV*hd = 2048, internlm2 KV*hd = 1024
    ("stablelm-1.6b", 4 * 2048 * 300, 2 * 300 * 2048 * 2 + 2 * 2048 * 2 * 2),
    ("internlm2-1.8b", 4 * 2048 * 300, 2 * 300 * 1024 * 2 + 2 * 2048 * 2 * 2),
])
def test_decode_attention_cost(name, flops, nbytes):
    assert counting.decode_attention_cost(conf(name), [100, 200]) == (flops, nbytes)
    assert (flops, nbytes) == (
        {"stablelm-1.6b": (2_457_600, 2_473_984), "internlm2-1.8b": (2_457_600, 1_245_184)}[name]
    )


@pytest.mark.parametrize("name,flops,nbytes", [
    ("stablelm-1.6b", 3_288_334_336, 411_074_624),
    ("internlm2-1.8b", 3_032_481_792, 379_093_056),
])
def test_exit_confidence_cost(name, flops, nbytes):
    # 2 * 8 rows * 2048 * V; the bf16 head, 8 bf16 rows in, 8 bytes out each
    assert counting.exit_confidence_cost(conf(name), 8) == (flops, nbytes)


@pytest.mark.parametrize("name,per_layer", [
    ("stablelm-1.6b", 51_380_224), ("internlm2-1.8b", 62_914_560)])
def test_layer_matmul_params(name, per_layer):
    assert counting.layer_matmul_params(conf(name)) == per_layer
    assert counting.stage_layers(conf(name)) == [6, 6, 6, 6]


def test_model_flops_of_passes():
    c = conf("stablelm-1.6b")
    # a 128-token prompt through stage 1: 6 layers of 2 * 51,380,224 * 128
    # plus causal attention, 4 * 2048 * (1 + ... + 128) = 4 * 2048 * 8256
    assert counting.stage_pass_flops(c, 1, 128, 128) == 6 * (
        2 * 51_380_224 * 128 + 4 * 2048 * 8256)
    # one cached token at context 200
    assert counting.stage_pass_flops(c, 2, 1, 200) == 6 * (2 * 51_380_224 + 4 * 2048 * 200)
    assert counting.head_flops(c, 3) == 3 * 2 * 2048 * 100352


def test_roofline_bound_takes_the_larger_term():
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    assert counting.roofline_s(197e12, 1.0, peaks) == 1.0
    assert counting.roofline_s(1.0, 819e9 * 2, peaks) == 2.0
