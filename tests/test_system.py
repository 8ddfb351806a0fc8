"""End-to-end behaviour: paper-claim checks + a real (small-mesh) dry-run
in a subprocess (device-count override must not leak into this process)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_paper_claim_dto_ee_beats_baselines_static():
    """DTO-EE: lower delay than CF/BF (Figs 3-4 regime, simulated)."""
    from repro.core import baselines, dto_ee, simulator
    from repro.core.thresholds import synthetic_validation
    from repro.core.topology import build_edge_network
    from repro.core.types import DtoHyperParams, RESNET101_PROFILE

    profile = RESNET101_PROFILE
    hyper = DtoHyperParams()
    topo = build_edge_network(seed=0, profile=profile, arrival_rate_scale=3.0)
    ep = synthetic_validation(seed=1, profile=profile)
    res = dto_ee.solve(topo, profile, ep, hyper)
    p_dto, thr = np.asarray(res.state.carry.p), res.state.thresholds
    dto = simulator.simulate_slot(topo, profile, ep, p_dto, thr, seed=42)

    for p_b in (baselines.computing_first(topo), baselines.bandwidth_first(topo)):
        thr_b, _, _ = baselines.adapt_thresholds_for_strategy(
            topo, profile, ep, p_b, hyper
        )
        sim_b = simulator.simulate_slot(
            topo, profile, ep, np.asarray(p_b), thr_b, seed=42
        )
        assert dto.mean_delay < sim_b.mean_delay * 0.9  # >=10% better


def test_paper_claim_threshold_ablation_direction():
    """DTO-EE vs fixed-1.0: >=15% lower delay at <=1.5pt accuracy cost."""
    from repro.core import dto_ee, simulator
    from repro.core.thresholds import synthetic_validation
    from repro.core.topology import build_uniform_network
    from repro.core.types import DtoHyperParams, RESNET101_PROFILE

    profile = RESNET101_PROFILE
    hyper = DtoHyperParams()
    ep = synthetic_validation(seed=1, profile=profile)
    topo = build_uniform_network(seed=0, profile=profile, ed_arrival_rate=2.2)

    res = dto_ee.solve(topo, profile, ep, hyper)
    dto = simulator.simulate_slot(
        topo, profile, ep, np.asarray(res.state.carry.p), res.state.thresholds, seed=5
    )
    res10 = dto_ee.solve(topo, profile, ep, hyper, adapt_thresholds=False)
    base = simulator.simulate_slot(
        topo,
        profile,
        ep,
        np.asarray(res10.state.carry.p),
        np.ones(ep.num_early_branches),
        seed=5,
    )
    assert dto.mean_delay < base.mean_delay * 0.85
    # the utility tradeoff may spend a few accuracy points for the delay cut;
    # it must stay within the paper's 1-5pt band and win on utility U (Eq. 9)
    assert dto.accuracy > base.accuracy - 0.05
    from repro.core.thresholds import synthetic_validation as _sv
    from repro.core.utility import utility

    a = hyper.utility_a
    u_dto = utility(dto.mean_delay, ep.normalized_accuracy(dto.accuracy), a)
    u_base = utility(base.mean_delay, ep.normalized_accuracy(base.accuracy), a)
    assert u_dto < u_base


@pytest.mark.slow
def test_dryrun_cell_compiles_in_subprocess():
    """A real (reduced-arch) lower+compile on a forced 16-device host mesh."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, json
import numpy as np
from repro.configs import get_config, SHAPES
from repro.launch import dryrun
# dryrun imported the symbol directly; patch it there
dryrun.make_production_mesh = lambda multi_pod=False: jax.make_mesh(
    (2, 2, 4) if multi_pod else (4, 4),
    ("pod", "data", "model") if multi_pod else ("data", "model"),
)
import repro.configs.registry as reg
cfg = reg.get_config("stablelm-1.6b").reduced(vocab_size=512)
reg._cache["stablelm-1.6b"] = cfg
# gates only: full fits are too heavy for a contended 1-core CI box
row = dryrun.run_cell("stablelm-1.6b", "train_4k", multi_pod=False, fit=False, save=False)
assert row.get("gate") == "ok", row
row2 = dryrun.run_cell("stablelm-1.6b", "decode_32k", multi_pod=True, fit=False, save=False)
assert row2.get("gate") == "ok", row2
print("SUBPROCESS_OK", row["memory"].get("peak_gb_per_device_tpu"))
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=env,
        timeout=1200,
    )
    assert "SUBPROCESS_OK" in out.stdout, out.stdout + out.stderr


def test_data_pipeline_deterministic_resume():
    from repro.configs import get_config
    from repro.data import DataConfig, token_stream

    cfg = get_config("stablelm-1.6b").reduced()
    dcfg = DataConfig(batch_size=2, seq_len=16, seed=3)
    a = token_stream(cfg, dcfg, start_step=0)
    batches = [next(a) for _ in range(5)]
    b = token_stream(cfg, dcfg, start_step=3)
    resumed = next(b)
    np.testing.assert_array_equal(
        np.asarray(batches[3]["tokens"]), np.asarray(resumed["tokens"])
    )


REPO = os.path.join(os.path.dirname(__file__), "..")


def test_compile_cache_goes_where_the_env_var_says(tmp_path):
    code = (
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "import jax, jax.numpy as jnp\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n"
    )
    env = dict(
        os.environ,
        PYTHONPATH=SRC,
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == str(tmp_path)
    assert any(tmp_path.iterdir())  # the compiled program was written there


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == os.path.join(os.path.realpath(REPO), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr
