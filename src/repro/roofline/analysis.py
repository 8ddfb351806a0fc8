"""Three-term roofline from a compiled dry-run artifact.

    compute term    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory term     = HLO_bytes / (chips * HBM_bw)
    collective term = collective_bytes / (chips * link_bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()`` (whole-program,
all devices); collective_bytes comes from parsing the SPMD HLO (see hlo.py).
MODEL_FLOPS = 6*N*D for dense archs (6*N_active*D for MoE) measures how much
of the compiled compute is "useful" — remat recompute, padding and dead work
show up as a low ratio.
"""
from __future__ import annotations

import dataclasses

from repro.roofline import constants
from repro.roofline.hlo import CollectiveStats, collective_stats


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    num_devices: int
    hlo_flops: float
    hlo_bytes: float
    collective: CollectiveStats
    model_flops: float
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops > 0 else 0.0

    @property
    def roofline_fraction(self) -> float:
        """What fraction of the bound-term time is useful model compute —
        the headline score: model_flops_time / achievable_step_time."""
        peak = constants.peaks(constants.DRYRUN_TARGET).flops_bf16
        ideal = self.model_flops / (self.num_devices * peak)
        return ideal / self.bound_s if self.bound_s > 0 else 0.0

    def row(self) -> dict:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "devices": self.num_devices,
            "hlo_gflops": self.hlo_flops / 1e9,
            "hlo_gbytes": self.hlo_bytes / 1e9,
            "coll_gbytes_global": self.collective.global_bytes / 1e9,
            "compute_ms": self.compute_s * 1e3,
            "memory_ms": self.memory_s * 1e3,
            "collective_ms": self.collective_s * 1e3,
            "dominant": self.dominant,
            "model_gflops": self.model_flops / 1e9,
            "useful_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def model_flops_for(cfg, shape) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE); decode counts one new token."""
    n = cfg.param_count(active_only=cfg.moe is not None)
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens  # forward only
    # decode: one token per sequence; attention reads the cache but
    # param-FLOPs dominate the 6ND-style accounting (2*N per token fwd)
    return 2.0 * n * shape.global_batch


def build_report(
    *,
    arch: str,
    shape_name: str,
    mesh_name: str,
    num_devices: int,
    cost_analysis: dict,
    hlo_text: str,
    model_flops: float,
) -> RooflineReport:
    # cost_analysis() reports the per-device SPMD program; globalize.
    flops = float(cost_analysis.get("flops", 0.0)) * num_devices
    nbytes = float(cost_analysis.get("bytes accessed", 0.0)) * num_devices
    coll = collective_stats(hlo_text, num_devices)
    pk = constants.peaks(constants.DRYRUN_TARGET)
    return RooflineReport(
        arch=arch,
        shape=shape_name,
        mesh=mesh_name,
        num_devices=num_devices,
        hlo_flops=flops,
        hlo_bytes=nbytes,
        collective=coll,
        model_flops=model_flops,
        compute_s=flops / (num_devices * pk.flops_bf16),
        memory_s=nbytes / (num_devices * pk.hbm_bw),
        collective_s=coll.global_bytes / (num_devices * pk.ici_bw),
    )
