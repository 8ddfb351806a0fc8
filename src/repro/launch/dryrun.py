"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Two modes per cell:

  GATE (always)  — the full-depth production program (scan-over-layers)
    is lowered and compiled against the production mesh.  Success proves
    the sharding config is coherent (no mismatched collectives, no
    unpartitionable ops) and memory_analysis proves it fits.

  MEASURE (--fit) — XLA's cost analysis counts while-loop bodies ONCE, so
    exact FLOP/byte/collective totals come from two UNROLLED reduced-depth
    variants (k=1 and k=2 periods per stage) of the same program on the
    same mesh.  Every per-cell cost is linear in the period count
    (identical blocks), so  cost(P) = b + a*P  fits exactly and
    extrapolates to the production depth.  sLSTM's per-timestep recurrence
    (trip count == seq_len, not unrollable) is corrected analytically.

MUST set the host-device override before ANY jax-touching import — jax
locks the device count at first init.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import sharding  # noqa: E402
from repro.configs import SHAPES, get_config, input_specs, list_archs  # noqa: E402
from repro.configs.base import shape_applicable  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import layers as layers_lib  # noqa: E402
from repro.models import model as model_lib  # noqa: E402
from repro.roofline import analysis, corrections  # noqa: E402
from repro.roofline.hlo import collective_stats  # noqa: E402
from repro.serving.steps import make_decode_step, make_prefill_step  # noqa: E402
from repro.training import AdamWConfig, make_train_step  # noqa: E402
from repro.training import optimizer as opt_lib  # noqa: E402

ARTIFACT_DIR = os.path.normpath(
    os.path.join(os.path.dirname(__file__), "../../../experiments/dryrun")
)


def _ns(mesh, spec_tree):
    from jax.sharding import NamedSharding

    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree)


def _sds_with(shardings, abstract):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract,
        shardings,
    )


def _serving_params(aparams, cfg):
    """Serving checkpoints hold bf16 matrix weights (norm vectors stay f32)."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16)
        if (a.dtype == jnp.float32 and len(a.shape) >= 2)
        else a,
        aparams,
    )


def build_lowered(cfg, shape, mesh, microbatches: int = 1, policy: str = "dp_tp"):
    """Lower the cell's step program against ``mesh`` (no compile)."""
    rules = sharding.set_mesh(mesh, policy)
    aparams = model_lib.abstract_params(cfg)
    if shape.mode in ("prefill", "decode") and os.environ.get(
        "REPRO_SERVE_LAYOUT", "replicated"
    ) == "replicated":
        # inference: bf16 weights, TP-only sharding (no per-step FSDP gathers)
        aparams = _serving_params(aparams, cfg)
        pspecs = sharding.param_specs(aparams, rules.as_serving())
    else:
        pspecs = sharding.param_specs(aparams)
    abatch = input_specs(cfg, shape)
    bspecs = sharding.batch_specs(abatch)
    thresholds = jax.ShapeDtypeStruct((len(cfg.exit_stages),), jnp.float32)

    with mesh:
        aparams_s = _sds_with(_ns(mesh, pspecs), aparams)
        abatch_s = _sds_with(_ns(mesh, bspecs), abatch)
        if shape.mode == "train":
            aopt = jax.eval_shape(opt_lib.init_opt_state, aparams)
            ospecs = sharding.param_specs(aopt)
            aopt_s = _sds_with(_ns(mesh, ospecs), aopt)
            step_fn = make_train_step(cfg, AdamWConfig(), microbatches=microbatches)
            # donate (params, opt): params'/opt' alias their inputs
            return jax.jit(step_fn, donate_argnums=(0, 1)).lower(
                aparams_s, aopt_s, abatch_s
            )
        if shape.mode == "prefill":
            step_fn = make_prefill_step(cfg, max_len=shape.seq_len)
            return jax.jit(step_fn).lower(aparams_s, abatch_s, thresholds)
        # decode
        acaches = model_lib.cache_specs(cfg, shape.global_batch, shape.seq_len)
        cspecs = sharding.cache_specs(acaches)
        acaches_s = _sds_with(_ns(mesh, cspecs), acaches)
        step_fn = make_decode_step(cfg)
        # donate the KV/state caches: in-place update halves the HBM bill
        return jax.jit(step_fn, donate_argnums=(2,)).lower(
            aparams_s, abatch_s, acaches_s, thresholds
        )


def _cost_dict(compiled) -> dict:
    """compiled.cost_analysis() across the jax return-type change (older
    versions hand back a one-element list of dicts, newer a plain dict)."""
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost


def _compile_costs(cfg, shape, mesh, microbatches: int = 1, policy: str = "dp_tp"):
    """compile; returns (per_device_flops, per_device_bytes, coll_stats)."""
    num_devices = int(np.prod(list(mesh.shape.values())))
    lowered = build_lowered(cfg, shape, mesh, microbatches, policy)
    compiled = lowered.compile()
    cost = _cost_dict(compiled)
    coll = collective_stats(compiled.as_text(), num_devices)
    return (
        float(cost.get("flops", 0.0)),
        float(cost.get("bytes accessed", 0.0)),
        coll,
    )


def _reduced_depth(cfg, k: int):
    return dataclasses.replace(cfg, num_layers=k * len(cfg.period) * cfg.num_stages)


def gate_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    microbatches: int = 1,
    policy: str = "dp_tp",
):
    """Full-depth production compile — the runnability gate."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    num_devices = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    lowered = build_lowered(cfg, shape, mesh, microbatches, policy)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_size_gb": getattr(mem, "argument_size_in_bytes", 0) / 1e9,
            "output_size_gb": getattr(mem, "output_size_in_bytes", 0) / 1e9,
            "temp_size_gb": getattr(mem, "temp_size_in_bytes", 0) / 1e9,
            "peak_gb_per_device": (
                getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0)
            )
            / 1e9,
            # The CPU backend ignores donate_argnums; on TPU the donated
            # cache/params+opt alias their outputs, so the output-sized
            # buffer (and its temp copy) disappears from the peak.
            "peak_gb_per_device_tpu": max(
                getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0)
                - getattr(mem, "output_size_in_bytes", 0),
                getattr(mem, "argument_size_in_bytes", 0),
            )
            / 1e9,
        }
    except Exception as e:
        mem_info = {"error": str(e)}
    cost = _cost_dict(compiled)
    coll = collective_stats(compiled.as_text(), num_devices)
    return {
        "gate": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": mem_info,
        "gate_collective_counts": coll.counts,
        "gate_flops_per_device_loopbody1": cost.get("flops", 0.0),
    }


def measure_cell(arch: str, shape_name: str, multi_pod: bool, policy: str = "dp_tp"):
    """Unrolled 2-point depth fit -> exact roofline terms at production depth."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    num_devices = int(np.prod(list(mesh.shape.values())))

    layers_lib.set_unroll(True)
    try:
        costs = {}
        for k in (1, 2):
            costs[k] = _compile_costs(_reduced_depth(cfg, k), shape, mesh, policy=policy)
    finally:
        layers_lib.set_unroll(False)

    periods = {k: k * cfg.num_stages for k in (1, 2)}
    p_target = cfg.num_periods

    def fit(v1: float, v2: float) -> float:
        a = (v2 - v1) / (periods[2] - periods[1])
        b = v1 - a * periods[1]
        return b + a * p_target

    flops_dev = fit(costs[1][0], costs[2][0])
    bytes_dev = fit(costs[1][1], costs[2][1])
    coll_dev = fit(costs[1][2].per_device_bytes, costs[2][2].per_device_bytes)
    by_op = {
        op: fit(costs[1][2].by_op.get(op, 0.0), costs[2][2].by_op.get(op, 0.0))
        for op in set(costs[1][2].by_op) | set(costs[2][2].by_op)
    }
    counts = {
        op: int(
            fit(costs[1][2].counts.get(op, 0), costs[2][2].counts.get(op, 0))
        )
        for op in set(costs[1][2].counts) | set(costs[2][2].counts)
    }

    # analytic correction for the sLSTM time recurrence (global numbers)
    extra_flops, extra_bytes = corrections.slstm_missing_cost(cfg, shape)

    from repro.roofline import constants
    from repro.roofline.hlo import CollectiveStats

    coll = CollectiveStats(
        per_device_bytes=coll_dev,
        global_bytes=coll_dev * num_devices,
        by_op=by_op,
        counts=counts,
    )
    pk = constants.peaks(constants.DRYRUN_TARGET)
    flops_global = flops_dev * num_devices + extra_flops
    bytes_global = bytes_dev * num_devices + extra_bytes
    report = analysis.RooflineReport(
        arch=arch,
        shape=shape_name,
        mesh=mesh_name,
        num_devices=num_devices,
        hlo_flops=flops_global,
        hlo_bytes=bytes_global,
        collective=coll,
        model_flops=analysis.model_flops_for(cfg, shape),
        compute_s=flops_global / (num_devices * pk.flops_bf16),
        memory_s=bytes_global / (num_devices * pk.hbm_bw),
        collective_s=coll.global_bytes / (num_devices * pk.ici_bw),
    )
    row = report.row()
    row["collective_by_op_gb"] = {k: v * num_devices / 1e9 for k, v in by_op.items()}
    row["collective_counts"] = counts
    row["slstm_correction_gflops"] = extra_flops / 1e9
    return row


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    fit: bool = True,
    gate: bool = True,
    microbatches: int = 1,
    save: bool = True,
    policy: str = "dp_tp",
    tag: str = "",
):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"cell": cell, "skipped": reason}
    row = {"cell": cell, "arch": arch, "shape": shape_name, "mesh": mesh_name}
    path = os.path.join(ARTIFACT_DIR, cell + ".json")
    if os.path.exists(path):  # merge into an existing artifact (re-gate etc.)
        try:
            with open(path) as f:
                row = {**json.load(f), **row}
        except (OSError, json.JSONDecodeError):
            pass
    if gate:
        row.update(gate_cell(arch, shape_name, multi_pod, microbatches, policy))
    if fit:
        row.update(measure_cell(arch, shape_name, multi_pod, policy))
    if save:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        with open(path, "w") as f:
            json.dump(row, f, indent=1)
    return row


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-fit", action="store_true", help="gate only")
    ap.add_argument("--no-gate", action="store_true", help="fit only")
    ap.add_argument("--policy", default="dp_tp", help="dp_tp | pure_dp")
    ap.add_argument("--tag", default="", help="artifact suffix for variants")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                try:
                    row = run_cell(
                        arch,
                        shape_name,
                        mp,
                        fit=not args.no_fit,
                        gate=not args.no_gate,
                        microbatches=args.microbatches,
                        policy=args.policy,
                        tag=args.tag,
                    )
                except Exception:
                    failures.append((arch, shape_name, mp))
                    print(f"FAIL {arch} {shape_name} multi_pod={mp}", flush=True)
                    traceback.print_exc()
                    continue
                if "skipped" in row:
                    print(f"SKIP {row['cell']}: {row['skipped']}", flush=True)
                elif "dominant" in row:
                    print(
                        f"OK   {row['cell']}: dominant={row['dominant']} "
                        f"compute={row['compute_ms']:.2f}ms "
                        f"memory={row['memory_ms']:.2f}ms "
                        f"collective={row['collective_ms']:.2f}ms "
                        f"useful={row['useful_ratio']:.2f} "
                        f"roofline={row['roofline_fraction']:.3f}",
                        flush=True,
                    )
                else:
                    print(
                        f"OK   {row['cell']}: gate compile {row.get('compile_s')}s "
                        f"mem/dev {row['memory'].get('peak_gb_per_device', '?')}",
                        flush=True,
                    )
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: {failures}")


if __name__ == "__main__":
    main()
