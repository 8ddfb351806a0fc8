"""What a cell is: its entry in BENCHMARK.json and the data files it names.

Everything here is found by name: a workload names its configuration and
its traffic mix, the configuration names its file and its reference, and
each metric is a reader under ``bench/metrics/<name>.py``.  A new cell is
new files and new entries, never an edit here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a reader or reference from its file (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(f"bench_{name.replace('.', '_')}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(path: Path) -> dict:
    """A configuration file as run: its published keys, with the value of
    each of its ``departures`` (where the program differs from the
    published model, and the reference follows the program) in place."""
    conf = load_json(path)
    return dict(conf, **{k: d["run"] for k, d in conf.get("departures", {}).items()})


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict  # the configuration's file, as run (``load_config``)
    traffic: dict  # the traffic mix's file
    limits: dict  # bench/limits/<cell>.json
    end_to_end: tuple  # BENCHMARK.json metric entries reported by this cell
    per_layer: tuple

    @property
    def reference(self):
        name = self.config["reference"]
        return load_module(BENCH_DIR / "references" / f"{name}.py", name)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark: dict | None = None) -> Cell:
    bm = benchmark if benchmark is not None else load_json(ROOT / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bm["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in bm["configs"]}[w["config"]]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=load_config(ROOT / conf["file"]),
        traffic=load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(BENCH_DIR / "limits" / f"{name}.json"),
        end_to_end=tuple(m for m in bm["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bm["per_layer"] if _applies(m, name)),
    )


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file.

    Widths come from the published keys; the ``serve`` group says how the
    family maps onto the program's blocks and where the exits sit.
    """
    import jax.numpy as jnp

    from repro.configs.base import ArchConfig

    s = conf["serve"]
    d, heads = conf["hidden_size"], conf["num_attention_heads"]
    return ArchConfig(
        name=conf["name"],
        family="dense",
        num_layers=conf["num_hidden_layers"],
        d_model=d,
        num_heads=heads,
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        head_dim=d // heads,
        norm=s["norm"],
        act=conf["hidden_act"],
        ffn=s["ffn"],
        qkv_bias=s["qkv_bias"],
        rope_theta=float(conf["rope_theta"]),
        period=tuple(s["period"]),
        num_stages=s["num_stages"],
        exit_stages=tuple(s["exit_stages"]),
        dtype=getattr(jnp, s["compute_dtype"]),
    )

