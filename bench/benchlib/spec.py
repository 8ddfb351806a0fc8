"""What a cell is: its entry in BENCHMARK.json and the data files it names.

Everything here is found by name: a workload names its configuration and
its traffic mix, the configuration names its file, and the file names its
family (``bench/families/<family>.py``) and its reference
(``bench/references/<name>.py``); each metric is a reader under
``bench/metrics/<name>.py``.  A new cell, or a new architecture, is new
files and new entries, never an edit here.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from typing import Any

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
FAMILIES_DIR = BENCH_DIR / "families"


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a reader, reference or family from its file (names may hold
    dots)."""
    spec = importlib.util.spec_from_file_location(f"bench_{name.replace('.', '_')}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(path: Path) -> dict:
    """A configuration file as run.

    The file keeps each published key at its published value.  Each entry
    of its ``reduced`` (a size cut to fit the chip) and of its
    ``departures`` (where the program differs from the published model,
    and the reference follows the program) states the ``run`` value and
    ``why``; here the run value is put in place, and the published one is
    kept in the entry, beside its ``why``."""
    conf = load_json(path)
    out = dict(conf)
    for group in ("reduced", "departures"):
        entries = conf.get(group, {})
        missing = sorted(set(entries) - set(conf))
        if missing:
            raise KeyError(f"{path}: {group} names {missing}, which the file does not publish")
        out[group] = {k: dict(e, published=conf[k]) for k, e in entries.items()}
        out.update({k: e["run"] for k, e in entries.items()})
    return out


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


def family(conf: dict):
    """The family module that a configuration file names under ``family``:
    ``bench/families/<family>.py``, loaded once per file."""
    name = conf.get("family")
    if name is None:
        raise ValueError(
            f"configuration {conf.get('name')!r} names no family: give it \"family\", "
            f"the name of a module under {FAMILIES_DIR}")
    path = FAMILIES_DIR / f"{name}.py"
    try:
        return _family_module(path)
    except FileNotFoundError:
        known = sorted(p.stem for p in FAMILIES_DIR.glob("*.py"))
        raise ValueError(
            f"configuration {conf.get('name')!r} names family {name!r}, which has no "
            f"module {path.name} under {FAMILIES_DIR}; known: {known}") from None


@functools.cache
def _family_module(path: Path):
    return load_module(path, f"family_{path.stem}")


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file, by its family."""
    return family(conf).arch_config(conf)
