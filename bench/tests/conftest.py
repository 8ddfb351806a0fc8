"""The benchmark's own tests run on the CPU at small sizes; they import the
harness from ``bench/`` and the program from ``src/``."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import spec  # noqa: E402

# every cell of BENCHMARK.json: the tests that run a cell run each of them,
# at the CPU widths its configuration file gives under "cpu"
WORKLOADS = [w["name"] for w in spec.load_json(spec.ROOT / "BENCHMARK.json")["workloads"]]


@pytest.fixture
def no_compile_cache(monkeypatch):
    """Runs in tests keep JAX's persistent cache off (nothing written into
    the checkout)."""
    from benchlib import cell

    monkeypatch.setattr(cell, "use_compile_cache", lambda: "off")


# the published cells' traffic, shrunk to what a CPU runs in seconds: one
# short prompt length, batches of up to 4, three tokens
SMALL_TRAFFIC = {
    "requests_per_round": 8,
    "prompt_lengths": {"16": 1},
    "check_requests": 4,
    "batch_size": 4,
}


def small_cell(name, gen_len=3):
    """The cell ``name`` of BENCHMARK.json with ``SMALL_TRAFFIC``: one short
    prompt length, batches of up to 4, ``gen_len`` tokens, eight slots."""
    import dataclasses

    cell = spec.load_cell(name)
    traffic = dict(cell.traffic, **SMALL_TRAFFIC, num_slots=8)
    traffic["gen_len"] = min(traffic["gen_len"], gen_len)
    return dataclasses.replace(cell, traffic=traffic)


@pytest.fixture
def small_run(no_compile_cache):
    """``run(cell_name, trace=False, wrap_programs=None, seed=..., gen_len=3)``: one run
    of the harness at ``.reduced()`` widths (the configuration's ``cpu``) on
    the CPU, chip check skipped."""
    from time import perf_counter

    from benchlib import cell as cell_lib

    def run(name, trace=False, wrap_programs=None, seed=2**33 + 5, seconds=0.5, gen_len=3):
        cell = small_cell(name, gen_len)
        return cell_lib.run_cell(
            cell, seed, seconds, trace, t_start=perf_counter(), require_tpu=False,
            reduced=cell.config["cpu"], wrap_programs=wrap_programs,
        )

    return run
