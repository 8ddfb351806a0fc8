"""The benchmark's own tests run on the CPU at small sizes; they import the
harness from ``bench/`` and the program from ``src/``."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


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
REDUCED = {"stablelm-chat": {}, "internlm2-chat": {"num_kv_heads": 2}}


def small_cell(name, gen_len=3):
    """The cell ``name`` of BENCHMARK.json with ``SMALL_TRAFFIC``: one short
    prompt length, batches of up to 4, ``gen_len`` tokens, eight slots."""
    import dataclasses

    from benchlib import spec

    cell = spec.load_cell(name)
    traffic = dict(cell.traffic, **SMALL_TRAFFIC, num_slots=8)
    traffic["gen_len"] = min(traffic["gen_len"], gen_len)
    return dataclasses.replace(cell, traffic=traffic)


@pytest.fixture
def small_run(no_compile_cache):
    """``run(cell_name, trace=False, wrap_programs=None, seed=..., gen_len=3)``: one run
    of the harness at ``.reduced()`` widths on the CPU, chip check skipped."""
    from time import perf_counter

    from benchlib import cell as cell_lib

    def run(name, trace=False, wrap_programs=None, seed=2**33 + 5, seconds=0.5, gen_len=3):
        return cell_lib.run_cell(
            small_cell(name, gen_len), seed, seconds, trace, t_start=perf_counter(), require_tpu=False,
            reduced=REDUCED[name], wrap_programs=wrap_programs,
        )

    return run
