"""The control comes out not correct: the reference computed in float8, the
precision below the bfloat16 the configurations serve in, put where the
program was, read on three seeds at ``.reduced()`` widths on the CPU, fails
the cell's limits on every seed, while the program passes them."""
import dataclasses
import sys

import pytest

from benchlib import check

from conftest import BENCH, WORKLOADS, small_cell


@pytest.mark.parametrize("name", WORKLOADS)
def test_control_fails_where_the_program_passes(name, no_compile_cache):
    sys.path.insert(0, str(BENCH))
    import readings

    cell = small_cell(name)
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic, check_requests=32))  # four rounds
    rows = readings.readings(cell, [11, 12, 13], {11, 12, 13}, require_tpu=False,
                             reduced=cell.config["cpu"], emit=lambda line: None)
    for row in rows:
        assert row["failed"] == 0
        assert check.verdict(row["program"], cell.limits, 0), row
        assert not check.verdict(row["control"], cell.limits, 0), row
