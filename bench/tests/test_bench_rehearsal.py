"""A rehearsal of whole runs on the CPU: the round loop with its hook
subscriber at ``.reduced()`` widths, the result line's keys, the traced
reading, and the command's refusal to measure without a TPU."""
import json
import os
import subprocess
import sys

import pytest

from benchlib import check, spec

from conftest import WORKLOADS


KEYS = ["correct", "attempted", "failed", "metrics", "device", "check"]


@pytest.mark.parametrize("name", WORKLOADS)
def test_untraced_run_line(name, small_run):
    res = small_run(name)
    assert list(res) == KEYS  # the compared numbers come last
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 8 and res["attempted"] % 8 == 0
    assert set(res["metrics"]) == {m["name"] for m in spec.load_cell(name).end_to_end}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert set(res["check"]) == set(check.NUMBERS)
    assert res["device"]["platform"] == "cpu"
    json.dumps(res)


def test_traced_run_line(small_run):
    res = small_run("stablelm-chat", trace=True)
    cell = spec.load_cell("stablelm-chat")
    per_layer = {m["name"] for m in cell.per_layer}
    # without a chip there are no peaks: the roofline and mfu readers stay silent
    assert set(res["metrics"]) == per_layer - {
        "decode_attention_roofline", "exit_confidence_roofline", "step_mfu"}
    assert 0 < res["device"]["busy_s"] < res["device"]["window_s"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(res["breakdown"]["idle_gaps"]) <= 10
    assert res["correct"] is True


def test_command_refuses_without_a_tpu():
    root = spec.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "stablelm-chat", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
