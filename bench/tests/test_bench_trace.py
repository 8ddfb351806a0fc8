"""The trace reduction on a small trace the test records itself on the CPU,
and its interval arithmetic on hand-made spans."""
import time

import jax
import jax.numpy as jnp
import pytest

from benchlib import trace


def test_union_and_innermost_span():
    assert trace._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    spans, parent = trace._nest([(0, 100, "window"), (10, 40, "round"), (12, 20, "stage_decode"),
                                 (50, 90, "round"), (60, 61, "final_head")])
    starts = [s for s, _, _ in spans]
    assert trace._innermost(spans, parent, starts, 15) == "stage_decode"
    assert trace._innermost(spans, parent, starts, 30) == "round"
    assert trace._innermost(spans, parent, starts, 45) == "window"
    assert trace._innermost(spans, parent, starts, 60.5) == "final_head"
    assert trace._innermost(spans, parent, starts, 95) == "window"
    assert trace._innermost(spans, parent, starts, 150) == "none"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")

    @jax.jit
    def stage_decode(x):
        return jnp.tanh(x @ x) @ x

    x = jnp.ones((192, 192), jnp.float32)
    stage_decode(x).block_until_ready()
    jax.profiler.start_trace(str(d))
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("bench.stage_decode"):
                y = stage_decode(x)
            y.block_until_ready()
            with jax.profiler.TraceAnnotation("bench.engine"):
                time.sleep(0.02)
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    return trace.reduce_xplane(trace.find_xplane(str(d))), wall


def test_reduction_of_a_recorded_trace(recorded):
    s, wall = recorded
    assert s.devices == 1
    assert 0.08 <= s.window_s <= wall
    assert 0 < s.busy_s < s.window_s
    seconds, calls = s.program("stage_decode")
    assert calls == 4 and 0 < seconds <= s.busy_s * 1.0001
    assert s.kernel("dot") == (0, 0)  # a CPU trace holds no custom calls
    # the sleeps are idle time charged to the host span around them
    assert s.idle_gaps.get("engine", 0) >= 0.06
    assert abs(sum(s.idle_gaps.values()) + s.busy_s - s.window_s) < 1e-6
    assert [n for n, _ in s.top_gaps(1)] == ["engine"]


def test_kernel_counts_custom_calls_only():
    text = {
        "%custom-call.3 = bf16[8,2,2,128] custom-call(...), name=decode_attention": (0.5, 12),
        "%copy.7 = bf16[8,400,2048] copy(...), op_name=jit(decode_attention)/reshape": (0.2, 12),
        "%custom-call.9 = bf16[8,2,2,128] custom-call(...), name=paged_decode_attention": (0.3, 6),
    }
    s = trace.TraceSummary(
        busy_s=1.0, window_s=2.0, devices=1,
        op_time={k: v[0] for k, v in text.items()}, op_count={k: v[1] for k, v in text.items()},
        op_text={k: k for k in text}, module_time={}, module_count={}, idle_gaps={},
        op_short_time={},
    )
    assert s.kernel("decode_attention", exclude="paged_decode_attention") == (0.5, 12)
    assert s.kernel("paged_decode_attention") == (0.3, 6)
