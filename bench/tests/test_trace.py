"""Reduction of a trace to busy time, operation time and idle gaps."""
import pytest

from bench import trace

MS = 1_000_000  # ns


def events():
    dev = {"/device:TPU:0": [
        ("%while.3 = (s32[]) while(%tuple.1), body=%b", 0 * MS, 4 * MS),
        ("%fusion.1 = bf16[8] fusion(%p)", 0 * MS, 2 * MS),
        ("%fused_paged_decode_attention.6 = bf16[12,16,128] custom-call(%a)",
         2 * MS, 3 * MS),
        ("%fusion.1 = bf16[8] fusion(%p)", 3 * MS, 4 * MS),
        ("%fusion.2 = bf16[8] fusion(%fused_paged_decode_attention.6)",
         3.5 * MS, 4.5 * MS),
        ("%rwkv6_wkv.2 = f32[1,64,512,64] custom-call(%r)", 8 * MS, 9 * MS)]}
    host = [(trace.WINDOW_SPAN, 1 * MS, 10 * MS),
            ("bench.step", 1 * MS, 5 * MS),
            ("bench.wait", 5 * MS, 7.5 * MS),
            ("bench.step", 7.5 * MS, 10 * MS),
            ("bench.chunk", 7.6 * MS, 7.99 * MS)]
    return trace.from_events(dev, host)


def test_window_is_the_host_span():
    tr = events()
    assert tr.window == (1 * MS, 10 * MS)
    assert tr.window_s == pytest.approx(9e-3)


def test_busy_is_the_union_of_ops_inside_the_window():
    # [1, 4.5] and [8, 9] ms
    assert trace.busy_s(events()) == pytest.approx(4.5e-3)


def test_op_seconds_clip_to_the_window():
    ops = trace.op_seconds(events())
    assert ops["%fusion.1 = bf16[8] fusion(%p)"] == pytest.approx(2e-3)
    assert not any("while" in k for k in ops)      # the loop holds the rest
    # matched on the op's own name, not on its operands
    assert trace.kernel_seconds(
        events(), r"^%fused_paged_decode_attention") == pytest.approx(1e-3)
    assert trace.kernel_seconds(events(), r"^%rwkv6_wkv") == \
        pytest.approx(1e-3)


def test_idle_gaps_go_to_the_innermost_host_span():
    gaps = trace.idle_gaps(events())
    # (4.5, 8): middle 6.25 ms lies in bench.wait; (9, 10): bench.step
    assert gaps["bench.wait"] == pytest.approx(3.5e-3)
    assert gaps["bench.step"] == pytest.approx(1e-3)
    assert trace.top(gaps, 1) == [["bench.wait", pytest.approx(3.5e-3)]]


def test_short_gaps_are_the_devices_own():
    dev = {"/device:TPU:0": [("a", 0, 100_000), ("b", 105_000, 200_000)]}
    tr = trace.from_events(dev, [(trace.WINDOW_SPAN, 0, 200_000)])
    assert trace.idle_gaps(tr) == {trace.SHORT_GAP: pytest.approx(5e-6)}


def test_a_recorded_trace_file_reads_back(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(trace.latest_xplane(str(tmp_path)))
    assert tr.window_s > 0
    assert any(name == "bench.step" for name, *_ in tr.host_spans)
    # the CPU backend has no TPU device plane: nothing counts as busy
    assert tr.device_ops == {} and trace.busy_s(tr) == 0.0
