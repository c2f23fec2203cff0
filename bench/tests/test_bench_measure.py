"""The reduction from a trace to busy time, idle share, idle gaps by host
span, and the busiest device operations, on a small synthetic trace."""

import pytest

from bench.lib import measure
from bench.lib.measure import Trace


def _trace():
    # window [0, 100]; device ops overlap and spill past the window
    ops = [[("fusion.1", -5, 10), ("fusion.2", 5, 20), ("gather", 40, 60),
            ("fusion.1", 90, 120)]]
    spans = [("bench.query", 0, 30), ("bench.read", 30, 70),
             ("bench.wait_arrival", 70, 95)]
    return Trace((0, 100), ops, spans)


def test_union_merges_and_clips():
    assert measure.union([(5, 20), (-5, 10), (40, 60), (90, 120)],
                         0, 100) == [(0, 20), (40, 60), (90, 100)]
    assert measure.union([(1, 1), (3, 2)], 0, 10) == []


def test_busy_and_idle_share():
    tr = _trace()
    assert measure.busy_ns(tr) == 50
    assert measure.idle_share_pct(tr) == pytest.approx(50.0)
    assert measure.idle_share_pct(Trace((0, 100), [], [])) is None
    assert measure.idle_share_pct(None) is None


def test_busy_averages_over_devices():
    tr = _trace()
    two = Trace(tr.window, tr.ops + [[("x", 0, 100)]], tr.spans)
    assert measure.busy_ns(two) == 75


def test_busy_inside_spans():
    tr = _trace()
    assert measure.busy_in(tr, "bench.query") == 20
    assert measure.busy_in(tr, "bench.read") == 20
    assert measure.busy_in(tr, "bench.wait_arrival") == 5


def test_idle_gaps_named_by_span():
    # busy [0, 20] [40, 60] [90, 100]: the gap [60, 90] lies mostly in
    # the wait, [20, 40] half in the query and half in the read (a tie
    # goes to the span that started first)
    gaps = measure.idle_gaps(_trace())
    assert gaps == [["bench.wait_arrival", pytest.approx(30e-9)],
                    ["bench.query", pytest.approx(20e-9)]]
    assert measure.idle_gaps(_trace(), top=1)[0][0] == "bench.wait_arrival"


def test_idle_gap_outside_every_span_is_host():
    tr = Trace((0, 10), [[("a", 0, 2)]], [])
    assert measure.idle_gaps(tr) == [["host", pytest.approx(8e-9)]]


def test_top_ops_by_self_time_inside_window():
    """Ops wholly inside the window, each less what is nested in it, by
    instruction name."""
    ops = [[("%while.3 = (f32[4]) while(...)", 10, 50),
            ("%fusion.1 = f32[4] fusion(...)", 12, 20),
            ("%gather.2 = f32[4] gather(...)", 20, 45),
            ("%fusion.1 = f32[4] fusion(...)", 60, 70),
            ("%late = f32[4] add(...)", 95, 120)]]
    tr = Trace((0, 100), ops, [])
    assert measure.top_ops(tr) == [
        ["gather.2", pytest.approx(25e-9)], ["fusion.1", pytest.approx(18e-9)],
        ["while.3", pytest.approx(7e-9)]]
    assert measure.short_name("%x.1 = f32[] add(a, b)") == "x.1"


def test_spans_record_host_intervals():
    spans = measure.Spans()
    with spans("bench.stage"):
        pass
    with spans("bench.stage"):
        pass
    (n0, a0, b0), (n1, a1, b1) = spans.done
    assert n0 == n1 == "bench.stage" and a0 <= b0 <= a1 <= b1


def test_read_trace_finds_window_and_spans(tmp_path):
    """A recorded CPU trace: the window and the bench spans come back on
    one clock (the CPU has no device plane, so no device operations)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.query"):
            f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    tr = measure.read_trace(str(tmp_path))
    (name, a, b), = tr.spans
    assert name == "bench.query"
    assert tr.window[0] <= a <= b <= tr.window[1]
