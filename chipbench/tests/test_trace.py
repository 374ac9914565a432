"""The trace reduction on a small synthetic event list."""
import pytest

from chipbench import trace
from chipbench.trace import Trace


def test_union_gaps_and_coverage():
    ivs = [(5, 8), (0, 2), (1, 3), (7, 10), (12, 13)]
    assert trace.union(ivs) == [(0, 3), (5, 10), (12, 13)]
    assert trace.covered(ivs) == 3 + 5 + 1
    assert trace.gaps(trace.union(ivs), 0, 15) == [(3, 5), (10, 12),
                                                   (13, 15)]
    assert trace.gaps(trace.union(ivs), 1, 9) == [(3, 5)]
    assert trace.clip(ivs, 6, 12) == [(6, 8), (7, 10)]


def test_innermost_span_names_a_time():
    spans = [("loop", 0, 100), ("window", 0, 50), ("train", 10, 20)]
    assert trace.innermost(spans, 15) == "train"
    assert trace.innermost(spans, 30) == "window"
    assert trace.innermost(spans, 70) == "loop"
    assert trace.innermost(spans, 170) is None


def make():
    ns = 1_000_000_000
    dev = "/device:TPU:0"
    ops = {dev: [("fusion.1", 1 * ns, 2 * ns), ("custom-call.2", 4 * ns,
                                                  5 * ns),
                 ("fusion.1", 6 * ns, 7 * ns)]}
    mods = {dev: [("jit_train_step(3)", 1 * ns, 2 * ns),
                  ("jit_fleet_drift(7)", 4 * ns, 5 * ns),
                  ("jit_train_step(3)", 6 * ns, 7 * ns)]}
    spans = [("loop", 0, 10 * ns), ("window", ns // 2, 10 * ns),
             ("drift", 3 * ns, 5 * ns), ("serve", 7 * ns, 10 * ns)]
    return Trace(ops, mods, spans), ns


def test_busy_kernel_time_and_breakdown():
    tr, ns = make()
    lo, hi = tr.window("loop")
    assert (lo, hi) == (0, 10 * ns)
    assert tr.busy_s(lo, hi) == pytest.approx(3.0)
    assert tr.module_time("jit_fleet_drift", lo, hi) == (1.0, 1)
    assert tr.module_time("jit_train_step", lo, hi) == (2.0, 2)
    assert tr.module_time("jit_train_step", lo, 6.5 * ns) == (1.5, 2)
    assert tr.top_ops(lo, hi) == [("jit_train_step/fusion.1", 2.0),
                                  ("jit_fleet_drift/custom-call.2", 1.0)]
    assert tr.idle_gaps(lo, hi) == [("serve", 3.0), ("drift", 2.0),
                                    ("window", 1.0), ("window", 1.0)]


def test_missing_window_span_raises():
    tr, _ = make()
    with pytest.raises(ValueError):
        tr.window("nothing")
