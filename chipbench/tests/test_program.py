"""The program's spans placed on the trace's clock, and their readers,
on a synthetic trace; then one traced run on the CPU."""
import sys

import pytest

from chipbench import program
from chipbench.bench import HERE, Reading, load_module
from chipbench.trace import Trace

NS = 1_000_000_000
# the program's clock runs 5 s behind the trace's
SHIFT = 5 * NS

PROGRAM_READERS = [
    "window.sample_s", "window.requests_s", "window.eval_draws_s",
    "window.metrics_s", "window.self_s", "drift.histogram_s",
    "drift.kernel_s", "gc.pause_s", "grouping.span_s",
    "transmission.span_s", "eval.span_s", "serve.span_s",
    "serve.tick_idle_share", "serve.query_ms_p95", "drift.rescore_share"]


def make():
    """Two windows of 10 s; the device runs 1.2-2 s (drift kernel) and
    6-7, 8.5-9 and 16-17 s (decode). The program's spans: each window,
    its stages, a collection inside the first window's sampling, two
    ticks of which the device is busy for half of the first, and a
    collection after the loop."""
    dev = "/device:TPU:0"
    ops = {dev: [("fleet_drift.1", 1.2 * NS, 2 * NS),
                 ("fusion.3", 6 * NS, 7 * NS),
                 ("fusion.3", 8.5 * NS, 9 * NS),
                 ("fusion.3", 16 * NS, 17 * NS)]}
    mods = {dev: [("jit_fleet_drift(1)", 1.2 * NS, 2 * NS),
                  ("jit_fleet_decode_step(2)", 6 * NS, 7 * NS),
                  ("jit_fleet_decode_step(2)", 8.5 * NS, 9 * NS),
                  ("jit_fleet_decode_step(2)", 16 * NS, 17 * NS)]}
    spans = [("loop", 0, 20 * NS), ("window", 0, 10 * NS),
             ("window", 10 * NS, 20 * NS), ("drift", NS // 2, 3 * NS)]
    tr = Trace(ops, mods, spans)
    ivs = {"window": [(0, 10 * NS), (10 * NS, 20 * NS)],
           "window.sample": [(0, NS // 2)],
           "gc": [(NS // 10, NS // 5), (21 * NS, 24 * NS)],
           "drift.observe": [(NS // 2, 3 * NS)],
           "drift.histogram": [(NS // 2, NS)],
           "drift.kernel": [(NS, 2.5 * NS)],
           "serve.window": [(5 * NS, 9 * NS), (15 * NS, 19 * NS)],
           "serve.tick": [(6 * NS, 8 * NS), (16 * NS, 17 * NS)]}
    snap = {"spans": {}, "counters": {}, "samples": {},
            "intervals": {n: [[s - SHIFT, e - SHIFT] for s, e in v]
                          for n, v in ivs.items()}}
    return tr, snap


def reading(tr):
    lo, hi = tr.window("loop")
    return Reading(windows=2, elapsed=(hi - lo) / 1e9, spans={},
                   model_flops=0.0, drift_calls=[], peak={}, trace=tr,
                   window=(lo, hi))


def read(name, run):
    return load_module(HERE / "metrics" / f"{name}.py").read(run)


@pytest.fixture
def snap(monkeypatch):
    """The synthetic record, served as the program's own."""
    tr, snap = make()
    monkeypatch.setattr(program, "snapshot", lambda: snap)
    return snap


def test_spans_align_to_the_windows_and_clip_to_the_loop(snap):
    tr, _ = make()
    p = program.load(reading(tr))
    assert p.seconds("serve.window") == pytest.approx(8.0)
    assert p.seconds("serve.tick", "serve.window") == pytest.approx(8.0)
    # the collection after the loop lies outside it
    assert p.seconds("gc") == pytest.approx(0.1)
    assert p.seconds("nothing") is None
    assert p.per_window("drift.kernel") == pytest.approx(0.75)


def test_self_time_leaves_out_every_nested_span(snap):
    tr, _ = make()
    p = program.load(reading(tr))
    # window 1: 10 s less sampling 0.5, drift 2.5, serve 4; window 2:
    # 10 s less serve 4 (the gc inside sampling counts once)
    assert p.self_seconds("window") == pytest.approx(3.0 + 6.0)
    assert p.self_seconds("drift.observe") == pytest.approx(0.5)
    assert p.self_seconds("serve.window") == pytest.approx(2.0 + 3.0)
    assert p.self_seconds("nothing") is None


def test_idle_time_inside_a_named_span(snap):
    tr, _ = make()
    p = program.load(reading(tr))
    # ticks: 6-8 s with the device busy 6-7, 16-17 s busy throughout
    assert p.idle_seconds("serve.tick") == pytest.approx(1.0)
    assert p.idle_seconds("drift.kernel") == pytest.approx(0.7)
    assert p.idle_seconds("window") == pytest.approx(16.7)


def test_a_record_that_does_not_match_the_loop_is_not_aligned(snap):
    tr, _ = make()
    snap["intervals"]["window"].pop()
    assert program.load(reading(tr)) is None
    del snap["intervals"]
    assert program.load(reading(tr)) is None


def test_readers_reduce_the_program_spans(snap):
    tr, _ = make()
    snap["samples"]["serve.query_s"] = [i / 1000 for i in range(1, 101)]
    run = reading(tr)
    assert read("window.sample_s", run) == pytest.approx(0.25)
    assert read("window.self_s", run) == pytest.approx(4.5)
    assert read("drift.histogram_s", run) == pytest.approx(0.25)
    assert read("drift.kernel_s", run) == pytest.approx(0.75)
    assert read("gc.pause_s", run) == pytest.approx(0.05)
    assert read("serve.span_s", run) == pytest.approx(4.0)
    # 3 s of ticks, 1 s of it with the device idle
    assert read("serve.tick_idle_share", run) == pytest.approx(100 / 3)
    assert read("serve.query_ms_p95", run) == pytest.approx(95.05)
    # stages the program never entered read nothing
    for name in ("window.requests_s", "grouping.span_s",
                 "transmission.span_s", "eval.span_s"):
        assert read(name, run) is None


def test_rescore_share_reads_the_drift_counters(snap):
    tr, _ = make()
    counters = snap["counters"]
    counters.update({"drift.rows": 8, "drift.rescored": 2})
    assert read("drift.rescore_share", reading(tr)) == pytest.approx(25.0)
    del counters["drift.rescored"]      # no row near the threshold
    assert read("drift.rescore_share", reading(tr)) == 0.0
    counters.clear()                    # the loop scored no drift
    assert read("drift.rescore_share", reading(tr)) is None


@pytest.mark.parametrize("name", PROGRAM_READERS)
def test_readers_return_none_on_a_program_without_tracing(name,
                                                          monkeypatch):
    tr, _ = make()
    # the import fails, whether or not an earlier test imported it:
    # `from repro import obs` reads the package's attribute first
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr("repro.obs", raising=False)
    assert program.snapshot() is None
    assert read(name, reading(tr)) is None
    untraced = reading(tr)
    untraced.trace = None
    assert read(name, untraced) is None


def test_a_traced_run_reports_the_program_readers():
    """The small serving cell, traced on the CPU: the program follows the
    profiler, and each reader of a span the cell enters reads a number
    (the CPU trace has no TPU plane, so the tick's idle share is
    silent)."""
    import json
    from chipbench.bench import ROOT, run_cell
    from small import spec
    from repro import obs

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    s = spec()
    s["settings"]["serve"]["queries_per_stream"] = 8   # 32 a window
    s["per_layer"] = [m for m in bench["per_layer"]
                      if m["name"] in PROGRAM_READERS]
    res = run_cell(s, seed=2**33 + 11, seconds=1.0, trace=True, t0=0.0,
                   log=lambda *_: None, device_kind="TPU v5 lite")
    got = {k: v["value"] for k, v in res["metrics"].items()}
    # no TPU plane, and perhaps no collection inside the loop
    silent = {"serve.tick_idle_share", "gc.pause_s"}
    assert set(PROGRAM_READERS) - silent <= set(got)
    assert all(v >= 0 for v in got.values())
    assert got["serve.span_s"] < res["window_s"]
    obs.follow_profiler()               # the profile has ended
    assert not obs.enabled()
