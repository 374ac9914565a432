"""One run of one cell: set-up, the timed window loop, the check.

Set-up builds the weights on the device from the seed, generates the
cell's traffic, builds `SharedEngine` and `ECCOController` as a
deployment would, calls `warmup()`, and runs the cell's warm-up windows
so that groups form, a first snapshot is published and every
shape the timed windows use is compiled. The timed loop then calls
`ECCOController.run_window` for whole windows until `seconds` have
passed.

`Probe` wraps bound methods of the controller, its planes and the engine
(never editing the program): stage spans on the host clock, each also a
`jax.profiler.TraceAnnotation`; counts of the model steps the timed
windows ran; and records of what the timed path produced, for the
check.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import re
import shutil
import statistics
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from chipbench import check, flops, reference, weights

ROOT = Path(__file__).resolve().parent.parent
HERE = Path(__file__).resolve().parent


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ARCH_NAME = re.compile(r"[a-z][a-z0-9_]*")


def load_arch(name):
    """The architecture module `arch/<name>.py` (its contract is in
    `arch/dense.py`); a missing or unknown name is a KeyError."""
    if not (isinstance(name, str) and ARCH_NAME.fullmatch(name)
            and (HERE / "arch" / f"{name}.py").is_file()):
        raise KeyError(f"no architecture {name!r} in chipbench/arch")
    return importlib.import_module(f"chipbench.arch.{name}")


def program_config(model_file: dict):
    """The program's `ModelConfig` of a configuration file: its `model`
    object, with a nested `moe` or `ssm` object made the program's
    `MoEConfig` or `SSMConfig` and each JSON list a tuple."""
    from repro.configs.base import ModelConfig, MoEConfig, SSMConfig
    nested = {"moe": MoEConfig, "ssm": SSMConfig}
    kw = {}
    for k, v in model_file["model"].items():
        if k in nested and isinstance(v, dict):
            v = nested[k](**v)
        elif isinstance(v, list):
            v = tuple(v)
        kw[k] = v
    return ModelConfig(name=model_file["name"], **kw)


def cell_spec(name: str, bench: dict) -> dict:
    """Everything a run of cell `name` needs, found by name: its entry
    in BENCHMARK.json, workloads/<name>.json, configs/<config>.json and
    the architecture module it names, traffic/<traffic>.json, and the
    metrics that apply to it."""
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    spec = dict(entry)
    spec["settings"] = json.loads((HERE / "workloads" / f"{name}.json")
                                  .read_text())
    spec["model_file"] = json.loads(
        (HERE / "configs" / f"{entry['config']}.json").read_text())
    spec["arch"] = load_arch(spec["model_file"].get("arch"))
    spec["traffic_params"] = json.loads(
        (HERE / "traffic" / f"{entry['traffic']}.json").read_text())

    def applies(m):
        return name in m["workloads"] if "workloads" in m else True
    spec["end_to_end"] = [m for m in bench["end_to_end"] if applies(m)]
    e2e = {m["name"] for m in spec["end_to_end"]}
    spec["per_layer"] = [m for m in bench["per_layer"]
                         if applies(m) and m["moves"] in e2e]
    return spec


class CompileCount:
    """Lowerings (new programs this process traces to MLIR) while on."""

    def __init__(self):
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.count += 1


class Probe:
    """Spans, counters and records around the program's calls."""

    def __init__(self, ctl, engine, arch, model: dict, seed: int,
                 opt: dict, streams=()):
        self.ctl, self.engine = ctl, engine
        self.arch, self.model = arch, model
        self.seed, self.opt = seed, opt
        self.open: List[str] = []
        self.timing = False
        self.spans: Dict[str, float] = {}
        self.model_flops = 0.0
        self.drift_calls: List[tuple] = []
        self.train = {"batches": [], "loss": [], "grad": None,
                      "update": None, "chain": None}
        self.train_steps = 0
        self.camera_ids = sorted(s.stream_id for s in streams)
        self.window: Dict[str, object] = {}
        self.ref_tokens = None
        self.traffic_clock = streams[0].clock if streams else None
        self._install()

    # -- wrapping ----------------------------------------------------------
    def _span(self, obj, attr: str, name: str, after: Optional[Callable] = None,
              top_only: bool = False):
        orig = getattr(obj, attr)
        annotate = jax.profiler.TraceAnnotation

        def wrapped(*a, **kw):
            if top_only and self.open:
                return orig(*a, **kw)
            self.open.append(name)
            t0 = time.perf_counter()
            try:
                with annotate("chipbench." + name):
                    out = orig(*a, **kw)
            finally:
                dt = time.perf_counter() - t0
                self.open.pop()
                if self.timing and not self.open:
                    self.spans[name] = self.spans.get(name, 0.0) + dt
            if after is not None:
                after(a, kw, out)
            return out
        setattr(obj, attr, wrapped)

    def _install(self):
        ctl, eng = self.ctl, self.engine
        self._span(ctl.fleet, "observe", "drift", after=self._on_observe)
        self._span(ctl.grouper, "group_request", "grouping")
        self._span(ctl.grouper, "update_grouping", "grouping")
        self._span(ctl.tx_plane, "allocate", "transmission")
        self._span(ctl.tx_plane, "decide_many", "transmission")
        self._span(ctl.allocator, "run_window", "alloc")
        self._span(eng, "eval_pairs", "eval", after=self._on_eval,
                   top_only=True)
        refs = ctl.fleet.set_references

        def set_references(ids, toks):
            self.ref_tokens = (list(ids), np.asarray(toks).copy())
            return refs(ids, toks)
        ctl.fleet.set_references = set_references

        train = eng._train

        def train_step(state, batch):
            out = train(state, batch)
            self.train_steps += 1
            if self.timing:
                r, s = batch["inputs"].shape
                self.model_flops += flops.train_flops(self.arch,
                                                      self.model, r, s)
            if self.train["update"] is None:
                self._on_train(state, batch, out)
            return out
        eng._train = train_step
        many_fn = eng._train_many_fn

        def train_many_fn(steps):
            fn = many_fn(steps)

            def counted(*a, **kw):
                self.train_steps += steps
                return fn(*a, **kw)
            return counted
        eng._train_many_fn = train_many_fn

        scalar = eng._acc

        def acc(params, toks):
            if self.timing:
                r, s = toks.shape
                self.model_flops += self.arch.forward_flops(self.model,
                                                            r, s)
            return scalar(params, toks)
        eng._acc = eng._acc_prec["fp32"] = acc

        flat_fn = eng._acc_flat_fn
        wrapped_flat = {}

        def acc_flat_fn(b, precision="fp32"):
            fn = flat_fn(b, precision)
            if fn not in wrapped_flat:
                def counted(params, toks):
                    if self.timing:
                        r, s = toks.shape
                        self.model_flops += self.arch.forward_flops(
                            self.model, r, s)
                    return fn(params, toks)
                wrapped_flat[fn] = counted
            return wrapped_flat[fn]
        eng._acc_flat_fn = acc_flat_fn

        plane = ctl.serve_plane
        if plane is None:
            return
        self._span(ctl, "_serve_window", "serve")
        prefill = plane._prefill

        def prefill_step(params, prompts):
            if self.timing:
                r, s = prompts.shape
                self.model_flops += self.arch.forward_flops(self.model,
                                                            r, s)
            return prefill(params, prompts)
        plane._prefill = prefill_step
        run = plane._fleet_decode_run

        def decode_run(stack, rows, toks, pool, lanes, live, pos, k):
            # counted from the launch's host arguments, outside the run's
            # trace: `k` ticks of the `live` lanes from their positions
            if self.timing:
                start = np.asarray(pos)[:int(live)]
                self.model_flops += sum(
                    self.arch.decode_flops(self.model, start + j)
                    for j in range(int(k)))
            return run(stack, rows, toks, pool, lanes, live, pos, k)
        plane._fleet_decode_run = decode_run
        enqueue, drain = plane.enqueue, plane.drain

        def enqueue_q(request_id, group_id, prompt):
            self.window.setdefault("queries", {})[request_id] = (
                group_id, np.asarray(prompt).copy())
            return enqueue(request_id, group_id, prompt)

        def drain_q():
            done = drain()
            self.window.setdefault("served", {}).update(
                {k: list(v) for k, v in done.items()})
            return done
        plane.enqueue, plane.drain = enqueue_q, drain_q

    # -- records -----------------------------------------------------------
    # The controller builds each window's token arrays afresh and never
    # writes to them again, so the records below keep references, not
    # copies: the timed loop pays for a pointer, and the scores are read
    # once, after the loop.
    def _on_observe(self, a, kw, triggered):
        ids, toks = a[0], a[1]
        if self.timing:
            self.drift_calls.append((len(ids), toks[0].size,
                                     self.ctl.fleet.buckets))
        self.window["drift"] = (ids, toks)

    def _on_eval(self, a, kw, accs):
        self.window["eval"] = [(job.job_id, s, float(acc))
                               for (job, s), acc in zip(a[0], accs)]

    def _on_train(self, state, batch, out):
        """The first three steps of the first job to train: its losses,
        its first clipped gradient's leaf norms (AdamW's first moment
        over 1 - b1 after one step) and its parameters' change after
        three steps, on the device, as host floats."""
        tr = self.train
        if tr["chain"] is None:
            if int(state["opt"]["count"]) != 0:
                return
        elif state is not tr["chain"]:
            return
        new, metrics = out
        tr["batches"].append(np.asarray(batch["inputs"]).copy())
        tr["loss"].append(float(metrics["loss"]))
        if len(tr["batches"]) == 1:
            tr["grad"] = reference.leaf_norms(jax.tree.map(
                lambda m: m / (1 - self.opt["b1"]), new["opt"]["mu"]))
        tr["chain"] = new
        if len(tr["batches"]) == 3:
            p0 = weights.make_params(self.arch, self.model, self.seed)
            tr["update"] = reference.leaf_norms(
                jax.tree.map(lambda a, b: a - b, new["params"], p0))
            tr["chain"] = None

    def record(self) -> dict:
        """What the check compares, as host arrays."""
        out = {}
        if self.train["update"] is not None:
            out["train"] = {k: self.train[k]
                            for k in ("batches", "loss", "grad", "update")}
        if "eval" in self.window:
            out["eval"] = self.window["eval"]
        if "served" in self.window:
            q = self.window.get("queries", {})
            out["serve"] = [(q[rid][0], q[rid][1], np.asarray(tokens))
                            for rid, tokens in sorted(
                                self.window["served"].items()) if rid in q]
        if "drift" in self.window and self.ref_tokens is not None:
            ids, toks = self.window["drift"]
            row = {s: i for i, s in enumerate(self.ref_tokens[0])}
            # a window that scores part of the fleet has no drift record,
            # and the check reads its number as missing
            if sorted(ids) != self.camera_ids:
                return out
            out["drift"] = {
                "tokens": np.asarray(toks).reshape(len(ids), -1),
                "scores": [self.ctl.fleet.score(s) for s in ids],
                "ref_tokens": self.ref_tokens[1][[row[s] for s in ids]]}
        return out


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader may read."""
    windows: int
    elapsed: float
    spans: Dict[str, float]
    model_flops: float
    drift_calls: List[tuple]
    peak: dict
    trace: object = None
    window: tuple = (0.0, 0.0)
    busy_s: Optional[float] = None
    window_s: Optional[float] = None

    def span_per_window(self, name: str) -> Optional[float]:
        if name not in self.spans:
            return None
        return self.spans[name] / self.windows


def quantile95(xs: Sequence[float]) -> float:
    return statistics.quantiles(xs, n=20, method="inclusive")[18]


def run_cell(spec: dict, *, seed: int, seconds: float, trace: bool,
             t0: float, mode: Optional[str] = None,
             hooks: Sequence[Callable] = (), log=print,
             device_kind: Optional[str] = None) -> dict:
    """Set up, time and check one run. Returns the result object of the
    benchmark's last line (without `device` platform fields, which the
    caller adds). `hooks` run on the built controller before the probe
    wraps it (tests plant faults there, underneath the timed path);
    `device_kind` picks the peak table's row where the device is not the
    chip (tests on the CPU)."""
    from repro.core.controller import ControllerConfig, ECCOController
    from repro.core.trainer import SharedEngine
    from repro.serve.plane import ServeConfig

    st = spec["settings"]
    mf = spec["model_file"]
    arch = spec["arch"]
    model = mf["model"]
    opt = mf["optimizer"]
    dev = jax.devices()[0]
    peak = flops.peaks(device_kind or dev.device_kind)
    setup: Dict[str, float] = {}

    cfg = program_config(mf)
    key = weights.key_from_seed(seed)
    t = time.perf_counter()
    tp = spec["traffic_params"]
    gen = load_module(HERE / "traffic" / f"{tp['generator']}.py")
    streams = gen.build(tp, vocab=cfg.vocab_size, seed=seed,
                        key=jax.random.fold_in(key, 1))
    setup["traffic"] = time.perf_counter() - t

    t = time.perf_counter()
    engine = SharedEngine(cfg)
    # every job starts from the benchmark's weights, made on the device
    # in one jitted call when the job is created
    engine.fresh_state = lambda _seed=0: weights.make_state(arch, model,
                                                            seed)
    serve = st.get("serve")
    cc = ControllerConfig(**st["controller"],
                          serve=ServeConfig(**serve) if serve else None)
    ctl = ECCOController(engine, streams, cc, seed=seed)
    for hook in hooks:
        hook(ctl)
    probe = Probe(ctl, engine, arch, model, seed, opt, streams)
    ctl.warmup()
    setup["build"] = time.perf_counter() - t

    t = time.perf_counter()
    for _ in range(st["warmup_windows"]):
        ctl.run_window()
    jax.block_until_ready(engine.bank.params_stack())
    setup["warmup_windows"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t0

    # -- the timed loop ------------------------------------------------
    # set-up's long-lived objects (cameras, pools, the controller) move to
    # the collector's permanent generation, so that collections in the
    # window scan only what the window makes
    gc.collect()
    gc.freeze()
    compiles = CompileCount()
    plane = ctl.serve_plane
    ticks0 = len(plane.tick_log) if plane is not None else 0
    slots0 = (len(engine.bank), engine.bank.capacity)
    trace_dir = ROOT / ".chipbench" / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    probe.timing = compiles.on = True
    windows, queries, dropped = 0, 0, 0
    traffic0 = probe.traffic_clock.seconds if probe.traffic_clock else 0.0
    start = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.loop"):
        while True:
            probe.window = {}
            with jax.profiler.TraceAnnotation("chipbench.window"):
                wm = ctl.run_window()
            windows += 1
            if wm.serve is not None:
                queries += wm.serve["queries"] + wm.serve["dropped"]
                dropped += wm.serve["dropped"]
            if time.perf_counter() - start >= seconds:
                break
        jax.block_until_ready(engine.bank.params_stack())
    elapsed = time.perf_counter() - start
    gc.unfreeze()
    probe.timing = compiles.on = False
    if probe.traffic_clock is not None:
        probe.spans["traffic"] = probe.traffic_clock.seconds - traffic0
    if trace:
        jax.profiler.stop_trace()
    memory_peak = max(d.memory_stats()["peak_bytes_in_use"]
                      for d in jax.devices()[:spec["chips"]]) \
        if dev.platform == "tpu" else 0
    ticks = [dt for _, dt in plane.tick_log[ticks0:]] if plane else []
    slots1 = (len(engine.bank), engine.bank.capacity)
    log(json.dumps({"log": "run", "compiles_in_window": compiles.count,
                    "bank_slots": [slots0[0], slots1[0]],
                    "bank_capacity": [slots0[1], slots1[1]],
                    "windows": windows, "ticks_pooled": len(ticks),
                    "jobs": len(ctl.jobs), "setup": setup}))

    # -- per-layer readings --------------------------------------------
    reading = Reading(windows=windows, elapsed=elapsed, spans=probe.spans,
                      model_flops=probe.model_flops,
                      drift_calls=probe.drift_calls, peak=peak)
    breakdown = None
    if trace:
        from chipbench.trace import Trace
        tr = Trace.load(str(trace_dir))
        lo, hi = tr.window("loop")
        reading.trace, reading.window = tr, (lo, hi)
        reading.busy_s = tr.busy_s(lo, hi)
        reading.window_s = (hi - lo) / 1e9
        breakdown = {"device_ops": [list(x) for x in tr.top_ops(lo, hi)],
                     "idle_gaps": [list(x) for x in tr.idle_gaps(lo, hi)]}
        shutil.rmtree(trace_dir, ignore_errors=True)

    # -- what the check needs, then the program is freed ---------------
    rec = probe.record()
    prompt_len = serve["prompt_len"] if serve else 0
    trained = probe.train_steps > 0
    eval_ids = {p[0] for p in rec.get("eval", [])}
    groups = {q[0] for q in rec.get("serve", [])}
    if trained:
        # after retraining, eval and serve are compared on the weights
        # the program holds: no reference follows every step of it
        jobs = {j.job_id: j for j in ctl.jobs}
        eval_params = {jid: jax.device_get(jobs[jid].serving_snapshot())
                       for jid in eval_ids if jid in jobs}
        serve_params = {g: jax.device_get(plane.store.row(g))
                        for g in groups if g in plane.store} \
            if plane is not None else {}
        del jobs
    buckets = ctl.fleet.buckets
    log(json.dumps({"log": "check", "train_steps": probe.train_steps,
                    "reference_weights": "program" if trained else "seed"}))
    del ctl, engine, probe, plane, streams, wm
    gc.collect()

    t = time.perf_counter()
    params0 = weights.make_params(arch, model, seed)
    if not trained:
        # nothing trained: every job and serving snapshot should hold
        # the benchmark's weights, so the reference scores with those
        eval_params = dict.fromkeys(eval_ids, params0)
        serve_params = dict.fromkeys(groups, params0)
    numbers = check.compare(arch, model, opt, rec, params0, serve_params,
                            eval_params, prompt_len, buckets)
    readings = None
    if mode == "readings":
        readings = {m: check.compare(arch, model, opt, rec, params0,
                                     serve_params, eval_params, prompt_len,
                                     buckets, mode=m)
                    for m in ("control", "bf16", "half_batch", "altered")}
        readings["program"] = numbers
        # each control and fault judged by the cell's own limits
        readings["verdict"] = {m: check.judge(readings[m], st["limits"])[0]
                               for m in ("program", "control", "altered")}
    correct, checks = check.judge(numbers, st["limits"])
    log(json.dumps({"log": "checked", "seconds": time.perf_counter() - t}))

    metrics = {}
    if not trace:
        values = {"window_s": elapsed / windows,
                  "setup_s": setup_s,
                  "peak_hbm_gb": memory_peak / 1e9,
                  "tick_ms_p95": quantile95(ticks) * 1e3
                  if len(ticks) >= 20 else None}
        for m in spec["end_to_end"]:
            v = values.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in spec["per_layer"]:
            reader = load_module(HERE / "metrics" / f"{m['name']}.py")
            v = reader.read(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": windows + queries,
              "failed": dropped, "metrics": metrics,
              "memory_peak_bytes": memory_peak}
    if trace:
        result["busy_s"], result["window_s"] = reading.busy_s, \
            reading.window_s
        result["breakdown"] = breakdown
    if readings is not None:
        result["readings"] = readings
    result["checks"] = checks
    return result
