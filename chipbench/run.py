#!/usr/bin/env python3
"""The chip benchmark of the ECCO retraining loop.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the machine it is started on: set-up
(weights and traffic from the seed, the controller, its warm-up
windows), then whole `run_window` calls until `--seconds` have passed,
then the comparison with the plain reference that decides `correct`.
With `--trace 0` the last line's metrics are the cell's end-to-end
metrics; with `--trace 1` the timed loop is profiled and they are its
per-layer metrics. Every line before the last is a log. The run exits
non-zero, printing no result, when the first device is not a TPU, when
there are fewer chips than the cell asks for, or when the program's
sources (`src/`) are not beside this directory.

`--readings 1` also prints, in the result, the same numbers with the
lower-precision control and the planted faults in the program's place
(what the limits were set from); the benchmark's own runs never use it.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def fail(msg: str, code: int = 1) -> int:
    print(f"chipbench: {msg}", file=sys.stderr)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--readings", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    try:
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
        from chipbench.bench import cell_spec, run_cell
        spec = cell_spec(args.workload, bench)
        from repro.launch.cache import enable_compile_cache
    except (ImportError, OSError, KeyError) as e:
        return fail(f"cannot set up {args.workload!r}: {e}", 2)
    cache_dir = enable_compile_cache()

    import jax
    # keep every program in the persistent cache, however quick to
    # compile, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return fail(f"needs a TPU; the first device is platform "
                    f"{devs[0].platform!r}")
    if len(devs) < spec["chips"]:
        return fail(f"{args.workload} needs {spec['chips']} chips; JAX sees "
                    f"{len(devs)}")
    print(json.dumps({"log": "start", "workload": args.workload,
                      "seed": args.seed, "device_kind": devs[0].device_kind,
                      "jax": jax.__version__, "compile_cache": cache_dir}),
          flush=True)
    res = run_cell(spec, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), t0=T0,
                   mode="readings" if args.readings else None)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": spec["chips"],
              "memory_peak_bytes": res.pop("memory_peak_bytes")}
    if args.trace:
        device["busy_s"] = res.pop("busy_s")
        device["window_s"] = res.pop("window_s")
    checks = res.pop("checks")
    out = {**res, "device": device, "checks": checks}
    for name, c in checks.items():
        print(f"check {name} value {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
