#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration file
and a traffic file; the traffic's ``kind`` picks the job in ``jobs.py``.
``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1``
records the window with the profiler and prints the per-layer metrics,
each read by ``metrics/<name>.py``. The last line of standard output is
one JSON object (correct, attempted, failed, metrics, device, and with
``--trace 1`` the breakdown; the compared numbers last, under
``checks``); the same numbers close standard error.

Exits non-zero, printing no result, without a TPU or with fewer chips
than the cell asks for.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import harness as hz  # noqa: E402


def per_layer(spec: dict, name: str, ctx: dict) -> dict:
    """Each per-layer metric of this cell that its reader finds."""
    out = {}
    for met in spec["per_layer"]:
        if name not in met.get("workloads", [name]):
            continue
        mod = importlib.import_module("metrics." + met["name"])
        v = mod.read(ctx)
        if v is not None:
            out[met["name"]] = {"value": v, "unit": met["unit"]}
    return out


def end_to_end(spec: dict, name: str, run) -> dict:
    vals = dict(run.metrics, setup_s=run.setup_s)
    out = {}
    for met in spec["end_to_end"]:
        if name in met.get("workloads", [name]):
            out[met["name"]] = {"value": vals[met["name"]],
                                "unit": met["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    cell = hz.load_cell(args.workload)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        sys.exit(f"bench: the system under test is missing ({e})")
    enable_compile_cache()
    try:
        devs = hz.require_tpu(cell["cell"]["chips"])
    except hz.NoDevice as e:
        sys.exit(f"bench: {e}; nothing was run")
    hz.log(f"set-up: devices found at {time.perf_counter() - t_start:.3f}s")
    hz.COMPILES = hz.CompileLog()

    import jobs
    job = jobs.JOBS[cell["traffic"]["kind"]]
    trace_dir = hz.TRACE_DIR if args.trace else None
    hz.clean_ckpt()
    shutil.rmtree(hz.TRACE_DIR, ignore_errors=True)
    try:
        run = job(cell, args.seed, args.seconds, trace_dir, devs, t_start)
    finally:
        hz.clean_ckpt()
    hz.log(f"bench: checkpoints wrote {run.bytes_written} bytes; device "
           f"peak {run.device['memory_peak_bytes']} bytes in use")

    limits = cell["config"]["limits"]
    missing = set(run.readings) - set(limits)
    if missing:
        sys.exit(f"bench: no limit for {sorted(missing)}")
    ok, checks = hz.verdict(run.readings, {k: limits[k]
                                           for k in run.readings})
    spec, name = cell["spec"], args.workload
    result = {"correct": ok and run.failed == 0,
              "attempted": run.attempted, "failed": run.failed}
    if args.trace:
        import peaks
        import trace_reduce
        red = trace_reduce.reduce_dir(trace_dir, run.window_s)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"records": run.records, "trace": red,
               "peaks": peaks.for_device(devs[0].device_kind)}
        result["metrics"] = per_layer(spec, name, ctx)
        result["device"] = dict(run.device, busy_s=red["busy_s"],
                                window_s=red["window_s"])
        result["breakdown"] = red["breakdown"]
    else:
        result["metrics"] = end_to_end(spec, name, run)
        result["device"] = run.device
    result["checks"] = checks
    for k, v in checks.items():
        hz.log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
