#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in BENCHMARK.json; its configuration, traffic
mix, limits and per-layer metric readers are files under bench/ found by
name. With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` a profiled window follows the measured one
and the result carries the per-layer metrics. Without a TPU, with fewer
chips than the cell asks for, or outside a checkout of the program, it
exits nonzero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from common import (ROOT, BenchError, CompileClock, bench_file,  # noqa: E402
                    emit, finite, load_cell, load_module, peaks_for,
                    place_compile_cache)

TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def readers(cell) -> dict:
    """metric name -> its reader module, bench/metrics/<name>.py."""
    return {m["name"]: load_module(bench_file("metrics", m["name"] + ".py"),
                                   "metric_" + m["name"].replace(".", "_"))
            for m in cell.per_layer}


def checks_of(readings: dict, limits: dict) -> dict:
    return {k: {"value": _number(readings.get(k)), "limit": lim}
            for k, lim in limits.items()}


def _number(x):
    """A finite float, or None (JSON has no NaN)."""
    return float(x) if x is not None and math.isfinite(x) else None


def result(cell, out: dict, trace: bool, device: dict, peaks: dict) -> dict:
    checks = checks_of(out["readings"], cell.limits)
    correct = out["failed"] == 0 and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    res = {"correct": correct, "attempted": out["attempted"],
           "failed": out["failed"], "metrics": {}, "device": device}
    if not trace:
        for m in cell.end_to_end:
            v = out["e2e"].get(m["name"])
            if finite(v):
                res["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        import trace_reduce

        rd = readers(cell)
        kernels = {}
        for mod in rd.values():
            kernels.update(getattr(mod, "KERNELS", {}))
        summary = trace_reduce.reduce(out["summary"]["events"], kernels)
        rec = dict(out["rec"], trace=summary,
                   trace_calls=out["summary"].get("calls"),
                   config=cell.config["model"], traffic=cell.traffic,
                   peaks=peaks, chips=device["count"], setup=out["setup"],
                   memory_peak_bytes=out["memory_peak_bytes"])
        for name, mod in rd.items():
            v = _number(mod.read(rec))
            if v is not None:
                unit = next(m["unit"] for m in cell.per_layer
                            if m["name"] == name)
                res["metrics"][name] = {"value": v, "unit": unit}
        res["device"] = dict(device, busy_s=summary["busy_s"],
                             window_s=summary["window_s"])
        res["breakdown"] = summary["breakdown"]
        emit("TRACE", kernels=summary["kernels"],
             collective_exposed_s=summary["collective_exposed_s"],
             devices=summary["devices"])
    res["checks"] = checks
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("bench: no program next to the benchmark (src/repro is "
              "missing)", file=sys.stderr)
        return 2
    try:
        cell = load_cell(args.workload)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax

    place_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX has "
              f"{len(devices)} {devices[0].platform} device(s). The "
              "benchmark never falls back to another platform.",
              file=sys.stderr)
        return 3
    devices = devices[:cell.chips]
    try:
        peaks = peaks_for(devices[0].device_kind)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    emit("DEVICE", **device, compile_cache=jax.config.jax_compilation_cache_dir)

    clock = CompileClock()
    runner = load_module(bench_file(cell.traffic["kind"] + ".py"),
                         "runner_" + cell.traffic["kind"])
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(TRACE_DIR, cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        out = runner.run(cell, seed=args.seed, seconds=args.seconds,
                         trace_dir=trace_dir, t_start=T_START, clock=clock,
                         devices=devices)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    res = result(cell, out, bool(args.trace), device, peaks)
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(res, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
