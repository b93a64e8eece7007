#!/usr/bin/env python3
"""Readings that a cell's limits are set from (bench/limits/<cell>.json):
the program's numbers over many seeds, the control's (the reference in
float8, put in the program's place) and the faults' over a few. All in
one process; short windows. Not run by the benchmark's runs.

    python3 bench/calibrate.py --workload gpt-1.5B-stage.train \
        --seeds 11,12,13 --control-seeds 11,12,13 --seconds 2

One line per reading: ``READING {"seed", "kind", <numbers>}``.
"""

from __future__ import annotations

import argparse
import gc
import os
import sys
import time

import faults
from common import (ROOT, CompileClock, bench_file, emit, load_cell,
                    load_module, place_compile_cache)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    import model

    place_compile_cache()
    cell = load_cell(args.workload)
    devices = jax.devices()[:cell.chips]
    if devices[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 3
    kind = cell.traffic["kind"]
    runner = load_module(bench_file(kind + ".py"), "runner_" + kind)
    m, t = cell.config["model"], cell.traffic
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    clock = CompileClock()

    sess = None

    def program_run(seed, patch=None):
        nonlocal sess
        clock.reset()
        out = runner.run(cell, seed=seed, seconds=args.seconds,
                         trace_dir=None, t_start=time.perf_counter(),
                         clock=clock, devices=devices, patch=patch,
                         sess=None if patch else sess)
        if patch is None:
            sess = out["sess"]
        gc.collect()
        return out

    for seed in ints(args.seeds):
        out = program_run(seed)
        emit("READING", seed=seed, kind="program", **out["readings"],
             setup_s=out["setup_s"], e2e=out["e2e"])
        if seed in ints(args.control_seeds):
            if kind == "train":
                batches = out["batches"]
                for what, kw in (("control_fp8", {"precision": "fp8"}),
                                 ("ref_half_batch",
                                  {"fault": "half_batch"}),
                                 ("ref_grad_altered",
                                  {"fault": "grad_altered"})):
                    got = model.train_reference(m, seed, batches,
                                                t["optim"], **kw)
                    emit("READING", seed=seed, kind=what,
                         **runner.compare(got["losses"], got["grad1"],
                                          got["delta"], out["reference"]))
            else:
                got = model.serve_reference(m, seed, out["sample"],
                                            t["context"], control="fp8")
                emit("READING", seed=seed, kind="control_fp8",
                     served_gap=got["control_gap"], tokens=got["tokens"])
        if seed in ints(args.fault_seeds):
            for patch in (faults.TRAIN if kind == "train" else faults.SERVE):
                out = program_run(seed, patch)
                emit("READING", seed=seed, kind="fault_" + patch.__name__,
                     **out["readings"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
