#!/usr/bin/env python3
"""Find a serving cell's knee: one set-up, then the cell's mix offered at
each of several fixed rates in turn, each for a window of its own and
drained before the next. Prints one line per rate; not run by the
benchmark's runs (the slots and rate it finds are written into the
mix's file).

    python3 bench/sweep.py --workload gpt-1.5B.chat --seed 7 \
        --seconds 20 --rates 1,2,3,4 --slots 8 --round-prompts 16

``--slots`` overrides the mix's slot count. ``--round-prompts M``
rounds each prompt up to a multiple of M tokens, so that a sweep over
several pools warms up 128 / M prefill widths for each instead of one
per tail width; the cell itself runs the mix as it is. The sweep stops
after the first rate that leaves more than ``--stop-queue`` requests
queued at its close.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from common import (ROOT, emit, load_cell, percentile,  # noqa: E402
                    place_compile_cache)


def rounded(reqs, m: int, vocab: int, seed: int):
    """``reqs`` with each prompt grown to a multiple of ``m`` tokens."""
    import generator

    r = generator.rng(seed, 9)
    return [dataclasses.replace(q, prompt=r.integers(
                0, vocab, math.ceil(len(q.prompt) / m) * m,
                dtype=q.prompt.dtype)) for q in reqs]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--round-prompts", type=int, default=1)
    ap.add_argument("--stop-queue", type=int, default=10)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    import generator
    import serve

    place_compile_cache()
    cell = load_cell(args.workload)
    t = dict(cell.traffic)
    if args.slots:
        t["slots"] = args.slots
    cell = dataclasses.replace(cell, traffic=t)
    vocab, m = cell.config["model"]["vocab"], args.round_prompts
    devices = jax.devices()[:cell.chips]
    if devices[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 3
    warm = None
    if m > 1:
        lo, hi, chunk = t["prompt"]["min"], t["prompt"]["max"], \
            t["prefill_chunk"]
        first = {}
        for n in range(math.ceil(lo / m) * m, hi + 1, m):
            first.setdefault(n % chunk or chunk, n)
        warm = sorted(first.values())
    sess, params, eng, drv, calls = serve.setup(cell, args.seed, devices,
                                                warm=warm)
    emit("SETUP", slots=t["slots"], seconds=time.perf_counter() - T_START)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        reqs = generator.serve_requests(dict(t, rate=rate), vocab,
                                        args.seed, args.seconds,
                                        stream=10 + i)
        if m > 1:
            reqs = rounded(reqs, m, vocab, args.seed + i)
        drv.done, drv.tick_s, drv.busy = [], [], []
        t0 = time.perf_counter()
        drv.run(reqs, t0, t0 + args.seconds)
        backlog = eng.scheduler.n_queued
        in_flight = len(drv.live)
        occupancy = drv.occupancy()
        done_in_window = sum(1 for x in drv.done
                             if x["times"] and x["times"][-1] <= t0
                             + args.seconds)
        drv.drain(t["drain_seconds"])
        lat = serve.latencies(drv.done + list(drv.live.values()),
                              t0 + args.seconds)
        emit("RATE", slots=t["slots"], rate=rate, requests=len(reqs),
             finished_in_window=done_in_window,
             queued_at_close=backlog, in_flight_at_close=in_flight,
             failed=lat["failed"], occupancy=occupancy,
             ttft_p50_ms=1e3 * percentile(lat["ttft"], 50),
             ttft_p90_ms=1e3 * percentile(lat["ttft"], 90),
             ttft_p95_ms=1e3 * percentile(lat["ttft"], 95),
             itl_p50_ms=1e3 * percentile(lat["gaps"], 50),
             itl_p95_ms=1e3 * percentile(lat["gaps"], 95),
             tick_ms=1e3 * sum(drv.tick_s) / max(1, len(drv.tick_s)),
             generator_late_max_s=max(lat["late"]))
        if backlog > args.stop_queue:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
