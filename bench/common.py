"""Pieces every cell runner shares: files found by name, the compile
clock, device memory, percentiles, spans and the result line."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import re

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class BenchError(RuntimeError):
    """The run cannot measure what the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def bench_file(*parts: str) -> str:
    """A file of the benchmark, by name; missing is an error."""
    path = os.path.join(BENCH, *parts)
    if not os.path.isfile(path):
        raise BenchError(f"no file {os.path.relpath(path, ROOT)}")
    return path


def load_module(path: str, name: str):
    """Import a Python file of the benchmark by its path (metric readers
    have dots in their names, so they are not importable by name)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# What an architecture module exports, by the kind of cell that uses it.
ARCH_EXPORTS = {
    "train": ("layer_leaves", "io_leaves", "objective",
              "train_flops_per_token", "FAULT_LEAF"),
    "serve": ("layer_leaves", "io_leaves", "logits", "serve_call_flops"),
}
_ARCHES: dict = {}   # one module object a file: jitted code keyed by it


def load_arch(name: str, kind: str):
    """The architecture module ``bench/arch/<name>.py`` that a
    configuration's ``"arch"`` names, holding what belongs to one
    architecture; everything else in the benchmark is shared. ``m`` is
    the configuration's "model" block, and the module imports nothing
    of the program. It exports, for a training cell:

    - ``layer_leaves(m, layer)``: ``{name: (shape, init, fan_in)}``, the
      leaves of layer ``layer`` (layers may differ); init is "normal"
      (scaled by 1/sqrt(fan_in)), "ones" or "zeros";
    - ``io_leaves(m)``: the same for the embedding, final norm and head;
    - ``objective(m, params, tokens, labels, mm)`` -> ``(reported,
      differentiated)``: ``reported`` is the loss per token that the
      program's ``loss_sum`` reports; ``differentiated`` adds any term
      the program's gradients carry and ``loss_sum`` leaves out (a
      router's balance term, say). ``params`` is the reference's layout
      (``model.reference_params``), ``mm(eq, a, b)`` the matrix product
      (``model.make_mm``: float32, or float8 for the control);
    - ``train_flops_per_token(m, seq)``: model FLOPs of one trained
      token, forward and backward, no recomputation counted;
    - ``FAULT_LEAF``: the layer leaf whose gradient the "grad_altered"
      fault doubles;

    and for a serving cell ``layer_leaves``, ``io_leaves``,
    ``logits(m, params, tokens, mm)`` -> float32 ``[b, s, vocab]``, and
    ``serve_call_flops(m, width, positions)``: model FLOPs of one serve
    step of ``width`` tokens a row, rows starting at ``positions``."""
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", name):
        raise BenchError(f"architecture {name!r} is not a module name")
    path = bench_file("arch", name + ".py")
    if path not in _ARCHES:
        _ARCHES[path] = load_module(path, "arch_" + name)
    mod = _ARCHES[path]
    missing = [x for x in ARCH_EXPORTS[kind] if not hasattr(mod, x)]
    if missing:
        raise BenchError(f"bench/arch/{name}.py lacks {missing}, which a "
                         f"{kind} cell needs")
    return mod


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of BENCHMARK.json with its files loaded."""

    name: str
    chips: int
    config: dict      # bench/configs/<config>.json
    arch: object      # bench/arch/<config's "arch">.py
    traffic: dict     # bench/traffic/<traffic>.json
    limits: dict      # bench/limits/<workload>.json
    end_to_end: tuple  # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec: dict | None = None) -> Cell:
    spec = spec if spec is not None else load_json(
        os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(os.path.join(ROOT, configs[w["config"]]["file"]))
    import generator

    try:
        traffic = generator.check_mix(
            load_json(bench_file("traffic", w["traffic"] + ".json")))
    except ValueError as e:
        raise BenchError(f"traffic {w['traffic']!r}: {e}") from None
    if "arch" not in config:
        raise BenchError(f"configuration {w['config']!r} names no \"arch\"")
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        arch=load_arch(config["arch"], traffic["kind"]), traffic=traffic,
        limits=load_json(bench_file("limits", name + ".json")),
        end_to_end=tuple(m for m in spec["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in spec["per_layer"] if _reports(m, name)))


def peaks_for(device_kind: str) -> dict:
    table = load_json(bench_file("peaks.json"))["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def place_compile_cache() -> str:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout (``<checkout>/.jax_cache``), with no size limit: a serving
    cell compiles one program per prefill width, more than a capped
    cache keeps, and an evicting cache compiles them again in every run.
    So only a cell's first run in a checkout compiles. Every program is
    cached, however quick."""
    import jax

    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileClock:
    """Backend compile seconds (or cache-retrieval seconds), compiles,
    and persistent-cache hits and misses, from JAX's monitoring events,
    between ``reset()`` calls."""

    def __init__(self):
        import jax

        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def reset(self):
        self.compile_s, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def summary(self) -> dict:
        return {"compile_s": self.compile_s, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


def memory_peak_bytes(devices) -> int | None:
    """``peak_bytes_in_use`` of the fullest device (None where the
    backend keeps no statistics)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def percentile(values, q: float) -> float | None:
    """Linear-interpolated percentile (numpy's default); None if empty."""
    import numpy as np

    return float(np.percentile(np.asarray(values, float), q)) \
        if len(values) else None


def span(name: str):
    """A host span in the profiler's trace (no cost when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def emit(tag: str, **kv) -> None:
    """An earlier line of standard output: counters and notes."""
    print(f"{tag} " + json.dumps(kv, sort_keys=True, default=str),
          flush=True)


def finite(x: float | None) -> bool:
    return x is not None and math.isfinite(x)
