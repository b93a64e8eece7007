"""The one traffic generator: reads a mix's parameters from its data file
(bench/traffic/<name>.json) and makes the inputs of a run from the seed.

The seed draws everything: sizes, inter-arrival gaps, their order and
the token ids. Sizes and gaps are stratified within a seed (one draw
from each of ``n`` equal-probability strata of the mix's distribution),
so every seed offers nearly the same amount of work, in its own
schedule, and a run's spread is the system's more than the draw's.
A mix file holds only the keys this module reads; any other is an
error, so a setting that nothing reads cannot pass unnoticed.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for (seed, stream...); any non-negative seed."""
    return np.random.default_rng([int(seed), *map(int, stream)])


MIX_KEYS = {
    "serve": {"kind", "note", "slots", "page", "prefill_chunk", "kv",
              "context", "rate", "arrivals", "prompt", "output",
              "trace_seconds", "drain_seconds", "check"},
    "train": {"kind", "note", "seq", "microbatch_size", "optim",
              "check_steps", "trace_steps"},
}
SIZE_KEYS = {"median", "sigma", "min", "max"}
ARRIVALS = ("poisson",)


def check_mix(t: dict) -> dict:
    """``t`` unchanged, or an error naming what no code reads."""
    kind = t.get("kind")
    if kind not in MIX_KEYS:
        raise ValueError(f"mix kind {kind!r}: known {sorted(MIX_KEYS)}")
    extra = set(t) - MIX_KEYS[kind]
    if kind == "serve":
        if t["arrivals"] not in ARRIVALS:
            raise ValueError(f"arrivals {t['arrivals']!r}: the generator "
                             f"knows {ARRIVALS}")
        for k in ("prompt", "output"):
            extra |= {f"{k}.{x}" for x in set(t[k]) - SIZE_KEYS}
    if extra:
        raise ValueError(f"mix keys nothing reads: {sorted(extra)}")
    return t


def _strata(n: int, r: np.random.Generator) -> np.ndarray:
    """One uniform draw in each of ``n`` equal strata of (0, 1)."""
    return (np.arange(n) + r.random(n)) / n


def lognormal_sizes(n: int, p: dict, r: np.random.Generator) -> np.ndarray:
    """``n`` whole sizes from a lognormal with the given median and sigma,
    stratified, clipped to [min, max]; ascending."""
    z = np.array([NormalDist().inv_cdf(u) for u in _strata(n, r)])
    x = np.clip(p["median"] * np.exp(p["sigma"] * z), p["min"], p["max"])
    return np.ceil(x).astype(np.int64)


def poisson_gaps(n: int, rate: float, r: np.random.Generator) -> np.ndarray:
    """``n`` exponential inter-arrival gaps, stratified; ascending."""
    return -np.log1p(-_strata(n, r)) / rate


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float          # seconds after the window opens
    prompt: np.ndarray    # int32 token ids
    max_gen: int          # output tokens (greedy, no stop token)


def serve_requests(t: dict, vocab: int, seed: int, seconds: float,
                   start_s: float = 0.0, stream: int = 0) -> list[Request]:
    """Open-loop arrivals for ``seconds`` at the mix's rate, from
    ``start_s`` on. ``stream`` separates the sets of one run (the
    measured window, the traced window)."""
    n = max(1, round(t["rate"] * seconds))
    r = rng(seed, 1, stream)
    prompts = r.permutation(lognormal_sizes(n, t["prompt"], r))
    outputs = r.permutation(lognormal_sizes(n, t["output"], r))
    gaps = r.permutation(poisson_gaps(n, t["rate"], r))
    if int((prompts + outputs).max()) > t["context"]:
        raise ValueError("the mix's longest prompt and output exceed its "
                         "context")
    due = start_s + np.cumsum(gaps) - gaps[0] * 0.5
    return [Request(i, float(due[i]),
                    r.integers(0, vocab, int(prompts[i]), dtype=np.int32),
                    int(outputs[i]))
            for i in range(n)]


def warm_prompt_lengths(t: dict) -> list[int]:
    """One prompt length for each prefill width the mix can produce:
    the engine runs chunks of ``prefill_chunk`` and one tail chunk whose
    width is the prompt length modulo the chunk."""
    chunk = int(t["prefill_chunk"])
    lo, hi = int(t["prompt"]["min"]), int(t["prompt"]["max"])
    lengths = {}
    for n in range(lo, hi + 1):
        lengths.setdefault(n % chunk or chunk, n)
    return sorted(lengths.values())


def train_batch(t: dict, vocab: int, batch: int, seed: int,
                step: int) -> dict:
    """Step ``step``'s batch: ``batch`` rows of ``seq`` + 1 uniform
    token ids, split into inputs and next-token labels."""
    toks = rng(seed, 0, step).integers(
        0, vocab, (batch, t["seq"] + 1), dtype=np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
