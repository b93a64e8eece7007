"""Runner of a serving cell: set-up, the open-loop window, the traced
window, and the comparison of served tokens with the reference.

The benchmark drives ``ServeEngine.step()`` itself from one thread: it
submits each request when it is due, ticks the engine while it has
work, and stamps each token with the host time at which the engine had
it. Every call of ``Session.serve_step_batched`` (one prefill chunk
group or one decode step) is recorded with its rows and positions, for
the readers that count attention and matrix work.
"""

from __future__ import annotations

import gc
import re
import time

import numpy as np

import generator
import model
import program
from common import emit, memory_peak_bytes, percentile, span


class Calls:
    """Records each serve step call: (width, positions, rows) of the rows
    it computes for; ``on`` gates recording to a window."""

    def __init__(self, sess, on_call):
        self.log, self.on, self.sess = [], False, sess
        inner = sess.serve_step_batched

        def recorded(params, caches, batch, want_logits=False):
            on_call()
            if self.on:
                mask = np.asarray(batch["slot_mask"], bool)
                self.log.append((int(batch["tokens"].shape[1]),
                                 np.asarray(batch["pos"])[mask].tolist()))
            return inner(params, caches, batch, want_logits=want_logits)

        sess.serve_step_batched = recorded

    def restore(self):
        del self.sess.serve_step_batched     # the class's method again


class OpenLoop:
    """Open-loop driving of one engine: requests submitted when due,
    tokens stamped when the engine has them."""

    def __init__(self, eng):
        self.eng, self.live, self.done = eng, {}, []
        self.t0 = None
        self.tick_s: list[float] = []
        self.busy: list[tuple] = []   # (slots, tokens, pages) a tick

    def stamp(self):
        now = time.perf_counter()
        for rec in list(self.live.values()):
            req = rec["req"]
            new = len(req.tokens) - len(rec["times"])
            rec["times"].extend([now] * new)
            if req.done.is_set():
                rec["error"] = None if req.error is None else repr(req.error)
                self.done.append(self.live.pop(id(req)))

    def submit(self, r, t0: float):
        with span("bench.submit"):
            req = self.eng.submit(r.prompt, max_gen=r.max_gen)
        self.live[id(req)] = {"r": r, "req": req, "due": t0 + r.due_s,
                              "sent": time.perf_counter(), "times": [],
                              "error": None}

    def tick(self, timed: bool) -> bool:
        t = time.perf_counter()
        with span("bench.engine_step"):
            worked = self.eng.step()
        with span("bench.collect"):
            self.stamp()
        if worked and timed:
            self.tick_s.append(time.perf_counter() - t)
            active = self.eng.pool.active()
            self.busy.append((len(active), sum(s.pos for s in active),
                              self.eng.stats.pages_in_use))
        return worked

    def occupancy(self) -> dict:
        """Mean shares over the ticks that did work: of the slots busy, of
        the pool's tokens that live requests hold, and of the KV pages in
        use (EngineStats, which counts prompt pages the prefix cache
        keeps after their request ended)."""
        if not self.busy:
            return {"slots": None, "kv_live_tokens": None, "kv_pages": None}
        slots, tokens, pages = np.mean(np.asarray(self.busy, float), axis=0)
        pool = self.eng.pool
        return {"slots": slots / pool.n_slots,
                "kv_live_tokens": tokens / (pool.n_slots * pool.max_seq),
                "kv_pages": pages / self.eng.session.n_pages}

    def run(self, requests, t0: float, until: float, timed: bool = True):
        """Submit ``requests`` (due after ``t0``) and tick until host time
        ``until``."""
        todo = list(requests)
        while True:
            now = time.perf_counter()
            while todo and t0 + todo[0].due_s <= now:
                self.submit(todo.pop(0), t0)
            if now >= until and not todo:
                return
            if not self.tick(timed):
                nxt = t0 + todo[0].due_s if todo else until
                with span("bench.idle"):
                    time.sleep(max(0.0, min(nxt, until) - time.perf_counter()))

    def drain(self, limit_s: float, first_token_only: bool = False):
        """Tick without new arrivals until every live request is done (or
        has its first token), or ``limit_s`` has passed."""
        end = time.perf_counter() + limit_s
        while time.perf_counter() < end and any(
                not first_token_only or not x["times"]
                for x in self.live.values()):
            if not self.tick(False):
                time.sleep(0.001)
        self.stamp()


def build(cell, data: int):
    from repro.api import session

    t = cell.traffic
    return session(program.register(cell.config), reduced=False,
                   mode="serve", max_slots=t["slots"], max_seq=t["context"],
                   page_size=t["page"], prefill_chunk=t["prefill_chunk"],
                   kv_cache_dtype=t["kv"], data=data,
                   overrides=dict(microbatches=1))


def setup(cell, seed: int, devices, patch=None, sess=None, warm=None,
          clock=None):
    """Session (or ``sess`` again), weights, engine and open loop, with every
    prefill width the mix produces (or the prompt lengths ``warm``) and
    the decode step warmed up, a slot's worth of prompts at a time, each
    group's progress on a ``WARM`` line."""
    t, m = cell.traffic, cell.config["model"]
    sess = sess or build(cell, data=len(devices) // cell.config["run"]["pp"])
    with span("bench.params"):
        params = model.program_params(seed, m, sess.geo, sess.param_shapes())
    eng = sess.serve_engine(params)
    drv = OpenLoop(eng)
    calls = Calls(sess, drv.stamp)
    if patch is not None:
        patch(eng)
    warm_rng = generator.rng(seed, 3)
    lengths = warm or generator.warm_prompt_lengths(t)
    t0 = time.perf_counter()
    for i in range(0, len(lengths), t["slots"]):
        for n in lengths[i:i + t["slots"]]:
            eng.submit(warm_rng.integers(0, m["vocab"], n, dtype=np.int32),
                       max_gen=2)
        eng.run_until_idle()
        emit("WARM", prompts=min(i + t["slots"], len(lengths)),
             of=len(lengths), seconds=time.perf_counter() - t0,
             **(clock.summary() if clock is not None else {}))
    return sess, params, eng, drv, calls


def latencies(due: list, close: float) -> dict:
    """TTFT from when each request was due, every gap between tokens that
    ended by ``close``, how late the generator submitted each request,
    and failures (an error, or no first token by the end of the drain)."""
    ttft, gaps, late, failed = [], [], [], 0
    for x in due:
        late.append(x["sent"] - x["due"])
        if x["error"] is not None or not x["times"]:
            failed += 1
            continue
        ttft.append(x["times"][0] - x["due"])
        gaps.extend(b - a for a, b in zip(x["times"], x["times"][1:])
                    if b <= close)
    return {"ttft": ttft, "gaps": gaps, "late": late, "failed": failed}


def run(cell, *, seed: int, seconds: float, trace_dir: str | None,
        t_start: float, clock, devices, patch=None, sess=None) -> dict:
    """One run of the cell; ``patch(engine)`` may plant a fault, and
    ``sess`` reuse a session of an earlier run (tests, calibration)."""
    t, m = cell.traffic, cell.config["model"]
    sess, params, eng, drv, calls = setup(cell, seed, devices, patch, sess,
                                          clock=clock)
    setup_info = clock.summary()
    setup_s = time.perf_counter() - t_start

    reqs = generator.serve_requests(t, m["vocab"], seed, seconds)
    compiles0 = clock.compiles
    t0 = time.perf_counter()
    calls.on = True
    drv.run(reqs, t0, t0 + seconds)
    calls.on = False
    at_close = {"queued": eng.scheduler.n_queued, "in_flight": len(drv.live)}
    window_calls, tick_s = list(calls.log), list(drv.tick_s)
    occupancy = drv.occupancy()
    compiles_in_window = clock.compiles - compiles0

    summary = None
    if trace_dir is not None:
        import trace_reduce as tr

        more = generator.serve_requests(t, m["vocab"], seed,
                                        t["trace_seconds"], stream=1)
        calls.log, calls.on = [], True
        t1 = time.perf_counter()
        with tr.capture(trace_dir):
            with span(tr.WINDOW_SPAN):
                drv.run(more, t1, t1 + t["trace_seconds"], timed=False)
        calls.on = False
        summary = {"events": tr.load(trace_dir), "calls": list(calls.log)}
    drv.drain(t["drain_seconds"], first_token_only=True)
    mem = memory_peak_bytes(devices)

    window_ids = {id(r) for r in reqs}
    due = [x for x in drv.done + list(drv.live.values())
           if id(x["r"]) in window_ids]
    in_window = {id(x["r"]) for x in due}
    lat = latencies(due, t0 + seconds)
    ttft, gaps, late, failed = lat["ttft"], lat["gaps"], lat["late"], \
        lat["failed"]
    out_tokens = sum(sum(1 for s in x["times"] if s <= t0 + seconds)
                     for x in due)
    rec = {"window_s": seconds, "calls": window_calls, "tick_s": tick_s,
           "requests": len(due), "failed": failed,
           "output_tokens_in_window": out_tokens,
           "compiles_in_window": compiles_in_window}
    emit("SERVE", requests=len(due), failed=failed, ticks=len(tick_s),
         serve_calls=len(window_calls), output_tokens_in_window=out_tokens,
         ttft_p50_s=percentile(ttft, 50), ttft_p85_s=percentile(ttft, 85),
         ttft_p90_s=percentile(ttft, 90), ttft_p95_s=percentile(ttft, 95),
         itl_p50_s=percentile(gaps, 50), itl_p95_s=percentile(gaps, 95),
         occupancy=occupancy, at_close=at_close,
         generator_late_p50_s=percentile(late, 50),
         generator_late_max_s=max(late) if late else None,
         setup=setup_info, compiles_in_window=compiles_in_window,
         stats=vars(eng.stats), kernels=sess.describe()["kernels"],
         memory_peak_bytes=mem)

    # free the engine's pool and weights before the reference runs
    finished = [x for x in drv.done if id(x["r"]) in in_window
                and x["error"] is None and len(x["req"].tokens)]
    sample = _sample(finished, t["check"], seed)
    eng.caches = eng.params = None
    calls.restore()
    del params, eng, drv, calls
    gc.collect()
    ref = model.serve_reference(m, seed, sample, t["context"])
    return {"setup_s": setup_s, "setup": setup_info, "rec": rec, "sess": sess,
            "memory_peak_bytes": mem, "summary": summary,
            "readings": {"served_gap": ref["gap"]},
            "compared_tokens": ref["tokens"], "sample": sample,
            "attempted": len(due), "failed": failed,
            "e2e": dict(tails(cell, {"ttft": ttft, "itl": gaps}),
                        setup_s=setup_s)}


TAIL = re.compile(r"(ttft|itl)_p(\d+)_ms")


def tails(cell, samples: dict) -> dict:
    """Each end-to-end metric of the cell named ``<ttft|itl>_p<q>_ms``:
    the q-th percentile of those samples, in ms."""
    out = {}
    for metric in cell.end_to_end:
        hit = TAIL.fullmatch(metric["name"])
        if hit:
            v = percentile(samples[hit[1]], int(hit[2]))
            out[metric["name"]] = None if v is None else v * 1e3
    return out


def _sample(finished, check: dict, seed: int):
    """(prompt, served tokens) of the finished request with the most
    served tokens, then others drawn from the seed, up to
    ``min_tokens`` served tokens or ``max_requests`` requests."""
    if not finished:
        return []
    order = sorted(finished, key=lambda x: (-len(x["req"].tokens),
                                            x["r"].index))
    rest = order[1:]
    picks = [order[0]] + [rest[i] for i in generator.rng(seed, 2)
                          .permutation(len(rest))]
    out, n = [], 0
    for x in picks[: check["max_requests"]]:
        out.append((x["r"].prompt, np.asarray(x["req"].tokens, np.int32)))
        n += len(x["req"].tokens)
        if n >= check["min_tokens"]:
            break
    return out
