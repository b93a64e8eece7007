"""Runner of a training cell: set-up, the measured window, the traced
window, and the comparison with the reference.

Set-up builds one session with its compiled train and optimizer steps,
makes the weights from the seed, and drives the first ``check_steps``
steps through the window's own calls and feed. What those steps leave
(their losses, the first gradient as AdamW holds it, the change of the
master weights) is what the reference is compared with, after the
window, once the program's state is freed.
"""

from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

import generator
import model
import program
from common import emit, span


class Feed:
    """A fresh batch for every step, from (seed, step), put on the
    devices in the program's batch sharding."""

    def __init__(self, sess, traffic: dict, vocab: int, seed: int):
        self.t, self.vocab, self.seed = traffic, vocab, seed
        self.batch = sess.shape_cfg.global_batch
        specs = sess.input_specs()
        self.shardings = {k: specs[k].sharding for k in ("tokens", "labels")}

    def host(self, step: int) -> dict:
        return generator.train_batch(self.t, self.vocab, self.batch,
                                     self.seed, step)

    def device(self, step: int) -> dict:
        with span("bench.batch"):
            return {k: jax.device_put(v, self.shardings[k])
                    for k, v in self.host(step).items()}


def build(cell, data: int):
    """The session a training cell runs."""
    from repro.api import session

    t = cell.traffic
    return session(program.register(cell.config), reduced=False,
                   seq_len=t["seq"], microbatch_size=t["microbatch_size"],
                   optim=model.freeze(t["optim"]), data=data)


def _step(sess, state: dict, batch) -> dict:
    """One train step and AdamW step on ``state`` ("params", "opt"),
    replaced in place: no other reference to the donated trees stays
    alive while the next step's buffers are allocated. Returns the train
    step's ``metrics`` (its counters, on the devices)."""
    with span("bench.train_step"):
        grads, metrics = sess.train_step(state["params"], batch)
    with span("bench.opt_step"):
        state["params"], state["opt"], _ = sess.opt_step(
            state.pop("params"), grads, state.pop("opt"))
    return metrics


def _loop(sess, state: dict, feed, first: int, seconds: float | None,
          steps: int | None):
    """Steps from batch ``first`` on, each next batch put on the devices
    while a step runs; one step in flight, since two steps' gradients do
    not fit beside the state. For ``seconds`` of host time or ``steps``
    steps; ends when the devices are done. Also returns the last step's
    ``metrics``, still on the devices; an earlier step's are dropped
    before the next step runs, all but its loss."""
    t0 = time.perf_counter()
    nxt, losses, n, ends = feed.device(first), [], 0, [t0]
    while True:
        metrics = _step(sess, state, nxt)
        loss = metrics["loss_sum"]
        n += 1
        nxt = feed.device(first + n)
        with span("bench.wait"):
            jax.block_until_ready(state["opt"])
            losses.append(float(loss))
        ends.append(time.perf_counter())
        if (steps is not None and n >= steps) or \
                (seconds is not None and ends[-1] - t0 >= seconds):
            return losses, n, ends[-1] - t0, np.diff(ends), metrics
        del metrics


def run(cell, *, seed: int, seconds: float, trace_dir: str | None,
        t_start: float, clock, devices, patch=None, sess=None) -> dict:
    """One run of the cell; ``patch(sess, arch)`` may plant a fault, and
    ``sess`` reuse a session of an earlier run (tests, calibration)."""
    t, m, arch = cell.traffic, cell.config["model"], cell.arch
    sess = sess or build(cell, data=len(devices) // cell.config["run"]["pp"])
    if patch is not None:
        patch(sess, arch)
    b1 = t["optim"]["b1"]
    make, key = model.program_maker(arch, m, sess.geo,
                                    sess.param_shapes()), \
        model.seed_key(seed)
    with span("bench.params"):
        params = make(key)
    state = {"params": params, "opt": sess.init_opt_state(params)}
    del params
    feed = Feed(sess, t, m["vocab"], seed)

    # the first steps: the window's own calls and feed, compared later.
    # Norms are taken inside jitted calls; the initial weights are made
    # again by the same executable, so the change is exact.
    moment = jax.jit(lambda t: program.row_norms(t, 1.0 / (1.0 - b1)))
    change = jax.jit(lambda master, p0: program.row_norms(jax.tree.map(
        lambda x, y: x - y.astype(jnp.float32), master, p0)))
    losses = []
    for k in range(t["check_steps"]):
        loss = _step(sess, state, feed.device(k))["loss_sum"]
        jax.block_until_ready(state["opt"])
        losses.append(float(loss))
        if k == 0:
            grad1 = program.leaf_norms(moment(state["opt"]["m"]), sess.geo,
                                       arch, m)
    p0 = make(key)
    delta = program.leaf_norms(change(state["opt"]["master"], p0),
                               sess.geo, arch, m)
    del p0
    setup = clock.summary()
    setup_s = time.perf_counter() - t_start

    compiles0 = clock.compiles
    wl, steps, window_s, step_s, last = _loop(
        sess, state, feed, t["check_steps"], seconds, None)
    tokens = steps * feed.batch * t["seq"]
    kernels = sess.describe()["kernels"]
    rec = {"window_s": window_s, "steps": steps, "tokens": tokens,
           "train_tokens_per_s": tokens / window_s,
           "compiles_in_window": clock.compiles - compiles0,
           "failed_steps": sum(not math.isfinite(x) for x in wl),
           "step_metrics": jax.device_get(last), "kernel_counters": kernels}
    del last

    summary = None
    if trace_dir is not None:
        import trace_reduce as tr

        with tr.capture(trace_dir):
            with span(tr.WINDOW_SPAN):
                _loop(sess, state, feed, t["check_steps"] + steps + 1, None,
                      t["trace_steps"])
        summary = {"events": tr.load(trace_dir)}
    from common import memory_peak_bytes
    mem = memory_peak_bytes(devices)
    emit("TRAIN", steps=steps, window_s=window_s, tokens=tokens,
         batch=feed.batch, seq=t["seq"], losses_first=losses,
         losses_window_last=wl[-3:], setup=setup,
         step_s={"min": float(step_s.min()),
                 "median": float(np.median(step_s)),
                 "max": float(step_s.max()),
                 "over_1.1x_median": int(
                     (step_s > 1.1 * np.median(step_s)).sum())},
         compiles_in_window=rec["compiles_in_window"],
         kernels=kernels, memory_peak_bytes=mem,
         geometry={"pp": sess.rc.pp, "microbatches": sess.rc.microbatches,
                   "unit": sess.rc.unit_size, "mesh": dict(sess.mesh.shape)})
    state.clear()
    gc.collect()

    # the reference, on the first steps' batches
    batches = [feed.host(k) for k in range(t["check_steps"])]
    ref = model.train_reference(arch, m, seed, batches, t["optim"])
    readings = compare(losses, grad1, delta, ref)
    emit("TRAIN_CHECK", losses=losses, reference_losses=ref["losses"],
         worst_grad=program.leaf_gaps(grad1, ref["grad1"])[:3],
         worst_delta=program.leaf_gaps(delta, ref["delta"])[:3])
    return {"setup_s": setup_s, "setup": setup, "rec": rec, "sess": sess,
            "memory_peak_bytes": mem, "summary": summary,
            "readings": readings, "reference": ref, "batches": batches,
            "attempted": steps,
            "failed": rec["failed_steps"],
            "e2e": {"train_tokens_per_s": rec["train_tokens_per_s"],
                    "setup_s": setup_s}}


def compare(losses, grad1, delta, ref) -> dict:
    """The numbers compared with the reference (see bench/limits)."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses,
                                                       ref["losses"]))
    med = np.median(np.concatenate(list(ref["grad1"].values())))
    moved = {k: v >= 1e-3 * med for k, v in ref["grad1"].items()}
    return {"loss_gap": loss_gap,
            "grad_gap": program.worst_leaf_gap(grad1, ref["grad1"]),
            "delta_gap": program.worst_leaf_gap(delta, ref["delta"], moved)}
