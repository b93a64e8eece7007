#!/usr/bin/env python3
"""Bring-up smoke test: the ZeroPP trainer and the serving engine at the
published widths of the paper's GPT 1.5B on a TPU.

    python3 chip_smoke.py               # one chip: train, then serve
    python3 chip_smoke.py --four-chips  # v5e-4 host: 2x2 mesh vs 1x1

One chip: ``gpt_paper``'s one-stage cut (``configs/gpt_paper.py``
``chip_share``: d_model 2304, 24 heads of 96, d_ff 9216, vocab 50304,
6 of 22 layers) trains a few zeropp steps at seq 1024 over 8
micro-batches of a fixed batch through ``repro.api.session``, then
serves 8 requests (prompts of 128-512 tokens, 32-64 generated) through
``ServeEngine`` on paged bf16 KV. One request's prefill logits are
compared with the float32 ``reference_logits`` at "highest" matmul
precision. Weights are random from a fixed seed.

Four chips: the same cut's train step on a data=2 x model=2 mesh (pp=2,
FSDP=2) and the same global batch on one chip of the host (data=1 x
model=1), compared on step-0 loss and global gradient norm; every
device's memory must hold its shard, not the whole.

Each phase prints its device, compile and run times, peak device memory
and kernel-dispatch counters; the last line of stdout is the JSON result
``{"ok": true, "device": {...}}``. Without a TPU, or outside a checkout
of the repository, it exits nonzero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "gpt_paper"

# Serving logits vs the float32 reference, relative L2 error of the
# last-position logit vector. The served path computes in bf16 (8-bit
# mantissa, 2^-9 relative rounding per op) with f32 accumulation; over 6
# layers the roundings of activations add up to ~0.5-1%. 2e-2 leaves 2x
# margin; a path computing in fp8 (2^-4 per op) or dropping a layer's
# term would exceed it several times over.
LOGIT_RTOL = 2e-2
# Four chips vs one, same params and batch: the forward math is the same
# bf16 ops per micro-batch, so step-0 losses differ only through the
# loss reduction (vocab-sharded jnp ring vs the Pallas kernel, both f32)
# and XLA fusion choices: expected ~1e-5 relative, bounded at 1e-3.
LOSS_RTOL = 1e-3
# Gradients additionally sum FSDP shards and micro-batches in another
# order and pass bf16 cotangents through different fusions: 1e-2.
GNORM_RTOL = 1e-2


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


@dataclasses.dataclass(frozen=True)
class Shapes:
    """What the smoke runs; the defaults are the chip run."""

    reduced: bool = False          # False: published widths (chip_share)
    seq: int = 1024
    steps: int = 5
    lr: float = 1e-3
    slots: int = 8
    page: int = 16
    chunk: int = 128               # prefill chunk: one compiled width
    prompts: tuple = (128, 256, 384, 512, 128, 256, 384, 512)
    gens: tuple = (32, 64, 48, 40, 64, 32, 56, 48)
    check_request: int = 3         # its prefill logits meet the reference
    seed: int = 0


class CompileClock:
    """Sums backend compile time (or compile-cache retrieval time) and
    counts persistent-cache hits and misses, from JAX's monitoring
    events, between ``reset()`` calls."""

    def __init__(self):
        import jax

        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def reset(self):
        self.compile_s, self.hits, self.misses = 0.0, 0, 0

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def summary(self) -> dict:
        return {"compile_s": round(self.compile_s, 3),
                "cache_hits": self.hits, "cache_misses": self.misses}


def _emit(tag: str, **kv) -> None:
    print(f"{tag} " + json.dumps(kv, sort_keys=True, default=str),
          flush=True)


def _memory(device) -> dict | None:
    """The device allocator's peak and limit (None where the backend
    keeps no statistics)."""
    stats = device.memory_stats()
    if stats is None:
        return None
    return {k: stats[k] for k in ("peak_bytes_in_use", "bytes_limit",
                                  "peak_bytes_reserved") if k in stats}


def _finite(x) -> bool:
    return bool(np.all(np.isfinite(np.asarray(x, np.float32))))


def _global_norm(tree) -> float:
    import jax
    import jax.numpy as jnp

    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
             for g in jax.tree.leaves(tree))
    return float(jnp.sqrt(sq))


# --------------------------------------------------------------------------- #
# One chip: train, then serve
# --------------------------------------------------------------------------- #


def train_phase(shapes: Shapes, clock: CompileClock) -> dict:
    """A few zeropp steps on a fixed batch; every requested step runs, no
    checkpoint is read and no failure is retried."""
    import jax

    from repro.api import session

    sess = session(ARCH, reduced=shapes.reduced, seq_len=shapes.seq,
                   schedule="zeropp", optim=dict(lr=shapes.lr))
    clock.reset()
    params = sess.init_params(jax.random.PRNGKey(shapes.seed))
    opt = sess.init_opt_state(params)
    batch = sess.stream(seed=shapes.seed).batch(0)
    losses, step_s = [], []
    for _ in range(shapes.steps):
        t0 = time.perf_counter()
        grads, metrics = sess.train_step(params, batch)
        params, opt, om = sess.opt_step(params, grads, opt)
        # free this step's f32 grads before the next step writes its own:
        # both at once do not fit beside the params and Adam moments
        del grads
        loss = float(metrics["loss_sum"])     # waits for the step
        jax.block_until_ready(params)
        step_s.append(time.perf_counter() - t0)
        losses.append(loss)
    d = sess.describe()
    out = {
        "config": d["arch"], "n_params": d["n_params"],
        "geometry": {k: d["geometry"][k] for k in ("pp", "vpp", "groups")},
        "mesh": dict(sess.mesh.shape), "seq": sess.shape_cfg.seq_len,
        "global_batch": sess.shape_cfg.global_batch,
        "microbatches": sess.rc.microbatches, "unit": sess.rc.unit_size,
        "steps_run": len(losses), "losses": losses,
        "grad_norm_last": float(om["grad_norm"]),
        "first_step_s": round(step_s[0], 3),
        "steady_step_s": round(float(np.median(step_s[1:])), 4)
        if len(step_s) > 1 else None,
        "memory": _memory(jax.devices()[0]),
        "kernels": d["kernels"], **clock.summary(),
    }
    _emit("TRAIN", **out)
    if out["steps_run"] != shapes.steps:
        raise SmokeFailure(f"ran {out['steps_run']} of {shapes.steps} "
                           "train steps")
    if not all(np.isfinite(losses)) or not losses[-1] < losses[0]:
        raise SmokeFailure(f"loss not finite and falling: {losses}")
    del params, opt
    return out


def _requests(shapes: Shapes, vocab: int):
    rng = np.random.RandomState(shapes.seed)
    return [(rng.randint(0, vocab, size=p).astype(np.int32), g)
            for p, g in zip(shapes.prompts, shapes.gens)]


def _prefill_logits(sess, params, prompt, chunk: int):
    """Last-position logits of ``prompt`` prefilled alone into slot 0 of
    fresh paged caches, chunk by chunk through the engine's serve step
    (``Session.serve_step_batched``)."""
    n = sess.max_slots
    caches = sess.init_caches()
    pt = np.zeros((n, sess.pages_per_slot), np.int32)
    pt[0] = np.arange(sess.pages_per_slot)         # slot 0 owns pages 0..
    mask = np.zeros(n, bool)
    mask[0] = True
    logits = None
    for off in range(0, len(prompt), chunk):
        toks = np.zeros((n, chunk), np.int32)
        toks[0] = prompt[off:off + chunk]
        pos = np.zeros(n, np.int32)
        pos[0] = off
        _, logits, caches = sess.serve_step_batched(
            params, caches, {"tokens": toks, "pos": pos, "slot_mask": mask,
                             "page_tables": pt}, want_logits=True)
    return np.asarray(logits, np.float32)[0]


def _reference_last_logits(sess, params, prompt):
    """float32 reference forward at "highest" matmul precision, on a
    float32 copy of the served params, with the jnp reference kernels."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as M

    rc = dataclasses.replace(sess.rc, compute_dtype="float32",
                             param_dtype="float32", kernel_impl="ref")
    # one unsharded float32 copy (the reference indexes stages directly)
    p32 = jax.tree.map(
        lambda a: jnp.asarray(jax.device_get(a), jnp.float32), params)

    @jax.jit
    def fwd(p, toks):
        logits, _ = M.reference_logits(sess.cfg, rc, p, toks)
        return logits[0, -1]

    with jax.default_matmul_precision("highest"):
        return np.asarray(fwd(p32, jnp.asarray(prompt)[None]), np.float32)


def serve_phase(shapes: Shapes, clock: CompileClock) -> dict:
    import jax

    from repro.api import SessionSpec, session

    vocab = SessionSpec(ARCH, reduced=shapes.reduced).resolve_configs(
    )[1].vocab
    work = _requests(shapes, vocab)
    need = max(len(t) + g for t, g in work) + 1
    max_seq = -(-need // shapes.page) * shapes.page
    sess = session(ARCH, reduced=shapes.reduced, mode="serve",
                   max_slots=shapes.slots, max_seq=max_seq,
                   page_size=shapes.page, prefill_chunk=shapes.chunk,
                   kv_cache_dtype="bf16", overrides=dict(microbatches=1))
    clock.reset()
    params = sess.init_params(jax.random.PRNGKey(shapes.seed))
    t0 = time.perf_counter()
    with sess.serve_engine(params) as eng:
        handles = [eng.submit(toks, max_gen=g) for toks, g in work]
        results = [h.result(timeout=900) for h in handles]
    serve_s = time.perf_counter() - t0
    engine_compile = clock.summary()
    st = eng.stats

    prompt = work[shapes.check_request][0]
    got = _prefill_logits(sess, params, prompt, shapes.chunk)
    kernels = sess.describe()["kernels"]      # before the reference runs
    want = _reference_last_logits(sess, params, prompt)
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    out = {
        "config": sess.cfg.name, "mesh": dict(sess.mesh.shape),
        "slots": shapes.slots, "max_seq": max_seq, "page": shapes.page,
        "prefill_chunk": shapes.chunk, "kv": "bf16",
        "requests": len(work), "answered": sum(
            len(r) == g for r, (_, g) in zip(results, work)),
        "prompt_lens": [len(t) for t, _ in work],
        "generated_tokens": st.generated_tokens,
        "prefill_steps": st.prefill_steps, "decode_steps": st.decode_steps,
        "serve_wall_s": round(serve_s, 3), "engine": engine_compile,
        "memory": _memory(jax.devices()[0]),
        "logits_check": {"request": shapes.check_request,
                         "prompt_len": len(prompt), "rel_l2": rel,
                         "rtol": LOGIT_RTOL, "finite": _finite(got),
                         "argmax_got": int(got.argmax()),
                         "argmax_ref": int(want.argmax())},
        "kernels": kernels,
    }
    _emit("SERVE", **out)
    if out["answered"] != len(work):
        raise SmokeFailure(f"{len(work) - out['answered']} requests not "
                           "answered in full")
    if not (_finite(got) and rel <= LOGIT_RTOL):
        raise SmokeFailure(f"served logits off the reference: rel L2 "
                           f"{rel:.3e} > {LOGIT_RTOL}")
    return out


def check_kernels(phase: str, kernels: dict, need: set[str],
                  allowed_ref: set[str] = frozenset()) -> None:
    """Pallas on the chip: the needed kernels ran, nothing fell back, and
    no attention / cross-entropy took a jnp reference path other than
    the documented ones."""
    counters = kernels["counters"]
    missing = sorted(k for k in need if not counters.get(k))
    refs = sorted(k for k in counters
                  if k.startswith("ref_") and k not in allowed_ref)
    if missing or refs or kernels["fallbacks"]:
        raise SmokeFailure(
            f"{phase} kernels: missing {missing}, reference paths {refs}, "
            f"fallbacks {kernels['fallbacks']}")


# --------------------------------------------------------------------------- #
# Four chips: 2x2 mesh vs one chip
# --------------------------------------------------------------------------- #


def restack_stages(tree, src_geo, dst_geo):
    """Move a params tree between pipeline geometries: logical layer i of
    the source (stage i // k, slot i % k) becomes the same layer of the
    destination. io params are geometry-free."""
    from repro.models.model import storage_index

    def layer_index(geo, seg):
        out = {}
        for s in range(geo.seg_stages(seg)):
            p, v = s % geo.pp, s // geo.pp
            for j in range(seg.k):
                out[s * seg.k + j] = (storage_index(p, v, seg.vpp), j)
        return out

    (src_seg,), (dst_seg,) = src_geo.segments, dst_geo.segments
    src_at, dst_at = layer_index(src_geo, src_seg), layer_index(dst_geo,
                                                                 dst_seg)
    src = tree["segments"][src_seg.name]
    names = {n.split(".", 1)[1] for n in src}
    out = {}
    for name in names:
        ref = src[f"L0.{name}"]
        for j in range(dst_seg.k):
            out[f"L{j}.{name}"] = np.zeros(
                (dst_geo.seg_stages(dst_seg),) + ref.shape[1:], ref.dtype)
    for i in range(src_seg.n_layers):
        si, sj = src_at[i]
        di, dj = dst_at[i]
        for name in names:
            out[f"L{dj}.{name}"][di] = src[f"L{sj}.{name}"][si]
    return {"io": tree["io"], "segments": {dst_seg.name: out}}


def four_chip_phase(shapes: Shapes, clock: CompileClock) -> dict:
    import jax

    from repro.api import session

    devices = jax.devices()
    if len(devices) != 4:
        raise SmokeFailure(f"--four-chips needs a 4-device host, JAX "
                           f"sees {len(devices)}")
    common = dict(reduced=shapes.reduced, seq_len=shapes.seq,
                  schedule="zeropp")
    s1 = session(ARCH, data=1, overrides=dict(pp=1, microbatches=16,
                                              unit=1), **common)
    s4 = session(ARCH, data=2, global_batch=s1.shape_cfg.global_batch,
                 overrides=dict(pp=2, microbatches=8, unit=1), **common)
    batch = s1.stream(seed=shapes.seed).batch(0)
    host = jax.device_get(s1.init_params(jax.random.PRNGKey(shapes.seed)))
    runs = {}
    for tag, sess, tree in (
            ("data2_model2", s4, restack_stages(host, s1.geo, s4.geo)),
            ("data1_model1", s1, host)):
        clock.reset()
        params = sess.adopt_params(tree)
        shard = {d: 0 for d in sess.mesh.devices.flat}
        for leaf in jax.tree.leaves(params):
            for sh in leaf.addressable_shards:
                shard[sh.device] += sh.data.nbytes
        t0 = time.perf_counter()
        grads, metrics = sess.train_step(params, batch)
        loss = float(metrics["loss_sum"])
        gnorm = _global_norm(grads)
        run_s = time.perf_counter() - t0
        whole = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
        runs[tag] = {
            "mesh": dict(sess.mesh.shape),
            "geometry": {"pp": sess.rc.pp, "vpp": sess.geo.segments[0].vpp,
                         "k": sess.geo.segments[0].k},
            "global_batch": sess.shape_cfg.global_batch,
            "loss": loss, "grad_norm": gnorm, "first_step_s": round(run_s, 3),
            "param_bytes_whole": whole,
            "param_bytes_per_device": {str(d.id): b
                                       for d, b in shard.items()},
            "memory": {str(d.id): _memory(d) for d in devices},
            "kernels": sess.describe()["kernels"], **clock.summary(),
        }
        del params, grads
        gc.collect()
    a, b = runs["data2_model2"], runs["data1_model1"]
    d_loss = abs(a["loss"] - b["loss"]) / abs(b["loss"])
    d_gn = abs(a["grad_norm"] - b["grad_norm"]) / b["grad_norm"]
    share = max(a["param_bytes_per_device"].values()) / a["param_bytes_whole"]
    out = {"runs": runs, "loss_rel_diff": d_loss, "loss_rtol": LOSS_RTOL,
           "grad_norm_rel_diff": d_gn, "grad_norm_rtol": GNORM_RTOL,
           "max_device_param_share": share}
    _emit("FOUR_CHIPS", **out)
    if not (np.isfinite(a["loss"]) and np.isfinite(b["loss"])):
        raise SmokeFailure("non-finite step-0 loss")
    if d_loss > LOSS_RTOL or d_gn > GNORM_RTOL:
        raise SmokeFailure(
            f"2x2 vs 1x1 disagree: loss rel {d_loss:.3e} (tol {LOSS_RTOL}),"
            f" grad norm rel {d_gn:.3e} (tol {GNORM_RTOL})")
    if len(a["param_bytes_per_device"]) != 4 or share > 0.5 \
            or min(a["param_bytes_per_device"].values()) == 0:
        raise SmokeFailure(
            "the 2x2 params are not spread over the four devices: "
            f"{a['param_bytes_per_device']} of {a['param_bytes_whole']}")
    return out


# --------------------------------------------------------------------------- #


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-vs-1x1 train-step comparison "
                         "on a four-chip host")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.api import ensure_host_devices   # fails outside a checkout

    ensure_host_devices()        # also places the compile cache
    import jax

    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    _emit("DEVICE", **device,
          compile_cache=jax.config.jax_compilation_cache_dir)
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {device['platform']!r}); "
              "this smoke never falls back to the CPU", file=sys.stderr)
        return 2

    clock = CompileClock()
    shapes = Shapes()
    if args.four_chips:
        out = four_chip_phase(shapes, clock)
        for tag, run in out["runs"].items():
            check_kernels(f"four-chip {tag}", run["kernels"],
                          {"pallas_flash"}, {"ref_attention_bwd"})
    else:
        train = train_phase(shapes, clock)
        check_kernels("train", train["kernels"],
                      {"pallas_flash", "pallas_xent"}, {"ref_attention_bwd"})
        gc.collect()
        serve = serve_phase(shapes, clock)
        check_kernels("serve", serve["kernels"], {"pallas_paged"})
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
