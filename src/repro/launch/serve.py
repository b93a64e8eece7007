"""Serving driver: the continuous-batching engine over the ``repro.api``
Session facade.

Requests stream through a fixed pool of KV-cache slots (``--slots``);
finished requests release their slot mid-decode and the FIFO queue
refills it without rebuilding the jitted step. The workload comes from
``--requests FILE`` (JSON / JSON-lines, see ``--help``) or is
synthesized with staggered lengths from ``--n-requests/--prompt/--gen``.

Usage (CPU demo):
  SPMD_DEVICES=8 PYTHONPATH=src python -m repro.launch.serve \
      --arch llama3.2-1b --slots 4 --n-requests 8 --prompt 16 --gen 8

Workload file: a JSON array (or one JSON object per line) of requests::

  {"prompt_len": 12, "max_gen": 8}          # synthetic prompt (seeded)
  {"tokens": [3, 14, 15], "max_gen": 4, "stop": [0]}   # explicit prompt

A serve session can boot straight from a train checkpoint
(``--ckpt DIR``): ``Session.restore_params`` re-lays the trained params
out onto the serving mesh (train→serve handoff).
"""

from __future__ import annotations

import argparse
import json
import time

from repro.api import SessionSpec, ensure_host_devices, session


def load_requests(path: str, vocab: int, seed: int = 0):
    """Parse a --requests workload file into (tokens, max_gen, stop)."""
    import numpy as np

    with open(path) as f:
        text = f.read().strip()
    if text.startswith("["):
        entries = json.loads(text)
    else:
        entries = [json.loads(line) for line in text.splitlines() if line]
    rng = np.random.RandomState(seed)
    out = []
    for i, e in enumerate(entries):
        if "tokens" in e:
            toks = np.asarray(e["tokens"], np.int32)
            if toks.size and (toks.min() < 0 or toks.max() >= vocab):
                raise SystemExit(
                    f"--requests entry {i}: token ids must be in "
                    f"[0, {vocab}) for this config, got range "
                    f"[{toks.min()}, {toks.max()}] — reduced() configs "
                    "use a small demo vocab")
        elif "prompt_len" in e:
            toks = rng.randint(0, vocab, size=int(e["prompt_len"])
                               ).astype(np.int32)
        else:
            raise SystemExit(
                f"--requests entry {i} needs 'tokens' or 'prompt_len': "
                f"{e}")
        out.append((toks, int(e.get("max_gen", 8)),
                    tuple(e.get("stop", ()))))
    if not out:
        raise SystemExit(f"--requests file {path!r} holds no requests")
    return out


def synth_requests(n: int, prompt: int, gen: int, vocab: int,
                   seed: int = 0):
    """Staggered synthetic workload: lengths skewed around the means."""
    import numpy as np

    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        p = max(2, int(prompt * (0.5 + i / max(n - 1, 1))))
        g = max(2, int(gen * (0.25 + 1.5 * (i % 4) / 3)))
        toks = rng.randint(0, vocab, size=p).astype(np.int32)
        out.append((toks, g, ()))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--slots", type=int, default=4,
                    help="KV-cache slots (in-flight requests)")
    ap.add_argument("--n-requests", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=16,
                    help="mean synthetic prompt length")
    ap.add_argument("--gen", type=int, default=8,
                    help="mean synthetic generation budget")
    ap.add_argument("--requests", default=None,
                    help="workload file (JSON array or JSON-lines)")
    ap.add_argument("--data", type=int, default=None,
                    help="FSDP axis width (default: what the devices "
                         "give after the pipeline axis)")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="the arch's tiny reduced() config; --no-reduced "
                         "serves its published widths cut to one chip's "
                         "share")
    ap.add_argument("--max-seq", type=int, default=None,
                    help="KV cache length (default: fits the workload)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="split prompts into chunks of this width "
                         "(bounds distinct prefill compilations)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="paged KV cache: tokens per page (shared "
                         "prompt prefixes prefill once; default: "
                         "contiguous per-slot rows)")
    ap.add_argument("--max-pages", type=int, default=None,
                    help="paged KV cache: total page count (default: "
                         "the contiguous footprint slots*max_seq/page)")
    ap.add_argument("--prefix-sharing", default="on",
                    choices=("on", "off"),
                    help="radix prefix sharing across requests "
                         "(off: pages stay private per request)")
    ap.add_argument("--kv-cache-dtype", default=None,
                    choices=("fp32", "bf16", "int8"),
                    help="KV-cache storage dtype (default: the compute "
                         "dtype); int8 quantizes the page pool with "
                         "per-page scales and needs --page-size")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy, in-graph)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (with --temperature)")
    ap.add_argument("--seed", type=int, default=None,
                    help="per-request sampling seed base: request i "
                         "draws from seed+i (restart-deterministic)")
    ap.add_argument("--schedule", default=None,
                    help="registered schedule name or 'auto' (§4 plan "
                         "selection; serving itself runs the fwd-only "
                         "table, the choice sizes the unit buffers)")
    ap.add_argument("--preset", default="a800",
                    help="cost preset for schedule='auto' simulation "
                         "(a800 | tpu_v5e)")
    ap.add_argument("--moe-mode", default=None,
                    help="expert placement for MoE archs: gathered | ep "
                         "| auto (cost both under the a2a-aware model)")
    ap.add_argument("--moe-stats", action="store_true",
                    help="per-expert load histogram + capacity-drop "
                         "counters in the serving summary")
    ap.add_argument("--ckpt", default=None,
                    help="train checkpoint dir to boot params from "
                         "(train→serve handoff)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel serving tier: N engine replicas "
                         "behind an EngineRouter (least-outstanding-"
                         "tokens dispatch, radix-affinity hinting, "
                         "replica-failure failover)")
    ap.add_argument("--kill-replica-after", type=int, default=None,
                    help="failover drill (needs --replicas >= 2): kill "
                         "replica 0 after this many requests finish; "
                         "its in-flight work moves to the survivors")
    args = ap.parse_args()

    ensure_host_devices()
    import jax

    # size the workload before the session so max_seq can default to
    # whatever the requests actually need; ids come from the vocab of the
    # config the session will resolve (reduced or published widths)
    vocab = SessionSpec(args.arch, reduced=args.reduced).resolve_configs(
    )[1].vocab
    if args.requests:
        work = load_requests(args.requests, vocab)
    else:
        work = synth_requests(args.n_requests, args.prompt, args.gen,
                              vocab)
    if not work:
        raise SystemExit("no requests to serve (--n-requests 0?)")
    need = max(len(t) + g for t, g, _ in work) + 1
    max_seq = args.max_seq or need
    if max_seq < need:
        raise SystemExit(f"--max-seq {max_seq} too small for the "
                         f"workload (needs >= {need})")
    if args.page_size:
        max_seq = -(-max_seq // args.page_size) * args.page_size

    sess_kw = dict(
        mode="serve", reduced=args.reduced, data=args.data,
        max_slots=args.slots,
        max_seq=max_seq, schedule=args.schedule, cost_preset=args.preset,
        prefill_chunk=args.prefill_chunk, page_size=args.page_size,
        max_pages=args.max_pages, prefix_sharing=args.prefix_sharing,
        kv_cache_dtype=args.kv_cache_dtype, moe_mode=args.moe_mode,
        overrides=dict(microbatches=2,
                       **({"moe_stats": True} if args.moe_stats else {})),
    )
    if args.replicas > 1:
        return _serve_routed(args, work, sess_kw)
    if args.kill_replica_after is not None:
        raise SystemExit("--kill-replica-after needs --replicas >= 2 "
                         "(there is no survivor to fail over to)")

    sess = session(args.arch, **sess_kw)
    d = sess.describe()["schedule"]
    print(f"serving with schedule={d['name']} "
          f"(simulated bubble {d['bubble_ratio']:.3f}, "
          f"preset {d['preset']}); {args.slots} slots, "
          f"max_seq {max_seq}")

    if args.ckpt:
        params = sess.restore_params(args.ckpt)
        print(f"params restored from train checkpoint {args.ckpt}")
    else:
        params = sess.init_params(jax.random.PRNGKey(0))

    eng = sess.serve_engine(params)
    t0 = time.time()
    with eng:
        handles = [
            eng.submit(toks, max_gen=g, stop=stop,
                       temperature=args.temperature, top_p=args.top_p,
                       seed=(None if args.seed is None
                             else args.seed + i))
            for i, (toks, g, stop) in enumerate(work)]
        results = [h.result(timeout=600) for h in handles]
    dt = time.time() - t0
    for i, ((toks, g, _), res) in enumerate(zip(work, results)):
        print(f"  req{i}: prompt {len(toks):3d} -> {len(res)} tokens "
              f"{res[:8]}{'...' if len(res) > 8 else ''}")
    st = eng.stats
    total = st.generated_tokens
    print(f"{len(work)} requests, {total} tokens in {dt:.3f}s "
          f"({total / max(dt, 1e-9):.1f} tok/s, "
          f"{st.prefill_steps} prefill + {st.decode_steps} decode steps, "
          f"slot occupancy {st.occupancy:.2f})")
    if sess.paged:
        prompt_total = sum(len(t) for t, _, _ in work)
        print(f"paged: pages_in_use={st.pages_in_use} "
              f"peak={st.peak_pages_in_use}/{sess.n_pages} "
              f"prefix_hits={st.prefix_hits} "
              f"prefix_hit_tokens={st.prefix_hit_tokens} "
              f"prefilled {st.prefill_tokens}/{prompt_total} prompt "
              f"tokens, evictions={st.evictions}")
    srv = sess.describe().get("serving", {})
    moe = srv.get("moe")
    if moe is not None or srv.get("capacity_deferrals", 0):
        # MoE serving summary: capacity-aware admission + dispatch load
        line = (f"moe: capacity_deferrals="
                f"{srv.get('capacity_deferrals', 0)}")
        if moe is not None:
            line += f" dropped_tokens={moe['dropped_tokens']}"
            if "load_per_expert" in moe:
                line += f" load_per_expert={moe['load_per_expert']}"
        print(line)
    print("SERVE_OK")


def _serve_routed(args, work, sess_kw):
    """The --replicas N path: N sessions/engines behind an EngineRouter,
    optional mid-workload replica kill (--kill-replica-after)."""
    import jax

    from repro.api import session
    from repro.serving import EngineRouter

    engines = []
    for r in range(args.replicas):
        sess = session(args.arch, **sess_kw)
        if args.ckpt:
            params = sess.restore_params(args.ckpt)
        else:
            params = sess.init_params(jax.random.PRNGKey(0))
        engines.append(sess.serve_engine(params))
    d = engines[0].session.describe()["schedule"]
    print(f"serving with schedule={d['name']} x{args.replicas} replicas "
          f"({args.slots} slots each, max_seq "
          f"{engines[0].session._max_seq()})")
    router = EngineRouter(engines)
    t0 = time.time()
    failed = 0
    with router:
        handles = [
            router.submit(toks, max_gen=g, stop=stop,
                          temperature=args.temperature, top_p=args.top_p,
                          seed=(None if args.seed is None
                                else args.seed + i))
            for i, (toks, g, stop) in enumerate(work)]
        if args.kill_replica_after is not None:
            k = min(args.kill_replica_after, len(handles))
            for h in handles[:k]:
                h.result(timeout=600)
            moved = router.kill_replica(0)
            print(f"replica 0 killed after {k} results; "
                  f"{moved} in-flight/queued requests moved to survivors")
        results = []
        for h in handles:
            try:
                results.append(h.result(timeout=600))
            except BaseException as e:  # noqa: BLE001 — report, not die
                failed += 1
                results.append(e)
    dt = time.time() - t0
    for i, ((toks, g, _), res) in enumerate(zip(work, results)):
        if isinstance(res, BaseException):
            print(f"  req{i}: prompt {len(toks):3d} -> FAILED ({res})")
        else:
            print(f"  req{i}: prompt {len(toks):3d} -> {len(res)} tokens "
                  f"{res[:8]}{'...' if len(res) > 8 else ''}")
    st = router.stats()
    total = st["generated_tokens"]
    print(f"router: replicas={st['replicas']} alive={st['alive']} "
          f"failovers={st['failovers']} "
          f"dispatched={router.dispatched} "
          f"resubmitted={[p['resubmitted_requests'] for p in st['per_replica']]}")
    print(f"{len(work)} requests, {total} tokens in {dt:.3f}s "
          f"({total / max(dt, 1e-9):.1f} tok/s aggregate, "
          f"failed={failed})")
    if failed == 0:
        print("SERVE_OK")
    else:
        raise SystemExit(f"{failed} requests failed")


if __name__ == "__main__":
    main()
