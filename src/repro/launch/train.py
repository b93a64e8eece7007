"""End-to-end training driver: data pipeline → ZeroPP pipeline step →
sharded AdamW → checkpoint/restart under the fault-tolerance controller.

All assembly goes through the ``repro.api`` Session facade.
``--schedule auto`` runs the §4 plan selection (every registered schedule
+ the autogen heuristic, simulated under ``--preset``) and trains with
the winner.

Usage (CPU demo; device count via SPMD_DEVICES, default 8):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
      --steps 100 --data 2 [--schedule auto] [--ckpt-dir /tmp/ckpt]

On a TPU host the topology is the attached one (its chips, as JAX sees
them); ``--no-reduced`` trains the arch's published widths cut to one
chip's share (``chip_share()`` in its config), e.g.
  python -m repro.launch.train --arch gpt_paper --no-reduced --seq 1024 \
      --microbatches 8 --unit 1 --steps 5 --ckpt-dir <fresh dir>
"""

from __future__ import annotations

import argparse

from repro.api import ensure_host_devices, session


def build_session(arch: str, *, data: int | None = None, seq: int,
                  microbatches: int, schedule: str, lr: float,
                  unit: int = 0, preset: str = "a800", reduced: bool = True,
                  profile_top_k: int = 3,
                  profile_budget_s: float | None = None,
                  moe_mode: str | None = None, moe_stats: bool = False,
                  topology=None, global_batch: int | None = None):
    """One facade call replaces the old 8-step assembly ritual.

    ``topology=`` (a preset name or a ``repro.runtime.topology.Topology``)
    subsumes ``data=`` — the axis layout is derived from the hardware.
    ``global_batch=`` pins the batch across elastic restarts so the data
    stream (and the loss trajectory) continues on a shrunk mesh.
    """
    kw = {}
    if schedule == "auto_profiled":
        kw = dict(profile_top_k=profile_top_k,
                  profile_budget_s=profile_budget_s)
    if topology is not None:
        kw["topology"] = topology
    else:
        kw["data"] = data
    if global_batch is not None:
        kw["global_batch"] = global_batch
    ov = dict(schedule=schedule, microbatches=microbatches, unit=unit)
    if moe_mode is not None:
        ov["moe_mode"] = moe_mode
    if moe_stats:
        ov["moe_stats"] = True
    sess = session(
        arch, mode="train", seq_len=seq, cost_preset=preset,
        reduced=reduced, overrides=ov,
        optim=dict(lr=lr, warmup=20, total=10_000), **kw,
    )
    sched = sess.describe()["schedule"]
    auto_moe = sched.get("moe_mode_auto")
    if auto_moe:
        # the provenance line CI's moe-smoke job greps for
        print("moe_mode=auto resolved -> "
              f"{auto_moe['resolved']!r}; scores: "
              + ", ".join(f"{m}={s:.3e}"
                          for m, s in sorted(auto_moe["scores"].items())))
    coll = sched.get("collectives", {})
    if coll.get("a2a_per_f_tick", 0) or coll.get("a2a_per_b_tick", 0):
        print(f"a2a: {coll['a2a_per_f_tick']}xF+{coll['a2a_per_b_tick']}xB "
              f"events/tick, {coll['a2a_bytes']:.3e} B/event, "
              f"t_event {coll['a2a_t_event_s']:.3e}s, simulated total "
              f"{coll['a2a_total_s']:.3e}s")
    if sess.plan_selection is not None:
        sel = sess.plan_selection
        src = sess._plan_source
        if src in ("memory-hit", "persisted-hit"):
            # the provenance line CI's warm-cache re-run greps for
            kind = "persisted" if src == "persisted-hit" else "memory"
            print(f"plan-cache: hit ({kind}) -> "
                  f"{sel.selected.name!r} [{sel.provenance}]")
        else:
            print(f"plan-cache: miss (ran {src})")
        print(f"schedule={schedule} selected {sel.selected.name!r} "
              f"(makespan {sel.analysis.makespan:.3e}, preset "
              f"{sel.preset}); ranking: "
              + ", ".join(f"{n}={m:.3e}" for n, m in sel.ranking()))
        if sel.measured:
            sim_best = (sel.profile or {}).get("simulated_best")
            sim_us = (sel.profile or {}).get("simulated_best_us")
            win_us = sel.measured.get(sel.selected.name)
            print("measured us/call: "
                  + ", ".join(f"{n}={us:.1f}" for n, us in
                              sel.measured_ranking()))
            if win_us is not None and sim_us is not None \
                    and win_us <= sim_us + 1e-9:
                # CI asserts the coarse→fine contract on this line:
                # measured(winner) <= measured(simulated-best)
                print(f"AUTO_PROFILED_OK selected={sel.selected.name} "
                      f"us={win_us:.1f} simulated_best={sim_best} "
                      f"us={sim_us:.1f} "
                      f"delta={(sim_us - win_us) / max(sim_us, 1e-9):.1%}")
    return sess


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--data", type=int, default=None,
                    help="FSDP axis width (default: what the devices "
                         "give after the pipeline axis)")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="the arch's tiny reduced() config; --no-reduced "
                         "trains its published widths cut to one chip's "
                         "share")
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--unit", type=int, default=0)
    ap.add_argument("--schedule", default="zeropp",
                    help="a registered schedule name, 'auto' for the §4 "
                         "simulated plan selection, or 'auto_profiled' "
                         "to also time the top-K finalists on the live "
                         "mesh and pick the fastest measured step")
    ap.add_argument("--preset", default="a800",
                    help="cost preset for schedule=auto (a800 | tpu_v5e)")
    ap.add_argument("--profile-top-k", type=int, default=3,
                    help="auto_profiled: how many simulated survivors "
                         "get a real measurement")
    ap.add_argument("--profile-budget-s", type=float, default=None,
                    help="auto_profiled: wall-clock cap on the measuring "
                         "phase (the simulated-best is always measured)")
    ap.add_argument("--moe-mode", default=None,
                    help="expert placement for MoE archs: gathered | ep "
                         "| auto (cost both under the a2a-aware model)")
    ap.add_argument("--moe-stats", action="store_true",
                    help="collect per-layer expert-load histograms + "
                         "capacity-drop counters (train metrics "
                         "moe_load/moe_dropped)")
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--inject-failure-at", type=int, default=None)
    ap.add_argument("--topology", default=None,
                    help="hardware topology preset (fake_cpu | "
                         "gpu_cluster | tpu_pod | tpu_pod_x2); default: "
                         "the attached host (its chips, or the fake CPU "
                         "devices), data axis pinned by --data if given")
    ap.add_argument("--max-failures", type=int, default=3)
    args = ap.parse_args()

    ensure_host_devices()
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from repro.runtime.fault_tolerance import (
        FaultToleranceConfig,
        TrainController,
    )
    from repro.runtime.topology import attached_topology, resolve_topology

    ctl = TrainController(args.ckpt_dir,
                          FaultToleranceConfig(
                              ckpt_every=args.ckpt_every,
                              max_failures=args.max_failures))
    box: dict = {}   # first-build facts pinned across elastic restarts

    def build(restored, manifest):
        # fresh session per (re)start: an elastic restart rebuilds on a
        # topology whose data axis halves per failure (node-loss model:
        # the survivors re-mesh; params relayout from the checkpoint)
        topo = (resolve_topology(args.topology) if args.topology
                else attached_topology())
        if ctl.failures:
            d0 = box["data"]
            topo = _dc.replace(topo, name=None,
                               data=max(1, d0 // (2 ** ctl.failures)))
        elif args.data is not None:
            topo = _dc.replace(topo, data=args.data)
        sess = build_session(
            args.arch, seq=args.seq,
            microbatches=args.microbatches, schedule=args.schedule,
            lr=args.lr, unit=args.unit, preset=args.preset,
            reduced=args.reduced, profile_top_k=args.profile_top_k,
            profile_budget_s=args.profile_budget_s,
            moe_mode=args.moe_mode, moe_stats=args.moe_stats,
            topology=topo, global_batch=box.get("gb"))
        ctl.attach(sess)
        box.setdefault("data", sess.data_size)
        # pin the global batch so the stream (and the loss trajectory)
        # continues unchanged when the data axis shrinks
        box.setdefault("gb", sess.shape_cfg.global_batch)
        if ctl.failures:
            start = (manifest or {}).get("extra", {}).get("step", 0)
            print(f"elastic: restart {ctl.failures}/"
                  f"{ctl.cfg.max_failures} resumed at step {start} on "
                  f"{topo.label()} (data {box['data']}->"
                  f"{sess.data_size}, global_batch {box['gb']})")
        stream = sess.stream()
        if restored is None:
            params = sess.init_params(jax.random.PRNGKey(0))
            opt_state = sess.init_opt_state(params)
        else:
            # relayout the verified checkpoint onto THIS session's mesh
            # and shardings (the restart topology may be smaller)
            params = sess.adopt_params(restored["params"])
            opt_state = jax.tree.map(jnp.asarray, restored["opt"])
            opt_state["step"] = jnp.asarray(opt_state["step"])
        state = {"params": params, "opt": opt_state}

        def run_one(state, step_no):
            batch = stream.batch(step_no)
            grads, metrics = sess.train_step(state["params"], batch)
            params, opt, om = sess.opt_step(state["params"], grads,
                                            state["opt"])
            loss = float(metrics["loss_sum"])
            extra = ""
            if "moe_load" in metrics:
                import numpy as np
                load = np.asarray(metrics["moe_load"]).sum(axis=0)
                imb = float(load.max()) / max(float(load.mean()), 1e-9)
                extra = (f" moe_imb {imb:.2f} "
                         f"dropped {int(metrics['moe_dropped'])}")
            print(f"step {step_no:4d} loss {loss:.4f} "
                  f"gnorm {float(om['grad_norm']):.3f}{extra}")
            return {"params": params, "opt": opt}, {
                "loss": loss,
                "straggler_flags": ctl.watchdog.flags,
                "failures": ctl.failures,
            }

        return state, run_one, lambda s: s

    state, history = ctl.run(build, args.steps,
                             inject_failure_at=args.inject_failure_at)
    losses = [m["loss"] for _, m in history]
    ft = ctl.summary()
    tail = (f"straggler_flags={ft['straggler_flags']} "
            f"failures={ft['failures']} "
            f"resume_steps={ft['resume_steps']}")
    if losses:
        print(f"DONE first_loss={losses[0]:.4f} "
              f"last_loss={losses[-1]:.4f} steps={len(history)} {tail}")
    else:
        # a checkpoint at/past --steps resumes to a zero-step run
        print(f"DONE resumed-at-target (checkpoint >= --steps "
              f"{args.steps}) {tail}")


if __name__ == "__main__":
    main()
