import os

from repro.api import ensure_host_devices, get_arch, session

ensure_host_devices(int(os.environ.get("DRYRUN_DEVICES", "512")),
                    force=True)

"""Multi-pod dry-run: lower + compile every (architecture × input shape ×
mesh), print the compiled memory/cost analyses, scrape the collective
schedule, and emit the roofline terms.

Must be run as its own process (the fake host devices — 512, or
DRYRUN_DEVICES — are forced before any other JAX use above; do NOT
import this module from tests/benchmarks).

Usage:
  PYTHONPATH=src:. python -m repro.launch.dryrun --arch llama3.2-1b \
      --shape train_4k [--multi-pod] [--out results/]
  PYTHONPATH=src:. python -m repro.launch.dryrun --all [--multi-pod]

Budgeted CI cell (8 fake CPU devices, reduced smoke config, compile-time
budget enforced):
  DRYRUN_DEVICES=8 PYTHONPATH=src:. python -m repro.launch.dryrun \
      --reduced --arch llama3.2-1b --schedule auto --budget-s 600
"""

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

from repro.models.common import SHAPES  # noqa: E402

ARCHS = [
    "whisper-large-v3", "qwen2-moe-a2.7b", "deepseek-v3-671b",
    "jamba-v0.1-52b", "phi-3-vision-4.2b", "minitron-4b", "yi-9b",
    "phi4-mini-3.8b", "llama3.2-1b", "xlstm-1.3b",
]

# long_500k needs sub-quadratic attention: only the SSM/hybrid archs run it
# (brief: skip for pure full-attention archs; noted in DESIGN.md §5).
LONG_OK = {"jamba-v0.1-52b", "xlstm-1.3b"}

_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?(?:\.\d+)?\s*=\s*\(?([a-z0-9]+)\[([0-9,]*)\]"
)
_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8}


def scrape_collectives(hlo_text: str) -> dict:
    """Count collective instructions + static operand bytes in the HLO.

    Ops inside while-loop bodies appear once (the analytic model in
    benchmarks/roofline.py accounts for trip counts); this scrape is the
    structural fingerprint: which collectives exist, with what shapes.
    """
    out: dict = {}
    for m in _COLL_RE.finditer(hlo_text):
        op, dt, dims = m.group(1), m.group(2), m.group(3)
        nbytes = _DTYPE_BYTES.get(dt, 4)
        for tok in dims.split(","):
            if tok:
                nbytes *= int(tok)
        rec = out.setdefault(op, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += nbytes
    return out


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str | None):
    import benchmarks.roofline as RL

    shape_cfg = SHAPES[shape]
    overrides = {}
    if multi_pod and shape_cfg.kind == "train":
        # pods split the global batch: half the micro-batches per pipeline
        rc0 = get_arch(arch).production_run(shape)
        per_dp = max(shape_cfg.global_batch // (2 * 16), 1)
        overrides = dict(
            microbatches=max(per_dp // rc0.groups, 1),
            unit=min(rc0.unit or 10**9, max(per_dp // rc0.groups, 1)))
    t0 = time.time()
    sess = session(arch, mode="dry-run", shape=shape, reduced=False,
                   topology="tpu_pod_x2" if multi_pod else "tpu_pod",
                   overrides=overrides)
    lowered = sess.lower()
    t_lower = time.time() - t0
    rt = sess.rt  # roofline analysis reads the runtime's static tables

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # older JAX: one dict per device
        cost = cost[0] if cost else {}
    print(f"--- memory_analysis [{arch} × {shape} "
          f"{'multi-pod' if multi_pod else 'single-pod'}] ---")
    print(mem)
    print("--- cost_analysis (flops/bytes; while-bodies counted once) ---")
    print({k: v for k, v in sorted(cost.items())
           if isinstance(v, (int, float)) and v})

    hlo = compiled.as_text()
    colls = scrape_collectives(hlo)
    print("--- collective schedule (instructions in compiled HLO) ---")
    for op, rec in sorted(colls.items()):
        print(f"  {op:20s} n={rec['count']:4d} bytes={rec['bytes']:.3e}")

    roof = RL.analyze_cell(rt, shape_cfg)
    print("--- roofline (analytic, per device per step) ---")
    print(f"  compute    {roof.compute_s:10.4f} s")
    print(f"  memory     {roof.memory_s:10.4f} s")
    print(f"  collective {roof.collective_s:10.4f} s")
    print(f"  bottleneck {roof.bottleneck}")
    print(f"  MODEL_FLOPS/HLO_FLOPS {roof.useful_ratio:.3f}")

    rec = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory_analysis": {
            "bytes_per_device": getattr(
                mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "repr": str(mem)[:2000],
        },
        "cost_analysis": {k: v for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "collectives": colls,
        "roofline": {
            "compute_s": roof.compute_s, "memory_s": roof.memory_s,
            "collective_s": roof.collective_s,
            "flops": roof.flops, "hbm_bytes": roof.hbm_bytes,
            "coll_bytes": roof.coll_bytes,
            "model_flops": roof.model_flops,
            "useful_ratio": roof.useful_ratio,
            "bottleneck": roof.bottleneck,
        },
        "status": "ok",
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}_{shape}_{'mp' if multi_pod else 'sp'}"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    print(f"CELL_OK {arch} {shape} lower={t_lower:.1f}s "
          f"compile={t_compile:.1f}s")
    return rec


def run_reduced_cell(arch: str, schedule: str | None, budget_s: float,
                     out_dir: str | None):
    """Budgeted smoke dry-run: reduced() config through the facade on the
    forced (small) device count — lower + compile the train step, print
    the compiled analyses, enforce a wall-clock budget. This is the CI
    cell ROADMAP asked for once compile times were budgeted."""
    import jax

    t_start = time.time()
    overrides = dict(microbatches=4, unit=2)
    if schedule:
        overrides["schedule"] = schedule
    sess = session(arch, mode="dry-run", seq_len=32, overrides=overrides)
    d = sess.describe()
    print(f"plan: {d['schedule']['name']} "
          f"(preset={d['schedule']['preset']}, "
          f"bubble={d['schedule']['bubble_ratio']:.3f}, "
          f"makespan={d['schedule']['makespan']:.3e}, "
          f"stash_depth={d['schedule']['stash_depth']}, "
          f"rs_saved={d['schedule']['rs_overlap']['saved_s']:.2e}s)")
    if "auto" in d["schedule"]:
        print("auto candidates (makespan / peak_mem / stash depth):")
        for n, c in d["schedule"]["auto"]["candidates"].items():
            if isinstance(c, dict):
                print(f"  {n:14s} {c['makespan']:.3e}  "
                      f"mem={c['peak_mem']:.2e}  U={c['stash_depth']}")
            else:
                print(f"  {n:14s} {c}")

    t0 = time.time()
    lowered = sess.lower()
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    print(f"--- memory_analysis [{arch} reduced, "
          f"{jax.device_count()} fake devices] ---")
    print(mem)
    colls = scrape_collectives(compiled.as_text())
    print("--- collective schedule ---")
    for op, rec in sorted(colls.items()):
        print(f"  {op:20s} n={rec['count']:4d} bytes={rec['bytes']:.3e}")
    elapsed = time.time() - t_start
    over_budget = elapsed > budget_s
    rec = {
        "arch": arch, "shape": "reduced",
        "schedule": d["schedule"]["name"],
        "devices": jax.device_count(),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "collectives": colls,
        "status": ("budget_exceeded" if over_budget else "ok"),
        "budget_s": budget_s, "elapsed_s": round(elapsed, 1),
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"{arch}_reduced.json"), "w") as f:
            json.dump(rec, f, indent=1)
    if over_budget:
        print(f"CELL_FAIL {arch} reduced: {elapsed:.0f}s exceeded the "
              f"{budget_s:.0f}s budget")
        raise SystemExit(1)
    print(f"CELL_OK {arch} reduced lower={t_lower:.1f}s "
          f"compile={t_compile:.1f}s budget={elapsed:.0f}/{budget_s:.0f}s")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--reduced", action="store_true",
                    help="budgeted smoke cell: reduced() config on the "
                         "forced device count (set DRYRUN_DEVICES)")
    ap.add_argument("--schedule", default=None,
                    help="schedule override for --reduced (e.g. auto)")
    ap.add_argument("--budget-s", type=float, default=600.0,
                    help="wall-clock budget for the --reduced cell")
    args = ap.parse_args()

    if args.reduced:
        run_reduced_cell(args.arch or "llama3.2-1b", args.schedule,
                         args.budget_s, args.out)
        return

    cells = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        cells.append((args.arch, args.shape))

    for arch, shape in cells:
        if shape == "long_500k" and arch not in LONG_OK:
            print(f"CELL_SKIP {arch} long_500k (pure full attention; "
                  "DESIGN.md §5)")
            if args.out:
                os.makedirs(args.out, exist_ok=True)
                tag = f"{arch}_{shape}_{'mp' if args.multi_pod else 'sp'}"
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump({"arch": arch, "shape": shape,
                               "status": "skipped_full_attention"}, f)
            continue
        try:
            run_cell(arch, shape, args.multi_pod, args.out)
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            print(f"CELL_FAIL {arch} {shape}: {e}")
            if args.out:
                os.makedirs(args.out, exist_ok=True)
                tag = f"{arch}_{shape}_{'mp' if args.multi_pod else 'sp'}"
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump({"arch": arch, "shape": shape,
                               "status": f"fail: {e}"}, f)


if __name__ == "__main__":
    main()
