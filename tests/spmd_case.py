"""Multi-device SPMD test cases, run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=N (tests must not pollute
the main process's device count).

Usage: python -m tests.spmd_case <case_name> [arch]
Prints "CASE_OK <name>" on success. Tests run the cases through
tests/spmd_run.py.
"""

import os
import sys

N_DEV = os.environ.get("SPMD_DEVICES", "8")
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={N_DEV} "
    + os.environ.get("XLA_FLAGS_EXTRA", "")
)

import dataclasses  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.pipeline import Runtime, make_train_step  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.common import ShapeConfig  # noqa: E402


def _mesh(data, model, pod=None):
    if pod:
        return jax.make_mesh((pod, data, model), ("pod", "data", "model"))
    return jax.make_mesh((data, model), ("data", "model"))


def _batch(cfg, gb, seq, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    if cfg.frontend == "vision":
        toks = (jax.random.normal(k1, (gb, seq, cfg.d_model)) * 0.1
                ).astype(jnp.float32)
    else:
        toks = jax.random.randint(k1, (gb, seq), 0, cfg.vocab)
    batch = {"tokens": toks,
             "labels": jax.random.randint(k2, (gb, seq), 0, cfg.vocab)}
    if cfg.encdec is not None:
        batch["enc_tokens"] = (jax.random.normal(
            k3, (gb, cfg.encdec.enc_ctx, cfg.d_model)) * 0.1
        ).astype(jnp.float32)
    return batch


def _ref_grads(cfg, rc, params_ref, batch):
    def loss_fn(p):
        return M.reference_loss(
            cfg, rc, p, batch["tokens"], batch["labels"],
            enc_tokens=batch.get("enc_tokens"))
    return jax.value_and_grad(loss_fn)(params_ref)


def _pipeline_params_from_ref(rt, ref_params):
    """Re-layout reference params into the runtime's duplicated stacking."""
    segs = {}
    for seg in rt.geo.segments:
        st = ref_params["segments"][seg.name]
        V, Pe, G = seg.vpp, rt.Pe, rt.G
        order = []
        for mr in range(G * Pe):
            p = mr % Pe
            for v in range(V):
                order.append(M.storage_index(p, v, V))
        segs[seg.name] = {n: jnp.stack([a[i] for i in order])
                          for n, a in st.items()}
    return {"io": ref_params["io"], "segments": segs}


def _grads_back_to_ref(rt, grads):
    """Undo the duplicated stacking (take group 0's copy)."""
    segs = {}
    for seg in rt.geo.segments:
        V, Pe = seg.vpp, rt.Pe
        g = grads["segments"][seg.name]
        out = {}
        for n, a in g.items():
            rows = []
            for s in range(Pe * V):
                p, v = s % Pe, s // Pe
                # group 0's stacked row for (p, v):
                rows.append(a[p * V + v])
            # reorder into storage order (p-major) used by reference
            reord = [None] * (Pe * V)
            for s in range(Pe * V):
                p, v = s % Pe, s // Pe
                reord[M.storage_index(p, v, V)] = rows[s]
            out[n] = jnp.stack(reord)
        segs[seg.name] = out
    return {"io": grads["io"], "segments": segs}


def case_train_equiv(arch: str, schedule="zeropp", data=None, model=None,
                     pod=None, moe_mode=None):
    mod = M.get_arch(arch)
    cfg, rc = mod.reduced()
    n_dev = int(N_DEV)
    rc = dataclasses.replace(
        rc, schedule=schedule, microbatches=4, unit=2,
        **({"moe_mode": moe_mode} if moe_mode else {}))
    geo = M.build_geometry(cfg, rc)
    model = model or geo.model_ranks
    data = data or max(1, n_dev // ((pod or 1) * model))
    assert (pod or 1) * data * model <= n_dev
    assert geo.model_ranks == model, (geo.model_ranks, model)
    mesh = _mesh(data, model, pod)
    rt = Runtime(cfg, rc, mesh, multi_pod=pod is not None)

    gb = (pod or 1) * data * rc.groups * rc.microbatches
    seq = 16
    batch = _batch(cfg, gb, seq)
    ref_params = M.init_all_params(cfg, rc, jax.random.PRNGKey(0))
    loss_ref, gref = _ref_grads(cfg, rc, ref_params, batch)

    from jax.sharding import NamedSharding
    pparams = _pipeline_params_from_ref(rt, ref_params)
    pparams = jax.tree.map(
        lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec)),
        pparams,
        {"io": rt.pspecs["io"], "segments": rt.pspecs["segments"]})

    shape_cfg = ShapeConfig("toy", seq, gb, "train")
    step = make_train_step(rt, shape_cfg)
    grads, metrics = step(pparams, batch)
    # loss_and_dy returns per-microbatch losses already divided by the
    # global token count, so loss_sum is the mean xent.
    loss_pipe = float(metrics["loss_sum"])
    # compare the xent part of the loss
    ref_xent = float(loss_ref)
    if cfg.moe is not None:
        # recompute reference aux to subtract
        logits, aux = M.reference_logits(
            cfg, rc, ref_params, batch["tokens"],
            enc_tokens=batch.get("enc_tokens"))
        ref_xent = ref_xent - cfg.moe.router_aux_weight * float(aux)
    assert abs(loss_pipe - ref_xent) < 5e-3 * max(1.0, abs(ref_xent)), (
        loss_pipe, ref_xent)

    gpipe = _grads_back_to_ref(rt, jax.device_get(grads))
    flat_r = jax.tree_util.tree_flatten_with_path(gref)[0]
    flat_p = dict(jax.tree_util.tree_flatten_with_path(gpipe)[0])
    n_checked = 0
    worst = (0.0, None)
    worst_router = (0.0, None)
    for kp, vr in flat_r:
        vp = flat_p[kp]
        vr = np.asarray(vr, np.float32)
        vp = np.asarray(vp, np.float32)
        assert vr.shape == vp.shape, (kp, vr.shape, vp.shape)
        denom = np.maximum(np.abs(vr).max(), 1e-6)
        err = np.abs(vr - vp).max() / denom
        # MoE routers: the Switch aux loss is a *product of batch means*,
        # so per-microbatch aux (pipeline) differs from full-batch aux
        # (reference) by O(1/B) — expected, weight 0.01, router-only.
        if "router" in jax.tree_util.keystr(kp):
            if err > worst_router[0]:
                worst_router = (err, jax.tree_util.keystr(kp))
            n_checked += 1
            continue
        if err > worst[0]:
            worst = (err, jax.tree_util.keystr(kp))
        n_checked += 1
    assert worst[0] < 3e-2, f"grad mismatch {worst}"
    assert worst_router[0] < 8e-2, f"router mismatch {worst_router}"
    print(f"  checked {n_checked} tensors, worst rel err "
          f"{worst[0]:.2e} at {worst[1]}")
    print(f"CASE_OK train_equiv {arch} {schedule}")


def case_loss_decreases(arch: str):
    """Few pipeline SGD steps must reduce the loss."""
    mod = M.get_arch(arch)
    cfg, rc = mod.reduced()
    rc = dataclasses.replace(rc, microbatches=4, unit=4)
    geo = M.build_geometry(cfg, rc)
    mesh = _mesh(2, geo.model_ranks)
    rt = Runtime(cfg, rc, mesh)
    gb = 2 * rc.groups * rc.microbatches
    seq = 16
    batch = _batch(cfg, gb, seq)
    params = rt.init_params(jax.random.PRNGKey(0))
    shape_cfg = ShapeConfig("toy", seq, gb, "train")
    step = make_train_step(rt, shape_cfg)
    losses = []
    lr = 0.1 if not (cfg.mamba or cfg.xlstm) else 0.03
    for i in range(6):
        grads, metrics = step(params, batch)
        losses.append(float(metrics["loss_sum"]))
        params = jax.tree.map(
            lambda p, g: (p - lr * g.astype(p.dtype)).astype(p.dtype),
            params, grads)
    assert losses[-1] < losses[0], losses
    print(f"  losses: {[round(l, 3) for l in losses]}")
    print(f"CASE_OK loss_decreases {arch}")


CASES = {
    "train_equiv": case_train_equiv,
    "loss_decreases": case_loss_decreases,
}



def case_serve_decode(arch: str):
    """Prefill + greedy decode through the pipeline must match the
    reference model's greedy continuation."""
    from repro.core.pipeline import make_serve_step, init_serve_caches
    mod = M.get_arch(arch)
    cfg, rc = mod.reduced()
    rc = dataclasses.replace(rc, microbatches=2)
    geo = M.build_geometry(cfg, rc)
    n_dev = int(N_DEV)
    model = geo.model_ranks
    data = max(1, n_dev // model)
    mesh = _mesh(data, model)
    rt = Runtime(cfg, rc, mesh)
    gb = data * rc.groups * rc.microbatches
    prompt, gen, max_seq = 8, 4, 32
    shape_cfg = ShapeConfig("toy", max_seq, gb, "decode")

    ref_params = M.init_all_params(cfg, rc, jax.random.PRNGKey(0))
    batch0 = _batch(cfg, gb, prompt)
    toks = batch0["tokens"]
    enc = batch0.get("enc_tokens")

    # reference greedy continuation (re-run full forward each step)
    ref_seq = toks
    for i in range(gen + 1):
        logits, _ = M.reference_logits(cfg, rc, ref_params, ref_seq,
                                       enc_tokens=enc)
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), -1)
        ref_seq = jnp.concatenate([ref_seq, nxt[:, None].astype(jnp.int32)],
                                  axis=1)
    ref_gen = np.asarray(ref_seq[:, prompt:])

    from jax.sharding import NamedSharding
    pparams = _pipeline_params_from_ref(rt, ref_params)
    pparams = jax.tree.map(
        lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec)),
        pparams, {"io": rt.pspecs["io"], "segments": rt.pspecs["segments"]})
    caches = jax.tree.map(
        lambda s: jax.device_put(jnp.zeros(s.shape, s.dtype), s.sharding),
        init_serve_caches(rt, shape_cfg, max_seq=max_seq),
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    if cfg.encdec is not None:
        # precompute encoder memory with the reference encoder
        geo2 = M.build_geometry(cfg, rc)
        mem = enc
        seg_e = geo2.segments[0]
        from repro.core.tape import Tape
        stacked = ref_params["segments"]["enc"]
        from repro.models import blocks as B
        x = jnp.asarray(enc, jnp.float32)
        for s_ in range(geo2.seg_stages(seg_e)):
            p_, v_ = s_ % geo2.pp, s_ // geo2.pp
            idx = M.storage_index(p_, v_, seg_e.vpp)
            sp_ = {n: a[idx] for n, a in stacked.items()}
            t = Tape(sp_, mode="fwd")
            rope, _ = M.make_rope_ctx(cfg, rc, x.shape[1])
            ctx = B.LayerCtx(cfg=cfg, rc=rc, rope=rope, causal=False)
            xv, _ = M.apply_stage(t, ctx, seg_e, t.value(x), s_)
            x = xv.val
        caches["enc_memory"] = jax.device_put(
            x.astype(jnp.dtype(rc.compute_dtype)),
            NamedSharding(mesh, jax.tree.leaves(
                __import__("repro.core.pipeline", fromlist=["x"]
                           ).serve_cache_pspecs(rt, shape_cfg)[0][
                               "enc_memory"])[0]
                if False else NamedSharding(mesh, jax.sharding.PartitionSpec())))

    prefill = make_serve_step(rt, shape_cfg, prompt_len=prompt,
                              max_seq=max_seq)
    tok, caches = prefill(pparams, caches, {"tokens": toks,
                                            "pos": jnp.int32(0)})
    got = [np.asarray(tok)]
    decode = make_serve_step(rt, shape_cfg, prompt_len=1, max_seq=max_seq)
    cur = tok[:, None]
    for i in range(gen):
        cur, caches = decode(pparams, caches,
                             {"tokens": cur, "pos": jnp.int32(prompt + i)})
        cur = cur[:, None]
        got.append(np.asarray(cur[:, 0]))
    got = np.stack(got, axis=1)
    match = (got == ref_gen).mean()
    assert match > 0.9, (match, got[:2], ref_gen[:2])
    print(f"  greedy continuation agreement: {match:.2%}")
    print(f"CASE_OK serve_decode {arch}")


CASES["serve_decode"] = case_serve_decode




def case_hlo_gather_count(arch: str = "llama3.2-1b"):
    """Structural claim (§3.3): the lowered FS-ZeroPP step contains ONE
    conditional all-gather site per gatherable stage param executed
    (2V-1)·units times, vs FS-1F1B-style per-microbatch gathering — we
    verify the executor's gather events match #AllGather = B·L·(2V-1)/(U·P·V)
    and that the compiled HLO contains the gather/reduce collectives."""
    import re
    from repro.core.pipeline import Runtime, make_train_step
    mod = M.get_arch(arch)
    cfg, rc = mod.reduced()
    rc = dataclasses.replace(rc, microbatches=8, unit=4)
    geo = M.build_geometry(cfg, rc)
    mesh = _mesh(2, geo.model_ranks)
    rt = Runtime(cfg, rc, mesh)
    pt = rt.tables["main"]
    V, U, B = rc.vpp, rc.unit_size, rc.microbatches
    n_units = B // U
    per_rank = (pt.gather_v >= 0).sum() / pt.Pe
    assert per_rank == (2 * V - 1) * n_units, (per_rank, V, n_units)
    # paper formula in layer-gathers (k layers per stage):
    k = geo.segments[0].k
    L = geo.padded_layers(geo.segments[0])
    expect = B * L * (2 * V - 1) / (U * rc.pp * V)
    assert per_rank * k == expect, (per_rank, k, expect)

    gb = 2 * rc.groups * rc.microbatches
    shape_cfg = ShapeConfig("toy", 16, gb, "train")
    step = make_train_step(rt, shape_cfg)
    params = rt.param_shapes()
    batch = {
        "tokens": jax.ShapeDtypeStruct((gb, 16), jnp.int32),
        "labels": jax.ShapeDtypeStruct((gb, 16), jnp.int32),
    }
    txt = step.lower(params, batch).compile().as_text()
    ops = set(re.findall(
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)", txt))
    assert "all-gather" in ops, ops          # FSDP param gathers
    assert "collective-permute" in ops, ops  # pipeline wires
    assert ("reduce-scatter" in ops) or ("all-reduce" in ops), ops
    print(f"  gathers/rank={per_rank:.0f} (= (2V-1)·units), HLO ops: "
          f"{sorted(ops)}")
    print(f"CASE_OK hlo_gather_count {arch}")


CASES["hlo_gather_count"] = case_hlo_gather_count




def case_prefetch_equiv(arch: str = "llama3.2-1b"):
    """gather_prefetch must not change numerics, only HLO issue order."""
    case_train_equiv_with(arch, {"gather_prefetch": 2})
    print(f"CASE_OK prefetch_equiv {arch}")


def case_int8_grads(arch: str = "llama3.2-1b"):
    """int8 reduce-scatter with shared-scale summation: grads within 2%
    of fp32, and still optimizes."""
    from repro.core import fsdp as F
    from repro.models.common import ParamSpec
    from jax.sharding import PartitionSpec as P
    mesh = _mesh(4, 2)
    spec = ParamSpec((32, 16), fsdp_dim=0)
    g = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 16)) * 0.1

    def body(gl):
        full = F.reduce_scatter_grad(gl[0], spec, 4, False)
        err0 = jnp.zeros_like(gl[0])
        q, err = F.reduce_scatter_grad_int8(gl[0], err0, spec, 4, False)
        return full, q, err

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(P(None, "data"),),
                      out_specs=(P("data"), P("data"), P(None, "data")),
                      check_vma=False)
    # feed each data rank a *different* gradient contribution
    gs = g.transpose(1, 0, 2).reshape(1, 32, 4 * 16)[..., :16 * 4]
    full, q, err = jax.jit(f)(g.sum(0)[None].repeat(4, 0).reshape(
        1, 32 * 4, 16)[:, :32] if False else g.reshape(1, 4 * 32, 16)[:, :32])
    # simpler: single shared grad; int8 must match fp32 closely
    rel = float(jnp.abs(q - full).max() / jnp.abs(full).max())
    assert rel < 0.02, rel
    assert float(jnp.abs(err).max()) < 0.01  # error feedback bounded
    print(f"  int8 vs fp32 rel err {rel:.4f}")
    print(f"CASE_OK int8_grads {arch}")


def case_train_equiv_with(arch, extra_rc):
    """train_equiv with extra RunConfig overrides."""
    mod = M.get_arch(arch)
    cfg, rc = mod.reduced()
    rc = dataclasses.replace(rc, schedule="zeropp", microbatches=4,
                             unit=2, **extra_rc)
    geo = M.build_geometry(cfg, rc)
    model = geo.model_ranks
    data = max(1, int(N_DEV) // model)
    mesh = _mesh(data, model)
    rt = Runtime(cfg, rc, mesh)
    gb = data * rc.groups * rc.microbatches
    seq = 16
    batch = _batch(cfg, gb, seq)
    ref_params = M.init_all_params(cfg, rc, jax.random.PRNGKey(0))
    loss_ref, gref = _ref_grads(cfg, rc, ref_params, batch)
    from jax.sharding import NamedSharding
    pparams = _pipeline_params_from_ref(rt, ref_params)
    pparams = jax.tree.map(
        lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec)),
        pparams, {"io": rt.pspecs["io"], "segments": rt.pspecs["segments"]})
    step = make_train_step(rt, ShapeConfig("toy", seq, gb, "train"))
    grads, metrics = step(pparams, batch)
    gpipe = _grads_back_to_ref(rt, jax.device_get(grads))
    flat_r = jax.tree_util.tree_flatten_with_path(gref)[0]
    flat_p = dict(jax.tree_util.tree_flatten_with_path(gpipe)[0])
    worst = 0.0
    for kp, vr in flat_r:
        vp = flat_p[kp]
        vr = np.asarray(vr, np.float32)
        vp = np.asarray(vp, np.float32)
        worst = max(worst, float(
            np.abs(vr - vp).max() / max(np.abs(vr).max(), 1e-6)))
    assert worst < 3e-2, worst
    print(f"  worst rel err {worst:.2e}")


def case_elastic_reshard(arch: str = "llama3.2-1b"):
    """Checkpoint at D=4, restore + continue at D=2 (elastic re-mesh)."""
    import tempfile
    from repro.ckpt.checkpoint import CheckpointManager
    from jax.sharding import NamedSharding
    mod = M.get_arch(arch)
    cfg, rc = mod.reduced()
    rc = dataclasses.replace(rc, microbatches=4, unit=4)
    geo = M.build_geometry(cfg, rc)
    seq = 16

    def run(data, params_in=None, steps=2, seed=0):
        mesh = _mesh(data, geo.model_ranks)
        rt = Runtime(cfg, rc, mesh)
        gb = data * rc.groups * rc.microbatches
        step = make_train_step(rt, ShapeConfig("t", seq, gb, "train"))
        params = params_in if params_in is not None else rt.init_params(
            jax.random.PRNGKey(seed))
        shardings = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec),
            {"io": rt.pspecs["io"], "segments": rt.pspecs["segments"]})
        params = jax.tree.map(lambda a, sh: jax.device_put(
            jnp.asarray(a), sh), params, shardings)
        losses = []
        for s_ in range(steps):
            batch = _batch(cfg, gb, seq, seed=s_)
            grads, metrics = step(params, batch)
            losses.append(float(metrics["loss_sum"]))
            params = jax.tree.map(
                lambda p, g: (p - 0.1 * g.astype(p.dtype)).astype(p.dtype),
                params, grads)
        return params, losses, shardings

    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        params4, losses4, _ = run(4, steps=2)
        saved = jax.device_get(params4)
        mgr.save(2, saved)
        tree, manifest = mgr.restore(2)
        # restore must be exact: the round-trip is the deterministic
        # invariant (the loss comparison below has run-to-run XLA noise)
        flat_s = jax.tree_util.tree_flatten_with_path(saved)[0]
        flat_r = dict(jax.tree_util.tree_flatten_with_path(tree)[0])
        for kp, vs in flat_s:
            assert np.array_equal(np.asarray(vs), np.asarray(flat_r[kp])), (
                f"restore mismatch at {jax.tree_util.keystr(kp)}")
        # resume on HALF the data axis (elastic shrink). The D=2 batch
        # is a different draw (gb halves), so 2 SGD steps of D=4
        # progress give no reliable loss-direction signal on it — the
        # robust invariants are: the restored params are actually used
        # (first-step loss deterministically differs from a fresh
        # PRNGKey(0) init on the same batch/mesh/program) and training
        # continues finitely from them.
        _, losses_fresh, _ = run(2, steps=1)
        params2, losses2, _ = run(2, params_in=tree, steps=2)
        assert losses2[0] != losses_fresh[0], (
            "resume ignored the restored params", losses_fresh, losses2)
        assert all(np.isfinite(l) for l in losses2), losses2
        assert abs(losses2[0] - losses_fresh[0]) < 1.0, (
            "resumed loss implausibly far from the trained state",
            losses_fresh, losses2)
    print(f"  D=4 losses {losses4} -> D=2 fresh {losses_fresh[0]:.4f} "
          f"vs resume losses {losses2}")
    print(f"CASE_OK elastic_reshard {arch}")


def case_api_parity(arch: str = "llama3.2-1b"):
    """repro.api.session must reproduce the hand-assembled path exactly:
    same params from the same key, allclose grads and metrics."""
    from repro.api import session

    mod = M.get_arch(arch)
    cfg, rc = mod.reduced()
    rc = dataclasses.replace(rc, microbatches=4, unit=2)
    geo = M.build_geometry(cfg, rc)
    model = geo.model_ranks
    data = max(1, int(N_DEV) // model)
    mesh = _mesh(data, model)
    rt = Runtime(cfg, rc, mesh)
    gb = data * rc.groups * rc.microbatches
    seq = 16
    batch = _batch(cfg, gb, seq)

    # hand-assembled path (the old 8-step ritual)
    params_h = rt.init_params(jax.random.PRNGKey(0))
    step = make_train_step(rt, ShapeConfig("toy", seq, gb, "train"))
    g_h, m_h = step(params_h, batch)

    # facade path
    sess = session(arch, overrides=dict(microbatches=4, unit=2),
                   data=data, seq_len=seq)
    assert sess.shape_cfg.global_batch == gb, (
        sess.shape_cfg.global_batch, gb)
    params_f = sess.init_params(jax.random.PRNGKey(0))
    g_f, m_f = sess.train_step(params_f, batch)

    for kp, vh in jax.tree_util.tree_flatten_with_path(params_h)[0]:
        vf = dict(jax.tree_util.tree_flatten_with_path(params_f)[0])[kp]
        assert np.array_equal(np.asarray(vh), np.asarray(vf)), (
            f"param mismatch at {jax.tree_util.keystr(kp)}")
    worst = (0.0, None)
    flat_f = dict(jax.tree_util.tree_flatten_with_path(g_f)[0])
    n = 0
    for kp, vh in jax.tree_util.tree_flatten_with_path(g_h)[0]:
        vh = np.asarray(vh, np.float32)
        vf = np.asarray(flat_f[kp], np.float32)
        assert vh.shape == vf.shape, (kp, vh.shape, vf.shape)
        err = np.abs(vh - vf).max() / max(np.abs(vh).max(), 1e-6)
        if err > worst[0]:
            worst = (err, jax.tree_util.keystr(kp))
        n += 1
    assert worst[0] < 1e-5, f"grad mismatch {worst}"
    assert np.allclose(float(m_h["loss_sum"]), float(m_f["loss_sum"]),
                       rtol=1e-6), (m_h, m_f)
    print(f"  {n} grad tensors allclose (worst rel err {worst[0]:.2e}); "
          f"loss {float(m_f['loss_sum']):.5f}")
    print(f"CASE_OK api_parity {arch}")


def case_auto_schedule(arch: str = "llama3.2-1b"):
    """schedule="auto" end-to-end: the session must pick the plan with
    the minimum simulated makespan among every registered schedule, then
    train AND serve with it on the fake-device mesh."""
    from repro.api import session
    from repro.core.plan import PlanAnalysis

    mod = M.get_arch(arch)
    cfg, rc0 = mod.reduced()
    geo = M.build_geometry(cfg, dataclasses.replace(rc0, microbatches=4,
                                                    unit=2))
    data = max(1, int(N_DEV) // geo.model_ranks)

    sess = session(arch, schedule="auto", data=data, seq_len=16,
                   overrides=dict(microbatches=4, unit=2))
    sel = sess.plan_selection
    assert sel is not None
    span = {n: a.makespan for n, a in sel.candidates.items()
            if isinstance(a, PlanAnalysis)}
    assert len(span) >= 5, span  # all builtins (+ autogen) simulated
    for n, m in span.items():
        assert sel.analysis.makespan <= m + 1e-12, (
            f"selected {sel.selected.name} ({sel.analysis.makespan}) "
            f"worse than {n} ({m})")
    assert sess.rc.schedule == sel.selected.name
    d = sess.describe()
    assert d["schedule"]["auto"]["selected"] == sel.selected.name
    assert d["schedule"]["preset"] in ("a800", "tpu_v5e")

    # train: two steps must run and reduce the loss direction-agnostically
    params = sess.init_params(jax.random.PRNGKey(0))
    batch = sess.stream().batch(0)
    grads, metrics = sess.train_step(params, batch)
    loss = float(metrics["loss_sum"])
    assert np.isfinite(loss), loss
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)

    # serve: prefill + decode through an auto-scheduled serve session
    sess_s = session(arch, mode="serve", schedule="auto", data=data,
                     global_batch=data * rc0.groups * 2, max_seq=24,
                     overrides=dict(microbatches=2))
    params_s = sess_s.init_params(jax.random.PRNGKey(0))
    caches = jax.tree.map(
        lambda s: jax.device_put(jnp.zeros(s.shape, s.dtype), s.sharding),
        sess_s.init_caches(abstract=True),
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    gb_s = sess_s.shape_cfg.global_batch
    toks = jax.random.randint(jax.random.PRNGKey(3), (gb_s, 8), 0,
                              cfg.vocab)
    tok, caches = sess_s.serve_prefill(params_s, caches,
                                       {"tokens": toks,
                                        "pos": jnp.int32(0)})
    tok2, caches = sess_s.serve_decode(params_s, caches,
                                       {"tokens": tok[:, None],
                                        "pos": jnp.int32(8)})
    assert tok2.shape == (gb_s,)
    assert (np.asarray(tok2) >= 0).all()
    print(f"  selected={sel.selected.name} "
          f"makespan={sel.analysis.makespan:.3e} "
          f"candidates={sorted(span, key=span.get)} loss={loss:.4f}")
    print(f"CASE_OK auto_schedule {arch}")


def case_serving_engine_equiv(arch: str = "llama3.2-1b"):
    """Continuous-batching correctness bar: engine output for 8 staggered
    requests through 4 slots must be bit-identical to 8 independent
    single-request serve_prefill/serve_decode runs. Slots are reclaimed
    and refilled mid-decode (8 requests > 4 slots, staggered lengths),
    so this also covers reset + reuse."""
    from repro.api import session

    sess = session(arch, mode="serve", data=2, max_slots=4, max_seq=24,
                   overrides=dict(microbatches=2))
    params = sess.init_params(jax.random.PRNGKey(0))
    vocab = sess.cfg.vocab
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, vocab, size=n).astype(np.int32)
               for n in (3, 8, 5, 11, 4, 7, 9, 6)]  # staggered lengths
    gens = [4, 2, 6, 3, 5, 2, 4, 6]

    # reference: each request alone, via the legacy scalar-pos API
    # (prompt broadcast to every row; row 0 is the request)
    def ref_run(prompt, max_gen):
        c = sess.init_caches(abstract=False)
        toks = jnp.asarray(np.tile(prompt[None], (sess.max_slots, 1)))
        t, c = sess.serve_prefill(params, c, {"tokens": toks,
                                              "pos": jnp.int32(0)})
        out = [int(np.asarray(t)[0])]
        cur = t[:, None]
        for i in range(max_gen - 1):
            cur, c = sess.serve_decode(
                params, c,
                {"tokens": cur, "pos": jnp.int32(len(prompt) + i)})
            out.append(int(np.asarray(cur)[0]))
            cur = cur[:, None]
        return out

    refs = [ref_run(p, g) for p, g in zip(prompts, gens)]

    eng = sess.serve_engine(params)
    handles = []
    for i, (p, g) in enumerate(zip(prompts, gens)):
        handles.append(eng.submit(p, max_gen=g))
        if i % 3 == 2:
            eng.step()  # stagger admission so reclaim interleaves
    eng.run_until_idle()
    got = [h.result(timeout=5) for h in handles]
    for i, (r, g) in enumerate(zip(refs, got)):
        assert r == g, f"request {i}: engine {g} != sequential {r}"
    st = eng.stats
    assert st.finished_requests == len(prompts)
    assert st.generated_tokens == sum(len(r) for r in refs)
    # 8 requests through 4 slots forces reclaim+refill mid-decode
    assert st.decode_steps < sum(gens), (st.decode_steps, sum(gens))
    print(f"  8 staggered requests bit-identical through 4 slots "
          f"({st.decode_steps} decode ticks, occupancy "
          f"{st.occupancy:.2f})")

    # an untileable slot count (6 slots -> 3 rows/shard, tiled 2) must be
    # rejected up front, not silently drop rows
    from repro.api import SessionError
    sess_bad = session(arch, mode="serve", data=2, max_slots=6,
                       max_seq=24, overrides=dict(microbatches=2))
    try:
        sess_bad.serve_engine(params)
    except SessionError as e:
        assert "covering only" in str(e), e
    else:
        raise AssertionError("untileable max_slots=6 was accepted")

    # chunked prefill must not change tokens either
    sess_c = session(arch, mode="serve", data=2, max_slots=4, max_seq=24,
                     prefill_chunk=3, overrides=dict(microbatches=2))
    eng_c = sess_c.serve_engine(params)
    hs = [eng_c.submit(p, max_gen=g) for p, g in zip(prompts, gens)]
    eng_c.run_until_idle()
    for i, (r, h) in enumerate(zip(refs, hs)):
        assert h.result(timeout=5) == r, f"chunked prefill diverged at {i}"
    assert eng_c.stats.prefill_steps > len(prompts)  # actually chunked
    print(f"  prefill_chunk=3 identical "
          f"({eng_c.stats.prefill_steps} prefill steps)")
    print(f"CASE_OK serving_engine_equiv {arch}")


CASES["serving_engine_equiv"] = case_serving_engine_equiv


def case_serving_paged_equiv(arch: str = "llama3.2-1b"):
    """Paged-KV correctness bar: the paged engine (radix sharing on) is
    token-identical to the contiguous engine on a staggered 8-request
    greedy workload, while a shared-system-prompt workload prefills
    fewer tokens than requests×prompt_len (radix hits) and never holds
    pages beyond the contiguous n_slots×max_seq footprint."""
    from repro.api import session

    sess = session(arch, mode="serve", data=2, max_slots=4, max_seq=24,
                   overrides=dict(microbatches=2))
    params = sess.init_params(jax.random.PRNGKey(0))
    vocab = sess.cfg.vocab
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, vocab, size=n).astype(np.int32)
               for n in (3, 8, 5, 11, 4, 7, 9, 6)]  # staggered lengths
    gens = [4, 2, 6, 3, 5, 2, 4, 6]

    def run(s, ps):
        eng = s.serve_engine(ps)
        handles = []
        for i, (p, g) in enumerate(zip(prompts, gens)):
            handles.append(eng.submit(p, max_gen=g))
            if i % 3 == 2:
                eng.step()  # stagger admission so reclaim interleaves
        eng.run_until_idle()
        return [h.result(timeout=5) for h in handles], eng.stats

    refs, _ = run(sess, params)

    sess_p = session(arch, mode="serve", data=2, max_slots=4, max_seq=24,
                     page_size=4, overrides=dict(microbatches=2))
    got, st = run(sess_p, params)
    for i, (r, g) in enumerate(zip(refs, got)):
        assert r == g, f"request {i}: paged {g} != contiguous {r}"
    # the page arena never exceeds the contiguous per-slot footprint
    ppr = 24 // 4
    assert st.peak_pages_in_use < sess.max_slots * ppr, st
    print(f"  8 staggered requests token-identical paged vs contiguous "
          f"(peak pages {st.peak_pages_in_use} < {sess.max_slots * ppr})")

    # shared system prompt: later requests resume prefill mid-prompt
    sys_prompt = rng.randint(0, vocab, size=12).astype(np.int32)
    shared = [np.concatenate([sys_prompt,
                              rng.randint(0, vocab, size=3).astype(
                                  np.int32)])
              for _ in range(6)]
    eng = sess_p.serve_engine(params)
    hs = [eng.submit(p, max_gen=3) for p in shared]
    eng.run_until_idle()
    outs = [h.result(timeout=5) for h in hs]
    st = eng.stats
    assert st.prefix_hits > 0, "no radix hits on a shared prompt"
    assert st.prefix_hit_tokens > 0, st
    total = sum(len(p) for p in shared)
    assert st.prefill_tokens < total, (st.prefill_tokens, total)
    # shared-prefix outputs must match a fresh contiguous run too
    eng_c = sess.serve_engine(params)
    hc = [eng_c.submit(p, max_gen=3) for p in shared]
    eng_c.run_until_idle()
    for i, h in enumerate(hc):
        assert h.result(timeout=5) == outs[i], f"shared-prefix req {i}"
    print(f"  shared prompt: {st.prefix_hits} hits, "
          f"{st.prefix_hit_tokens} cached tokens, prefilled "
          f"{st.prefill_tokens}/{total} prompt tokens")
    # no leaked refs: with every request finished, only the radix holds
    # pages — refcount exactly 1 on each live page (a stuck copy-source
    # pin or an unreturned request ref would show up as 2+)
    pp = eng.pool.pool
    live = [g for g in range(pp.n_pages) if pp.refcount(g) > 0]
    assert live, "shared prefix left nothing cached"
    bad = {g: pp.refcount(g) for g in live if pp.refcount(g) != 1}
    assert not bad, f"leaked page references: {bad}"

    # prefix_sharing='off' escape hatch still decodes identically
    sess_o = session(arch, mode="serve", data=2, max_slots=4, max_seq=24,
                     page_size=4, prefix_sharing="off",
                     overrides=dict(microbatches=2))
    eng_o = sess_o.serve_engine(params)
    ho = [eng_o.submit(p, max_gen=3) for p in shared]
    eng_o.run_until_idle()
    for i, h in enumerate(ho):
        assert h.result(timeout=5) == outs[i], f"sharing-off req {i}"
    assert eng_o.stats.prefix_hits == 0
    print("  prefix_sharing='off' identical, zero hits")

    # int8 quantized pages: per-page scales ride beside the pool and the
    # dequantized greedy decode stays token-identical to the contiguous
    # run within the same kernel implementation
    sess_q = session(arch, mode="serve", data=2, max_slots=4, max_seq=24,
                     page_size=4, kv_cache_dtype="int8",
                     overrides=dict(microbatches=2))
    got_q, st_q = run(sess_q, params)
    for i, (r, g) in enumerate(zip(refs, got_q)):
        assert r == g, f"request {i}: int8 paged {g} != contiguous {r}"
    kv = sess_q.init_caches()
    leaves = jax.tree_util.tree_leaves_with_path(kv)
    assert any("_scale" in jax.tree_util.keystr(p) for p, _ in leaves), \
        "int8 cache tree carries no scale leaves"
    assert all(l.dtype == jnp.int8 for p, l in leaves
               if jax.tree_util.keystr(p).endswith(("k']", "v']"))
               and "_scale" not in jax.tree_util.keystr(p)), leaves
    print(f"  kv_cache_dtype='int8' token-identical "
          f"(peak pages {st_q.peak_pages_in_use})")

    # explicit Pallas: the slot-aware paged kernel (interpret mode on
    # CPU) must actually be exercised — no ref.attention fallback — and
    # contiguous-Pallas vs paged-Pallas stay token-identical
    from repro.kernels import ops as kops
    p3, g3 = prompts[:3], [2, 2, 2]

    def run3(s):
        eng3 = s.serve_engine(params)
        hs3 = [eng3.submit(p, max_gen=g) for p, g in zip(p3, g3)]
        eng3.run_until_idle()
        return [h.result(timeout=60) for h in hs3]

    sess_cp = session(arch, mode="serve", data=2, max_slots=4, max_seq=24,
                      overrides=dict(microbatches=2,
                                     kernel_impl="pallas"))
    ref_pal = run3(sess_cp)
    rep = sess_cp.describe()["kernels"]
    assert rep["counters"].get("pallas_slotted", 0) > 0, rep
    assert not rep["fallbacks"], rep
    sess_pp = session(arch, mode="serve", data=2, max_slots=4, max_seq=24,
                      page_size=4,
                      overrides=dict(microbatches=2,
                                     kernel_impl="pallas"))
    got_pal = run3(sess_pp)
    for i, (r, g) in enumerate(zip(ref_pal, got_pal)):
        assert r == g, f"request {i}: pallas paged {g} != contiguous {r}"
    rep = sess_pp.describe()["kernels"]
    assert rep["counters"].get("pallas_paged", 0) > 0, rep
    assert rep["counters"].get("fallback_attention_ref", 0) == 0, rep
    assert kops.kernel_counters().get("pallas_paged", 0) > 0
    print("  kernel_impl='pallas': paged kernel exercised, "
          "token-identical to contiguous Pallas, zero fallbacks")
    print(f"CASE_OK serving_paged_equiv {arch}")


CASES["serving_paged_equiv"] = case_serving_paged_equiv


def case_serve_handoff(arch: str = "llama3.2-1b"):
    """Train→serve handoff: a serve session booted from a train
    checkpoint (Session.restore_params, different data axis) must serve
    the exact tokens of a session holding the trained params directly."""
    import tempfile
    from repro.api import session
    from repro.ckpt.checkpoint import CheckpointManager

    tr = session(arch, data=4, seq_len=16,
                 overrides=dict(microbatches=4, unit=2))
    params = tr.init_params(jax.random.PRNGKey(0))
    opt = tr.init_opt_state(params)
    for i in range(2):
        grads, _ = tr.train_step(params, tr.stream().batch(i))
        params, opt, _ = tr.opt_step(params, grads, opt)

    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, tr.cfg.vocab, size=n).astype(np.int32)
               for n in (5, 8, 3, 6)]

    def serve_tokens(sess, ps):
        eng = sess.serve_engine(ps)
        hs = [eng.submit(p, max_gen=4) for p in prompts]
        eng.run_until_idle()
        return [h.result(timeout=5) for h in hs]

    with tempfile.TemporaryDirectory() as d:
        # the fault-tolerance controller's usual state layout
        CheckpointManager(d).save(
            7, {"params": jax.device_get(params), "opt_step": 7})
        sv = session(arch, mode="serve", data=2, max_slots=4, max_seq=16,
                     overrides=dict(microbatches=2))
        restored = sv.restore_params(d)
        # bit-exact round-trip of every leaf
        flat_a = jax.tree_util.tree_flatten_with_path(
            jax.device_get(params))[0]
        flat_b = dict(jax.tree_util.tree_flatten_with_path(
            jax.device_get(restored))[0])
        for kp, va in flat_a:
            assert np.array_equal(
                np.asarray(va), np.asarray(flat_b[kp])), (
                f"handoff round-trip differs at "
                f"{jax.tree_util.keystr(kp)}")
        # the trained params must differ from a fresh init — otherwise
        # the token comparison below would be vacuous. (Param-level, not
        # token-level: greedy argmax ties flip under cross-process
        # CPU-XLA noise, see the elastic_reshard deflake.)
        flat_fresh = dict(jax.tree_util.tree_flatten_with_path(
            jax.device_get(sv.init_params(jax.random.PRNGKey(0))))[0])
        assert any(
            not np.array_equal(np.asarray(va), np.asarray(flat_fresh[kp]))
            for kp, va in flat_a), "training left params at their init"
        # transplant the trained params directly (no disk) as reference
        sv2 = session(arch, mode="serve", data=2, max_slots=4,
                      max_seq=16, overrides=dict(microbatches=2))
        want = serve_tokens(sv2, jax.tree.map(jnp.asarray,
                                              jax.device_get(params)))
        got = serve_tokens(sv, restored)
        assert got == want, (got, want)
    print(f"  ckpt->serve tokens match direct transplant for "
          f"{len(prompts)} requests")
    print(f"CASE_OK serve_handoff {arch}")


CASES["serve_handoff"] = case_serve_handoff


def _golden_path():
    return os.path.join(os.path.dirname(__file__), "golden",
                        "pipeline_llama3p2_1b.npz")


def _golden_outputs(arch: str = "llama3.2-1b"):
    """Deterministic train grads/metrics + serve tokens for one config."""
    from repro.core.pipeline import make_serve_step, init_serve_caches
    from jax.sharding import NamedSharding

    mod = M.get_arch(arch)
    cfg, rc = mod.reduced()
    rc = dataclasses.replace(rc, schedule="zeropp", microbatches=4, unit=2)
    geo = M.build_geometry(cfg, rc)
    data = max(1, int(N_DEV) // geo.model_ranks)
    mesh = _mesh(data, geo.model_ranks)
    rt = Runtime(cfg, rc, mesh)
    gb = data * rc.groups * rc.microbatches
    seq = 16
    batch = _batch(cfg, gb, seq)
    params = rt.init_params(jax.random.PRNGKey(0))
    step = make_train_step(rt, ShapeConfig("toy", seq, gb, "train"))
    grads, metrics = step(params, batch)

    out = {}
    for kp, v in jax.tree_util.tree_flatten_with_path(
            jax.device_get(grads))[0]:
        out["grad:" + jax.tree_util.keystr(kp)] = np.asarray(v)
    for k, v in jax.device_get(metrics).items():
        out["metric:" + k] = np.asarray(v)

    # serve path: prefill + 2 decode steps on a fresh serve runtime
    rc_s = dataclasses.replace(rc, microbatches=2)
    rt_s = Runtime(cfg, rc_s, mesh)
    gb_s = data * rc_s.groups * rc_s.microbatches
    prompt, max_seq = 8, 16
    shape_s = ShapeConfig("toy", max_seq, gb_s, "decode")
    params_s = rt_s.init_params(jax.random.PRNGKey(0))
    caches = jax.tree.map(
        lambda s: jax.device_put(jnp.zeros(s.shape, s.dtype), s.sharding),
        init_serve_caches(rt_s, shape_s, max_seq=max_seq),
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    toks = jax.random.randint(jax.random.PRNGKey(7), (gb_s, prompt), 0,
                              cfg.vocab)
    prefill = make_serve_step(rt_s, shape_s, prompt_len=prompt,
                              max_seq=max_seq)
    tok, caches = prefill(params_s, caches, {"tokens": toks,
                                             "pos": jnp.int32(0)})
    serve_toks = [np.asarray(tok)]
    decode = make_serve_step(rt_s, shape_s, prompt_len=1, max_seq=max_seq)
    cur = tok[:, None]
    for i in range(2):
        cur, caches = decode(params_s, caches,
                             {"tokens": cur, "pos": jnp.int32(prompt + i)})
        serve_toks.append(np.asarray(cur))
        cur = cur[:, None]
    out["serve:tokens"] = np.stack(serve_toks, 1)
    return out


def case_golden_parity(arch: str = "llama3.2-1b", write=None):
    """The executor must reproduce the recorded seed step outputs
    bit-for-bit (train grads + metrics + served tokens). Regenerate the
    golden file with ``python -m tests.spmd_case golden_parity write=1``
    only when a change is *intended* to alter numerics."""
    path = _golden_path()
    out = _golden_outputs(arch)
    if write:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        np.savez_compressed(path, **out)
        print(f"  wrote {path} ({len(out)} arrays)")
        print(f"CASE_OK golden_parity {arch} (wrote)")
        return
    ref = np.load(path)
    assert sorted(ref.files) == sorted(out), (
        set(ref.files) ^ set(out))
    n_bad = 0
    for k in ref.files:
        if not np.array_equal(ref[k], out[k]):
            n_bad += 1
            err = np.abs(np.asarray(ref[k], np.float64)
                         - np.asarray(out[k], np.float64)).max()
            print(f"  MISMATCH {k}: max abs err {err:.3e}")
    assert n_bad == 0, f"{n_bad}/{len(ref.files)} arrays differ from seed"
    print(f"  {len(ref.files)} arrays bit-for-bit equal to the seed")
    print(f"CASE_OK golden_parity {arch}")


def case_flat_parity(arch: str = "llama3.2-1b"):
    """coalesce="flat" (one all-gather / one reduce-scatter per tick) must
    be BIT-IDENTICAL to the per-tensor path: train grads + metrics and
    served tokens, same params, same batch."""
    from repro.core.pipeline import make_serve_step, init_serve_caches
    mod = M.get_arch(arch)
    cfg, rc0 = mod.reduced()
    rc0 = dataclasses.replace(rc0, microbatches=4, unit=2)
    geo = M.build_geometry(cfg, rc0)
    data = max(1, int(N_DEV) // geo.model_ranks)
    mesh = _mesh(data, geo.model_ranks)
    gb = data * rc0.groups * rc0.microbatches
    seq = 16
    batch = _batch(cfg, gb, seq)

    outs = {}
    for mode in ("flat", "none"):
        rc = dataclasses.replace(rc0, coalesce=mode)
        rt = Runtime(cfg, rc, mesh)
        if mode == "flat":
            fl = rt.flat_layouts["main"]
            assert fl is not None and len(fl.entries) > 1, (
                "flat parity is vacuous: layout empty or single-tensor")
        else:
            assert rt.flat_layouts["main"] is None
        params = rt.init_params(jax.random.PRNGKey(0))
        step = make_train_step(rt, ShapeConfig("toy", seq, gb, "train"))
        grads, metrics = step(params, batch)
        outs[mode] = (jax.device_get(grads), jax.device_get(metrics))

    flat_g = dict(jax.tree_util.tree_flatten_with_path(outs["flat"][0])[0])
    none_g = jax.tree_util.tree_flatten_with_path(outs["none"][0])[0]
    for kp, vn in none_g:
        assert np.array_equal(np.asarray(vn), np.asarray(flat_g[kp])), (
            f"flat grads differ at {jax.tree_util.keystr(kp)}")
    for k in outs["none"][1]:
        assert np.array_equal(np.asarray(outs["none"][1][k]),
                              np.asarray(outs["flat"][1][k])), k
    print(f"  train: {len(none_g)} grad tensors bit-identical")

    # serve: prefill + 2 decode steps under both modes
    toks_out = {}
    for mode in ("flat", "none"):
        rc = dataclasses.replace(rc0, microbatches=2, coalesce=mode)
        rt = Runtime(cfg, rc, mesh)
        gb_s = data * rc.groups * rc.microbatches
        prompt, max_seq = 8, 16
        shape_s = ShapeConfig("toy", max_seq, gb_s, "decode")
        params = rt.init_params(jax.random.PRNGKey(0))
        caches = jax.tree.map(
            lambda s: jax.device_put(jnp.zeros(s.shape, s.dtype),
                                     s.sharding),
            init_serve_caches(rt, shape_s, max_seq=max_seq),
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        toks = jax.random.randint(jax.random.PRNGKey(7), (gb_s, prompt),
                                  0, cfg.vocab)
        prefill = make_serve_step(rt, shape_s, prompt_len=prompt,
                                  max_seq=max_seq)
        tok, caches = prefill(params, caches, {"tokens": toks,
                                               "pos": jnp.int32(0)})
        seqs = [np.asarray(tok)]
        decode = make_serve_step(rt, shape_s, prompt_len=1,
                                 max_seq=max_seq)
        cur = tok[:, None]
        for i in range(2):
            cur, caches = decode(params, caches,
                                 {"tokens": cur,
                                  "pos": jnp.int32(prompt + i)})
            seqs.append(np.asarray(cur))
            cur = cur[:, None]
        toks_out[mode] = np.stack(seqs, 1)
    assert np.array_equal(toks_out["flat"], toks_out["none"]), (
        toks_out["flat"][:2], toks_out["none"][:2])
    print(f"  serve: {toks_out['flat'].shape} tokens bit-identical")
    print(f"CASE_OK flat_parity {arch}")


CASES["flat_parity"] = case_flat_parity


def case_gated_autogen_parity(arch: str = "llama3.2-1b"):
    """ISSUE-5 acceptance: the unit-gated §4 schedule must (a) actually
    claim unit-depth stash buffers (U < n_mb), (b) produce BIT-IDENTICAL
    gradients + metrics to the baseline zeropp schedule on the smoke
    config (unit blocks stay contiguous and per-slot W order is FIFO, so
    every accumulation and reduce-scatter batch is order-identical), and
    (c) simulate strictly below full-depth autogen on peak memory."""
    from repro.core.autogen import autogen
    from repro.core.generators import SchedParams
    from repro.core.simulator import CostModel, simulate

    mod = M.get_arch(arch)
    cfg, rc0 = mod.reduced()
    rc0 = dataclasses.replace(rc0, microbatches=4, unit=2)
    geo = M.build_geometry(cfg, rc0)
    data = max(1, int(N_DEV) // geo.model_ranks)
    mesh = _mesh(data, geo.model_ranks)
    gb = data * rc0.groups * rc0.microbatches
    seq = 16
    batch = _batch(cfg, gb, seq)

    outs = {}
    for sched in ("zeropp", "autogen_gated"):
        rc = dataclasses.replace(rc0, schedule=sched)
        rt = Runtime(cfg, rc, mesh)
        pt = rt.tables["main"]
        assert pt.U == rc0.unit_size, (sched, pt.U)  # unit-depth stash
        params = rt.init_params(jax.random.PRNGKey(0))
        step = make_train_step(rt, ShapeConfig("toy", seq, gb, "train"))
        grads, metrics = step(params, batch)
        outs[sched] = (jax.device_get(grads), jax.device_get(metrics))

    base_g = dict(jax.tree_util.tree_flatten_with_path(
        outs["zeropp"][0])[0])
    gated_g = jax.tree_util.tree_flatten_with_path(
        outs["autogen_gated"][0])[0]
    n_bad = 0
    for kp, vg in gated_g:
        if not np.array_equal(np.asarray(vg), np.asarray(base_g[kp])):
            n_bad += 1
            err = np.abs(np.asarray(vg, np.float64)
                         - np.asarray(base_g[kp], np.float64)).max()
            print(f"  MISMATCH {jax.tree_util.keystr(kp)}: {err:.3e}")
    assert n_bad == 0, f"{n_bad}/{len(gated_g)} grads differ from zeropp"
    for k in outs["zeropp"][1]:
        assert np.array_equal(np.asarray(outs["zeropp"][1][k]),
                              np.asarray(outs["autogen_gated"][1][k])), k
    print(f"  {len(gated_g)} grad tensors bit-identical to zeropp")

    # simulated peak activation memory: gated strictly below full-depth
    sp = SchedParams(P=rc0.pp, V=rc0.vpp, n_mb=rc0.microbatches,
                     unit=rc0.unit)
    cm = CostModel()
    sim_g = simulate(autogen(sp, cm, unit_gated=True).table, cm)
    sim_f = simulate(autogen(
        dataclasses.replace(sp, unit=sp.n_mb), cm).table, cm)
    assert sim_g.peak_mem < sim_f.peak_mem, (sim_g.peak_mem,
                                             sim_f.peak_mem)
    print(f"  simulated peak mem: gated {sim_g.peak_mem:.2f} < "
          f"full-depth {sim_f.peak_mem:.2f}")
    print(f"CASE_OK gated_autogen_parity {arch}")


CASES["gated_autogen_parity"] = case_gated_autogen_parity


def case_flat_int8(arch: str = "llama3.2-1b"):
    """grad_compress="int8" through the FLAT reduce (one int32
    psum_scatter + segment-wide shared scale + error feedback): grads
    must track the fp32 path closely and stay finite."""
    mod = M.get_arch(arch)
    cfg, rc0 = mod.reduced()
    # microbatches=4, unit=2 -> 2 reduce units per slot: the second unit's
    # quantization sees the first's error feedback re-injected.
    rc0 = dataclasses.replace(rc0, microbatches=4, unit=2)
    geo = M.build_geometry(cfg, rc0)
    data = max(1, int(N_DEV) // geo.model_ranks)
    mesh = _mesh(data, geo.model_ranks)
    gb = data * rc0.groups * rc0.microbatches
    seq = 16
    batch = _batch(cfg, gb, seq)

    grads = {}
    for compress, mode in (("none", "flat"), ("int8", "flat"),
                           ("int8", "none")):
        rc = dataclasses.replace(rc0, grad_compress=compress,
                                 coalesce=mode)
        rt = Runtime(cfg, rc, mesh)
        assert (rt.flat_layouts["main"] is not None) == (mode == "flat")
        params = rt.init_params(jax.random.PRNGKey(0))
        step = make_train_step(rt, ShapeConfig("toy", seq, gb, "train"))
        g, m = step(params, batch)
        grads[(compress, mode)] = jax.device_get(g)
        assert np.isfinite(float(m["loss_sum"]))

    flat_f = jax.tree_util.tree_flatten_with_path(
        grads[("none", "flat")])[0]
    gmax = max(np.abs(np.asarray(v, np.float32)).max()
               for _, v in flat_f)
    for key in (("int8", "flat"), ("int8", "none")):
        flat_q = dict(jax.tree_util.tree_flatten_with_path(grads[key])[0])
        worst = (0.0, None)
        for kp, vf in flat_f:
            vq = np.asarray(flat_q[kp], np.float32)
            vf = np.asarray(vf, np.float32)
            assert np.isfinite(vq).all(), kp
            # int8 quantization error is bounded by the shared scale;
            # normalize by the global grad magnitude, not per-tensor.
            err = np.abs(vq - vf).max() / gmax
            if err > worst[0]:
                worst = (err, jax.tree_util.keystr(kp))
        assert worst[0] < 0.02, f"int8 {key[1]} reduce too lossy: {worst}"
        print(f"  int8({key[1]})-vs-fp32 worst err {worst[0]:.2e} "
              f"(of global max |g|={gmax:.2e}) at {worst[1]}")
    print(f"CASE_OK flat_int8 {arch}")


CASES["flat_int8"] = case_flat_int8


def case_flat_fallback(arch: str = "llama3.2-1b"):
    """Mixed divisibility: tensors the flat layout cannot cover
    (non-divisible -> replicated) must fall back to the per-tensor path,
    bit-identically, including an ld != 0 tensor in the flat pack."""
    from repro.core import fsdp as F
    from repro.models.common import ParamSpec
    from jax.sharding import PartitionSpec as P

    D = 4
    mesh = _mesh(D, 2)
    specs = {
        "a": ParamSpec((8, 16), fsdp_dim=0),    # divisible on dim 0
        "b": ParamSpec((16, 12), fsdp_dim=1),   # divisible on dim 1 (ld=1)
        "c": ParamSpec((6, 5), fsdp_dim=0),     # 6 % 4 != 0 -> replicated
    }
    gatherable = sorted(n for n in specs
                        if F.local_dim(specs[n], D, False) is not None)
    assert gatherable == ["a", "b"] and "c" not in gatherable
    fl = F.build_flat_layout(specs, gatherable, D, False)
    assert fl is not None and fl.full_size == 8 * 16 + 16 * 12
    assert fl.entries[1].ld == 1  # the moveaxis path is exercised

    V = 2
    key = jax.random.PRNGKey(0)
    full = {n: jax.random.normal(jax.random.fold_in(key, i),
                                 (V, *specs[n].shape), jnp.float32)
            for i, n in enumerate(sorted(specs))}

    def shard_spec(n):
        sp = specs[n]
        dims = [None] * (1 + len(sp.shape))
        if F.local_dim(sp, D, False) is not None:
            dims[1 + sp.fsdp_dim] = "data"
        return P(*dims)

    in_specs = ({n: shard_spec(n) for n in specs},)

    def body_gather(seg_p):
        # per-tensor reference
        ref = {}
        for n in gatherable:
            ld = F.local_dim(specs[n], D, False)
            ref[n] = jax.lax.all_gather(seg_p[n][0], "data", axis=ld,
                                        tiled=True)
        # flat path: pack -> ONE all_gather -> unpack
        slab = F.pack_flat_stack(seg_p, fl)
        got = F.unpack_flat(F.all_gather_flat(slab[0], fl), fl)
        return ref, got

    out_specs = ({n: P() for n in gatherable}, {n: P() for n in gatherable})
    fg = jax.shard_map(body_gather, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    ref, got = jax.jit(fg)(full)
    for n in gatherable:
        assert np.array_equal(np.asarray(ref[n]), np.asarray(got[n])), n
        assert np.array_equal(np.asarray(got[n]),
                              np.asarray(full[n][0])), n

    def body_reduce(seg_p):
        grads = {n: seg_p[n][0] if F.local_dim(specs[n], D, False) is None
                 else jax.lax.all_gather(
                     seg_p[n][0], "data",
                     axis=F.local_dim(specs[n], D, False), tiled=True)
                 for n in specs}
        ref = {n: F.reduce_scatter_grad(grads[n], specs[n], D, False)
               for n in specs}
        got = F.reduce_scatter_flat(
            {n: grads[n] for n in gatherable}, fl, jnp.float32)
        got["c"] = F.reduce_scatter_grad(grads["c"], specs["c"], D, False)
        return ref, got

    def red_spec(n):
        sp = specs[n]
        dims = [None] * len(sp.shape)
        if F.local_dim(sp, D, False) is not None:
            dims[sp.fsdp_dim] = "data"
        return P(*dims)

    out_specs_r = ({n: red_spec(n) for n in specs},
                   {n: red_spec(n) for n in specs})
    fr = jax.shard_map(body_reduce, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs_r, check_vma=False)
    ref_r, got_r = jax.jit(fr)(full)
    for n in specs:
        assert np.array_equal(np.asarray(ref_r[n]),
                              np.asarray(got_r[n])), n
    print(f"  gather+reduce bit-identical; flat covers {gatherable}, "
          f"'c' replicated fallback (ld=1 moveaxis path exercised)")

    # engine-level: a data axis dividing nothing -> empty flat layout,
    # the pipeline must run the gather-free path and still match the
    # reference grads.
    case_train_equiv(arch, data=3, model=2)
    mod = M.get_arch(arch)
    cfg, rc = mod.reduced()
    rt = Runtime(cfg, dataclasses.replace(rc, microbatches=4, unit=2),
                 _mesh(3, 2))
    assert rt.flat_layouts["main"] is None and not rt.gatherable["main"]
    print("  data=3: nothing divisible -> empty layout, grads still match")
    print(f"CASE_OK flat_fallback {arch}")


CASES["flat_fallback"] = case_flat_fallback


def case_donation(arch: str = "llama3.2-1b"):
    """Buffer-donation audit: the serve step donates its caches and the
    opt step donates params + opt state — visible as input/output
    aliasing in the lowered modules (no spurious full-size copies)."""
    from repro.api import session

    mod = M.get_arch(arch)
    cfg, rc0 = mod.reduced()
    geo = M.build_geometry(cfg, dataclasses.replace(rc0, microbatches=2))
    data = max(1, int(N_DEV) // geo.model_ranks)

    def n_donated(txt):
        # donation lowers as an eager alias (tf.aliasing_output) or a
        # deferred XLA decision (jax.buffer_donor) depending on shardings
        return (txt.count("tf.aliasing_output")
                + txt.count("jax.buffer_donor"))

    sess = session(arch, mode="serve", data=data,
                   global_batch=data * rc0.groups * 2, max_seq=16,
                   overrides=dict(microbatches=2))
    n_alias = n_donated(sess.lower().as_text())
    n_caches = len(jax.tree_util.tree_leaves(
        sess.init_caches(abstract=True)))
    assert n_alias >= n_caches, (
        f"serve step donates {n_alias} buffers < {n_caches} cache leaves")

    tr = session(arch, data=data, seq_len=16,
                 overrides=dict(microbatches=2))
    params = tr.init_params(jax.random.PRNGKey(0))
    opt = tr.init_opt_state(params)
    g_shapes = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params)
    lo = tr.opt_step_fn().lower(params, g_shapes, opt)
    n_alias_o = n_donated(lo.as_text())
    n_p = len(jax.tree_util.tree_leaves(params))
    assert n_alias_o >= n_p, (
        f"opt step donates {n_alias_o} buffers < {n_p} param leaves")

    d = tr.describe()
    assert d["donation"]["opt_step"] == ["params", "opt_state"]
    assert d["donation"]["serve_step"] == ["caches"]
    # and the update still actually runs + callers' rebind pattern works
    grads, _ = tr.train_step(params, tr.stream().batch(0))
    params, opt, om = tr.opt_step(params, grads, opt)
    assert np.isfinite(float(om["grad_norm"]))
    print(f"  serve aliases {n_alias}/{n_caches} cache leaves, "
          f"opt aliases {n_alias_o} (>= {n_p} params)")
    print(f"CASE_OK donation {arch}")


CASES["donation"] = case_donation


def case_moe_ep_equiv(arch: str = "qwen2-moe-a2.7b"):
    """EP as a first-class tick-engine citizen: expert-parallel training
    matches the reference model, the lowered EP step moves tokens via
    all-to-all while keeping expert weights out of the FSDP gathers, and
    ep-vs-gathered serve engines emit identical greedy tokens with live
    expert-load stats."""
    import re
    from repro.api import session

    # 1) EP pipeline grads vs the reference model
    case_train_equiv(arch, moe_mode="ep")

    # 2) structural: EP lowers all-to-all dispatch/combine and shrinks
    # the FSDP gather footprint (expert slabs stay sharded over data)
    def sites(txt, op):
        return len(re.findall(rf"\b{op}(?:-start)?\(", txt))

    txts = {}
    ep_names = {}
    for mode in ("ep", "gathered"):
        sess = session(arch, data=2, seq_len=16, moe_mode=mode,
                       overrides=dict(microbatches=2))
        txts[mode] = sess.lower().compile().as_text()
        ep_names[mode] = (set(sess.rt.ep_names["main"]),
                          set(sess.rt.gatherable["main"]))
    a2a_ep = sites(txts["ep"], "all-to-all")
    a2a_g = sites(txts["gathered"], "all-to-all")
    # gathered may still carry a couple of XLA-synthesized all-to-alls
    # (layout shuffles); EP's explicit dispatch/combine dominates them
    assert a2a_ep > a2a_g, (a2a_ep, a2a_g)
    # EP keeps the expert slabs out of the FSDP gather set entirely
    eps, gat = ep_names["ep"]
    assert eps and not (eps & gat), (eps, gat)
    eps_g, gat_g = ep_names["gathered"]
    assert not eps_g and eps <= gat_g, (eps_g, gat_g)
    print(f"  HLO: ep all-to-all={a2a_ep} (gathered {a2a_g}); "
          f"{len(eps)} expert tensors out of the gather set")

    # 3) serve engines: ep tokens == gathered tokens, load histogram live
    def serve(mode):
        s = session(arch, mode="serve", data=2, max_slots=4, max_seq=24,
                    moe_mode=mode,
                    overrides=dict(microbatches=2, moe_stats=True))
        ps = s.init_params(jax.random.PRNGKey(0))
        eng = s.serve_engine(ps)
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, s.cfg.vocab, size=n).astype(np.int32)
                   for n in (3, 8, 5, 6)]
        hs = [eng.submit(p, max_gen=4) for p in prompts]
        eng.run_until_idle()
        return [h.result(timeout=10) for h in hs], s.describe()["serving"]

    toks_ep, srv_ep = serve("ep")
    toks_g, _ = serve("gathered")
    assert toks_ep == toks_g, (toks_ep, toks_g)
    load = srv_ep["moe"]["load_per_expert"]
    assert len(load) == 8 and sum(load) > 0, load
    assert srv_ep["capacity_deferrals"] == 0  # cf=8 never defers 4 slots
    print(f"  serve ep == gathered tokens; load/expert {load}")
    print(f"CASE_OK moe_ep_equiv {arch}")


CASES["moe_ep_equiv"] = case_moe_ep_equiv


def case_moe_ep_flat(arch: str = "qwen2-moe-a2.7b"):
    """Per-expert-shard flat segments: in EP mode the expert tensors'
    cross-group reductions pack into ONE slab collective per segment
    (coalesce="flat") with grads bit-identical to the per-tensor path,
    and strictly fewer collective sites in the compiled HLO."""
    import re
    from repro.api import session

    assert int(N_DEV) >= 12, "run with SPMD_DEVICES=12 (data 2 x model 6)"
    outs = {}
    sites = {}
    for mode in ("flat", "none"):
        sess = session(arch, data=2, seq_len=16, moe_mode="ep",
                       coalesce=mode,
                       overrides=dict(microbatches=2, groups=2))
        efl = sess.rt.ep_flat_layouts["main"]
        assert (efl is not None) == (mode == "flat"), (mode, efl)
        params = sess.init_params(jax.random.PRNGKey(0))
        batch = sess.stream().batch(0)
        lo = sess.train_step_fn().lower(params, batch).compile()
        txt = lo.as_text()
        sites[mode] = {
            op: len(re.findall(rf"\b{op}(?:-start)?\(", txt))
            for op in ("all-gather", "reduce-scatter", "all-reduce",
                       "collective-permute", "all-to-all")}
        g, m = sess.train_step(params, batch)
        outs[mode] = (jax.device_get(g), float(m["loss_sum"]))

    assert outs["flat"][1] == outs["none"][1], (outs["flat"][1],
                                                outs["none"][1])
    flat_g = jax.tree_util.tree_flatten_with_path(outs["flat"][0])[0]
    base_g = dict(jax.tree_util.tree_flatten_with_path(outs["none"][0])[0])
    for kp, vg in flat_g:
        assert np.array_equal(np.asarray(vg), np.asarray(base_g[kp])), \
            jax.tree_util.keystr(kp)
    tot = {m: sum(s.values()) for m, s in sites.items()}
    assert tot["flat"] < tot["none"], (sites["flat"], sites["none"])
    print(f"  {len(flat_g)} grad tensors bit-identical; collective "
          f"sites {tot['flat']} < {tot['none']} "
          f"(permute {sites['flat']['collective-permute']} < "
          f"{sites['none']['collective-permute']})")
    print(f"CASE_OK moe_ep_flat {arch}")


CASES["moe_ep_flat"] = case_moe_ep_flat


def case_elastic_train(arch: str = "llama3.2-1b"):
    """End-to-end elastic training through the topology layer: a mid-run
    injected failure on a data=4 fake topology (8 devices) resumes from
    the verified checkpoint on a data=2 topology (4 devices) and the
    post-restore loss trajectory is BIT-EXACT against a clean
    restore-and-continue on the same shrunk topology — the restart adds
    no numerical drift, only the re-mesh."""
    import tempfile

    from repro.api import session
    from repro.runtime.fault_tolerance import (
        FaultToleranceConfig,
        TrainController,
    )
    from repro.runtime.topology import Topology

    GB = 8          # pinned across the shrink so the stream continues

    def make_sess(data):
        return session(arch, topology=Topology(kind="fake_cpu", data=data),
                       seq_len=16, global_batch=GB,
                       overrides=dict(microbatches=2),
                       optim=dict(lr=1e-2, warmup=20, total=10_000))

    ckpt = tempfile.mkdtemp(prefix="elastic_train_")
    ctl = TrainController(ckpt, FaultToleranceConfig(
        ckpt_every=2, max_failures=3, async_save=False))
    sessions = []

    def build(restored, manifest):
        sess = make_sess(2 if ctl.failures else 4)
        ctl.attach(sess)
        sessions.append(sess)
        stream = sess.stream()
        if restored is None:
            params = sess.init_params(jax.random.PRNGKey(0))
            opt = sess.init_opt_state(params)
        else:
            params = sess.adopt_params(restored["params"])
            opt = jax.tree.map(jnp.asarray, restored["opt"])
            opt["step"] = jnp.asarray(opt["step"])

        def run_one(state, step_no):
            batch = stream.batch(step_no)
            grads, metrics = sess.train_step(state["params"], batch)
            p2, o2, _ = sess.opt_step(state["params"], grads,
                                      state["opt"])
            return ({"params": p2, "opt": o2},
                    {"loss": float(metrics["loss_sum"])})

        return {"params": params, "opt": opt}, run_one, lambda s: s

    state, history = ctl.run(build, 6, inject_failure_at=4)
    assert ctl.failures == 1, ctl.failures
    assert [s.data_size for s in sessions] == [4, 2], \
        [s.data_size for s in sessions]
    assert [s for s, _ in history] == list(range(6)), history
    # the controller surfaced itself in the facade's introspection
    ft = sessions[-1].describe()["fault_tolerance"]
    assert ft["failures"] == 1 and ft["resume_steps"] == [4], ft
    topo = sessions[-1].describe()["topology"]
    assert topo["kind"] == "fake_cpu" and topo["layout"]["data"] == 2, topo
    losses = {s: m["loss"] for s, m in history}

    # reference: clean restore of the step-4 checkpoint on the SAME
    # shrunk topology, steps 4..5 — must match the elastic run bit-exactly
    sess_ref = make_sess(2)
    tree, manifest = ctl.mgr.restore(4)
    assert manifest["extra"]["step"] == 4, manifest
    params = sess_ref.adopt_params(tree["params"])
    opt = jax.tree.map(jnp.asarray, tree["opt"])
    opt["step"] = jnp.asarray(opt["step"])
    stream = sess_ref.stream()
    state_r = {"params": params, "opt": opt}
    for step_no in (4, 5):
        batch = stream.batch(step_no)
        grads, metrics = sess_ref.train_step(state_r["params"], batch)
        p2, o2, _ = sess_ref.opt_step(state_r["params"], grads,
                                      state_r["opt"])
        state_r = {"params": p2, "opt": o2}
        ref = float(metrics["loss_sum"])
        assert losses[step_no] == ref, \
            f"step {step_no}: elastic {losses[step_no]!r} != clean {ref!r}"
    print(f"  elastic 4->2 data shrink: steps 4..5 bit-exact vs clean "
          f"restore (losses {losses[4]:.6f}, {losses[5]:.6f})")
    print(f"CASE_OK elastic_train {arch}")


CASES["elastic_train"] = case_elastic_train


def case_serve_reshard(arch: str = "llama3.2-1b"):
    """ServeEngine.reshard: park a staggered in-flight workload, rebuild
    on a shrunk topology, re-admit — zero dropped requests and token
    streams identical to an uninterrupted run, on both the contiguous
    and the paged (radix-sharing) pool."""
    from repro.api import session
    from repro.runtime.topology import Topology

    def make(data, **kw):
        return session(arch, mode="serve",
                       topology=Topology(kind="fake_cpu", data=data),
                       max_slots=4, max_seq=24,
                       overrides=dict(microbatches=2), **kw)

    vocab = None
    rng = np.random.RandomState(0)
    for paged in (False, True):
        kw = dict(page_size=4) if paged else {}
        sess = make(2, **kw)
        vocab = sess.cfg.vocab
        prompts = [rng.randint(0, vocab, size=n).astype(np.int32)
                   for n in (3, 8, 5, 11, 4, 7, 9, 6)]
        gens = [4, 2, 6, 3, 5, 2, 4, 6]
        params = sess.init_params(jax.random.PRNGKey(0))
        eng = sess.serve_engine(params)
        hs = [eng.submit(p, max_gen=g) for p, g in zip(prompts, gens)]
        eng.run_until_idle()
        refs = [h.result(timeout=5) for h in hs]

        sess2 = make(2, **kw)
        eng2 = sess2.serve_engine(
            sess2.init_params(jax.random.PRNGKey(0)))
        hs2 = [eng2.submit(p, max_gen=g) for p, g in zip(prompts, gens)]
        eng2.step()
        eng2.step()     # mixture: finished + mid-decode + still queued
        in_flight = len(eng2._by_slot)
        queued = eng2.scheduler.n_queued
        assert in_flight > 0 and queued > 0, (in_flight, queued)
        r = eng2.reshard(Topology(kind="fake_cpu", data=1))
        assert eng2.session.data_size == 1
        assert r["parked"] == in_flight + queued, r
        eng2.run_until_idle()
        got = [h.result(timeout=5) for h in hs2]
        for i, (a, b) in enumerate(zip(refs, got)):
            assert a == b, f"paged={paged} request {i}: {b} != {a}"
        st = eng2.stats
        assert st.reshards == 1 and st.finished_requests == len(prompts)
        label = "paged" if paged else "contiguous"
        print(f"  {label}: reshard parked {r['parked']} "
              f"({in_flight} in flight, {queued} queued), streams "
              f"identical on data=1")
    print(f"CASE_OK serve_reshard {arch}")


CASES["serve_reshard"] = case_serve_reshard


def case_router_equiv(arch: str = "llama3.2-1b"):
    """EngineRouter correctness: 2 replicas serve the staggered PR-3
    workload token-identically to 1 engine; killing a replica mid-
    workload moves its requests to the survivor with zero drops; a
    seeded sampled stream survives the replica move bit-exactly."""
    from repro.api import session
    from repro.serving import EngineRouter

    def engine():
        sess = session(arch, mode="serve", data=2, max_slots=4,
                       max_seq=24, overrides=dict(microbatches=2))
        return sess.serve_engine(sess.init_params(jax.random.PRNGKey(0)))

    rng = np.random.RandomState(0)
    eng0 = engine()
    vocab = eng0.session.cfg.vocab
    prompts = [rng.randint(0, vocab, size=n).astype(np.int32)
               for n in (3, 8, 5, 11, 4, 7, 9, 6)]
    gens = [4, 2, 6, 3, 5, 2, 4, 6]
    seeds = [None] * 7 + [123]      # one seeded sampled request
    temps = [0.0] * 7 + [0.8]

    def submit_all(target):
        return [target.submit(p, max_gen=g, temperature=t, seed=s)
                for p, g, t, s in zip(prompts, gens, temps, seeds)]

    hs = submit_all(eng0)
    eng0.run_until_idle()
    refs = [h.result(timeout=5) for h in hs]

    # 2 replicas, no failure: token-identical, both replicas served work
    router = EngineRouter([engine(), engine()])
    hs = submit_all(router)
    router.run_until_idle()
    got = [h.result(timeout=5) for h in hs]
    assert got == refs, "2-replica output diverged from single engine"
    assert all(n > 0 for n in router.dispatched), router.dispatched
    print(f"  2 replicas token-identical "
          f"(dispatched {router.dispatched})")

    # kill replica 0 mid-workload: in-flight work (including the seeded
    # sampled stream) moves to the survivor and finishes identically
    router = EngineRouter([engine(), engine()])
    hs = submit_all(router)
    for _ in range(2):
        for i in router.alive():
            router.engines[i].step()
    moved = router.kill_replica(0)
    assert moved > 0, "kill before any work was in flight on replica 0"
    router.run_until_idle()
    got = [h.result(timeout=5) for h in hs]
    assert got == refs, "failover output diverged"
    st = router.stats()
    assert st["alive"] == 1 and st["failovers"] == 1, st
    assert st["finished_requests"] == len(prompts), st
    print(f"  replica-0 kill moved {moved} requests; streams (incl. "
          f"seeded sampling) bit-identical")
    print(f"CASE_OK router_equiv {arch}")


CASES["router_equiv"] = case_router_equiv


CASES["prefetch_equiv"] = case_prefetch_equiv
CASES["int8_grads"] = case_int8_grads
CASES["elastic_reshard"] = case_elastic_reshard
CASES["api_parity"] = case_api_parity
CASES["golden_parity"] = case_golden_parity
CASES["auto_schedule"] = case_auto_schedule


if __name__ == "__main__":
    case = sys.argv[1]
    args = sys.argv[2:]
    kwargs = {}
    pos = []
    for a in args:
        if "=" in a:
            k, v = a.split("=", 1)
            kwargs[k] = int(v) if v.isdigit() else v
        else:
            pos.append(a)
    CASES[case](*pos, **kwargs)
