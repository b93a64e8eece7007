"""Moonlight-16B-A3B's mechanisms at tiny widths on the CPU: the expert
layer that holds a share of the routed experts (shares add up to the
uncut layer, dropless under skew, the grouped product against plain
einsums), the sigmoid gate with its selection-only bias, latent attention
without a q projection, the attention backward taken by blocks, decode
against prefill, the program against the benchmark's float32 reference
of the architecture, and the grouped product's roofline count."""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tape import Tape
from repro.models import blocks
from repro.models.common import (MLACfg, ModelConfig, MoECfg, RunConfig,
                                 rope_tables)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MOE = MoECfg(n_experts=4, top_k=3, d_ff_expert=32, n_shared=2,
             d_ff_shared=64, first_dense=1, router_aux_weight=0.0,
             scoring="sigmoid", routed_scale=2.446, n_routed=16)
CFG = ModelConfig(name="moon_tiny", n_layers=3, d_model=64, n_heads=4,
                  n_kv_heads=4, d_ff=128, vocab=256, rope_theta=50000.0,
                  moe=MOE, mla=MLACfg(q_lora=0, kv_lora=32, rope_dims=16,
                                      v_head=16, qk_nope=16),
                  max_seq=64, norm_eps=1e-5)
F32 = RunConfig(pp=1, vpp=1, param_dtype="float32", compute_dtype="float32")


def _params(specs, key, dtype=jnp.float32):
    keys = jax.random.split(key, len(specs))
    return {n: (jax.random.normal(k, s.shape, jnp.float32)
                / np.sqrt(s.shape[0] if len(s.shape) > 1 else 4.0)
                ).astype(dtype)
            for k, (n, s) in zip(keys, sorted(specs.items()))}


def _moe(cfg, params, x, impl="ref", stats=None):
    rc = dataclasses.replace(F32, kernel_impl=impl)
    ctx = blocks.LayerCtx(cfg=cfg, rc=rc, rope={}, moe_stats=stats)
    t = Tape(params, mode="fwd")
    y, _ = blocks.apply_moe(t, ctx, "f", t.value(x))
    return y.val


def _swiglu(x, wg, wu, wd):
    return jnp.einsum("...f,fd->...d", jax.nn.silu(x @ wg) * (x @ wu), wd,
                      precision="highest")


def _plain_moe(mo, p, x):
    """Every held expert on every token, weighted by its routing weight
    (0 where not chosen), plus the shared experts."""
    lg = jnp.einsum("nd,de->ne", x, p["f.router"], precision="highest")
    sc = jax.nn.sigmoid(lg)
    _, idx = jax.lax.top_k(sc + p["f.router_bias"], mo.top_k)
    w = jnp.take_along_axis(sc, idx, -1)
    w = w / w.sum(-1, keepdims=True) * mo.routed_scale
    dense = (w[..., None] * jax.nn.one_hot(idx, mo.routed)).sum(-2)
    held = dense[:, mo.held_offset:mo.held_offset + mo.n_experts]
    y = sum(held[:, e:e + 1] * _swiglu(x, p["f.e_wg"][e], p["f.e_wu"][e],
                                       p["f.e_wd"][e])
            for e in range(mo.n_experts))
    return y + _swiglu(x, p["f.s_wg"], p["f.s_wu"], p["f.s_wd"])


def test_shares_sum_to_the_whole_layer():
    """The routed parts of the 4 shares of 16 experts, with the shared
    experts counted once, equal the layer that holds all 16."""
    whole = dataclasses.replace(CFG, moe=dataclasses.replace(
        MOE, n_experts=16))
    p = _params(blocks.moe_specs(whole, "f"), jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 64))
    shared = _swiglu(x, p["f.s_wg"], p["f.s_wu"], p["f.s_wd"])
    total = shared
    for off in range(0, 16, 4):
        cfg = dataclasses.replace(CFG, moe=dataclasses.replace(
            MOE, held_offset=off))
        part = {n: (a[off:off + 4] if n.startswith("f.e_") else a)
                for n, a in p.items()}
        total = total + _moe(cfg, part, x) - shared
    np.testing.assert_allclose(total, _moe(whole, p, x), rtol=2e-5,
                               atol=2e-5)


def test_router_follows_the_published_gate():
    """The bias moves the choice of some tokens and never the weights:
    the chosen sigmoid scores renormalised, summing to routed_scale; no
    gradient reaches the bias."""
    lg = jax.random.normal(jax.random.PRNGKey(3), (64, 16))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(4), (16,))
    w0, i0, aux = blocks.route(MOE, lg, jnp.zeros(16))
    w1, i1, _ = blocks.route(MOE, lg, bias)
    assert float(aux) == 0.0
    moved = (np.sort(i0, -1) != np.sort(i1, -1)).any(-1)
    assert 0 < moved.sum() < len(moved)
    for w, i in ((w0, i0), (w1, i1)):
        chosen = jnp.take_along_axis(jax.nn.sigmoid(lg), i, -1)
        np.testing.assert_allclose(
            w, chosen / chosen.sum(-1, keepdims=True) * MOE.routed_scale,
            rtol=1e-6)
        np.testing.assert_allclose(w.sum(-1), MOE.routed_scale, rtol=1e-6)
    g = jax.grad(lambda b: (blocks.route(MOE, lg, b)[0] ** 2).sum())(bias)
    assert not np.asarray(g).any()


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_dropless_under_skew(impl):
    """A bias that sends every token to one held expert: nothing is
    dropped, every pair is computed, and the layer matches the plain
    einsums (the grouped product in Pallas interpret mode, too)."""
    p = _params(blocks.moe_specs(CFG, "f"), jax.random.PRNGKey(5))
    p["f.router_bias"] = p["f.router_bias"].at[1].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 40, 64))
    stats = []
    y = _moe(CFG, p, x, impl, stats)
    (_, load, dropped, held), = stats
    assert int(dropped) == 0
    assert int(load[1]) == 80 and int(load.sum()) == 80 * MOE.top_k
    assert int(held) == int(load[:MOE.n_experts].sum()) > 80
    np.testing.assert_allclose(
        y, _plain_moe(MOE, p, x.reshape(80, 64)).reshape(y.shape),
        rtol=2e-4, atol=2e-4)


def _plain_mla(cfg, p, x):
    m, (s, e) = cfg.mla, (x.shape[1], cfg.mla.qk_nope + cfg.mla.rope_dims)
    ein = lambda eq, a, b: jnp.einsum(eq, a, b, precision="highest")  # noqa
    rms = lambda v, sc: v / jnp.sqrt((v * v).mean(-1, keepdims=True)  # noqa
                                     + cfg.norm_eps) * sc
    cos, sin = rope_tables(s, m.rope_dims, cfg.rope_theta)

    def rope(v):
        h = m.rope_dims // 2
        c, sn = cos[:, None], sin[:, None]
        return jnp.concatenate([v[..., :h] * c - v[..., h:] * sn,
                                v[..., h:] * c + v[..., :h] * sn], -1)

    q = ein("bsd,dhe->bshe", x, p["a.wq"])
    ckv = ein("bsd,dc->bsc", x, p["a.wdkv"])
    c = rms(ckv[..., :m.kv_lora], p["a.kvnorm.scale"])
    k = ein("bsc,che->bshe", c, p["a.wuk"])
    v = ein("bsc,che->bshe", c, p["a.wuv"])
    q = jnp.concatenate([q[..., :m.qk_nope], rope(q[..., m.qk_nope:])], -1)
    kr = jnp.broadcast_to(rope(ckv[:, :, None, m.kv_lora:]),
                          k.shape[:3] + (m.rope_dims,))
    k = jnp.concatenate([k, kr], -1)
    sc = ein("bqhe,bkhe->bhqk", q, k) / np.sqrt(e)
    sc = jnp.where(np.tril(np.ones((s, s), bool)), sc, -jnp.inf)
    o = ein("bhqk,bkhe->bqhe", jax.nn.softmax(sc, -1), v)
    return ein("bshe,hed->bsd", o, p["a.wo"])


def test_mla_without_q_lora_matches_plain_einsums():
    specs = blocks.mla_specs(CFG, "a")
    assert "a.wq" in specs and "a.wdq" not in specs
    assert specs["a.wq"].shape == (64, 4, 32)
    p = _params(specs, jax.random.PRNGKey(7))
    p["a.kvnorm.scale"] = 1.0 + 0.1 * p["a.kvnorm.scale"]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, 64))
    rope = {e: rope_tables(24, e, CFG.rope_theta)
            for e in (CFG.head_dim, CFG.mla.rope_dims)}
    ctx = blocks.LayerCtx(cfg=CFG, rc=dataclasses.replace(
        F32, kernel_impl="ref"), rope=rope)
    t = Tape(p, mode="fwd")
    y = blocks.apply_mla(t, ctx, "a", t.value(x)).val
    np.testing.assert_allclose(y, _plain_mla(CFG, p, x), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("shape", [(1, 64, 4, 4, 24, 16, True),
                                   (2, 50, 4, 2, 16, 16, True),
                                   (1, 40, 2, 1, 8, 8, False)])
def test_blocked_attention_backward_matches_the_vjp(shape):
    """The backward that takes scores again a block at a time (the flash
    kernel's backward) equals the vjp of the reference attention: causal
    and full, grouped heads, a value width of its own, rows not a
    multiple of the block."""
    from repro.kernels import ref

    b, s, h, g, e, ev, causal = shape
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q, k = (jax.random.normal(ks[0], (b, s, h, e)),
            jax.random.normal(ks[1], (b, s, g, e)))
    v, do = (jax.random.normal(ks[2], (b, s, g, ev)),
             jax.random.normal(ks[3], (b, s, h, ev)))
    _, vjp = jax.vjp(lambda *a: ref.attention(*a, causal=causal,
                                              block_k=16), q, k, v)
    for got, want in zip(ref.attention_bwd(q, k, v, do, causal=causal,
                                           block_k=16), vjp(do)):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


class _Arch:
    def __init__(self, cfg, rc):
        self._c = (cfg, rc)

    def chip_share(self):
        return self._c

    def config(self):
        return self._c[0]

    def reduced(self):
        return self._c


def test_decode_step_matches_prefill():
    """One cached decode step (latent cache through the direct q, the
    expert layer through moe_fwd) gives the logits that the prefill of
    the same tokens gives at its last position."""
    from repro.api import register_arch, session

    register_arch("test:moon_tiny", _Arch(CFG, dataclasses.replace(
        F32, microbatches=1)), overwrite=True)
    s = session("test:moon_tiny", mode="serve", max_slots=2, max_seq=32,
                data=1)
    params = s.init_params(jax.random.PRNGKey(9))
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(10), (2, 12), 0,
                                         CFG.vocab), np.int32)

    def step(caches, t, pos):
        return s.serve_step_batched(
            params, caches, {"tokens": jnp.asarray(t),
                             "pos": jnp.full((2,), pos, jnp.int32)},
            want_logits=True)

    _, whole, _ = step(s.init_caches(), toks, 0)
    _, _, caches = step(s.init_caches(), toks[:, :-1], 0)
    _, last, _ = step(caches, toks[:, -1:], 11)
    np.testing.assert_allclose(last, whole, rtol=1e-4, atol=1e-4)


_DRIVE = r"""
import json, sys, time
sys.path.insert(0, "bench")
import jax, numpy as np, common, faults, model, run, train
cell = common.load_cell("moon_tiny.train_tiny")
dev = {"platform": "cpu", "kind": "cpu", "count": 1}
kw = dict(seed=2**33 + 5, seconds=0.3, trace_dir=None,
          devices=jax.devices()[:1])
out = train.run(cell, t_start=time.perf_counter(),
                clock=common.CompileClock(), **kw)
sess, m = out["sess"], cell.config["model"]
# the bias takes no gradient and AdamW leaves it as drawn
params = model.program_params(cell.arch, 2**33 + 5, m, sess.geo,
                              sess.param_shapes())
bias0 = np.asarray(params["segments"]["main"]["L1.ffn.router_bias"])
grads, met = sess.train_step(params, train.Feed(sess, cell.traffic,
                                                m["vocab"], 1).device(0))
new, _, _ = sess.opt_step(params, grads, sess.init_opt_state(params))
bad = train.run(cell, t_start=time.perf_counter(),
                clock=common.CompileClock(), patch=faults.half_batch,
                sess=sess, **kw)
print(json.dumps({
    "sound": run.result(cell, out, False, dev, {})["correct"],
    "checks": run.checks_of(out["readings"], cell.limits),
    "half_batch": run.result(cell, bad, False, dev, {})["correct"],
    "bias_grad": float(np.abs(np.asarray(
        grads["segments"]["main"]["L1.ffn.router_bias"])).max()),
    "bias_moved": bool((np.asarray(
        new["segments"]["main"]["L1.ffn.router_bias"]) != bias0).any()),
    "bias_std": float(bias0.std()),
    "dropped": int(met["moe_dropped"]),
    "held": np.asarray(met["moe_rows_held"]).tolist(),
    "kernels": out["rec"]["kernel_counters"]["counters"]}))
"""


def test_program_against_the_reference(tmp_path):
    """The tiny configuration, cut from the benchmark's as new files in a
    copy of bench/, through the harness: ``correct`` true against the
    architecture's float32 reference, false under the half-batch
    fault."""
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cfg = json.load(open(bench / "configs" / "moonlight-16B-A3B-stage.json"))
    cfg["name"] = "moon_tiny"
    cfg["model"].update(name="moon_tiny", n_layers=3, d_model=64, n_heads=4,
                        n_kv_heads=4, d_ff=128, vocab=256, max_seq=64)
    cfg["model"]["moe"].update(n_experts=4, n_routed=16, top_k=3,
                               d_ff_expert=32, d_ff_shared=64)
    cfg["model"]["mla"].update(kv_lora=32, rope_dims=16, v_head=16,
                               qk_nope=16)
    (bench / "configs" / "moon_tiny.json").write_text(json.dumps(cfg))
    traffic = json.load(open(bench / "traffic" / "train_4k.json"))
    (bench / "traffic" / "train_tiny.json").write_text(
        json.dumps(dict(traffic, seq=32)))
    # bf16 at d_model 64 routes a few pairs otherwise than f32 does
    (bench / "limits" / "moon_tiny.train_tiny.json").write_text(json.dumps(
        {"loss_gap": 2e-3, "grad_gap": 0.03, "delta_gap": 0.03}))
    spec["configs"].append({"name": "moon_tiny", "file":
                            "bench/configs/moon_tiny.json"})
    spec["workloads"].append({"name": "moon_tiny.train_tiny", "chips": 1,
                              "config": "moon_tiny",
                              "traffic": "train_tiny"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", _DRIVE], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["sound"], got
    assert not got["half_batch"], got
    assert got["bias_grad"] == 0.0 and not got["bias_moved"], got
    assert 0.03 < got["bias_std"] < 0.08, got
    assert got["dropped"] == 0 and got["held"][0] == 0 and all(
        h > 0 for h in got["held"][1:]), got
    assert got["kernels"].get("ref_expert_gmm", 0) > 0, got


_GMM_COUNT = r"""
import json, math, sys
sys.path.insert(0, "bench")
import numpy as np, common
cell = common.load_cell("moonlight-16B-A3B-stage.train_4k")
m, a = cell.config["model"], cell.arch
peaks = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1e-12}  # bytes bound
d, fe = m["d_model"], m["moe"]["d_ff_expert"]
load = np.zeros((2, 64))
load[1, 0], load[1, 9] = 3 * 4096 + 1, 4096   # one held group, one not
got = a.expert_gmm_step(m, [0, 3 * 4096 + 1], load, 8, 4096, peaks)
r = (3 * 4096 + 1) / 8
gmm = 2 * r * (d + fe) + 2 * (4 / 8) * d * fe
tgmm = 2 * r * (d + fe) + 2 * 8 * d * fe
print(json.dumps({"got": {k: [c, s] for k, (c, s) in got.items()},
                  "want": {"gmm": [96, 96 * gmm * 1e12],
                           "tgmm": [24, 24 * tgmm * 1e12]}}))
"""


def test_expert_gmm_count_reads_only_filled_groups():
    """The grouped product's roofline count reads a held expert's
    weights only in the micro-batches its load must fill (megablox's
    forward and dx skip empty groups), and dW writes every held
    expert's; a routed expert not held counts nothing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", _GMM_COUNT], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    for k, (calls, secs) in got["want"].items():
        assert got["got"][k][0] == calls, got
        np.testing.assert_allclose(got["got"][k][1], secs, rtol=1e-9)
