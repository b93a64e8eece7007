"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention, fwd_plan
from repro.kernels.fused_xent import softmax_xent
from repro.kernels.paged_attention import (
    decode_attention as pallas_decode_attention,
    flash_attention_slotted,
    paged_attention as pallas_paged_attention,
)
from repro.kernels.selective_scan import selective_scan
from tests.proptest import propcase


@propcase(n_cases=10)
def test_flash_attention_sweep(draw):
    b = draw.ints(1, 2)
    h = draw.choice([2, 4, 8])
    g = draw.choice([x for x in (1, 2, 4) if h % x == 0])
    e = draw.choice([32, 64])
    ev = draw.choice([e, e // 2])
    sq = draw.choice([64, 128, 200, 256])
    sk = draw.choice([sq, 2 * sq])
    causal = draw.bool() if sq == sk else False
    dtype = draw.choice([jnp.float32, jnp.bfloat16])
    # explicit blocks (equal or not) or the shape-derived default
    block_q = draw.choice([64, 128, None])
    block_k = draw.choice([64, 128, None])
    q = jax.random.normal(jax.random.PRNGKey(draw.ints(0, 99)),
                          (b, sq, h, e)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, sk, g, e)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, sk, g, ev)).astype(dtype)
    got = flash_attention(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k, interpret=True)
    want = ref.attention(q, k, v, causal=causal, block_k=128)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


# (b, sq, sk, h, g, e, ev, causal, block_q, block_k)
TILINGS = {
    "gqa_blocks64": (1, 128, 128, 4, 2, 32, 32, True, 64, 64),
    "causal_bq_gt_bk": (1, 256, 256, 4, 2, 32, 32, True, 128, 64),
    "causal_bq_lt_bk": (1, 256, 256, 4, 2, 32, 32, True, 64, 128),
    "sq200_neither_block": (1, 200, 200, 4, 4, 32, 32, True, 64, 128),
    "sq1000_default_blocks": (1, 1000, 1000, 2, 1, 32, 32, True, None, None),
    "gqa_rep4_ev_ne_e": (2, 200, 200, 8, 2, 64, 32, True, None, None),
    "e96_default_blocks": (1, 512, 512, 8, 8, 96, 96, True, None, None),
    "cross_sk_ne_sq": (1, 200, 300, 4, 2, 32, 16, False, None, None),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(TILINGS))
def test_flash_attention_matches_naive_oracle(case, dtype):
    """Causal masking over unequal blocks, ragged sequences, GQA,
    ev != e and the default blocks: f32 against the O(S²) oracle at 1e-5, bf16 against the
    float32 streaming reference at the sweep's 2e-2."""
    b, sq, sk, h, g, e, ev, causal, bq, bk = TILINGS[case]
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    dt = jnp.dtype(dtype)
    q = jax.random.normal(ks[0], (b, sq, h, e)).astype(dt)
    k = jax.random.normal(ks[1], (b, sk, g, e)).astype(dt)
    v = jax.random.normal(ks[2], (b, sk, g, ev)).astype(dt)
    got = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    if dt == jnp.float32:
        want, atol = ref.attention_naive(q, k, v, causal=causal), 1e-5
    else:
        want, atol = ref.attention(q, k, v, causal=causal), 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


@pytest.mark.parametrize("given", [False, True], ids=["default", "given"])
@pytest.mark.parametrize("shape,blocks", [
    (((1, 1024, 24, 96), (1, 1024, 24, 96)), (1024, 1024)),   # GPT 1.5B
    (((1, 1024, 32, 64), (1, 1024, 8, 64)), (1024, 1024)),    # GQA 32/8
    (((2, 448, 20, 64), (2, 1500, 20, 64)), (448, 768)),      # cross
    (((1, 4096, 8, 128), (1, 4096, 8, 128)), (1024, 1024)),   # long
], ids=["gpt", "gqa", "cross", "long"])
def test_flash_fwd_plan_engagement(shape, blocks, given):
    """The forward's tiling is a pure function of the shapes: each
    sequence in the fewest blocks of at most 1024 rows, evened out, and
    explicit blocks are honoured. At the GPT 1.5B training shape that is
    one block per head: 24 grid steps a call."""
    (b, sq, h, _), (_, sk, _, _) = shape
    if given:
        blocks = (64, 128)
    plan = fwd_plan(*shape, block_q=blocks[0] if given else None,
                    block_k=blocks[1] if given else None)
    assert (plan.block_q, plan.block_k) == blocks
    assert plan.grid == (b * h, -(-sq // blocks[0]), -(-sk // blocks[1]))


def test_flash_attention_vjp_is_the_reference_vjp():
    """The kernel's custom_vjp backward is the jnp reference's vjp, and
    each traced backward is counted under ``ref_attention_bwd``."""
    from repro.kernels import ops

    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (1, 128, 4, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))
    w = jax.random.normal(ks[3], (1, 128, 4, 32))

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v) * w).sum()

    before = ops.kernel_counters().get("ref_attention_bwd", 0)
    got = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=64, block_k=64, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    assert ops.kernel_counters()["ref_attention_bwd"] == before + 1
    want = jax.grad(loss(lambda q, k, v: ref.attention(q, k, v,
                                                       causal=True)),
                    argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=1e-5)


@propcase(n_cases=8)
def test_selective_scan_sweep(draw):
    b = draw.ints(1, 2)
    s = draw.choice([64, 130, 256])
    d = draw.choice([64, 128, 192])
    n = draw.choice([4, 8, 16])
    ks = jax.random.split(jax.random.PRNGKey(draw.ints(0, 99)), 6)
    x = jax.random.normal(ks[0], (b, s, d))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, d)) * 0.5)
    A = -jnp.exp(jax.random.normal(ks[2], (d, n)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    D = jax.random.normal(ks[5], (d,))
    got = selective_scan(x, dt, A, B, C, D, chunk=64, block_d=64,
                         interpret=True)
    want = ref.selective_scan(x, dt, A, B, C, D, chunk=128)
    np.testing.assert_allclose(got, want, atol=5e-4)


def test_selective_scan_matches_sequential():
    b, s, d, n = 1, 100, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(ks[0], (b, s, d))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, d)))
    A = -jnp.exp(jax.random.normal(ks[2], (d, n)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    D = jax.random.normal(ks[5], (d,))
    h = jnp.zeros((b, d, n))
    ys = []
    for t in range(s):
        h, y = ref.selective_scan_step(h, x[:, t], dt[:, t], A, B[:, t],
                                       C[:, t], D)
        ys.append(y)
    seq = jnp.stack(ys, 1)
    got = selective_scan(x, dt, A, B, C, D, chunk=32, block_d=32,
                         interpret=True)
    np.testing.assert_allclose(got, seq, atol=5e-4)


@propcase(n_cases=6)
def test_fused_xent_sweep(draw):
    n = draw.choice([64, 200, 256])
    d = draw.choice([32, 64])
    v = draw.choice([500, 1000, 1024])
    ks = jax.random.split(jax.random.PRNGKey(draw.ints(0, 99)), 3)
    h = jax.random.normal(ks[0], (n, d)) * 0.5
    w = jax.random.normal(ks[1], (d, v)) * 0.1
    labels = jax.random.randint(ks[2], (n,), 0, v)
    # the training loss masks padded positions out
    mask = (jnp.arange(n) % draw.choice([1, 3]) == 0).astype(jnp.float32)
    loss_k, (dh_k, dw_k) = softmax_xent(h, w, labels, mask=mask,
                                        block_n=128, block_v=256,
                                        interpret=True)
    loss_r, (dh_r, dw_r) = ref.softmax_xent(h, w, labels, chunk=256,
                                            mask=mask)
    assert abs(float(loss_k) - float(loss_r)) < 1e-4
    np.testing.assert_allclose(dh_k, dh_r, atol=1e-5)
    np.testing.assert_allclose(dw_k, dw_r, atol=1e-5)


def test_xent_ref_matches_autodiff():
    n, d, v = 64, 16, 300
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(ks[0], (n, d)) * 0.5
    w = jax.random.normal(ks[1], (d, v)) * 0.2
    labels = jax.random.randint(ks[2], (n,), 0, v)
    loss_r, (dh_r, dw_r) = ref.softmax_xent(h, w, labels, chunk=128)
    l_n, (gh, gw) = jax.value_and_grad(
        lambda h, w: ref.softmax_xent_naive(h, w, labels),
        argnums=(0, 1))(h, w)
    assert abs(float(loss_r) - float(l_n)) < 1e-5
    np.testing.assert_allclose(dh_r, gh, atol=1e-5)
    np.testing.assert_allclose(dw_r, gw, atol=1e-5)


def test_mlstm_chunkwise_matches_step():
    b, s, h, e = 1, 64, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (b, s, h, e))
    k = jax.random.normal(ks[1], (b, s, h, e))
    v = jax.random.normal(ks[2], (b, s, h, e))
    ig = jax.random.normal(ks[3], (b, s, h)) * 0.5
    fg = jax.random.normal(ks[4], (b, s, h)) + 2.0
    got = ref.mlstm_chunkwise(q, k, v, ig, fg, chunk=16)
    state = None
    ys = []
    C = jnp.zeros((b, h, e, e))
    nrm = jnp.zeros((b, h, e))
    m = jnp.zeros((b, h))
    st = (C, nrm, m)
    for t in range(s):
        st, y = ref.mlstm_step(st, q[:, t], k[:, t], v[:, t], ig[:, t],
                               fg[:, t])
        ys.append(y)
    seq = jnp.stack(ys, 1)
    np.testing.assert_allclose(got, seq, atol=2e-4)


@propcase(n_cases=8)
def test_slotted_attention_sweep(draw):
    """Per-row pos vector (staggered slots), GQA and MLA-absorbed dims."""
    b = draw.ints(2, 4)
    h = draw.choice([2, 4])
    g = draw.choice([x for x in (1, 2) if h % x == 0])
    e = draw.choice([16, 32])
    ev = draw.choice([e, e // 2])   # MLA-absorbed: value dim != qk dim
    sq = draw.choice([1, 3, 5])
    S = draw.choice([32, 48])
    dtype = draw.choice([jnp.float32, jnp.bfloat16])
    ks = jax.random.split(jax.random.PRNGKey(draw.ints(0, 99)), 4)
    q = jax.random.normal(ks[0], (b, sq, h, e)).astype(dtype)
    k = jax.random.normal(ks[1], (b, S, g, e)).astype(dtype)
    v = jax.random.normal(ks[2], (b, S, g, ev)).astype(dtype)
    pos = jax.random.randint(ks[3], (b,), 0, S - sq + 1)
    got = flash_attention_slotted(q, k, v, pos=pos, block_k=16,
                                  interpret=True)
    want = ref.attention(q, k, v, causal=True, q_offset=pos, block_k=16)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_slotted_decode_stats_match_and_combine():
    """Window mode emits ref-layout (m, l, acc) partials that merge via
    combine_decode_shards identically to the unsharded reference."""
    b, h, g, e, S = 3, 4, 2, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (b, 1, h, e))
    k = jax.random.normal(ks[1], (b, S, g, e))
    v = jax.random.normal(ks[2], (b, S, g, e))
    cache_len = jnp.asarray([1, 13, 32], jnp.int32)
    got, (m, l, acc) = pallas_decode_attention(q, k, v, cache_len,
                                               block_k=8, interpret=True)
    want, (mr, lr, accr) = ref.decode_attention(q, k, v, cache_len)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(l, lr, rtol=1e-5)
    np.testing.assert_allclose(acc, accr, rtol=1e-5, atol=1e-5)
    # split the cache in two "sequence shards" and merge the partials
    half = S // 2
    p1 = pallas_decode_attention(q, k[:, :half], v[:, :half],
                                 jnp.minimum(cache_len, half),
                                 block_k=8, interpret=True)[1]
    p2 = pallas_decode_attention(q, k[:, half:], v[:, half:],
                                 jnp.maximum(cache_len - half, 0),
                                 block_k=8, interpret=True)[1]
    comb = ref.combine_decode_shards([p1, p2])
    np.testing.assert_allclose(comb, want, atol=2e-5)


@propcase(n_cases=8)
def test_paged_attention_sweep(draw):
    """Page-table-native kernel vs gather+attend ref: staggered pos,
    sentinel tail pages, slot masking, GQA and MLA dims."""
    b = draw.ints(2, 3)
    h = draw.choice([2, 4])
    g = draw.choice([x for x in (1, 2) if h % x == 0])
    e = draw.choice([16, 32])
    ev = draw.choice([e, e // 2])
    sq = draw.choice([1, 4])
    ps = draw.choice([4, 8])
    ppr = draw.ints(3, 6)
    n_pages = draw.ints(8, 20)
    dtype = draw.choice([jnp.float32, jnp.bfloat16])
    ks = jax.random.split(jax.random.PRNGKey(draw.ints(0, 99)), 6)
    q = jax.random.normal(ks[0], (b, sq, h, e)).astype(dtype)
    kp = jax.random.normal(ks[1], (n_pages, ps, g, e)).astype(dtype)
    vp = jax.random.normal(ks[2], (n_pages, ps, g, ev)).astype(dtype)
    pt = jax.random.randint(ks[3], (b, ppr), 0, n_pages)
    pos = jax.random.randint(ks[4], (b,), 0, ppr * ps - sq + 1)
    # sentinel tail: zero every table entry past each row's live window
    # — the causal mask must neutralize whatever page id 0 aliases
    live = (pos + sq + ps - 1) // ps
    pt = jnp.where(jnp.arange(ppr)[None] < live[:, None], pt, 0)
    sm = jax.random.bernoulli(ks[5], 0.8, (b,))
    got = pallas_paged_attention(q, kp, vp, page_tables=pt, pos=pos,
                                 slot_mask=sm, interpret=True)
    want = ref.paged_attention(q, kp, vp, page_tables=pt, pos=pos,
                               slot_mask=sm, block_k=8)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)
    # masked-off rows emit exact zeros under both implementations
    assert not np.any(np.asarray(got)[~np.asarray(sm)])


def test_paged_attention_int8_error_bound():
    """int8 pages: kernel == ref bitwise-dequant; both within 0.5% of
    the max |o| of the fp32 pool attention."""
    b, sq, h, g, e, ps, ppr, n_pages = 3, 1, 4, 2, 32, 4, 6, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    q = jax.random.normal(ks[0], (b, sq, h, e))
    kf = jax.random.normal(ks[1], (n_pages, ps, g, e))
    vf = jax.random.normal(ks[2], (n_pages, ps, g, e))
    pt = jax.random.randint(ks[3], (b, ppr), 0, n_pages)
    pos = jax.random.randint(ks[4], (b,), 0, ppr * ps - sq + 1)
    # quantize per page × kv-head, the storage layout the cache uses
    k_sc = jnp.abs(kf).max(axis=(1, 3)) / 127.0     # [n_pages, g]
    v_sc = jnp.abs(vf).max(axis=(1, 3)) / 127.0
    ki = jnp.round(kf / k_sc[:, None, :, None]).astype(jnp.int8)
    vi = jnp.round(vf / v_sc[:, None, :, None]).astype(jnp.int8)
    o_fp = ref.paged_attention(q, kf, vf, page_tables=pt, pos=pos,
                               block_k=8)
    o_ker = pallas_paged_attention(q, ki, vi, page_tables=pt, pos=pos,
                                   k_scale=k_sc, v_scale=v_sc,
                                   interpret=True)
    o_ref = ref.paged_attention(q, ki, vi, page_tables=pt, pos=pos,
                                k_scale=k_sc, v_scale=v_sc, block_k=8)
    # kernel and ref share the exact dequant math
    np.testing.assert_allclose(o_ker, o_ref, atol=2e-5)
    bound = 0.005 * np.abs(np.asarray(o_fp)).max()
    assert np.abs(np.asarray(o_ker) - np.asarray(o_fp)).max() < bound


def test_slstm_state_continuity():
    b, s, h, e = 2, 32, 2, 8
    g = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, 4, e))
    full = ref.slstm_scan(g)
    y1, st = ref.slstm_scan(g[:, :16], return_state=True)
    y2 = ref.slstm_scan(g[:, 16:], state=st)
    np.testing.assert_allclose(
        jnp.concatenate([y1, y2], 1), full, atol=1e-5)
