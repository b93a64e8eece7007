"""Continuous-batching serving engine tests.

Fast cases exercise the host-side pieces (SlotPool bookkeeping,
FIFO/stop/max_gen scheduling policy) in-process — they never touch jax
devices. The SPMD cases (batched ≡ sequential token identity under
staggered lengths with slot reclaim, and the train→serve checkpoint
handoff) run in subprocesses with fake host devices via
tests/spmd_case.py, like the other pipeline tests.
"""

import numpy as np
import pytest

from tests.spmd_run import run_case


# --------------------------------------------------------------------------- #
# SlotPool (no devices)
# --------------------------------------------------------------------------- #


def test_slot_pool_alloc_release_cycle():
    from repro.serving import SlotPool

    pool = SlotPool(3, max_seq=16)
    a = pool.alloc(10, prompt_len=4)
    b = pool.alloc(11, prompt_len=4)
    assert (a.index, b.index) == (0, 1)
    assert pool.n_active == 2 and pool.n_free == 1
    pool.release(a.index)
    assert pool.n_free == 2
    # lowest free slot is reused, with position state reset
    a.pos = 9
    c = pool.alloc(12, prompt_len=4)
    assert c.index == 0 and c.pos == 0 and c.request_id == 12
    assert pool.alloc(13, 4) is not None
    assert pool.alloc(14, 4) is None  # full


def test_slot_pool_vectors_and_occupancy():
    from repro.serving import SlotPool

    pool = SlotPool(4, max_seq=32)
    s = pool.alloc(1, prompt_len=5)
    s.pos = 5
    assert pool.pos_vector().tolist() == [5, 0, 0, 0]
    assert pool.active_mask().tolist() == [True, False, False, False]
    assert pool.mask_for([1, 3]).tolist() == [False, True, False, True]
    pool.observe_tick()
    pool.alloc(2, prompt_len=5)
    pool.observe_tick()
    assert pool.occupancy == pytest.approx((1 + 2) / (2 * 4))


def test_slot_pool_rejects_oversized_prompt():
    from repro.serving import SlotPool

    pool = SlotPool(2, max_seq=8)
    with pytest.raises(ValueError, match="max_seq"):
        pool.alloc(1, prompt_len=8)  # no room for even one new token


# --------------------------------------------------------------------------- #
# RequestScheduler (no devices)
# --------------------------------------------------------------------------- #


def _req(n=4, **kw):
    from repro.serving import Request

    return Request(prompt=np.arange(1, n + 1, dtype=np.int32), **kw)


def test_scheduler_fifo_admission_respects_policy():
    from repro.serving import RequestScheduler, SchedulerPolicy, SlotPool

    sched = RequestScheduler(SchedulerPolicy(max_prefills_per_tick=2))
    pool = SlotPool(4, max_seq=16)
    reqs = [_req() for _ in range(5)]
    for r in reqs:
        sched.submit(r)
    first, _ = sched.admit(pool)
    # FIFO order, capped by the interleave policy
    assert [r.id for r in first] == [reqs[0].id, reqs[1].id]
    assert [r.slot for r in first] == [0, 1]
    second, _ = sched.admit(pool)
    assert [r.id for r in second] == [reqs[2].id, reqs[3].id]
    # pool is now full: admission stalls until a slot frees up
    assert sched.admit(pool) == ([], []) and sched.n_queued == 1
    pool.release(first[0].slot)
    refill, _ = sched.admit(pool)
    assert [r.id for r in refill] == [reqs[4].id]
    assert refill[0].slot == first[0].slot  # reclaimed slot refilled
    assert sched.admit(pool) == ([], []) and sched.n_queued == 0


def test_scheduler_static_mode_waits_for_idle_pool():
    from repro.serving import RequestScheduler, SchedulerPolicy, SlotPool

    sched = RequestScheduler(SchedulerPolicy(mode="static"))
    pool = SlotPool(2, max_seq=16)
    for _ in range(4):
        sched.submit(_req())
    batch1, _ = sched.admit(pool)
    assert len(batch1) == 2        # fills the whole pool at once
    assert sched.admit(pool) == ([], [])  # pool busy -> no admission
    pool.release(0)
    assert sched.admit(pool) == ([], [])  # still one active slot
    pool.release(1)
    admitted, _ = sched.admit(pool)
    assert len(admitted) == 2


def test_request_validation():
    from repro.serving import Request

    with pytest.raises(ValueError, match="empty"):
        Request(prompt=np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="max_gen"):
        _req(max_gen=0)
    r = _req(stop=[7.0])
    assert r.stop == (7,)


def test_scheduler_policy_validation():
    from repro.serving import SchedulerPolicy

    with pytest.raises(ValueError, match="admission mode"):
        SchedulerPolicy(mode="round-robin")
    with pytest.raises(ValueError, match="max_prefills"):
        SchedulerPolicy(max_prefills_per_tick=0)


def test_moe_capacity_bound_semantics():
    from repro.serving import MoECapacity

    # capacity(n) = ceil8(int(n*top_k/E*cf)+1) floored at 8; the bound
    # admits while skew x the uniform share still fits.
    cap = MoECapacity(n_experts=8, top_k=2, capacity_factor=8.0, skew=12.0)
    assert cap.fits(0) and cap.fits(1) and cap.fits(2)
    assert not cap.fits(3)          # hot = 3*2/8*12 = 9 > cap(3) = 8
    assert cap.max_admissible(16) == 2
    # skew=0 disables the bound entirely
    assert MoECapacity(8, 2, skew=0.0).fits(10**6)
    # uniform routing (skew=1) always fits: the capacity factor covers it
    uni = MoECapacity(n_experts=8, top_k=2, capacity_factor=1.25, skew=1.0)
    assert all(uni.fits(n) for n in range(1, 2048))

    # from_moe_cfg mirrors the model's MoE config
    from repro.models.common import MoECfg

    mo = MoECfg(n_experts=4, top_k=1, d_ff_expert=8, capacity_factor=2.0)
    c2 = MoECapacity.from_moe_cfg(mo)
    assert (c2.n_experts, c2.top_k, c2.capacity_factor) == (4, 1, 2.0)


def test_scheduler_capacity_aware_admission():
    from repro.serving import (MoECapacity, RequestScheduler,
                               SchedulerPolicy, SlotPool)

    # max_admissible = 2: the third co-resident request must wait
    cap = MoECapacity(n_experts=8, top_k=2, capacity_factor=8.0, skew=12.0)
    sched = RequestScheduler(SchedulerPolicy(max_prefills_per_tick=4,
                                             moe_capacity=cap))
    pool = SlotPool(4, max_seq=16)
    reqs = [_req() for _ in range(4)]
    for r in reqs:
        sched.submit(r)
    first, _ = sched.admit(pool)
    assert [r.id for r in first] == [reqs[0].id, reqs[1].id]
    assert sched.capacity_deferrals == 1
    # slots are free but the projected co-batch overflows: no admission
    assert sched.admit(pool) == ([], []) and sched.n_queued == 2
    assert sched.capacity_deferrals == 2
    # releasing one active slot re-opens exactly one seat, FIFO order
    pool.release(first[0].slot)
    refill, _ = sched.admit(pool)
    assert [r.id for r in refill] == [reqs[2].id]


def test_scheduler_capacity_never_livelocks_idle_pool():
    from repro.serving import (MoECapacity, RequestScheduler,
                               SchedulerPolicy, SlotPool)

    # an over-tight bound (max_admissible == 0) degrades to serial
    # serving: the first request into an idle pool always admits
    cap = MoECapacity(n_experts=8, top_k=2, capacity_factor=8.0, skew=40.0)
    assert cap.max_admissible(4) == 0
    sched = RequestScheduler(SchedulerPolicy(moe_capacity=cap))
    pool = SlotPool(4, max_seq=16)
    for _ in range(2):
        sched.submit(_req())
    one, _ = sched.admit(pool)
    assert len(one) == 1 and sched.capacity_deferrals == 1
    assert sched.admit(pool) == ([], [])   # co-residency still blocked
    pool.release(one[0].slot)
    two, _ = sched.admit(pool)
    assert len(two) == 1                    # next request proceeds alone


def test_scheduler_remove_with_multiple_queued():
    """ISSUE-5 regression: Request carries a numpy prompt, so the
    dataclass-generated __eq__ made ``req in queue`` raise "truth value
    of an array is ambiguous" whenever >= 2 requests were queued;
    requests now compare by identity (eq=False)."""
    from repro.serving import RequestScheduler

    sched = RequestScheduler()
    r1, r2, r3 = _req(), _req(), _req()
    for r in (r1, r2, r3):
        sched.submit(r)
    # crashed before the fix: r2 != r1 compares the numpy prompts
    assert sched.remove(r2) is True
    assert sched.n_queued == 2
    assert sched.remove(r2) is False          # already gone
    # an equal-valued but distinct request is NOT the queued one
    assert sched.remove(_req()) is False
    assert sched.n_queued == 2
    assert [r.id for r in sched.drain()] == [r1.id, r3.id]


def test_request_identity_semantics():
    """eq=False: equality and hashing are by identity, so requests with
    identical field values stay distinguishable in queues/dicts."""
    a, b = _req(), _req()
    assert a != b and a == a
    assert len({a, b}) == 2


def test_scheduler_admission_continues_past_poisoned_request():
    """ISSUE-6 regression: a request the pool can never hold (ValueError
    from try_admit) must fail alone — popped into the rejected list with
    its error — while admission of its queue neighbours continues. Before
    the (admitted, rejected) split the exception escaped admit() and took
    down the whole tick."""
    from repro.serving import RequestScheduler, SlotPool

    sched = RequestScheduler()
    pool = SlotPool(4, max_seq=8)
    good1, poison, good2 = _req(n=4), _req(n=9), _req(n=4)
    for r in (good1, poison, good2):
        sched.submit(r)
    admitted, rejected = sched.admit(pool)
    assert [r.id for r in admitted] == [good1.id, good2.id]
    assert len(rejected) == 1
    bad, err = rejected[0]
    assert bad is poison and isinstance(err, ValueError)
    assert "max_seq" in str(err)
    assert sched.n_queued == 0          # nothing left stranded
    assert poison.slot is None


# --------------------------------------------------------------------------- #
# Paged KV cache: PagePool / RadixIndex / PagedSlotPool (no devices)
# --------------------------------------------------------------------------- #


def test_page_pool_refcount_lifecycle():
    from repro.serving import PagePool

    pool = PagePool(8, page_size=4, shards=2)
    assert (pool.partitions, pool.n_loc, pool.dev_pages) == (2, 4, 4)
    assert pool.partition_of(5) == 1 and pool.local_id(5) == 1
    a = pool.alloc(0, 2)
    assert a == [0, 1]                     # lowest-id-first, partition 0
    assert pool.pages_in_use == 2 and pool.refcount(0) == 1
    pool.ref(0)
    assert pool.unref(0) is False          # still held
    assert pool.unref(0) is True           # went free
    assert pool.alloc(0, 1) == [0]         # lowest free id reused
    assert pool.alloc(0, 3) is None        # partition 0 short (2 free)
    assert pool.alloc(1, 4) == [4, 5, 6, 7]
    with pytest.raises(ValueError, match="free"):
        pool.ref(3)                        # never allocated
    pool.unref(1)
    with pytest.raises(ValueError, match="already free"):
        pool.unref(1)
    # groups subdivide each shard; group_of cycles per partition
    g = PagePool(8, page_size=4, shards=2, groups=2)
    assert g.n_loc == 2
    assert [g.group_of(p) for p in range(4)] == [0, 1, 0, 1]
    with pytest.raises(ValueError, match="partitions"):
        PagePool(6, page_size=4, shards=2, groups=2)


def _preq(tokens, max_gen=4, **kw):
    from repro.serving import Request

    return Request(prompt=np.asarray(tokens, np.int32), max_gen=max_gen,
                   **kw)


def test_paged_prefix_share_and_cow_divergence():
    """Two prompts sharing two full pages then diverging: the second
    request refs the shared pages and gets *private* fresh pages for its
    divergent tail (copy-on-write by construction — shared pages are
    only ever full prompt pages, never written after insert)."""
    from repro.serving import PagedSlotPool

    pool = PagedSlotPool(2, max_seq=16, page_size=4, n_pages=8)
    a = pool.try_admit(_preq(np.arange(9)))         # pages 0..8 tokens
    assert a is not None
    al_a = a.alloc
    assert (al_a.start_pos, al_a.n_shared) == (0, 0)
    assert al_a.fresh == al_a.pages                 # all 4 newly allocated
    pool.note_prefilled(a.index, np.arange(9, dtype=np.int32))

    # same first 8 tokens, divergent 9th: exactly 2 full pages shared
    b = pool.try_admit(_preq(list(range(8)) + [99]))
    al_b = b.alloc
    assert (al_b.start_pos, al_b.n_shared) == (8, 2)
    assert al_b.copies == []                        # same partition: refs
    assert al_b.pages[:2] == al_a.pages[:2]
    assert al_b.table[:2].tolist() == al_a.table[:2].tolist()
    # the divergent tail is private fresh pages, disjoint from A's
    assert set(al_b.pages[2:]) == set(al_b.fresh)
    assert not set(al_b.fresh) & set(al_a.pages)
    for gid in al_a.pages[:2]:
        assert pool.pool.refcount(gid) == 3         # A + B + radix
    assert (pool.prefix_hits, pool.prefix_hit_tokens) == (1, 8)

    pool.release(a.index)
    pool.release(b.index)
    for gid in al_a.pages[:2]:
        assert pool.pool.refcount(gid) == 1         # radix keeps them warm
    assert pool.pages_in_use == 2                   # everything else freed


def test_radix_lru_eviction_respects_refcounts():
    """evict() only drops pages whose sole reference is the trie's, and
    only leaf-first (a prefix chain never gets a hole); among droppable
    leaves the least-recently-used goes first."""
    from repro.serving import PagePool, RadixIndex

    pool = PagePool(8, page_size=2)
    radix = RadixIndex(2, pool)
    prompt = np.arange(6, dtype=np.int32)           # 3 full pages
    pages = pool.alloc(0, 3)
    assert radix.insert(prompt, 3, 0, pages) == 3
    pool.ref(pages[1])                   # an in-flight request's hold
    for gid in pages:
        pool.unref(gid)                  # the admitting request finished
    # refcounts now: [1 (trie), 2 (trie+holder), 1 (trie)]
    assert radix.evict(0, 3) == 1        # only the leaf was droppable:
    #                                      pages[1] is pinned, pages[0]
    #                                      sits above a cached descendant
    assert radix.evictions == 1
    assert pool.refcount(pages[1]) == 2 and pool.refcount(pages[0]) == 1
    pool.unref(pages[1])                 # holder done -> chain evictable
    assert radix.evict(0, 2) == 2
    assert pool.pages_in_use == 0 and radix.n_nodes == 0

    # LRU order among droppable leaves: the untouched branch goes first
    pa, pb = pool.alloc(0, 1), pool.alloc(0, 1)
    radix.insert(np.array([1, 2], np.int32), 1, 0, pa)
    radix.insert(np.array([3, 4], np.int32), 1, 0, pb)
    pool.unref(pa[0])
    pool.unref(pb[0])
    assert radix.match(np.array([1, 2], np.int32), 1)   # touch A
    assert radix.evict(0, 1) == 1
    assert radix.match(np.array([1, 2], np.int32), 1)   # A survived
    assert not radix.match(np.array([3, 4], np.int32), 1)


def test_paged_admission_evicts_under_pressure_and_defers():
    """A short free list defers admission (None, like a full SlotPool)
    while live requests pin their pages; once only the trie holds them,
    the next admission LRU-evicts exactly the shortfall."""
    from repro.serving import PagedSlotPool

    pool = PagedSlotPool(2, max_seq=16, page_size=4, n_pages=4)
    a = pool.try_admit(_preq(np.arange(13), max_gen=8))  # all 4 pages
    assert a is not None and pool.pages_in_use == 4
    assert pool.try_admit(_preq(50 + np.arange(9))) is None  # pinned
    assert pool.pages_in_use == 4                   # rollback left no refs
    pool.note_prefilled(a.index, np.arange(13, dtype=np.int32))
    pool.release(a.index)
    assert pool.pages_in_use == 3                   # 3 prompt pages cached
    b = pool.try_admit(_preq(50 + np.arange(9)))    # needs 4 fresh
    assert b is not None
    assert pool.radix.evictions == 3                # evicted the shortfall
    assert pool.pages_in_use == 4


def test_paged_pending_key_defers_co_admitted_twin():
    """A same-prefix request admitted while its twin is still mid-prefill
    would re-prefill the shared pages; it answers WAIT_PREFIX (not the
    out-of-capacity None) until the twin's radix insert, then hits it."""
    from repro.serving import PagedSlotPool
    from repro.serving.slots import WAIT_PREFIX

    pool = PagedSlotPool(2, max_seq=16, page_size=4, n_pages=8)
    prompt = np.arange(9, dtype=np.int32)
    a = pool.try_admit(_preq(prompt))
    assert pool.try_admit(_preq(prompt)) is WAIT_PREFIX   # twin: wait
    pool.note_prefilled(a.index, prompt)
    b = pool.try_admit(_preq(prompt))
    assert b is not None and b.alloc.n_shared == 2
    assert b.alloc.start_pos == 8


def test_paged_pending_defer_narrows_to_matched_extent():
    """REVIEW follow-up: the co-admission defer keys on the full pending
    prompt-page extent, not just the first page — a queued request whose
    cached chain already covers everything the in-flight prefill shares
    with it has nothing to gain by waiting and admits immediately."""
    from repro.serving import PagedSlotPool
    from repro.serving.slots import WAIT_PREFIX

    pool = PagedSlotPool(4, max_seq=16, page_size=4, n_pages=16)
    base = list(range(8))                           # 2 full shared pages
    r1 = pool.try_admit(_preq(base + [9]))
    pool.note_prefilled(r1.index, np.asarray(base + [9], np.int32))
    pool.release(r1.index)                          # pages 0-1 cached
    # in-flight prefill: shares page 0 with `base`, then diverges
    a = pool.try_admit(_preq(base[:4] + [50, 51, 52, 53, 54]))
    assert a is not None and a.alloc.pending_key is not None
    # shares only page 0 with A's pending prefill, and its own cached
    # chain already covers pages 0-1: admit now (the old first-page key
    # would have deferred this behind A's whole chunked prefill)
    b = pool.try_admit(_preq(base + [77, 78, 79, 80, 81]))
    assert b is not None and b.alloc.n_shared == 2
    # a true twin of A still waits — with the sentinel, not None
    assert pool.try_admit(_preq(base[:4] + [50, 51, 52, 53, 99])) \
        is WAIT_PREFIX


def test_scheduler_admits_past_prefix_waiting_request():
    """REVIEW follow-up: a WAIT_PREFIX verdict at the queue head no
    longer stalls the whole FIFO — neighbours behind it are admitted,
    the waiter keeps its queue position, and it admits with the shared
    pages once the holder's prefill completes."""
    from repro.serving import PagedSlotPool, RequestScheduler

    sched = RequestScheduler()
    pool = PagedSlotPool(4, max_seq=16, page_size=4, n_pages=16)
    prompt = np.arange(9, dtype=np.int32)
    holder = _preq(prompt)
    sched.submit(holder)
    admitted, _ = sched.admit(pool)
    assert [r.id for r in admitted] == [holder.id]
    twin, other = _preq(prompt), _preq(50 + np.arange(5))
    sched.submit(twin)
    sched.submit(other)
    admitted, _ = sched.admit(pool)
    assert [r.id for r in admitted] == [other.id]   # skipped the twin
    assert sched.n_queued == 1
    pool.note_prefilled(holder.slot, prompt)
    admitted, _ = sched.admit(pool)
    assert [r.id for r in admitted] == [twin.id]
    assert pool.slots[twin.slot].alloc.n_shared == 2


def test_paged_copy_sources_pinned_until_copies_executed():
    """REVIEW fix (high): a cross-partition copy SOURCE is ref-pinned at
    admission, so a later admission landing in the source's partition
    cannot LRU-evict it and re-allocate it as a fresh page — fresh pages
    are zeroed before any copy runs, so the copy (and every future
    sharer of the registered destination) would silently read zeros.
    The pin drops once the engine has executed the copies."""
    from repro.serving import PagedSlotPool

    prompt = np.arange(7, dtype=np.int32)           # 1 full page
    pool = PagedSlotPool(2, max_seq=8, page_size=4, n_pages=4, shards=2)
    a = pool.try_admit(_preq(prompt, max_gen=1))    # slot 0, partition 0
    pool.note_prefilled(a.index, prompt)            # page 0 in the radix
    src = a.alloc.pages[0]
    c = pool.try_admit(_preq(prompt, max_gen=1))    # slot 1, partition 1
    assert len(c.alloc.copies) == 1
    assert c.alloc.copies[0][0] == src
    assert c.alloc.src_refs == [src]
    assert pool.pool.refcount(src) == 3             # A + trie + C's pin
    pool.release(a.index)
    assert pool.pool.refcount(src) == 2             # trie + C's pin
    # page pressure in the SOURCE partition while the copy is pending:
    # eviction must not take the pinned source — admission defers
    big = _preq(50 + np.arange(7), max_gen=4)       # needs both pages
    assert pool.try_admit(big) is None
    assert pool.pool.refcount(src) == 2 and pool.radix.evictions == 0
    # the engine ran the copy: pin drops, eviction may proceed
    pool.copies_done(c.index)
    assert c.alloc.src_refs == []
    assert pool.pool.refcount(src) == 1             # trie only
    assert pool.try_admit(big) is not None
    assert pool.radix.evictions == 1


def test_paged_release_before_copy_returns_source_pins():
    """A request released with its copies never executed (e.g. the tick
    failed between admission and the device copy) must return its
    source pins too — otherwise the source page could never go free."""
    from repro.serving import PagedSlotPool

    prompt = np.arange(7, dtype=np.int32)
    pool = PagedSlotPool(2, max_seq=8, page_size=4, n_pages=4, shards=2)
    a = pool.try_admit(_preq(prompt, max_gen=1))    # pins partition 0
    pool.note_prefilled(a.index, prompt)
    c = pool.try_admit(_preq(prompt, max_gen=1))    # partition 1: copy
    src = c.alloc.copies[0][0]
    assert pool.pool.refcount(src) == 3             # A + trie + pin
    pool.release(c.index)                           # copies never ran
    assert pool.pool.refcount(src) == 2             # pin returned


def test_paged_sharing_off_keeps_pages_private():
    from repro.serving import PagedSlotPool

    pool = PagedSlotPool(2, max_seq=16, page_size=4, n_pages=8,
                         sharing=False)
    assert pool.radix is None
    prompt = np.arange(9, dtype=np.int32)
    a = pool.try_admit(_preq(prompt))
    pool.note_prefilled(a.index, prompt)
    b = pool.try_admit(_preq(prompt))               # identical prompt
    assert b.alloc.n_shared == 0 and b.alloc.start_pos == 0
    assert not set(b.alloc.pages) & set(a.alloc.pages)
    assert (pool.prefix_hits, pool.evictions) == (0, 0)


def test_paged_sharing_respects_fsdp_group_boundaries():
    """Cache leaves are sharded over the stage axis, so a page written by
    one FSDP group's rows does not exist in another group's replica: a
    prefix cached only in group-0 partitions is NOT a hit for a group-1
    slot (full re-prefill), while the same layout with plain data shards
    (one group) turns it into a device page-copy."""
    from repro.serving import PagedSlotPool

    prompt = np.arange(7, dtype=np.int32)           # 1 full page
    for shards, groups, shared, copies in ((1, 2, 0, 0), (2, 1, 1, 1)):
        pool = PagedSlotPool(4, max_seq=8, page_size=4, n_pages=8,
                             shards=shards, groups=groups)
        # fill partition 0 (slots 0-1) so the third request must land in
        # partition 1 — the other group (or the other data shard)
        a = pool.try_admit(_preq(prompt, max_gen=1))
        pool.note_prefilled(a.index, prompt)
        b = pool.try_admit(_preq(prompt, max_gen=1))
        assert {a.index, b.index} == {0, 1}
        assert b.alloc.n_shared == 1                # in-partition share
        c = pool.try_admit(_preq(prompt, max_gen=1))
        assert pool.partition_of_slot(c.index) == 1
        assert c.alloc.n_shared == shared
        assert len(c.alloc.copies) == copies
        if copies:
            src, dst = c.alloc.copies[0]
            assert pool.pool.partition_of(src) == 0
            assert pool.pool.partition_of(dst) == 1


# --------------------------------------------------------------------------- #
# Sampling (no devices)
# --------------------------------------------------------------------------- #


def test_sampling_params_validation():
    from repro.serving import SamplingParams

    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=1.5)
    assert SamplingParams().greedy
    assert not SamplingParams(temperature=0.7, top_p=0.9).greedy


def test_sample_token_determinism_and_top_p():
    from repro.serving import SamplingParams, sample_token
    from repro.serving.sampling import make_rng

    rng0 = np.random.default_rng(3)
    logits = rng0.normal(size=32).astype(np.float32)
    params = SamplingParams(temperature=0.8, top_p=0.9, seed=17)
    draw = [sample_token(logits, params, make_rng(params))
            for _ in range(3)]
    assert draw[0] == draw[1] == draw[2]    # same seed, fresh rng: pinned
    # the generator advances once per token: a sequence is reproducible
    r1, r2 = make_rng(params), make_rng(params)
    seq1 = [sample_token(logits, params, r1) for _ in range(8)]
    seq2 = [sample_token(logits, params, r2) for _ in range(8)]
    assert seq1 == seq2
    # a tiny nucleus collapses to the argmax regardless of seed
    tight = SamplingParams(temperature=1.0, top_p=1e-9, seed=None)
    assert sample_token(logits, tight, make_rng(tight)) \
        == int(np.argmax(logits))
    # greedy ignores the rng entirely
    assert sample_token(logits, SamplingParams(), None) \
        == int(np.argmax(logits))


# --------------------------------------------------------------------------- #
# ServeEngine failure paths + finished-request guards (fake session,
# no devices: the step fn is a numpy stub)
# --------------------------------------------------------------------------- #


class _FakeSession:
    """Duck-typed stand-in for a serve Session: a deterministic numpy
    step (token = 100*slot + per-slot call count) and no jax anywhere.
    ``want_logits`` returns a deterministic per-(slot, call) logit row so
    host-side sampling is reproducible across fresh engines."""

    vocab = 13

    def __init__(self, n_slots=2, max_seq=8):
        import types

        self.spec = types.SimpleNamespace(mode="serve", prefill_chunk=None)
        self.cfg = types.SimpleNamespace(encdec=None)
        seg = types.SimpleNamespace(kinds=("attn",))
        self.geo = types.SimpleNamespace(segments=[seg])
        self.max_slots = n_slots
        self.paged = False
        self._seq = max_seq
        self.calls = np.zeros(n_slots, np.int64)
        self.no_sampling = None     # layout's sampling_unsupported reason

    def sampling_unsupported_reason(self):
        return self.no_sampling

    def _max_seq(self):
        return self._seq

    def check_slot_sharding(self):
        pass

    def init_caches(self, abstract=False):
        return {}

    def reset_slot_caches(self, caches, mask):
        return caches

    def serve_step_batched(self, params, caches, batch,
                           want_logits=False):
        mask = batch.get("slot_mask")
        active = (np.ones(self.max_slots, bool) if mask is None
                  else np.asarray(mask))
        self.calls[active] += 1
        out = 100 * np.arange(self.max_slots) + self.calls
        if want_logits:
            phase = (np.arange(self.max_slots)[:, None] * 13
                     + self.calls[:, None] * 7
                     + np.arange(self.vocab)[None, :] * 0.7)
            return out, np.sin(phase).astype(np.float32), caches
        return out, caches


def _engine(n_slots=2, max_seq=8, **kw):
    from repro.serving import ServeEngine

    return ServeEngine(_FakeSession(n_slots, max_seq), params=None, **kw)


def test_engine_close_with_queued_requests_fails_all_waiters():
    """close() on an undriven engine must unblock every queued waiter
    with the close error instead of leaving them hanging."""
    eng = _engine(n_slots=2)
    reqs = [eng.submit([1, 2, 3], max_gen=2) for _ in range(3)]
    assert eng.scheduler.n_queued == 3
    eng.close()
    for r in reqs:
        with pytest.raises(RuntimeError, match="outstanding"):
            r.result(timeout=5)
    assert eng.scheduler.n_queued == 0
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit([1])


def test_engine_remove_after_failed_submit():
    """A submit that races an engine failure must pull the request back
    out of the queue (scheduler.remove — the numpy-__eq__ crash site,
    exercised here with a queued neighbour) and fail it loudly."""
    eng = _engine(n_slots=2)
    eng.submit([1, 2])              # a queued neighbour forces the
    #                                 req-vs-other __eq__ comparison
    # engine dies between the enqueue and submit()'s post-enqueue check
    orig_submit = eng.scheduler.submit

    def dying_submit(req):
        orig_submit(req)
        eng._failure = RuntimeError("driver died mid-submit")
        return req

    eng.scheduler.submit = dying_submit
    with pytest.raises(RuntimeError, match="engine stopped"):
        eng.submit([3, 4])
    assert eng.scheduler.n_queued == 1     # the failed one was removed
    # and a submit against the now-failed engine refuses up front
    eng.scheduler.submit = orig_submit
    with pytest.raises(RuntimeError, match="engine failed"):
        eng.submit([5, 6])
    assert eng.scheduler.n_queued == 1


def test_engine_finish_clears_slot_and_guards_late_emit():
    """ISSUE-5 regression: _finish used to release the slot but leave
    req.slot pointing at it, so a late _emit on the finished request read
    (and could finish!) a reallocated slot's state. The slot pointer is
    now cleared and _emit/_decode_tick skip finished requests."""
    eng = _engine(n_slots=1)
    r1 = eng.submit([1, 2], max_gen=1)     # finishes at prefill
    eng.step()
    assert r1.done.is_set() and r1.slot is None
    assert len(r1.tokens) == 1

    r2 = eng.submit([5], max_gen=4)        # reallocates slot 0
    eng.step()
    assert r2.slot == 0 and not r2.done.is_set()
    pos_before = eng.pool.slots[0].pos
    toks_before = list(r2.tokens)

    # late emit on the finished request: must be a no-op (before the fix
    # it dereferenced pool.slots[r1.slot] == r2's slot and could finish
    # r2's slot through r1)
    gen_before = eng.stats.generated_tokens
    eng._emit(r1, 999)
    assert len(r1.tokens) == 1 and 999 not in r1.tokens
    assert eng.stats.generated_tokens == gen_before
    assert eng.pool.slots[0].pos == pos_before
    assert eng.pool.slots[0].request_id == r2.id
    assert list(r2.tokens) == toks_before

    eng.run_until_idle()
    assert r2.done.is_set() and r2.slot is None
    assert len(r2.tokens) == 4
    assert eng.stats.finished_requests == 2


def test_engine_poisoned_request_fails_alone():
    """ISSUE-6 satellite: an admission-impossible request (slipped past
    submit-time validation) is failed with its ValueError while its queue
    neighbours are admitted and served normally — the tick, the daemon
    driver and every other request survive."""
    from repro.serving import Request

    eng = _engine(n_slots=2, max_seq=8)
    good1 = eng.submit([1, 2, 3], max_gen=2)
    # bypass submit()'s validate_prompt: a 9-token prompt can never fit
    # an 8-position cache
    poison = Request(prompt=np.arange(1, 10, dtype=np.int32), max_gen=2)
    eng.scheduler.submit(poison)
    good2 = eng.submit([4, 5], max_gen=2)
    eng.run_until_idle()
    assert eng.stats.rejected_requests == 1
    assert poison.done.is_set() and poison.slot is None
    with pytest.raises(ValueError, match="max_seq"):
        poison.result(timeout=5)
    assert len(good1.result(timeout=5)) == 2   # neighbours unharmed
    assert len(good2.result(timeout=5)) == 2
    assert eng.stats.finished_requests == 2
    assert eng._failure is None                # engine still healthy


def test_engine_rejects_sampling_on_unsupported_layout():
    """REVIEW fix: temperature>0 on a session whose serve step cannot
    return logits (multi-pod mesh / seq-sharded layout) is rejected at
    submit() — before queuing — instead of NotImplementedError surfacing
    mid-tick, failing the engine and stranding every greedy neighbour."""
    from repro.serving import ServeEngine

    fake = _FakeSession(2, 8)
    fake.no_sampling = "logits return is not wired for multi-pod meshes"
    eng = ServeEngine(fake, params=None)
    greedy = eng.submit([1, 2], max_gen=2)          # greedy still fine
    with pytest.raises(NotImplementedError, match="multi-pod"):
        eng.submit([3, 4], max_gen=2, temperature=0.7)
    assert eng.scheduler.n_queued == 1              # nothing was queued
    eng.run_until_idle()
    assert len(greedy.result(timeout=5)) == 2
    assert eng._failure is None                     # engine healthy


def test_engine_sampling_deterministic_across_restarts():
    """Same (prompt, temperature, top_p, seed) -> same sampled tokens on
    a fresh engine: the per-request generator advances once per emitted
    token, so slot placement and batch composition cannot perturb it."""
    def run():
        eng = _engine(n_slots=2, max_seq=8)
        sampled = eng.submit([1, 2], max_gen=4, temperature=0.8,
                             top_p=0.9, seed=7)
        greedy = eng.submit([3, 4], max_gen=3)
        eng.run_until_idle()
        return sampled.result(timeout=5), greedy.result(timeout=5)

    s1, g1 = run()
    s2, g2 = run()
    assert s1 == s2                       # restart-deterministic sampling
    assert g1 == g2 and len(s1) == 4
    assert all(0 <= t < _FakeSession.vocab for t in s1)

    # a different seed draws a different stream (same everything else)
    eng = _engine(n_slots=2, max_seq=8)
    other = eng.submit([1, 2], max_gen=4, temperature=0.8, top_p=0.9,
                       seed=8)
    eng.run_until_idle()
    assert other.result(timeout=5) != s1


# --------------------------------------------------------------------------- #
# Spec plumbing (no devices)
# --------------------------------------------------------------------------- #


def test_spec_serving_knobs_validate():
    from repro.api import SessionError, session

    with pytest.raises(SessionError, match="serving knob"):
        session("llama3.2-1b", mode="train", max_slots=4)
    with pytest.raises(SessionError, match="disagree"):
        session("llama3.2-1b", mode="serve", max_seq=16, max_slots=4,
                global_batch=8)
    with pytest.raises(SessionError, match="prefill_chunk"):
        session("llama3.2-1b", mode="serve", max_seq=16, prefill_chunk=0)
    with pytest.raises(SessionError, match="divide evenly"):
        session("llama3.2-1b", mode="serve", max_seq=16, max_slots=3,
                data=2)
    sess = session("llama3.2-1b", mode="serve", max_seq=16, max_slots=4)
    assert sess.max_slots == 4
    assert sess.shape_cfg.global_batch == 4


def test_spec_paged_knobs_validate():
    from repro.api import SessionError, session

    with pytest.raises(SessionError, match="serving knob"):
        session("llama3.2-1b", mode="train", page_size=4)
    with pytest.raises(SessionError, match="page_size must be"):
        session("llama3.2-1b", mode="serve", max_seq=16, max_slots=4,
                page_size=0)
    with pytest.raises(SessionError, match="divide max_seq"):
        session("llama3.2-1b", mode="serve", max_seq=18, max_slots=4,
                page_size=4)
    with pytest.raises(SessionError, match="needs page_size"):
        session("llama3.2-1b", mode="serve", max_seq=16, max_slots=4,
                max_pages=16)
    with pytest.raises(SessionError, match="pods×data"):
        session("llama3.2-1b", mode="serve", max_seq=16, max_slots=4,
                page_size=4, max_pages=7, data=2)
    with pytest.raises(SessionError, match="prefix_sharing"):
        session("llama3.2-1b", mode="serve", max_seq=16, max_slots=4,
                page_size=4, prefix_sharing="maybe")
    # REVIEW fix: the page arena partitions over shards×groups, so a
    # max_pages/max_slots that only divides the pods×data axes must be
    # rejected at spec time, not by PagePool at engine construction
    with pytest.raises(SessionError, match="FSDP groups"):
        session("llama3.2-1b", mode="serve", max_seq=16, max_slots=4,
                page_size=4, max_pages=10, data=2,
                overrides=dict(groups=2))
    with pytest.raises(SessionError, match="FSDP groups"):
        session("llama3.2-1b", mode="serve", max_seq=16, max_slots=2,
                page_size=4, overrides=dict(groups=4))
    ok = session("llama3.2-1b", mode="serve", max_seq=16, max_slots=4,
                 page_size=4, max_pages=16, data=2,
                 overrides=dict(groups=2))
    assert ok.n_pages == 16
    sess = session("llama3.2-1b", mode="serve", max_seq=16, max_slots=4,
                   page_size=4)
    assert sess.paged and sess.page_size == 4
    assert sess.pages_per_slot == 4
    assert sess.n_pages == 16           # default: contiguous footprint
    plain = session("llama3.2-1b", mode="serve", max_seq=16, max_slots=4)
    assert not plain.paged and plain.page_size == 0


# --------------------------------------------------------------------------- #
# SPMD cases (subprocess, fake devices)
# --------------------------------------------------------------------------- #


def test_batched_equals_sequential_serving():
    """The issue's correctness bar: token-identical engine output for a
    staggered 8-request workload vs independent sequential serving, with
    slot reclaim/refill mid-decode and chunked prefill."""
    run_case("serving_engine_equiv", "llama3.2-1b")


def test_train_serve_handoff_roundtrip():
    """mode='serve' sessions boot from a train checkpoint with
    cache-aware relayout; tokens equal a direct param transplant."""
    run_case("serve_handoff", "llama3.2-1b")


def test_paged_equals_contiguous_serving():
    """ISSUE-6 correctness bar: greedy paged decoding is token-identical
    to the contiguous path on the staggered 8-request workload (with
    peak pages strictly below the contiguous footprint), shared prompts
    prefill once via the radix, and prefix_sharing='off' still matches
    with zero hits.

    PYTHONHASHSEED is pinned like the golden-parity test: the case's
    int8 leg quantizes the KV cache, and hash-randomized trace-time set
    iteration can reorder accumulation enough to flip a near-tie argmax
    between the paged and contiguous programs (int8 perturbs logits by
    O(0.5%) — the API.md caveat; seed 2 reproduces the flip)."""
    run_case("serving_paged_equiv", "llama3.2-1b",
             env_extra={"PYTHONHASHSEED": "0"})


# --------------------------------------------------------------------------- #
# Park / resubmit / EngineRouter (no devices)
# --------------------------------------------------------------------------- #


def test_engine_park_resubmit_roundtrip():
    """park_all folds emitted tokens into the prompt and frees the slot;
    resubmit re-queues the SAME request object, which finishes with the
    full max_gen token count on re-admission."""
    eng = _engine(n_slots=2, max_seq=16)
    r = eng.submit([1, 2, 3], max_gen=6)
    eng.step()                      # prefill + 1 decode -> 2 tokens
    assert len(r.tokens) == 2 and r.slot is not None
    parked = eng.park_all()
    assert parked == [r]
    assert r.slot is None and eng.pool.n_active == 0
    assert r.prompt_len == 3 + 2    # emitted tokens folded into prompt
    assert not r.done.is_set()
    eng.resubmit(r)
    eng.run_until_idle()
    assert r.done.is_set() and r.error is None
    assert len(r.tokens) == 6
    assert eng.stats.resubmitted_requests == 1


def test_park_all_drains_queue_in_arrival_order():
    eng = _engine(n_slots=1, max_seq=16)
    rs = [eng.submit([1, 2], max_gen=2) for _ in range(3)]
    eng.step()                      # r0 in a slot, r1/r2 queued
    parked = eng.park_all()
    assert [p.id for p in parked] == [r.id for r in rs if not
                                      r.done.is_set()]
    assert eng.scheduler.n_queued == 0


def test_park_all_fails_cache_full_edge():
    """A request parked one decode short of cache-full folds to
    prompt_len == max_seq — it cannot re-prefill, so park_all fails it
    loudly instead of truncating its stream."""
    eng = _engine(n_slots=2, max_seq=8)
    r = eng.submit([1, 2, 3, 4, 5], max_gen=6)
    eng.step()                      # prefill + decode -> 2 tokens, pos=7
    eng.step()                      # decode -> 3 tokens, pos advances on
    # the emit *after* this one, so the request is still in flight
    assert len(r.tokens) == 3 and not r.done.is_set()
    parked = eng.park_all()
    assert parked == []             # nothing reusable survived
    with pytest.raises(RuntimeError, match="cannot continue after a "
                       "reshard"):
        r.result(timeout=5)


def test_router_least_loaded_dispatch():
    from repro.serving import EngineRouter

    router = EngineRouter([_engine(n_slots=2), _engine(n_slots=2)])
    router.submit([1, 2, 3], max_gen=4)     # load 0 -> replica 0
    router.submit([1, 2, 3], max_gen=4)     # replica 0 loaded -> 1
    router.submit([1, 2], max_gen=2)        # tie on count, 0 lighter? no:
    assert router.dispatched == [2, 1]      # equal load ties break low
    assert router.engines[0].outstanding_tokens() > 0


def test_router_affinity_override_within_slack():
    from repro.serving import EngineRouter

    e0, e1 = _engine(n_slots=2), _engine(n_slots=2)
    router = EngineRouter([e0, e1], affinity_slack=256)
    e1.prefix_affinity = lambda p: 8        # replica 1 caches a prefix
    router.submit([1, 2, 3], max_gen=4)
    assert router.dispatched == [0, 1]      # affinity beat the tie
    # outside the slack the least-loaded replica wins again
    tight = EngineRouter([_engine(n_slots=2), _engine(n_slots=2)],
                         affinity_slack=0)
    tight.engines[1].prefix_affinity = lambda p: 8
    tight.engines[1].submit([1] * 4, max_gen=8)   # out-of-band load
    tight.submit([1, 2, 3], max_gen=4)
    assert tight.dispatched == [1, 0]


def test_router_kill_replica_moves_queued_work():
    from repro.serving import EngineRouter

    router = EngineRouter([_engine(n_slots=2), _engine(n_slots=2)])
    rs = [router.submit([1, 2, 3], max_gen=3) for _ in range(4)]
    moved = router.kill_replica(0)
    assert moved == 2                       # replica 0's share moved over
    router.run_until_idle()
    for r in rs:
        assert r.done.is_set() and r.error is None
        assert len(r.tokens) == 3
    st = router.stats()
    assert st["alive"] == 1 and st["failovers"] == 1
    assert st["finished_requests"] == 4
    assert st["per_replica"][1]["resubmitted_requests"] == 2
    assert router.kill_replica(0) == 0      # idempotent


def test_router_detects_dead_driver_and_fails_over():
    """A replica whose driver died (engine._failure set) is failed over
    automatically on the next dispatch — its queued work moves."""
    from repro.serving import EngineRouter

    router = EngineRouter([_engine(n_slots=2), _engine(n_slots=2)])
    r0 = router.submit([1, 2, 3], max_gen=2)
    router.engines[0]._failure = RuntimeError("driver died")
    r1 = router.submit([4, 5], max_gen=2)   # triggers alive() detection
    assert router.stats()["alive"] == 1 and router.failovers == 1
    router.run_until_idle()
    assert r0.error is None and len(r0.tokens) == 2
    assert r1.error is None and len(r1.tokens) == 2


def test_router_no_survivors_fails_requests():
    from repro.serving import EngineRouter, RouterError

    router = EngineRouter([_engine(n_slots=2)])
    r = router.submit([1, 2, 3], max_gen=4)
    router.kill_replica(0)
    with pytest.raises(RouterError, match="no survivors"):
        r.result(timeout=5)
    with pytest.raises(RouterError, match="no live replicas"):
        router.submit([1], max_gen=1)
