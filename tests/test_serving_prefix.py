"""Prefix sharing + refcounted copy-on-write pages (the PR-7 tentpole).

Covers: the refcount-ownership property suite — for ANY interleaving of
admit(shared) / chunk / decode-grow / COW / preempt / escalate /
de-escalate / retire / defrag, every page's refcount equals the number of
block-table entries referencing it and free-list membership <=> refcount 0
(hypothesis); the token-parity acceptance matrix — greedy AND seeded
sampling are bit-identical with sharing on vs off across dense / T1 / MLA /
tiered on both the gather and fused paged-kernel paths, including COW at a
mid-page divergence and preemption-replay while holding shared pages; the
double-free regression (DoubleFree RAISES — an ``assert`` vanishes under
``-O``); and the defrag relabeling guarantee (refcount multiset preserved,
free list == zero-refcount pages, index ids renamed)."""
import dataclasses

from _hypothesis_compat import hypothesis, st  # optional dep; see pyproject
import numpy as np
import pytest

import jax

from repro.configs import ARCHS, ServingCfg, smoke_config
from repro.configs.base import MLACfg, ModelConfig
from repro.models import model as M
from repro.serving import paged_cache as pgc
from repro.serving.engine import ContinuousServeEngine, GenerationConfig
from repro.serving.paged_cache import NULL_PAGE, PageAllocator, defrag_plan
from repro.serving.prefix_index import PrefixIndex
from repro.serving.request import SamplingParams, ServeRequest
from repro.serving.scheduler import Request, Scheduler

# pure-MLA stack with dense MLPs (same rationale as test_serving_chunked:
# MoE drop patterns are group-dependent, so MLA parity runs on this stack)
MLA_DENSE = ModelConfig(
    name="mla-dense-test", family="dense", d_model=32, num_heads=4,
    num_kv_heads=4, head_dim=8, d_ff=64, vocab_size=256,
    block_pattern=(("mla", "dense"),), num_blocks=2,
    mla=MLACfg(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
               v_head_dim=8),
    dtype="float32")


def _mk(arch=None, mode=None):
    cfg = MLA_DENSE if arch == "mla-dense" else smoke_config(ARCHS[arch])
    cfg = dataclasses.replace(cfg, dtype="float32")
    if mode:
        cfg = cfg.with_attention(mode)
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _shared_prompts(cfg, tails=(5, 9, 3, 14, 7), prefix=24, seed=0):
    """Prompts opening with a common ``prefix``-token system prompt."""
    rng = np.random.default_rng(seed)
    sys_p = rng.integers(1, cfg.vocab_size, size=prefix).astype(np.int32)
    return [np.concatenate([sys_p,
                            rng.integers(1, cfg.vocab_size, size=t)
                            .astype(np.int32)]) for t in tails]


def _serve(cfg, params, prompts, *, share, fused=False, max_new=6, **kw):
    base = dict(num_slots=3, page_size=4, num_pages=65,
                max_blocks_per_slot=12, prefill_bucket=4, prefill_chunk=4,
                share_prefix=share)
    base.update(kw)
    rt = dataclasses.replace(cfg.attention, paged_kernels=fused)
    eng = ContinuousServeEngine(cfg, params, rt=rt,
                                serving=ServingCfg(**base))
    res, stats = eng.serve(
        [Request(rid=i, prompt=p, max_new_tokens=max_new)
         for i, p in enumerate(prompts)],
        GenerationConfig(max_new_tokens=max_new))
    return {i: res[i]["tokens"] for i in res}, stats, eng


# ------------------------------------------------- double-free regression


def test_double_free_raises_not_asserts():
    """Releasing a page more often than it was referenced RAISES DoubleFree
    (the old ``assert`` vanishes under ``python -O`` and silently corrupts
    the free list: the page double-allocates as live KV later)."""
    alloc = PageAllocator(9)
    (p,) = alloc.alloc(1)
    assert alloc.free([p]) == [p]
    with pytest.raises(PageAllocator.DoubleFree):
        alloc.free([p])
    with pytest.raises(PageAllocator.DoubleFree):
        alloc.free([NULL_PAGE])
    with pytest.raises(PageAllocator.DoubleFree):
        alloc.incref(p)              # unowned page cannot gain an owner
    with pytest.raises(PageAllocator.DoubleFree):
        alloc.incref(NULL_PAGE)
    # the failed frees must not have touched the free list
    assert alloc.num_free == 8 and alloc.num_used == 0


def test_refcount_release_order():
    """A shared page leaves the free list once and returns once: only the
    LAST decref releases it, and ``free`` reports exactly that."""
    alloc = PageAllocator(5)
    (p,) = alloc.alloc(1)
    alloc.incref(p)
    alloc.incref(p)
    assert alloc.refcount(p) == 3
    assert alloc.free([p]) == []
    assert alloc.free([p]) == []
    assert p not in alloc._free
    assert alloc.free([p]) == [p]
    assert alloc.refcount(p) == 0 and p in alloc._free
    with pytest.raises(PageAllocator.DoubleFree):
        alloc.free([p])


# ------------------------------------------------ defrag keeps refcounts


def test_relabel_preserves_refcount_multiset():
    """Defrag on a SHARED arena: the permutation carries each page's
    refcount to its new id (a shared page moves once, every owner's table
    entry is rewritten), and the rebuilt free list is exactly the zero-
    refcount pages. Dropping a count or mislabeling the free list raises."""
    alloc = PageAllocator(9)
    a, b, c = alloc.alloc(3)
    alloc.incref(b)                  # b is shared by two owners
    bt = np.full((2, 4), NULL_PAGE, np.int64)
    bt[0, :2] = [a, b]
    bt[1, :2] = [b, c]               # b appears in BOTH rows
    perm, new_bt, free = defrag_plan(bt, alloc.num_pages)
    before = sorted(alloc._refs)
    alloc.relabel(perm, free)
    assert sorted(alloc._refs) == before
    assert {p for p in range(1, 9) if alloc.refcount(p) == 0} == set(free)
    # b moved ONCE: the deduped plan maps 3 distinct used pages
    used = set(int(p) for p in new_bt.ravel()) - {NULL_PAGE}
    assert len(used) == 3
    with pytest.raises(PageAllocator.DoubleFree):
        alloc.relabel(list(range(9)), [])          # free list went missing
    bad = PageAllocator(5)
    bad.alloc(2)
    bad.incref(1)                    # refs: page1=2, page2=1
    with pytest.raises(PageAllocator.DoubleFree):
        # duplicates page 2's refcount and drops page 1's ({2,1} -> {1,1})
        bad.relabel([0, 2, 2, 3, 4], [3, 4])


def test_prefix_index_match_insert_forget():
    """Index unit semantics: full-page chain match capped at len(ctx)-1,
    ONE partial (mid-page) child continuation, watermark-honest insert
    (foreign dedup does NOT advance), forget-on-release self-healing, and
    relabel renaming physical ids under content-stable keys."""
    idx = PrefixIndex(page_size=4)
    ctx = np.arange(100, 112, dtype=np.int32)      # 3 full pages
    assert idx.insert(ctx, [5, 6, 7], 0, 3) == 3
    pages, shared = idx.match(np.concatenate([ctx, [1, 2]]))
    assert (pages, shared) == ([5, 6, 7], 12)
    # cap: an exact-context lookup must leave >= 1 token to prefill — the
    # last page is mounted via the PARTIAL continuation (11 of 12 tokens)
    pages, shared = idx.match(ctx)
    assert (pages, shared) == ([5, 6, 7], 11)
    # mid-page divergence: 2 full pages + 2 tokens into the third
    probe = np.concatenate([ctx[:10], [9, 9, 9]]).astype(np.int32)
    pages, shared = idx.match(probe)
    assert (pages, shared) == ([5, 6, 7], 10)
    # foreign dedup: a second owner of the same content does not advance
    assert idx.insert(ctx, [8, 9, 10], 0, 3) == 0
    # ... until the incumbent dies; then the retry heals the chain
    for p in (5, 6, 7):
        assert idx.forget(p)
    assert len(idx) == 0
    assert idx.insert(ctx, [8, 9, 10], 0, 3) == 3
    assert idx.match(np.concatenate([ctx, [1]]))[0] == [8, 9, 10]
    # relabel: physical renames, content keys untouched
    idx.relabel({8: 1, 9: 2, 10: 3})
    assert idx.match(np.concatenate([ctx, [1]]))[0] == [1, 2, 3]
    assert not idx.forget(77)                      # unknown page: no-op


# ---------------------------- refcount-ownership property suite (tentpole)


def _check_refcounts(sched: Scheduler, tiered: bool):
    """THE invariant: refcount(p) == number of block-table entries mapping
    p; free-list membership <=> refcount 0; the weak index never points at
    an unowned page; the CPQ arena stays exclusively owned."""
    alloc = sched.dense_alloc
    owners: dict[int, int] = {}
    for r in sched.occupied():
        if r.tier == 0:
            for p in r.pages:
                owners[int(p)] = owners.get(int(p), 0) + 1
    in_free = set(alloc._free)
    for p in range(1, alloc.num_pages):
        assert alloc.refcount(p) == owners.get(p, 0), f"page {p}"
        assert (alloc.refcount(p) == 0) == (p in in_free), f"page {p}"
    assert alloc.refcount(NULL_PAGE) == 0 and NULL_PAGE not in in_free
    for slot, r in enumerate(sched.slots):
        row = [int(p) for p in sched.block_tables[slot]]
        if r is None or r.tier != 0:
            assert set(row) == {NULL_PAGE}, "stale block-table row"
        else:
            n = len(r.pages)
            assert row[:n] == [int(p) for p in r.pages]
            assert set(row[n:]) <= {NULL_PAGE}
    if sched.prefix_index is not None:
        for p in sched.prefix_index.registered_pages():
            assert alloc.refcount(p) >= 1, f"index dangles on page {p}"
    if tiered:
        cpq_owned = [int(p) for r in sched.occupied() if r.tier == 1
                     for p in r.pages]
        assert len(set(cpq_owned)) == len(cpq_owned)
        for p in range(1, sched.cpq_alloc.num_pages):
            assert sched.cpq_alloc.refcount(p) == int(p in cpq_owned)


def _grow_one(sched, serving, r, rng, clock):
    """Engine-faithful decode growth for one running row: COW-guard the
    write target, map the next page, append the 'generated' token."""
    while True:
        try:
            if sched.cow_plan(r) is None:
                break
        except PageAllocator.OutOfPages:
            v = sched.preemption_victim(exclude=r)
            if v is None:
                sched.retire(r, clock, "oom")
                return
            sched.preempt(v)
    while not sched.ensure_writable(r):
        if r.length // serving.page_size >= serving.max_blocks_per_slot:
            sched.retire(r, clock, "length_cap")
            return
        v = sched.preemption_victim(exclude=r)
        if v is None:
            sched.retire(r, clock, "oom")
            return
        sched.preempt(v)
    r.generated.append(int(rng.integers(1, 7)))
    r.length += 1
    sched.lengths[r.slot] = r.length
    sched.register_prefix(r)


@hypothesis.given(seed=st.integers(0, 2 ** 31 - 1),
                  tiered=st.booleans(),
                  num_pages=st.integers(5, 17),
                  share=st.booleans())
@hypothesis.settings(max_examples=40, deadline=None)
def test_refcount_invariant_any_interleaving(seed, tiered, num_pages, share):
    """ACCEPTANCE: drive a Scheduler through a random interleaving of the
    FULL lifecycle vocabulary — admit (with prefix sharing live), chunk
    progress (+ eager registration), decode growth, COW splits, recompute
    preemption, escalation, de-escalation, retirement, defrag — drawing
    prompts from a tiny template pool so shared admissions actually happen,
    and assert the refcount-ownership invariant after EVERY op. At the end
    everything retires: both arenas drain to zero and the index empties."""
    rng = np.random.default_rng(seed)
    serving = ServingCfg(num_slots=3, page_size=2, num_pages=num_pages,
                         escalated_pages=9, max_blocks_per_slot=4,
                         low_watermark=0.5, critical_watermark=0.25,
                         high_watermark=0.6, enable_escalation=tiered,
                         prefill_chunk=2, share_prefix=share)
    sched = Scheduler(serving, tiered=tiered, share_prefix=share)
    # two prefix templates of 2 full pages each: collisions are the point
    # (template 4 + tail <= 2 + budget 2 == max_len 8)
    templates = [rng.integers(1, 7, 4).astype(np.int32) for _ in range(2)]
    next_rid = 0
    clock = 0
    for _ in range(80):
        op = rng.integers(0, 7)
        clock += 1
        if op == 0 and len(sched.queue) < 4:                 # submit
            t = templates[int(rng.integers(2))]
            keep = int(rng.integers(1, len(t) + 1))
            prompt = np.concatenate(
                [t[:keep], rng.integers(1, 7, rng.integers(1, 3))
                 .astype(np.int32)])
            sched.submit(Request(rid=next_rid, prompt=prompt,
                                 max_new_tokens=2))
            next_rid += 1
        elif op == 1:                                        # admit
            sched.admit_next(now=clock, step=clock)
        elif op == 2:                                        # chunk progress
            pre = sched.prefilling()
            if pre:
                r = pre[0]
                try:
                    while sched.cow_plan(r) is not None:
                        pass                                  # split applied
                except PageAllocator.OutOfPages:
                    sched.preempt(r)
                else:
                    sched.note_chunk(r, serving.page_size)
                    sched.register_prefix(r)
                    if r.length >= r.prefill_target:
                        sched.finish_prefill(r)
        elif op == 3:                                        # decode growth
            for r in list(sched.running()):
                if r.state == "running":
                    _grow_one(sched, serving, r, rng, clock)
        elif op == 4 and tiered:                             # escalate/recover
            cand = sched.escalation_candidate()
            if cand is not None:
                sched.apply_escalation(cand)
            elif (cand := sched.deescalation_candidate()) is not None:
                sched.deescalate(cand)
        elif op == 5:                                        # defrag
            sched.plan_defrag()
        else:                                                # retire/preempt
            occ = sched.occupied()
            if occ:
                victim = occ[int(rng.integers(len(occ)))]
                if rng.random() < 0.5:
                    sched.retire(victim, clock, "eos")
                else:
                    sched.preempt(victim)
        _check_refcounts(sched, tiered)
    for r in list(sched.occupied()):
        sched.retire(r, clock, "eos")
    _check_refcounts(sched, tiered)
    assert sched.dense_alloc.num_used == 0
    if sched.cpq_alloc is not None:
        assert sched.cpq_alloc.num_used == 0
    if sched.prefix_index is not None:
        assert len(sched.prefix_index) == 0


# ------------------------------------------------ token-parity acceptance


@pytest.mark.parametrize("arch,mode,fused", [
    ("qwen1.5-0.5b", None, False),           # dense K/V, gather
    ("qwen1.5-0.5b", None, True),            # dense K/V, fused kernels
    ("qwen1.5-0.5b", "decomposed", False),   # T1 X pages, gather
    ("qwen1.5-0.5b", "decomposed", True),    # T1 X pages, fused
    ("mla-dense", None, False),              # MLA latent pages, gather
    ("mla-dense", None, True),               # MLA latent pages, fused
])
def test_sharing_greedy_parity(arch, mode, fused):
    """ACCEPTANCE: greedy output with prefix sharing ON is bit-identical to
    OFF across the tier modes on both paged-attention paths — while sharing
    actually fires (hits > 0) and strictly reduces the prompt tokens that
    prefill writes into the arena."""
    cfg, params = _mk(arch, mode)
    prompts = _shared_prompts(cfg)
    on_t, on_s, eng = _serve(cfg, params, prompts, share=True, fused=fused)
    off_t, off_s, _ = _serve(cfg, params, prompts, share=False, fused=fused)
    assert eng.share_prefix
    for i in off_t:
        np.testing.assert_array_equal(on_t[i], off_t[i])
    assert on_s["prefix_hits"] > 0
    assert on_s["shared_prefix_tokens"] > 0
    assert on_s["prefill_tokens"] < off_s["prefill_tokens"]
    assert on_s["dense_pages_leaked"] == 0
    assert off_s["prefix_hits"] == 0 and not off_s["prefix_sharing"]


def test_sharing_seeded_sampling_parity():
    """Seeded non-greedy sampling is ALSO bit-identical on vs off: sharing
    changes which physical pages serve a prefix, never the logits or the
    per-request sampling streams."""
    cfg, params = _mk("qwen1.5-0.5b")
    prompts = _shared_prompts(cfg, seed=3)
    sps = [SamplingParams(temperature=0.9, seed=10 + i, max_tokens=6)
           for i in range(len(prompts))]

    def run(share):
        sv = ServingCfg(num_slots=3, page_size=4, num_pages=65,
                        max_blocks_per_slot=12, prefill_bucket=4,
                        prefill_chunk=4, share_prefix=share)
        eng = ContinuousServeEngine(
            cfg, params, serving=sv,
            rt=dataclasses.replace(cfg.attention, paged_kernels=False))
        res, stats = eng.serve(
            [ServeRequest(prompt=p, rid=i, sampling=sps[i])
             for i, p in enumerate(prompts)],
            GenerationConfig(max_new_tokens=6))
        return {i: res[i]["tokens"] for i in res}, stats

    on_t, on_s = run(True)
    off_t, _ = run(False)
    for i in off_t:
        np.testing.assert_array_equal(on_t[i], off_t[i])
    assert on_s["prefix_hits"] > 0 and on_s["dense_pages_leaked"] == 0


def test_cow_at_mid_page_divergence_is_exact():
    """A late arrival diverging MID-page mounts the divergence page shared
    and splits it on its first tail write (copy-on-write). The split is
    invisible token-wise: both requests match the sharing-off run."""
    cfg, params = _mk("qwen1.5-0.5b")
    rng = np.random.default_rng(1)
    sys_p = rng.integers(1, cfg.vocab_size, size=24).astype(np.int32)
    pa = np.concatenate([sys_p,
                         rng.integers(1, cfg.vocab_size, size=8)
                         .astype(np.int32)])
    pb = np.concatenate([sys_p[:22],
                         rng.integers(1, cfg.vocab_size, size=6)
                         .astype(np.int32)])  # diverges 2 tokens into page 6

    def run(share):
        sv = ServingCfg(num_slots=2, page_size=4, num_pages=65,
                        max_blocks_per_slot=12, prefill_bucket=4,
                        prefill_chunk=4, share_prefix=share)
        eng = ContinuousServeEngine(
            cfg, params, serving=sv,
            rt=dataclasses.replace(cfg.attention, paged_kernels=False))
        eng.reset(GenerationConfig(max_new_tokens=16))
        eng.add_request(Request(rid=0, prompt=pa, max_new_tokens=16))
        for _ in range(12):     # A's 8 prompt pages land and register
            eng.step()
        eng.add_request(Request(rid=1, prompt=pb, max_new_tokens=8))
        while eng.has_unfinished():
            eng.step()
        toks = {r: np.asarray(v["tokens"])
                for r, v in eng._st.results.items()}
        return toks, eng.stats()

    on_t, on_s = run(True)
    off_t, off_s = run(False)
    for i in off_t:
        np.testing.assert_array_equal(on_t[i], off_t[i])
    assert on_s["cow_copies"] >= 1            # the mid-page split happened
    assert on_s["shared_prefix_tokens"] == 22  # 5 full pages + 2 mid-page
    assert on_s["dense_pages_leaked"] == 0
    assert off_s["cow_copies"] == 0


def test_preemption_replay_with_shared_pages_is_exact():
    """A tiny arena forces recompute preemptions WHILE rows hold shared
    pages: victims decref (never free-under-sharer), replays re-match the
    index, and the final streams still equal the sharing-off run."""
    cfg, params = _mk("qwen1.5-0.5b")
    prompts = _shared_prompts(cfg, tails=(4, 6, 2, 5), prefix=12, seed=7)
    kw = dict(num_slots=3, num_pages=14, max_blocks_per_slot=8, max_new=12)
    on_t, on_s, _ = _serve(cfg, params, prompts, share=True, **kw)
    off_t, off_s, _ = _serve(cfg, params, prompts, share=False, **kw)
    for i in off_t:
        np.testing.assert_array_equal(on_t[i], off_t[i])
    assert on_s["preemptions"] > 0            # pressure actually bit
    assert on_s["prefix_hits"] > 0
    assert on_s["dense_pages_leaked"] == 0
    assert off_s["dense_pages_leaked"] == 0


def test_tiered_sharing_dense_arm_only_is_exact():
    """Tiered engine: the dense arm shares (CPQ pages read through per-slot
    side state and never do). Part 1 pins the watermarks to zero so
    escalation stays dormant: greedy streams must be bit-identical sharing
    on vs off. Part 2 turns pressure back on: escalation re-encodes a row
    lossily at whatever length it reached, and sharing CHANGES the pressure
    schedule — so the exactness oracle there is fused-vs-gather at the SAME
    sharing config, plus leak-free arenas."""
    cfg, params = _mk("qwen1.5-0.5b")
    prompts = _shared_prompts(cfg, tails=(8, 10, 6, 7), prefix=12, seed=5)
    kw = dict(num_pages=33, escalated_pages=33, enable_escalation=True,
              low_watermark=0.0, critical_watermark=0.0,
              max_blocks_per_slot=8, max_new=8)
    on_t, on_s, eng = _serve(cfg, params, prompts, share=True, **kw)
    off_t, off_s, _ = _serve(cfg, params, prompts, share=False, **kw)
    assert eng.tiered and eng.share_prefix
    for i in off_t:
        np.testing.assert_array_equal(on_t[i], off_t[i])
    assert on_s["prefix_hits"] > 0 and on_s["escalations"] == 0
    assert on_s["dense_pages_leaked"] == 0
    assert on_s["cpq_pages_leaked"] == 0
    # part 2: escalation under pressure composes with sharing
    kw2 = dict(num_pages=13, escalated_pages=33, enable_escalation=True,
               low_watermark=0.5, critical_watermark=0.25,
               max_blocks_per_slot=8, max_new=8)
    g_t, g_s, _ = _serve(cfg, params, prompts, share=True, **kw2)
    f_t, f_s, _ = _serve(cfg, params, prompts, share=True, fused=True, **kw2)
    for i in g_t:
        np.testing.assert_array_equal(g_t[i], f_t[i])
    assert g_s["escalations"] > 0 and f_s["escalations"] > 0
    assert g_s["dense_pages_leaked"] == 0
    assert g_s["cpq_pages_leaked"] == 0


def test_escalation_skips_rows_at_the_block_ceiling():
    """Regression (found by the interleaving suite): a running row at
    exactly ``max_len`` needs max_blocks+1 compressed blocks — volunteering
    it overflowed the alt block-table row. It must be skipped (it is one
    growth step from the length-cap retire); shorter rows still escalate."""
    serving = ServingCfg(num_slots=2, page_size=2, num_pages=9,
                         escalated_pages=17, max_blocks_per_slot=4,
                         low_watermark=1.0, critical_watermark=1.0,
                         enable_escalation=True)
    sched = Scheduler(serving, tiered=True)
    r = Request(rid=0, prompt=(np.arange(6, dtype=np.int32) % 5) + 1,
                max_new_tokens=2)
    sched.submit(r)
    sched.admit_next(now=0, step=0)
    sched.note_chunk(r, 6)
    sched.finish_prefill(r)
    while r.length < serving.max_len:
        assert sched.ensure_writable(r)
        r.generated.append(1)
        r.length += 1
        sched.lengths[r.slot] = r.length
    assert sched.escalation_candidate() is None   # at the ceiling: skip
    r.length -= 1                                  # one block of headroom
    sched.lengths[r.slot] = r.length
    assert sched.escalation_candidate() is r
    sched.apply_escalation(r)                      # and it lands cleanly
    assert r.tier == 1
    sched.retire(r, 1, "eos")
    assert sched.dense_alloc.num_used == 0
    assert sched.cpq_alloc.num_used == 0


def test_cpq_and_retrieval_modes_never_share():
    """Sharing is gated OFF for side-state tiers: a CPQ engine with
    share_prefix=True must not build an index (its pages are only readable
    through per-request HQE state — sharing them would break parity)."""
    cfg, params = _mk("qwen1.5-0.5b", "cpq")
    prompts = _shared_prompts(cfg, tails=(5, 3), prefix=8, seed=2)
    toks, stats, eng = _serve(cfg, params, prompts, share=True)
    assert not eng.share_prefix
    assert stats["prefix_hits"] == 0 and not stats["prefix_sharing"]
    for i in toks:
        assert len(toks[i]) == 6
