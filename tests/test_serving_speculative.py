"""Speculative decoding over paged arenas (the PR-9 tentpole).

Covers: prompt-lookup drafting unit semantics; the draft-extended
refcount-ownership property suite — for ANY interleaving of
begin/commit/abort draft with admit / chunk / decode-grow / COW / preempt /
escalate / retire / defrag, refcount == owner count (drafts COUNT as
owners: one per aliased page, one per scratch page) and free-list
membership <=> refcount 0 (hypothesis); the token-parity acceptance
matrix — greedy streams bit-identical speculative on-vs-off across
dense / T1 / MLA / tiered on both the gather and fused paged-kernel paths
and under a 2-way model mesh, seeded sampling replay-stable across
recompute preemption and A->B engine migration with speculation on; the
defrag-locality regression (shared pages compact to the lowest physical
ids and prefix-index entries stay exact across compaction); and the
defrag-vs-open-draft deferral."""
import dataclasses

from _hypothesis_compat import hypothesis, st  # optional dep; see pyproject
import numpy as np
import pytest

import jax

from conftest import run_with_devices
from repro.configs import ARCHS, ServingCfg, smoke_config
from repro.configs.base import MLACfg, ModelConfig
from repro.models import model as M
from repro.serving.engine import ContinuousServeEngine, GenerationConfig
from repro.serving.paged_cache import NULL_PAGE, PageAllocator, defrag_plan
from repro.serving.request import SamplingParams, ServeRequest
from repro.serving.scheduler import Request, Scheduler
from repro.serving.speculative import propose_ngram

# pure-MLA stack with dense MLPs (same rationale as test_serving_prefix:
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


def _loopy_prompts(cfg, n=3, motif=6, reps=3, seed=0):
    """Self-similar prompts (tiled motif + unique tail): the structure
    prompt-lookup drafting actually fires on."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        m = rng.integers(1, cfg.vocab_size, size=motif).astype(np.int32)
        out.append(np.concatenate(
            [np.tile(m, reps),
             rng.integers(1, cfg.vocab_size, size=2).astype(np.int32)]))
    return out


def _serve(cfg, params, prompts, *, spec, fused=False, max_new=12, **kw):
    base = dict(num_slots=3, page_size=4, num_pages=65,
                max_blocks_per_slot=12, prefill_bucket=4, prefill_chunk=4,
                spec_len=spec)
    base.update(kw)
    rt = dataclasses.replace(cfg.attention, paged_kernels=fused)
    eng = ContinuousServeEngine(cfg, params, rt=rt,
                                serving=ServingCfg(**base))
    res, stats = eng.serve(
        [Request(rid=i, prompt=p, max_new_tokens=max_new)
         for i, p in enumerate(prompts)],
        GenerationConfig(max_new_tokens=max_new))
    return {i: res[i]["tokens"] for i in res}, stats, eng


# --------------------------------------------------- prompt-lookup drafting


def test_propose_ngram_longest_suffix_latest_occurrence():
    """The longest recurring suffix n-gram wins; among equal-length matches
    the LATEST occurrence wins; the draft is the <= k tokens that followed."""
    #                 0  1  2  3  4  5  6  7  8
    ctx = np.array([5, 6, 7, 9, 5, 6, 7, 2, 5, 6, 7], np.int32)
    # suffix (5,6,7) recurs at 0 and 4; latest (4) wins -> draft starts at 7
    np.testing.assert_array_equal(propose_ngram(ctx, 3, 2), [2, 5])
    np.testing.assert_array_equal(propose_ngram(ctx, 3, 8), [2, 5, 6, 7])
    # max_ngram=1: suffix (7,) recurs latest at 6 -> followed by 2, 5, ...
    np.testing.assert_array_equal(propose_ngram(ctx, 1, 2), [2, 5])


def test_propose_ngram_falls_back_and_bounds():
    ctx = np.array([1, 2, 3, 4], np.int32)
    assert len(propose_ngram(ctx, 3, 4)) == 0         # nothing recurs
    assert len(propose_ngram(ctx, 3, 0)) == 0         # k = 0
    assert len(propose_ngram(np.array([7], np.int32), 3, 4)) == 0
    # suffix ngram shorter than max_ngram still matches (falls to n=1)
    ctx = np.array([9, 1, 9], np.int32)
    np.testing.assert_array_equal(propose_ngram(ctx, 3, 2), [1, 9])
    # the window at the suffix's own position is excluded: no self-match
    assert len(propose_ngram(np.array([3, 4], np.int32), 1, 2)) == 0


# ------------------------- draft-extended refcount-ownership property suite


def _check_refcounts(sched: Scheduler, tiered: bool):
    """THE invariant, draft-aware: refcount(p) == block-table owners PLUS
    one per reference an open draft holds (every aliased page, every
    scratch page); free-list membership <=> refcount 0; the weak index
    never points at an unowned page; drafts never appear in block tables."""
    alloc = sched.dense_alloc
    owners: dict[int, int] = {}
    for r in sched.occupied():
        if r.tier == 0:
            for p in r.pages:
                owners[int(p)] = owners.get(int(p), 0) + 1
        if r.draft is not None:
            assert r.tier == 0 and r.state == "running"
            for p in r.draft.aliased + r.draft.scratch:
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
            if r.draft is not None:  # scratch is invisible to the tables
                assert not (set(row) & set(map(int, r.draft.scratch)))
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
    """Engine-faithful decode growth for one running row."""
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
                  num_pages=st.integers(6, 17),
                  share=st.booleans())
@hypothesis.settings(max_examples=40, deadline=None)
def test_refcount_invariant_with_draft_ops(seed, tiered, num_pages, share):
    """ACCEPTANCE: the PR-7 interleaving suite extended with the draft
    lifecycle — begin_draft / commit_draft(+emit growth) / abort_draft
    interleaved with admit / chunk / grow / COW / preempt / escalate /
    retire / defrag — asserting the draft-aware refcount invariant after
    EVERY op. Drafts deliberately stay OPEN across foreign ops (the engine
    closes them within a tick; the scheduler must tolerate anything):
    preempt/retire/escalate of a drafted row abort via the hooks, and
    defrag defers while any draft is open. At the end everything retires
    and both arenas drain to zero."""
    rng = np.random.default_rng(seed)
    serving = ServingCfg(num_slots=3, page_size=2, num_pages=num_pages,
                         escalated_pages=9, max_blocks_per_slot=4,
                         low_watermark=0.5, critical_watermark=0.25,
                         high_watermark=0.6, enable_escalation=tiered,
                         prefill_chunk=2, share_prefix=share, spec_len=2)
    sched = Scheduler(serving, tiered=tiered, share_prefix=share)
    templates = [rng.integers(1, 7, 3).astype(np.int32) for _ in range(2)]
    next_rid = 0
    clock = 0

    def drafted():
        return [r for r in sched.occupied() if r.draft is not None]

    for _ in range(90):
        op = rng.integers(0, 9)
        clock += 1
        if op == 0 and len(sched.queue) < 4:                 # submit
            t = templates[int(rng.integers(2))]
            keep = int(rng.integers(1, len(t) + 1))
            prompt = np.concatenate(
                [t[:keep], rng.integers(1, 7, rng.integers(1, 3))
                 .astype(np.int32)])
            sched.submit(Request(rid=next_rid, prompt=prompt,
                                 max_new_tokens=3))
            next_rid += 1
        elif op == 1:                                        # admit
            sched.admit_next(now=clock, step=clock)
        elif op == 2:                                        # chunk progress
            pre = sched.prefilling()
            if pre:
                r = pre[0]
                try:
                    while sched.cow_plan(r) is not None:
                        pass
                except PageAllocator.OutOfPages:
                    sched.preempt(r)
                else:
                    sched.note_chunk(r, serving.page_size)
                    sched.register_prefix(r)
                    if r.length >= r.prefill_target:
                        sched.finish_prefill(r)
        elif op == 3:                                        # decode growth
            for r in list(sched.running()):
                if r.state == "running" and r.draft is None:
                    _grow_one(sched, serving, r, rng, clock)
        elif op == 4 and tiered:                             # escalate/recover
            cand = sched.escalation_candidate()
            if cand is not None:
                sched.apply_escalation(cand)     # aborts any open draft
            elif (cand := sched.deescalation_candidate()) is not None:
                sched.deescalate(cand)
        elif op == 5:                                        # defrag
            if drafted():
                assert sched.plan_defrag() is None, (
                    "defrag must defer while a draft holds scratch pages")
            else:
                sched.plan_defrag()
        elif op == 6:                                        # open a draft
            cands = [r for r in sched.running()
                     if r.state == "running" and r.tier == 0
                     and r.draft is None
                     and r.max_new_tokens - r.num_generated >= 2]
            if cands:
                r = cands[int(rng.integers(len(cands)))]
                cap = (serving.max_blocks_per_slot * serving.page_size
                       - 1 - r.length)
                budget = r.max_new_tokens - r.num_generated
                k = min(int(rng.integers(1, serving.spec_len + 1)),
                        budget - 1, cap)
                if k >= 1:
                    d = sched.begin_draft(r, k)
                    if d is not None:
                        d.tokens = [1] * k
        elif op == 7:                                        # close a draft
            ds = drafted()
            if ds:
                r = ds[int(rng.integers(len(ds)))]
                k = len(r.draft.tokens)
                if rng.random() < 0.3:
                    sched.abort_draft(r)
                else:
                    # engine-faithful commit: n_accept tokens emit with
                    # growth, retiring at the budget exactly like
                    # _emit_token does
                    n_accept = int(rng.integers(1, k + 2))
                    sched.commit_draft(r, n_accept)
                    for _ in range(n_accept):
                        if r.state != "running":
                            break
                        r.generated.append(int(rng.integers(1, 7)))
                        r.length += 1
                        sched.lengths[r.slot] = r.length
                        sched.register_prefix(r)
                        if r.num_generated >= r.max_new_tokens:
                            sched.retire(r, clock, "max_tokens")
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


def test_draft_lifecycle_unit():
    """Deterministic draft bookkeeping: begin increfs every mapped page and
    allocates scratch for exactly the blocks the candidates cover; a
    partial frontier names copy_src; commit adopts in block order and
    releases every alias; abort releases everything and leaves the row's
    arena untouched."""
    serving = ServingCfg(num_slots=2, page_size=4, num_pages=17,
                         max_blocks_per_slot=4, prefill_chunk=4)
    sched = Scheduler(serving)
    r = Request(rid=0, prompt=np.arange(1, 7, dtype=np.int32),
                max_new_tokens=8)
    sched.submit(r)
    sched.admit_next(now=0, step=0)
    sched.note_chunk(r, 6)
    sched.finish_prefill(r)                       # length 6: partial page 1
    assert sched.ensure_writable(r)
    pages0 = [int(p) for p in r.pages]
    d = sched.begin_draft(r, 3)                   # positions 6..9 -> blocks 1,2
    assert d is not None
    assert d.copy_src == pages0[1] and d.blocks == [1, 2]
    assert len(d.scratch) == 2 and len(d.aliased) == len(pages0)
    for p in pages0:
        assert sched.dense_alloc.refcount(p) == 2     # owner + draft alias
    row = sched.draft_block_row(r)
    assert list(row[:3]) == [pages0[0], d.scratch[0], d.scratch[1]]
    # commit 2 tokens: scratch block 1 replaces the frontier (old page
    # freed), block 2's scratch is surplus (position 7 is the last valid)
    sched.commit_draft(r, 2)
    assert r.draft is None
    assert int(r.pages[1]) == d.scratch[0]
    assert sched.dense_alloc.refcount(pages0[1]) == 0
    assert sched.dense_alloc.refcount(d.scratch[1]) == 0
    for _ in range(2):
        r.generated.append(1)
        r.length += 1
        sched.lengths[r.slot] = r.length
    # abort leaves the arena exactly as it was
    before = [int(p) for p in r.pages]
    d2 = sched.begin_draft(r, 2)
    assert d2 is not None
    sched.abort_draft(r)
    assert [int(p) for p in r.pages] == before
    for p in before:
        assert sched.dense_alloc.refcount(p) == 1
    sched.retire(r, 0, "eos")
    assert sched.dense_alloc.num_used == 0


def test_begin_draft_refuses_block_ceiling_and_pressure():
    serving = ServingCfg(num_slots=1, page_size=2, num_pages=5,
                         max_blocks_per_slot=2, prefill_chunk=2)
    sched = Scheduler(serving)
    r = Request(rid=0, prompt=np.array([1, 2], np.int32), max_new_tokens=2)
    sched.submit(r)
    sched.admit_next(now=0, step=0)
    sched.note_chunk(r, 2)
    sched.finish_prefill(r)                      # length 2 of max 4
    assert sched.begin_draft(r, 2) is None       # (2+2)//2 = block 2: ceiling
    d = sched.begin_draft(r, 1)                  # fits in block 1
    assert d is not None
    sched.abort_draft(r)
    sched.retire(r, 0, "eos")
    assert sched.dense_alloc.num_used == 0


# ------------------------------------------------ token-parity acceptance


@pytest.mark.parametrize("arch,mode,fused", [
    ("qwen1.5-0.5b", None, False),           # dense K/V, gather
    ("qwen1.5-0.5b", None, True),            # dense K/V, fused kernels
    ("qwen1.5-0.5b", "decomposed", False),   # T1 X pages, gather
    ("qwen1.5-0.5b", "decomposed", True),    # T1 X pages, fused
    ("mla-dense", None, False),              # MLA latent pages, gather
    ("mla-dense", None, True),               # MLA latent pages, fused
])
def test_speculative_greedy_parity(arch, mode, fused):
    """ACCEPTANCE: greedy output with speculation ON is bit-identical to
    OFF across the tier modes on both paged-attention paths — while
    verification actually runs (spec_steps > 0) and nothing leaks."""
    cfg, params = _mk(arch, mode)
    prompts = _loopy_prompts(cfg)
    on_t, on_s, eng = _serve(cfg, params, prompts, spec=3, fused=fused)
    off_t, off_s, _ = _serve(cfg, params, prompts, spec=0, fused=fused)
    assert eng.spec_on
    for i in off_t:
        np.testing.assert_array_equal(on_t[i], off_t[i])
    assert on_s["spec_steps"] > 0
    assert on_s["dense_pages_leaked"] == 0
    assert off_s["spec_steps"] == 0 and not off_s["spec_on"]


def test_speculative_accepts_on_loopy_trace():
    """On the self-similar trace with a long budget, drafts are ACCEPTED
    (not merely scored): accepted tokens raise tokens-per-invocation above
    the 1/step decode bound for the same total stream."""
    cfg, params = _mk("qwen1.5-0.5b")
    prompts = _loopy_prompts(cfg, n=1, seed=2)
    on_t, on_s, _ = _serve(cfg, params, prompts, spec=4, max_new=24)
    off_t, off_s, _ = _serve(cfg, params, prompts, spec=0, max_new=24)
    np.testing.assert_array_equal(on_t[0], off_t[0])
    assert on_s["spec_accepted"] > 0
    assert on_s["decode_steps"] < off_s["decode_steps"]


def test_speculative_seeded_sampling_parity():
    """Seeded non-greedy streams are ALSO bit-identical on vs off: a
    committed token is always the request's own fold_in(seed, index) draw —
    speculation changes when tokens land, never which. At temperature 0.9
    the sampled continuations rarely recur, so any single workload may
    never draft; two workload seeds together always do."""
    cfg, params = _mk("qwen1.5-0.5b")
    total_spec = 0
    for wseed in (4, 5):
        prompts = _loopy_prompts(cfg, seed=wseed)
        sps = [SamplingParams(temperature=0.9, seed=10 + i, max_tokens=10)
               for i in range(len(prompts))]

        def run(spec):
            sv = ServingCfg(num_slots=3, page_size=4, num_pages=65,
                            max_blocks_per_slot=12, prefill_bucket=4,
                            prefill_chunk=4, spec_len=spec)
            eng = ContinuousServeEngine(
                cfg, params, serving=sv,
                rt=dataclasses.replace(cfg.attention, paged_kernels=False))
            res, stats = eng.serve(
                [ServeRequest(prompt=p, rid=i, sampling=sps[i])
                 for i, p in enumerate(prompts)],
                GenerationConfig(max_new_tokens=10))
            return {i: res[i]["tokens"] for i in res}, stats

        on_t, on_s = run(3)
        off_t, _ = run(0)
        for i in off_t:
            np.testing.assert_array_equal(on_t[i], off_t[i])
        assert on_s["dense_pages_leaked"] == 0
        total_spec += on_s["spec_steps"]
    assert total_spec > 0


def test_speculative_tiered_dense_arm_parity():
    """Tiered engine with dormant watermarks: tier-0 rows speculate, the
    streams match spec-off bit-exactly, and both arenas drain."""
    cfg, params = _mk("qwen1.5-0.5b")
    prompts = _loopy_prompts(cfg, seed=5)
    kw = dict(num_pages=65, escalated_pages=33, enable_escalation=True,
              low_watermark=0.0, critical_watermark=0.0, max_new=8)
    on_t, on_s, eng = _serve(cfg, params, prompts, spec=3, **kw)
    off_t, off_s, _ = _serve(cfg, params, prompts, spec=0, **kw)
    assert eng.tiered and eng.spec_on
    for i in off_t:
        np.testing.assert_array_equal(on_t[i], off_t[i])
    assert on_s["spec_steps"] > 0 and on_s["escalations"] == 0
    assert on_s["dense_pages_leaked"] == 0
    assert on_s["cpq_pages_leaked"] == 0


def test_speculative_with_prefix_sharing_parity():
    """Speculation composes with prefix sharing + COW: shared-prefix
    admissions mount pages that drafts then alias; streams still match the
    both-off run bit-exactly and nothing leaks or dangles."""
    cfg, params = _mk("qwen1.5-0.5b")
    rng = np.random.default_rng(4)
    # a LOOPY shared system prefix: tiled motif, so prompt lookup fires
    sys_p = np.tile(rng.integers(1, cfg.vocab_size, size=4).astype(np.int32),
                    4)
    # 5 prompts over 3 slots: later admissions mount the indexed prefix
    prompts = [np.concatenate([sys_p,
                               rng.integers(1, cfg.vocab_size, size=t)
                               .astype(np.int32)]) for t in (5, 9, 3, 14, 7)]
    both_t, both_s, _ = _serve(cfg, params, prompts, spec=3,
                               share_prefix=True)
    off_t, off_s, _ = _serve(cfg, params, prompts, spec=0,
                             share_prefix=False)
    for i in off_t:
        np.testing.assert_array_equal(both_t[i], off_t[i])
    assert both_s["prefix_hits"] > 0 and both_s["spec_steps"] > 0
    assert both_s["dense_pages_leaked"] == 0


def test_preemption_replay_with_spec_is_exact():
    """A tiny arena forces recompute preemptions WHILE rows speculate:
    victims' drafts abort via the release hook, replays re-draw the same
    fold_in(seed, index) streams, and the final outputs equal the spec-off
    run bit-exactly."""
    cfg, params = _mk("qwen1.5-0.5b")
    prompts = _loopy_prompts(cfg, n=4, motif=4, reps=2, seed=7)
    kw = dict(num_slots=3, num_pages=14, max_blocks_per_slot=8, max_new=12)
    on_t, on_s, _ = _serve(cfg, params, prompts, spec=3, **kw)
    off_t, off_s, _ = _serve(cfg, params, prompts, spec=0, **kw)
    for i in off_t:
        np.testing.assert_array_equal(on_t[i], off_t[i])
    assert on_s["preemptions"] > 0            # pressure actually bit
    assert on_s["spec_steps"] > 0
    assert on_s["dense_pages_leaked"] == 0
    assert off_s["dense_pages_leaked"] == 0


def test_migration_replay_with_spec_is_exact():
    """drain_request mid-stream from engine A and replay on engine B, BOTH
    speculating, seeded sampling: the reassembled stream equals an
    uninterrupted spec-OFF run — speculative state is fully tick-local
    (drafts never outlive a step), so migration needs no draft handoff."""
    cfg, params = _mk("qwen1.5-0.5b")
    prompt = _loopy_prompts(cfg, n=1, motif=4, reps=4, seed=11)[0]
    sp = SamplingParams(temperature=0.3, seed=21, max_tokens=16)
    sv = ServingCfg(num_slots=2, page_size=4, num_pages=33,
                    max_blocks_per_slot=12, prefill_bucket=4, prefill_chunk=4)
    gather = dataclasses.replace(cfg.attention, paged_kernels=False)

    def engine(spec):
        return ContinuousServeEngine(cfg, params, rt=gather,
                                     serving=dataclasses.replace(
                                         sv, spec_len=spec))

    ref = engine(0)
    res, _ = ref.serve([ServeRequest(prompt=prompt, rid=0, sampling=sp)],
                       GenerationConfig(max_new_tokens=16))
    want = res[0]["tokens"]

    a = engine(3)
    a.reset(GenerationConfig(max_new_tokens=16))
    a.add_request(ServeRequest(prompt=prompt, rid=0, sampling=sp))
    for _ in range(12):                       # decode (and speculate) a while
        a.step()
    assert a._st.sched.stats["spec_steps"] > 0
    req = a.drain_request(0)
    assert req is not None and 0 < req.num_generated < 16   # mid-stream
    assert a._st.sched.dense_alloc.num_used == 0

    b = engine(3)
    b.reset(GenerationConfig(max_new_tokens=16))
    b.add_request(req)
    while b.has_unfinished():
        b.step()
    np.testing.assert_array_equal(b.results()[0]["tokens"], want)
    assert b.stats()["dense_pages_leaked"] == 0


_MESH_SPEC_CODE = """
import dataclasses
import numpy as np
import jax
from repro.configs import ARCHS, ServingCfg, smoke_config
from repro.models import model as M
from repro.serving.engine import ContinuousServeEngine, GenerationConfig
from repro.serving.scheduler import Request
from repro.launch.mesh import make_serve_mesh

cfg = dataclasses.replace(smoke_config(ARCHS["qwen1.5-0.5b"]), dtype="float32")
params = M.init_params(cfg, jax.random.PRNGKey(0))
serving = ServingCfg(num_slots=2, page_size=4, num_pages=33,
                     max_blocks_per_slot=8, prefill_bucket=4, prefill_chunk=4)
gather = dataclasses.replace(cfg.attention, paged_kernels=False)
rng = np.random.default_rng(0)
m = rng.integers(1, cfg.vocab_size, size=6).astype(np.int32)
prompts = [np.concatenate([np.tile(m, 3),
                           rng.integers(1, cfg.vocab_size, size=2)
                           .astype(np.int32)]) for _ in range(2)]
gen = GenerationConfig(max_new_tokens=10)

def serve(mesh, spec):
    sv = dataclasses.replace(serving, spec_len=spec)
    eng = ContinuousServeEngine(cfg, params, rt=gather, serving=sv, mesh=mesh)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=10)
            for i, p in enumerate(prompts)]
    return eng.serve(reqs, gen)

mesh = make_serve_mesh(1, 2)
r_off, _ = serve(None, 0)
r_on, s_on = serve(None, 3)
m_on, ms_on = serve(mesh, 3)
for rid in r_off:
    assert np.array_equal(r_off[rid]["tokens"], r_on[rid]["tokens"])
    assert np.array_equal(r_off[rid]["tokens"], m_on[rid]["tokens"]), (
        rid, r_off[rid]["tokens"], m_on[rid]["tokens"])
assert ms_on["spec_steps"] > 0 and ms_on["model_shards"] == 2
assert ms_on["dense_pages_leaked"] == 0
print("MESH-SPEC-OK", ms_on["spec_steps"])
"""


def test_sharded_speculative_greedy_parity():
    """mesh=(dp=1, model=2): speculative decoding under the model mesh is
    token-exact vs both the unsharded spec-on and the spec-off engine —
    the verify chunk routes through the same shard_map'd chunk attend."""
    out = run_with_devices(_MESH_SPEC_CODE, 2)
    assert "MESH-SPEC-OK" in out


# --------------------------------------------------- eligibility gating


def test_spec_opt_out_and_budget_gate():
    """Per-request SamplingParams(speculate=False) opts a row out; a
    1-token budget never drafts (nothing to accept). Outputs unchanged."""
    cfg, params = _mk("qwen1.5-0.5b")
    prompts = _loopy_prompts(cfg, n=2, seed=9)
    sv = ServingCfg(num_slots=2, page_size=4, num_pages=65,
                    max_blocks_per_slot=12, prefill_bucket=4,
                    prefill_chunk=4, spec_len=3)
    eng = ContinuousServeEngine(cfg, params, serving=sv)
    res, stats = eng.serve(
        [ServeRequest(prompt=prompts[0], rid=0,
                      sampling=SamplingParams(max_tokens=12,
                                              speculate=False)),
         ServeRequest(prompt=prompts[1], rid=1,
                      sampling=SamplingParams(max_tokens=1))],
        GenerationConfig(max_new_tokens=12))
    assert eng.spec_on and stats["spec_steps"] == 0
    assert len(res[0]["tokens"]) == 12 and len(res[1]["tokens"]) == 1


def test_spec_gated_off_for_side_state_tiers():
    """CPQ-mode pages read through per-slot side state: the engine gate
    keeps speculation off exactly like prefix sharing."""
    cfg, params = _mk("qwen1.5-0.5b", "cpq")
    prompts = _loopy_prompts(cfg, n=2, seed=1)
    toks, stats, eng = _serve(cfg, params, prompts, spec=3, max_new=6)
    assert not eng.spec_on
    assert stats["spec_steps"] == 0 and not stats["spec_on"]
    for i in toks:
        assert len(toks[i]) == 6


# ------------------------------------ defrag locality regression (ROADMAP 2)


def test_defrag_plan_orders_shared_pages_first():
    """Shared (refcount > 1) pages compact to the LOWEST physical ids —
    stably, keeping first-encounter order within each class — so the pages
    every sharer re-reads cluster in one hot region."""
    bt = np.full((3, 4), NULL_PAGE, np.int64)
    bt[0, :3] = [9, 4, 7]
    bt[1, :3] = [9, 4, 2]        # 9 and 4 are shared
    bt[2, :2] = [5, 7]           # 7 shared too
    perm, new_bt, free = defrag_plan(bt, 12, shared={9, 4, 7})
    # shared first in first-encounter order, then private
    assert list(perm[1:7]) == [9, 4, 7, 2, 5] + [p for p in range(12)
                                                 if p not in (0, 9, 4, 7, 2, 5)][:1]
    assert list(new_bt[0][:3]) == [1, 2, 3]
    assert list(new_bt[1][:3]) == [1, 2, 4]
    assert list(new_bt[2][:2]) == [5, 3]
    # without the hint the order is purely first-encounter
    perm0, _, _ = defrag_plan(bt, 12)
    assert list(perm0[1:6]) == [9, 4, 7, 2, 5]
    # free list unchanged by the partition (same page count)
    assert free == list(range(11, 5, -1))


def test_defrag_keeps_prefix_index_exact():
    """End-to-end compaction regression: retire-churn fragments a sharing
    scheduler, plan_defrag relabels with shared pages first, and the
    prefix index still resolves the template to EXACTLY the pages the
    surviving owner's block table maps (ids renamed, content keys
    untouched) — a follow-up admission keeps mounting them."""
    serving = ServingCfg(num_slots=3, page_size=2, num_pages=33,
                         max_blocks_per_slot=8, prefill_chunk=2,
                         share_prefix=True)
    sched = Scheduler(serving, share_prefix=True)
    template = np.arange(1, 9, dtype=np.int32)          # 4 full pages

    def admit(rid, prompt):
        r = Request(rid=rid, prompt=np.asarray(prompt, np.int32),
                    max_new_tokens=2)
        sched.submit(r)
        sched.admit_next(now=0, step=0)
        while r.length < r.prefill_target:
            while sched.cow_plan(r) is not None:
                pass
            sched.note_chunk(r, serving.page_size)
            sched.register_prefix(r)
        sched.finish_prefill(r)
        return r

    # filler occupies the LOW physical ids; the shared template lands high
    x = admit(9, np.full(8, 30, np.int32))
    a = admit(0, np.concatenate([template, [10, 10]]))
    b = admit(1, np.concatenate([template, [11, 11]]))   # mounts a's prefix
    assert sched.stats["prefix_hits"] >= 1
    sched.retire(x, 0, "eos")                            # holes at the bottom
    perm = sched.plan_defrag()
    assert perm is not None
    _check_refcounts(sched, tiered=False)
    # shared pages (template, refs 2) now sit on the lowest ids
    shared_ids = sorted(p for p in range(1, serving.num_pages)
                        if sched.dense_alloc.refcount(p) > 1)
    private_ids = [p for p in range(1, serving.num_pages)
                   if sched.dense_alloc.refcount(p) == 1]
    assert shared_ids and max(shared_ids) < min(private_ids)
    # the index resolves the template to exactly the owner's mapped pages
    pages, shared_tokens = sched.prefix_index.match(
        np.concatenate([template, [1, 2]]))
    assert shared_tokens == len(template)
    assert pages == [int(p) for p in a.pages[:len(pages)]]
    assert pages == shared_ids[:len(pages)]
    # and a follow-up admission still mounts them (no stale ids anywhere)
    d = admit(3, np.concatenate([template, [12, 12]]))
    assert [int(p) for p in d.pages[:4]] == pages
    for r in list(sched.occupied()):
        sched.retire(r, 0, "eos")
    assert sched.dense_alloc.num_used == 0
    assert len(sched.prefix_index) == 0


def test_defrag_defers_while_draft_open():
    serving = ServingCfg(num_slots=2, page_size=2, num_pages=9,
                         max_blocks_per_slot=4, prefill_chunk=2, spec_len=2)
    sched = Scheduler(serving)

    def admit(rid, prompt):
        r = Request(rid=rid, prompt=np.asarray(prompt, np.int32),
                    max_new_tokens=4)
        sched.submit(r)
        sched.admit_next(now=0, step=0)
        sched.note_chunk(r, len(prompt))
        sched.finish_prefill(r)
        return r

    filler = admit(0, [5, 5, 5])              # pins the low physical ids
    r = admit(1, [1, 2, 3])
    sched.retire(filler, 0, "eos")            # holes below r's pages
    assert sched.begin_draft(r, 2) is not None
    assert sched.plan_defrag() is None        # scratch invisible to tables
    sched.abort_draft(r)
    assert sched.plan_defrag() is not None    # same arena compacts now
    sched.retire(r, 0, "eos")
    assert sched.dense_alloc.num_used == 0
