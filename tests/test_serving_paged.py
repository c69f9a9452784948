"""Paged KV-cache arena tests: allocator/defrag invariants (deterministic +
property-based), page-plumbing round trips, paged-vs-contiguous decode
equivalence across cache modes, and fused paged-kernel engine parity."""
import dataclasses

from _hypothesis_compat import hypothesis, st  # optional dep; see pyproject test extra
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, ServingCfg, smoke_config
from repro.configs.base import CPQCfg, RetrievalCfg
from repro.core import kv_cache as kvc
from repro.models import model as M
from repro.serving import paged_cache as pgc
from repro.serving.engine import ContinuousServeEngine, GenerationConfig, ServeEngine
from repro.serving.scheduler import Request


# ---------------------------------------------------------------- allocator


def test_page_allocator_invariants():
    a = pgc.PageAllocator(9)  # pages 1..8 allocatable
    assert a.num_free == 8 and a.num_used == 0
    p1 = a.alloc(3)
    assert len(set(p1)) == 3 and pgc.NULL_PAGE not in p1
    assert a.num_used == 3 and abs(a.utilization - 3 / 8) < 1e-9
    with pytest.raises(pgc.PageAllocator.OutOfPages):
        a.alloc(6)
    a.free(p1[:2])
    assert a.num_free == 7
    with pytest.raises(pgc.PageAllocator.DoubleFree):  # double free RAISES
        a.free([p1[0]])
    with pytest.raises(pgc.PageAllocator.DoubleFree):  # null page never owned
        a.free([pgc.NULL_PAGE])


def test_pages_needed():
    assert pgc.pages_needed(0, 4) == 0
    assert pgc.pages_needed(1, 4) == 1
    assert pgc.pages_needed(4, 4) == 1
    assert pgc.pages_needed(5, 4) == 2


def test_defrag_compacts_and_preserves_views():
    rng = np.random.default_rng(0)
    num_pages, page, kv, dh = 17, 4, 2, 3
    pages = jnp.asarray(rng.normal(size=(num_pages, page, kv, dh)).astype(np.float32))
    # two slots with scattered pages
    bt = np.zeros((2, 4), np.int32)
    bt[0, :3] = [9, 2, 14]
    bt[1, :2] = [7, 11]
    before = np.asarray(pgc.gather_pages(pages, jnp.asarray(bt)))
    perm, new_bt, free = pgc.defrag_plan(bt, num_pages)
    new_pages = jnp.take(pages, jnp.asarray(perm), axis=0)
    after = np.asarray(pgc.gather_pages(new_pages, jnp.asarray(new_bt)))
    np.testing.assert_array_equal(before, after)
    # compaction: mapped pages occupy the lowest non-null ids
    mapped = sorted(p for p in new_bt.flatten() if p != pgc.NULL_PAGE)
    assert mapped == list(range(1, 6))
    assert set(free) == set(range(6, num_pages))


def check_allocator_cycle(seed, num_pages, n_ops):
    """Model-based allocator check: random alloc/free/preempt-style cycles
    never double-allocate, never hand out the null page, never leak, and
    raise OutOfPages exactly when the demand exceeds the free count."""
    rng = np.random.default_rng(seed)
    a = pgc.PageAllocator(num_pages)
    outstanding: list[list[int]] = []   # "requests" holding page lists
    ever_allocated = set()
    for _ in range(n_ops):
        assert a.num_free + a.num_used == num_pages - 1     # conservation
        op = rng.random()
        if op < 0.55:                                       # alloc a request
            n = int(rng.integers(1, max(num_pages // 3, 2)))
            if n > a.num_free:
                with pytest.raises(pgc.PageAllocator.OutOfPages):
                    a.alloc(n)
                continue
            pages = a.alloc(n)
            assert len(pages) == n == len(set(pages))       # no dup in grant
            assert pgc.NULL_PAGE not in pages
            held = {p for req in outstanding for p in req}
            assert not held & set(pages)                    # no double alloc
            assert all(0 < p < num_pages for p in pages)
            ever_allocated.update(pages)
            outstanding.append(pages)
        elif outstanding:                                   # retire/preempt
            req = outstanding.pop(int(rng.integers(0, len(outstanding))))
            a.free(req)
    for req in outstanding:                                 # drain: leak-free
        a.free(req)
    assert a.num_used == 0 and a.num_free == num_pages - 1


@pytest.mark.parametrize("seed", range(4))
def test_allocator_cycles_deterministic(seed):
    check_allocator_cycle(seed, num_pages=17, n_ops=120)


@hypothesis.given(seed=st.integers(0, 2 ** 16), num_pages=st.integers(2, 33))
@hypothesis.settings(max_examples=25, deadline=None)
def test_allocator_cycles_property(seed, num_pages):
    check_allocator_cycle(seed, num_pages, n_ops=60)


def check_defrag_roundtrip(seed, num_pages, n_slots, max_blocks, page, feat):
    """defrag_plan followed by the page moves preserves every live token
    (gathered logical contents identical), compacts mapped pages onto the
    lowest ids, and rebuilds a consistent free list."""
    rng = np.random.default_rng(seed)
    pages = jnp.asarray(rng.normal(size=(num_pages, page, feat)).astype(np.float32))
    avail = rng.permutation(np.arange(1, num_pages)).tolist()
    bt = np.zeros((n_slots, max_blocks), np.int32)
    for s in range(n_slots):
        for j in range(int(rng.integers(0, max_blocks + 1))):
            if not avail:
                break
            bt[s, j] = avail.pop()
    before = np.asarray(pgc.gather_pages(pages, jnp.asarray(bt)))
    perm, new_bt, free = pgc.defrag_plan(bt, num_pages)
    moved = jnp.take(pages, jnp.asarray(perm), axis=0)
    after = np.asarray(pgc.gather_pages(moved, jnp.asarray(new_bt)))
    np.testing.assert_array_equal(before, after)            # live tokens kept
    mapped = sorted({int(p) for p in new_bt.flatten() if p != pgc.NULL_PAGE})
    assert mapped == list(range(1, len(mapped) + 1))        # compacted
    assert set(free) == set(range(num_pages)) - {pgc.NULL_PAGE} - set(mapped)
    assert len(perm) == num_pages and sorted(perm) == list(range(num_pages))


@pytest.mark.parametrize("seed", range(4))
def test_defrag_roundtrip_deterministic(seed):
    check_defrag_roundtrip(seed, num_pages=19, n_slots=3, max_blocks=4,
                           page=4, feat=3)


@hypothesis.given(seed=st.integers(0, 2 ** 16), num_pages=st.integers(2, 25),
                  n_slots=st.integers(1, 4))
@hypothesis.settings(max_examples=25, deadline=None)
def test_defrag_roundtrip_property(seed, num_pages, n_slots):
    check_defrag_roundtrip(seed, num_pages, n_slots, max_blocks=3, page=2,
                           feat=2)


# ------------------------------------------------------------ page plumbing


def test_prompt_and_token_writes_roundtrip():
    page, max_blocks = 4, 4
    pages = jnp.zeros((9, page, 3))
    block_row = jnp.asarray([2, 5, 0, 0], jnp.int32)  # 2 pages mapped
    vals = jnp.arange(6 * 3, dtype=jnp.float32).reshape(6, 3)
    pages = pgc.write_prompt_pages(pages, block_row, vals)
    bt = jnp.asarray([[2, 5, 0, 0]], jnp.int32)
    logical = pgc.gather_pages(pages, bt)[0]
    np.testing.assert_array_equal(np.asarray(logical[:6]), np.asarray(vals))

    # append one token at position 6 (same page as slots 4..7)
    rows_active = jnp.asarray([True])
    tok = jnp.full((1, 3), 7.0)
    pages = pgc.write_token_pages(pages, bt, jnp.asarray([6]), rows_active, tok)
    logical = pgc.gather_pages(pages, bt)[0]
    np.testing.assert_array_equal(np.asarray(logical[6]), np.asarray(tok[0]))
    # inactive rows write the null page, never their mapped pages (the
    # logical view beyond the mapped blocks reads the null page and is
    # masked by lengths downstream, so only the first 8 slots matter)
    pages2 = pgc.write_token_pages(pages, bt, jnp.asarray([7]), jnp.asarray([False]),
                                   jnp.full((1, 3), -1.0))
    np.testing.assert_array_equal(np.asarray(pgc.gather_pages(pages2, bt)[0][:8]),
                                  np.asarray(logical[:8]))


def test_prompt_write_past_capacity_hits_null_page():
    """Bucket padding beyond max_blocks*page must land on the null page, not
    wrap around onto the slot's last mapped page (regression)."""
    page = 4
    pages = jnp.zeros((5, page, 2))
    block_row = jnp.asarray([1, 2], jnp.int32)        # capacity 8 tokens
    vals = jnp.ones((12, 2))                          # 4 tokens past capacity
    pages = pgc.write_prompt_pages(pages, block_row, vals)
    logical = pgc.gather_pages(pages, block_row[None])[0]
    np.testing.assert_array_equal(np.asarray(logical[:8]), np.ones((8, 2)))
    # overflow went to page 0, mapped pages untouched beyond their 8 slots
    assert np.asarray(pages[0]).sum() > 0
    np.testing.assert_array_equal(np.asarray(pages[3]), np.zeros((page, 2)))


# ------------------------------------------------- decode-path equivalence


def _mk(arch="qwen1.5-0.5b", mode=None):
    cfg = smoke_config(ARCHS[arch])
    if mode:
        cfg = cfg.with_attention(mode)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _static_refs(cfg, params, prompts, gen):
    eng = ServeEngine(cfg, params, max_len=64)
    return [eng.generate({"tokens": jnp.asarray(p[None])}, gen)[0][0] for p in prompts]


_PROMPT_LENS = (5, 12, 3, 9)


def _prompts(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=s).astype(np.int32)
            for s in _PROMPT_LENS]


def _gather(cfg):
    """The jnp gather path in place of the fused paged kernels."""
    return dataclasses.replace(cfg.attention, paged_kernels=False)


def test_paged_dense_greedy_equals_contiguous():
    """The PR-1 equivalence: mixed prompt lengths, greedy, paged continuous
    decode == contiguous dense decode, token for token. Pinned to the jnp
    gather path (``paged_kernels=False``), which shares every op with the
    static engine — construction-exact at any dtype. Fused-kernel parity is
    covered by test_paged_kernel_engine_parity (f32) below."""
    cfg, params = _mk()
    gen = GenerationConfig(max_new_tokens=6)
    prompts = _prompts(cfg)
    refs = _static_refs(cfg, params, prompts, gen)
    serving = ServingCfg(num_slots=4, page_size=4, num_pages=41,
                         max_blocks_per_slot=8, prefill_bucket=4,
                         prefill_chunk=0)  # one-shot oracle: shares static ops
    eng = ContinuousServeEngine(cfg, params, rt=_gather(cfg), serving=serving)
    res, stats = eng.serve(
        [Request(rid=i, prompt=p, max_new_tokens=6) for i, p in enumerate(prompts)],
        gen)
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(res[i]["tokens"], ref)
    assert stats["dense_pages_leaked"] == 0


@pytest.mark.parametrize("arch,mode", [
    ("opt-6.7b", "decomposed"),        # absolute positions: T1 exact
    ("qwen1.5-0.5b", "decomposed"),    # rope: decoupled T1
    ("qwen1.5-0.5b", "retrieval"),     # T3
    ("deepseek-v2-lite-16b", "decomposed"),  # MLA latent cache
    ("jamba-1.5-large-398b", None),    # hybrid: paged attn + slot SSM state
    ("xlstm-125m", None),              # pure recurrent (exact prefill path)
])
def test_paged_modes_match_contiguous(arch, mode):
    cfg, params = _mk(arch, mode)
    gen = GenerationConfig(max_new_tokens=5)
    prompts = _prompts(cfg, seed=1)
    refs = _static_refs(cfg, params, prompts, gen)
    serving = ServingCfg(num_slots=4, page_size=4, num_pages=65,
                         max_blocks_per_slot=8, prefill_bucket=4,
                         prefill_chunk=0)  # one-shot oracle: shares static ops
    # gather path == static ops
    eng = ContinuousServeEngine(cfg, params, rt=_gather(cfg), serving=serving)
    res, _ = eng.serve(
        [Request(rid=i, prompt=p, max_new_tokens=5) for i, p in enumerate(prompts)],
        gen)
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(res[i]["tokens"], ref)


@pytest.mark.parametrize("mode", ["cpq", "decomposed_cpq"])
def test_paged_cpq_modes_match_with_unbucketed_prefill(mode):
    """CPQ prefill statistics are fitted over the (possibly padded) prompt, so
    exact equality with the contiguous path needs prefill_bucket=1 (no
    padding). Bucketed admission stays VALID, just not bit-identical."""
    cfg, params = _mk(mode=mode)
    gen = GenerationConfig(max_new_tokens=5)
    prompts = _prompts(cfg, seed=2)
    refs = _static_refs(cfg, params, prompts, gen)
    serving = ServingCfg(num_slots=4, page_size=4, num_pages=65,
                         max_blocks_per_slot=8, prefill_bucket=1,
                         prefill_chunk=0)  # one-shot oracle: shares static ops
    # gather path == static ops
    eng = ContinuousServeEngine(cfg, params, rt=_gather(cfg), serving=serving)
    res, _ = eng.serve(
        [Request(rid=i, prompt=p, max_new_tokens=5) for i, p in enumerate(prompts)],
        gen)
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(res[i]["tokens"], ref)


# -------------------------------------------- fused paged-kernel parity


def _serve_tokens(cfg, params, prompts, *, paged_kernels, tiered=False,
                  max_new=8):
    kw = dict(num_slots=3, page_size=4, num_pages=65, max_blocks_per_slot=8,
              prefill_bucket=4)
    if tiered:
        kw.update(num_pages=13, escalated_pages=33, enable_escalation=True,
                  low_watermark=0.5, critical_watermark=0.25)
    rt = dataclasses.replace(cfg.attention, paged_kernels=paged_kernels)
    eng = ContinuousServeEngine(cfg, params, rt=rt, serving=ServingCfg(**kw))
    res, stats = eng.serve(
        [Request(rid=i, prompt=p, max_new_tokens=max_new)
         for i, p in enumerate(prompts)],
        GenerationConfig(max_new_tokens=max_new))
    return {i: res[i]["tokens"] for i in res}, stats


@pytest.mark.parametrize("arch,mode,tiered", [
    ("qwen1.5-0.5b", None, False),           # dense -> paged flash kernel
    ("qwen1.5-0.5b", "cpq", False),          # T2 -> paged CPQ-dequant kernel
    ("qwen1.5-0.5b", "decomposed", False),   # T1 -> paged decomposed kernel
    ("deepseek-v2-lite-16b", None, False),   # MLA latent -> paged decomposed
    ("qwen1.5-0.5b", None, True),            # tiered dense+CPQ dispatch
])
def test_paged_kernel_engine_parity(arch, mode, tiered):
    """ACCEPTANCE: the fused paged kernels (dense flash, CPQ-dequant, X/MLA
    decomposed — and the tiered dispatch over the first two) produce
    token-exact greedy output vs the PR-1 gather-based decode on the
    continuous engine. Run at f32 so both paths agree to reduction-order
    epsilon; the jnp gather oracle's bf16 rounding points are an XLA-fusion
    artifact no kernel can reproduce bit-for-bit at bf16."""
    cfg = smoke_config(ARCHS[arch])
    cfg = dataclasses.replace(cfg, dtype="float32")
    if mode:
        cfg = cfg.with_attention(mode)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg, seed=3) + _prompts(cfg, seed=4)
    fused, fstats = _serve_tokens(cfg, params, prompts,
                                  paged_kernels=True, tiered=tiered)
    gather, _ = _serve_tokens(cfg, params, prompts,
                              paged_kernels=False, tiered=tiered)
    assert set(fused) == set(gather) == set(range(len(prompts)))
    for i in fused:
        np.testing.assert_array_equal(fused[i], gather[i])
    if tiered:
        assert fstats["escalations"] >= 1  # the tiered dispatch really ran
    assert fstats["dense_pages_leaked"] == 0


def test_paged_kernel_bf16_decode_is_valid():
    """At the default bf16 the fused kernels are not bit-identical to the
    gather oracle (different rounding points), but decode must stay finite,
    in-vocab, and leak-free across all slots and steps."""
    cfg, params = _mk()  # bf16 smoke model, fused kernels on by default
    prompts = _prompts(cfg, seed=5)
    toks, stats = _serve_tokens(cfg, params, prompts, paged_kernels=True)
    assert set(toks) == set(range(len(prompts)))
    for i in toks:
        assert len(toks[i]) == 8
        assert (toks[i] >= 0).all() and (toks[i] < cfg.vocab_size).all()
    assert stats["dense_pages_leaked"] == 0


# ----------------------------------------------------------------- traffic


def test_bytes_per_token_every_container():
    """Satellite: every cache container reports traffic through ONE API —
    including the CPQ modes that used to raise TypeError."""
    cpq = CPQCfg()
    dense = kvc.init_dense(1, 8, 2, 4)
    x = kvc.init_x(1, 8, 16, 2, 4)
    cq = kvc.init_cpq(1, 8, 2, 4, cpq)
    ret = kvc.init_retrieval(1, 8, 2, 4, RetrievalCfg())
    cqx = kvc.init_cpq_x(1, 8, 16, 2, 4, cpq)
    vals = {c.__class__.__name__: kvc.bytes_per_token(c, cpq)
            for c in (dense, x, cq, ret, cqx)}
    assert all(v > 0 for v in vals.values()), vals
    assert vals["CPQKVCache"] < vals["DenseKVCache"]   # T2 compresses
    assert vals["CPQXCache"] < vals["XCache"]          # T1+T2 < T1

    serving = ServingCfg(num_slots=2, page_size=4, num_pages=9)
    paged = [
        pgc.init_paged_dense(9, 4, 2, 4),
        pgc.init_paged_x(9, 4, 16, 2, 4),
        pgc.init_paged_cpq(9, 4, 2, 2, 4, cpq),
        pgc.init_paged_retrieval(9, 4, 2, 2, 4, RetrievalCfg()),
        pgc.init_paged_cpq_x(9, 4, 2, 16, 2, 4, cpq),
    ]
    for contiguous, p in zip((dense, x, cq, ret, cqx), paged):
        bp = pgc.bytes_per_token(p, serving.page_size, cpq)
        bc = kvc.bytes_per_token(contiguous, cpq)
        # paged = payload + amortized block-table entry
        assert abs(bp - (bc + 4.0 / serving.page_size)) < 1e-6
        assert pgc.arena_bytes(p) > 0
