"""Chunked paged prefill tests (the PR-3 tentpole).

Covers: token-exact greedy parity of chunked vs one-shot admission at f32
(dense / decomposed / MLA / retrieval / tiered; CPQ single-chunk), the
fused Q-chunk>1 paged prefill kernels vs their jnp oracles, split-invariance
of page contents under arbitrary (prompt length, chunk size, page size)
splits (hypothesis), the no-scratch-cache guarantee on the admission path,
and the decode-interleaving property (running rows keep emitting while a
long prompt streams in)."""
import dataclasses

from _hypothesis_compat import hypothesis, st  # optional dep; see pyproject
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, ServingCfg, smoke_config
from repro.configs.base import MLACfg, ModelConfig
from repro.core import attention as core_attn
from repro.models import model as M
from repro.serving import paged_cache as pgc
from repro.serving.engine import ContinuousServeEngine, GenerationConfig
from repro.serving.scheduler import Request

# pure-MLA stack (dense MLPs): the MLA chunked-parity target without the
# expert layer (deepseek-v2-lite, MLA with MoE, is covered below and in
# tests/test_mla_moe.py).
MLA_DENSE = ModelConfig(
    name="mla-dense-test", family="dense", d_model=32, num_heads=4,
    num_kv_heads=4, head_dim=8, d_ff=64, vocab_size=256,
    block_pattern=(("mla", "dense"),), num_blocks=2,
    mla=MLACfg(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
               v_head_dim=8),
    dtype="float32")

_PROMPTS = (5, 12, 3, 21)  # spans 1..3 chunks at chunk=8


def _mk(arch=None, mode=None):
    cfg = MLA_DENSE if arch == "mla-dense" else smoke_config(ARCHS[arch])
    cfg = dataclasses.replace(cfg, dtype="float32")
    if mode:
        cfg = cfg.with_attention(mode)
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _prompts(cfg, sizes=_PROMPTS, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=s).astype(np.int32)
            for s in sizes]


def _serve(cfg, params, prompts, *, prefill_chunk, fused=False, bucket=4,
           max_new=6, **kw):
    base = dict(num_slots=3, page_size=4, num_pages=65, max_blocks_per_slot=8,
                prefill_bucket=bucket, prefill_chunk=prefill_chunk)
    base.update(kw)
    serving = ServingCfg(**base)
    rt = dataclasses.replace(cfg.attention, paged_kernels=fused)
    eng = ContinuousServeEngine(cfg, params, rt=rt, serving=serving)
    res, stats = eng.serve(
        [Request(rid=i, prompt=p, max_new_tokens=max_new)
         for i, p in enumerate(prompts)],
        GenerationConfig(max_new_tokens=max_new))
    return {i: res[i]["tokens"] for i in res}, stats, eng


# ------------------------------------------- chunked vs one-shot parity


@pytest.mark.parametrize("arch,mode", [
    ("qwen1.5-0.5b", None),            # dense K/V pages
    ("qwen1.5-0.5b", "decomposed"),    # T1 X pages (decoupled rope)
    ("opt-6.7b", "decomposed"),        # T1, absolute positions (exact T1)
    ("qwen1.5-0.5b", "retrieval"),     # T3: raw K/V pages + proxy codes
    ("mla-dense", None),               # MLA latent pages, absorbed chunks
])
def test_chunked_equals_oneshot(arch, mode):
    """ACCEPTANCE: chunked admission (prompts streamed into arena pages in
    page-aligned chunks, interleaved with decode) produces token-exact
    greedy output vs the one-shot admission oracle at f32 — on BOTH the jnp
    gather path and the fused Q-chunk>1 paged kernels."""
    cfg, params = _mk(arch, mode)
    prompts = _prompts(cfg)
    one, _, e0 = _serve(cfg, params, prompts, prefill_chunk=0)
    chg, sg, e1 = _serve(cfg, params, prompts, prefill_chunk=8)
    chf, sf, _ = _serve(cfg, params, prompts, prefill_chunk=8, fused=True)
    assert e1.chunked and not e0.chunked
    assert sg["prefill_chunks"] >= sum(-(-s // 8) for s in _PROMPTS[:1])
    for i in one:
        np.testing.assert_array_equal(one[i], chg[i])
        np.testing.assert_array_equal(one[i], chf[i])
    assert sg["dense_pages_leaked"] == 0 and sf["dense_pages_leaked"] == 0


def test_chunked_cpq_single_chunk_exact_and_multi_chunk_consistent():
    """CPQ tiers: a single-chunk admission is token-exact vs the unbucketed
    one-shot oracle (same level-0 fit over the valid tokens, raw within-chunk
    attention). Multi-chunk admissions compress incrementally and read their
    own codes across chunk boundaries — exactly what decode reads — so fused
    and gather agree token-exact at f32 and reruns are deterministic."""
    cfg, params = _mk("qwen1.5-0.5b", "cpq")
    short = _prompts(cfg, sizes=(5, 7, 3, 8))     # all fit one chunk of 8
    one, _, _ = _serve(cfg, params, short, prefill_chunk=0, bucket=1)
    chg, _, _ = _serve(cfg, params, short, prefill_chunk=8)
    chf, _, _ = _serve(cfg, params, short, prefill_chunk=8, fused=True)
    for i in one:
        np.testing.assert_array_equal(one[i], chg[i])
        np.testing.assert_array_equal(one[i], chf[i])

    multi = _prompts(cfg, sizes=(5, 12, 21), seed=1)
    mg, sg, _ = _serve(cfg, params, multi, prefill_chunk=8)
    mf, _, _ = _serve(cfg, params, multi, prefill_chunk=8, fused=True)
    mg2, _, _ = _serve(cfg, params, multi, prefill_chunk=8)
    for i in mg:
        np.testing.assert_array_equal(mg[i], mf[i])   # fused == gather
        np.testing.assert_array_equal(mg[i], mg2[i])  # deterministic
        assert (mg[i] >= 0).all() and (mg[i] < cfg.vocab_size).all()
    assert sg["dense_pages_leaked"] == 0


def test_chunked_decomposed_cpq_and_mla_cpq_valid():
    """T1+T2 and the CPQ latent tier (no fused kernel — gather like their
    decode): multi-chunk admissions stay valid, deterministic, leak-free."""
    for arch, mode in (("qwen1.5-0.5b", "decomposed_cpq"), ("mla-dense", "cpq")):
        cfg, params = _mk(arch, mode)
        prompts = _prompts(cfg, sizes=(5, 12, 21), seed=2)
        a, sa, eng = _serve(cfg, params, prompts, prefill_chunk=8)
        b, _, _ = _serve(cfg, params, prompts, prefill_chunk=8)
        assert eng.chunked
        for i in a:
            np.testing.assert_array_equal(a[i], b[i])
            assert len(a[i]) == 6
            assert (a[i] >= 0).all() and (a[i] < cfg.vocab_size).all()
        assert sa["dense_pages_leaked"] == 0


def test_chunked_tiered_matches_oneshot_and_escalates():
    """Tiered engine: chunked admission through the dense arm is exact vs
    one-shot; mid-request watermark escalation (dense -> T2) composes with
    chunked admission; both arenas end leak-free."""
    cfg, params = _mk("qwen1.5-0.5b")
    prompts = _prompts(cfg, sizes=(8, 10, 6, 7, 9), seed=3)
    kw = dict(num_pages=13, escalated_pages=33, enable_escalation=True,
              low_watermark=0.5, critical_watermark=0.25)
    tg, sg, _ = _serve(cfg, params, prompts, prefill_chunk=8, max_new=10, **kw)
    tf, sf, _ = _serve(cfg, params, prompts, prefill_chunk=8, max_new=10,
                       fused=True, **kw)
    assert sg["escalations"] >= 1 and sf["escalations"] >= 1
    for i in tg:
        np.testing.assert_array_equal(tg[i], tf[i])
    assert sg["dense_pages_leaked"] == 0 and sg["cpq_pages_leaked"] == 0


def test_group_routed_and_recurrent_archs_fall_back_to_oneshot():
    """Recurrent state cannot be cut at page boundaries: those stacks keep
    the exact one-shot admission even when prefill_chunk is set. MoE stacks
    no longer route per token GROUP (the serving expert layer is dropless
    and batch-independent), so they stream chunks, token-exact against
    one-shot admission at f32."""
    for arch, chunks in (("deepseek-v2-lite-16b", True), ("xlstm-125m", False)):
        cfg, params = _mk(arch)
        prompts = _prompts(cfg, sizes=(5, 9), seed=4)
        one, _, e0 = _serve(cfg, params, prompts, prefill_chunk=0, max_new=4)
        fb, sfb, e1 = _serve(cfg, params, prompts, prefill_chunk=16, max_new=4)
        assert e1.chunked == chunks
        assert (sfb["prefill_chunks"] > 0) == chunks
        for i in one:
            np.testing.assert_array_equal(one[i], fb[i])


# ----------------------------------------------- no-scratch-cache guarantee


def test_chunked_admission_allocates_no_scratch_cache(monkeypatch):
    """ACCEPTANCE: the default (chunked) admission path never allocates a
    contiguous scratch prefill cache — M.init_caches is only reachable from
    the one-shot oracle path."""
    cfg, params = _mk("qwen1.5-0.5b")
    prompts = _prompts(cfg)
    eng = ContinuousServeEngine(cfg, params, serving=ServingCfg(
        num_slots=3, page_size=4, num_pages=65, max_blocks_per_slot=8,
        prefill_bucket=4, prefill_chunk=8))
    assert eng.chunked

    def boom(*a, **k):
        raise AssertionError("contiguous scratch prefill cache allocated "
                             "on the chunked admission path")

    monkeypatch.setattr(M, "init_caches", boom)
    res, stats = eng.serve(
        [Request(rid=i, prompt=p, max_new_tokens=4)
         for i, p in enumerate(prompts)],
        GenerationConfig(max_new_tokens=4))
    assert len(res) == len(prompts) and stats["prefill_chunks"] > 0


# -------------------------------------------------- interleaving / latency


def test_long_prompt_no_longer_stalls_running_rows():
    """The head-of-line property the tentpole exists for: while a long
    prompt streams in chunk by chunk, an already-running row keeps emitting
    a token EVERY tick (max inter-token gap 1); under one-shot admission the
    same workload stalls it for the whole monolithic prefill."""
    cfg, params = _mk("qwen1.5-0.5b")
    rng = np.random.default_rng(7)
    short = rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
    long = rng.integers(0, cfg.vocab_size, 32).astype(np.int32)
    reqs = lambda: [Request(rid=0, prompt=short, max_new_tokens=16, arrival=0.0),  # noqa: E731
                    Request(rid=1, prompt=long, max_new_tokens=4, arrival=2.0)]
    kw = dict(num_slots=2, page_size=4, num_pages=65, max_blocks_per_slot=16)
    gen = GenerationConfig(max_new_tokens=16)

    eng_c = ContinuousServeEngine(cfg, params, serving=ServingCfg(
        prefill_bucket=8, prefill_chunk=8, **kw))
    res_c, _ = eng_c.serve(reqs(), gen)
    gaps_c = np.diff(res_c[0]["token_steps"])
    assert gaps_c.max() == 1, gaps_c                 # never stalled

    eng_o = ContinuousServeEngine(cfg, params, serving=ServingCfg(
        prefill_bucket=8, prefill_chunk=0, **kw))
    res_o, _ = eng_o.serve(reqs(), gen)
    gaps_o = np.diff(res_o[0]["token_steps"])
    assert gaps_o.max() >= -(-len(long) // 8)        # monolithic stall
    # and the long prompt's first token is not delayed by chunking
    assert res_c[1]["first_token_step"] <= res_o[1]["first_token_step"] + 1


# --------------------------------------- split-invariance (property tests)


def check_chunk_split_invariance(seed, S, chunk, page_size):
    """Writing a prompt through ANY (chunk size, page size) split leaves
    identical page contents (every page, null page excluded) and identical
    lengths as the unsplit reference write."""
    rng = np.random.default_rng(seed)
    feat = 3
    nb = -(-S // page_size)
    num_pages = nb + 2
    vals = jnp.asarray(rng.normal(size=(S, feat)).astype(np.float32))
    block_row = jnp.asarray(np.arange(1, nb + 1, dtype=np.int32))

    def write_chunked(C):
        pages = jnp.zeros((num_pages, page_size, feat))
        off = 0
        while off < S:
            valid = min(C, S - off)
            buf = jnp.zeros((C, feat)).at[:valid].set(vals[off:off + valid])
            pages = pgc.write_chunk_pages(pages, block_row,
                                          jnp.asarray(off, jnp.int32),
                                          jnp.asarray(valid, jnp.int32), buf)
            off += valid
        return np.asarray(pages)

    ref = np.asarray(pgc.write_prompt_pages(
        jnp.zeros((num_pages, page_size, feat)), block_row, vals))
    got = write_chunked(chunk)
    np.testing.assert_array_equal(got[1:], ref[1:])  # all non-null pages
    logical = pgc.gather_pages(jnp.asarray(got), block_row[None])[0]
    np.testing.assert_array_equal(np.asarray(logical[:S]), np.asarray(vals))


@pytest.mark.parametrize("seed,S,chunk,page_size", [
    (0, 12, 4, 4), (1, 21, 8, 4), (2, 5, 8, 2), (3, 16, 16, 8), (4, 7, 2, 2),
])
def test_chunk_split_invariance_deterministic(seed, S, chunk, page_size):
    check_chunk_split_invariance(seed, S, chunk, page_size)


@hypothesis.given(seed=st.integers(0, 2 ** 16), S=st.integers(1, 48),
                  chunk=st.integers(1, 24), page_size=st.integers(1, 8))
@hypothesis.settings(max_examples=40, deadline=None)
def test_chunk_split_invariance_property(seed, S, chunk, page_size):
    check_chunk_split_invariance(seed, S, chunk, page_size)


def test_engine_chunked_pages_match_oneshot_pack():
    """Model-level: streaming a prompt through prefill_chunk_rows leaves the
    SAME dense K/V page contents (on valid positions) and lengths as the
    one-shot prefill + pack path, for every split of the same prompt."""
    cfg, params = _mk("qwen1.5-0.5b")
    rt = cfg.attention
    rng = np.random.default_rng(5)
    S = 13
    prompt = rng.integers(0, cfg.vocab_size, S).astype(np.int32)
    serving = ServingCfg(num_slots=2, page_size=4, num_pages=17,
                         max_blocks_per_slot=8, prefill_bucket=4,
                         prefill_chunk=8)
    nb_needed = -(-S // 4)
    block_row = np.zeros((8,), np.int32)
    block_row[:nb_needed] = np.arange(1, nb_needed + 1)

    # one-shot: contiguous prefill packed into the pages
    caches1 = M.init_paged_caches(cfg, rt, serving, False)
    ctg = M.init_caches(cfg, rt, 1, 16)
    padded = np.concatenate([prompt, np.full((3,), prompt[-1], np.int32)])
    from functools import partial
    _, ctg = jax.jit(partial(M.prefill, cfg, rt))(
        params, {"tokens": jnp.asarray(padded[None])}, ctg,
        jnp.asarray(S - 1, jnp.int32))
    caches1 = jax.jit(partial(M.pack_prefill_caches, cfg, rt))(
        caches1, ctg, jnp.asarray(block_row), jnp.asarray(0, jnp.int32))

    def run_chunked(C):
        caches = M.init_paged_caches(cfg, rt, serving, False)
        off = 0
        while off < S:
            valid = min(C, S - off)
            ch = prompt[off:off + valid]
            if valid < C:
                ch = np.concatenate([ch, np.full((C - valid,), ch[-1], np.int32)])
            fn = partial(M.prefill_chunk_rows, cfg, rt, 0, off == 0)
            _, caches = jax.jit(fn)(
                params, jnp.asarray(ch[None]), jnp.asarray(0, jnp.int32),
                jnp.asarray(block_row), jnp.asarray(off, jnp.int32),
                jnp.asarray(valid, jnp.int32), caches)
            off += valid
        return caches

    def all_dense_k(caches):
        out = []
        for c in jax.tree.leaves(caches, is_leaf=lambda x: isinstance(
                x, pgc.PagedDenseKVCache)):
            if isinstance(c, pgc.PagedDenseKVCache):
                k = c.k  # (P, page, KV*Dh) or stacked (nb, P, page, KV*Dh)
                ks = k[None] if k.ndim == 3 else k
                for j in range(ks.shape[0]):
                    out.append(np.asarray(pgc.gather_pages(
                        ks[j], jnp.asarray(block_row[None])))[0, :S])
        assert out, "no dense paged caches found"
        return out

    ref = all_dense_k(caches1)
    for C in (4, 8, 12):
        got = all_dense_k(run_chunked(C))
        assert len(got) == len(ref)
        for a, b in zip(got, ref):
            np.testing.assert_allclose(a, b, rtol=0, atol=0)


# -------------------------------------------------- kernel-level oracles


def _rand_paged_dense(rng, P, page, KV, Dh, Dv, layers=3):
    """Stacked lane-dense pools (layers, P, page, KV*D), every layer with
    its own contents."""
    k = jnp.asarray(rng.normal(size=(layers, P, page, KV * Dh)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(layers, P, page, KV * Dv)).astype(np.float32))
    return k, v


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("seed,offset,valid", [
    (0, 0, 8), (1, 8, 8), (2, 8, 3), (3, 4, 1), (4, 12, 5)])
def test_paged_flash_prefill_kernel_vs_oracle(seed, offset, valid, layer):
    """Q-chunk>1 paged flash prefill at ``layer`` of a stacked arena ==
    dense attention over that layer's gathered logical view with
    (q_offset, kv_length) masking, on permuted pages."""
    from repro.kernels.flash_attn.ops import paged_flash_prefill_tpu

    rng = np.random.default_rng(seed)
    page, KV, Dh, Dv, C, H = 4, 2, 8, 8, 8, 4
    nb = 8
    P = nb + 2
    k, v = _rand_paged_dense(rng, P, page, KV, Dh, Dv)
    block_row = jnp.asarray(rng.permutation(np.arange(1, nb + 1)
                                            ).astype(np.int32))
    q = jnp.asarray(rng.normal(size=(1, C, H, Dh)).astype(np.float32))
    out = paged_flash_prefill_tpu(q, k, v, block_row,
                                  jnp.asarray(offset, jnp.int32),
                                  jnp.asarray(valid, jnp.int32), layer, 0.35)
    ref = core_attn.dense_attention(
        q, pgc.gather_layer_heads(k, layer, block_row[None], KV),
        pgc.gather_layer_heads(v, layer, block_row[None], KV), 0.35,
        causal=True, q_offset=jnp.asarray(offset, jnp.int32),
        kv_length=jnp.asarray(offset + valid, jnp.int32))
    np.testing.assert_allclose(np.asarray(out)[0, :valid],
                               np.asarray(ref)[0, :valid], atol=2e-5)


@pytest.mark.parametrize("seed,offset,valid,kv_r", [
    (0, 0, 8, 1), (1, 8, 4, 1), (2, 4, 8, 2), (3, 12, 2, 2)])
def test_paged_decomposed_prefill_kernel_vs_oracle(seed, offset, valid, kv_r):
    """Q-chunk>1 paged decomposed prefill == decomposed_attention over the
    gathered X view with causal query positions (shared and per-kv rope)."""
    from repro.core.decomposed_attention import decomposed_attention
    from repro.kernels.decomposed_attn.ops import paged_decomposed_prefill_tpu

    rng = np.random.default_rng(seed)
    page, Dm, C, H, Dn, Dv, Rr = 4, 16, 8, 4, 8, 8, 4
    nb = 8
    P = nb + 2
    x = jnp.asarray(rng.normal(size=(P, page, Dm)).astype(np.float32))
    kr = jnp.asarray(rng.normal(size=(P, page, kv_r, Rr)).astype(np.float32))
    block_row = jnp.asarray(rng.permutation(np.arange(1, nb + 1)
                                            ).astype(np.int32))
    q_nope = jnp.asarray(rng.normal(size=(1, C, H, Dn)).astype(np.float32))
    q_rope = jnp.asarray(rng.normal(size=(1, C, H, Rr)).astype(np.float32))
    w_k = jnp.asarray(rng.normal(size=(Dm, H, Dn)).astype(np.float32))
    w_v = jnp.asarray(rng.normal(size=(Dm, H, Dv)).astype(np.float32))
    out = paged_decomposed_prefill_tpu(
        q_nope, q_rope, x, kr, block_row, jnp.asarray(offset, jnp.int32),
        jnp.asarray(valid, jnp.int32), w_k, w_v, 0.3)
    ref = decomposed_attention(
        q_nope, q_rope, pgc.gather_pages(x, block_row[None]),
        pgc.gather_pages(kr, block_row[None]), w_k, w_v,
        jnp.asarray(offset + valid, jnp.int32), 0.3,
        query_positions=offset + jnp.arange(C, dtype=jnp.int32))
    np.testing.assert_allclose(np.asarray(out)[0, :valid],
                               np.asarray(ref)[0, :valid], atol=2e-4)


@pytest.mark.parametrize("seed,offset,valid", [(0, 0, 8), (1, 8, 4), (2, 12, 8)])
def test_paged_cpq_prefill_kernel_vs_oracle(seed, offset, valid):
    """Q-chunk>1 paged CPQ prefill kernel == the jnp gather oracle
    (dequantized earlier pages + raw causal chunk tail)."""
    from repro.configs.base import CPQCfg
    from repro.core import cpq as cpq_lib
    from repro.kernels.cpq_dequant_attn.ops import paged_cpq_prefill_tpu

    rng = np.random.default_rng(seed)
    cfgq = CPQCfg(max_levels=3)
    page, KV, Dh, C, H = 4, 2, 8, 8, 4
    nb = 8
    P = nb + 2
    num_slots = 2

    kt = pgc._init_paged_cpq_tensor(P, page, num_slots, KV, Dh, cfgq)
    vt = pgc._init_paged_cpq_tensor(P, page, num_slots, KV, Dh, cfgq)

    def fill(t, seed2):
        r2 = np.random.default_rng(seed2)
        return t._replace(
            codes=jnp.asarray(r2.integers(-128, 127, size=t.codes.shape,
                                          dtype=np.int64).astype(np.int8)),
            level=jnp.asarray(r2.integers(0, cfgq.max_levels,
                                          size=t.level.shape).astype(np.int32)),
            scale=jnp.asarray(np.abs(r2.normal(size=t.scale.shape)
                                     ).astype(np.float32) + 0.05),
            zero=jnp.asarray(r2.normal(size=t.zero.shape).astype(np.float32)))

    kt, vt = fill(kt, seed + 10), fill(vt, seed + 20)
    block_row = jnp.asarray(rng.permutation(np.arange(1, nb + 1)
                                            ).astype(np.int32))
    slot = jnp.asarray(1, jnp.int32)
    q = jnp.asarray(rng.normal(size=(1, C, H, Dh)).astype(np.float32))
    k_raw = jnp.asarray(rng.normal(size=(1, C, KV, Dh)).astype(np.float32))
    v_raw = jnp.asarray(rng.normal(size=(1, C, KV, Dh)).astype(np.float32))

    out = paged_cpq_prefill_tpu(q, kt, vt, k_raw, v_raw, slot, block_row,
                                jnp.asarray(offset, jnp.int32),
                                jnp.asarray(valid, jnp.int32), 0.3)
    ref = pgc.cpq_chunk_prefill_attention(
        q, kt, vt, block_row, slot, k_raw, v_raw,
        jnp.asarray(offset, jnp.int32), jnp.asarray(valid, jnp.int32), 0.3)
    np.testing.assert_allclose(np.asarray(out)[0, :valid],
                               np.asarray(ref)[0, :valid], atol=3e-5)
