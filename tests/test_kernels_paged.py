"""Paged Pallas decode kernels vs oracles (interpret=True).

Property tests (via the optional-hypothesis shim) and deterministic seed
sweeps share the same checkers, so the invariants are exercised even where
hypothesis is not installed. Each checker builds a physical page pool with:

  * a POISONED null page (page 0 filled with huge garbage — the layout
    convention says its contents must never reach an output),
  * PERMUTED physical page order (block tables need not be contiguous or
    sorted),
  * RAGGED per-row lengths including empty (length-0) rows and partial last
    pages,

and asserts the fused kernel matches the oracle computed straight from
``(pages, block_table, lengths)`` to fp tolerance, that outputs are invariant
under a physical-page relabeling, and that greedy argmax matches exactly
whenever the oracle's top-2 gap is resolvable (near-ties are skipped — they
are decided by reduction-order epsilon in any implementation).
"""
from _hypothesis_compat import hypothesis, st  # optional dep; see pyproject test extra
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import CPQCfg
from repro.core import cpq as C

ARGMAX_GAP = 1e-4  # top-2 oracle gap below which greedy ties are ignored
LAYERS = 3         # layers of the stacked dense arenas the flash kernels read


def _pool_layout(rng, B, nb, page):
    """Random paged layout: per-row lengths (0..capacity), pages assigned in
    PERMUTED physical order, unmapped entries left at the null page 0."""
    num_pages = 1 + B * nb + int(rng.integers(0, 4))  # spare pages stay stale
    lengths = np.array([int(rng.integers(0, nb * page + 1)) for _ in range(B)],
                       np.int32)
    if B > 1 and rng.random() < 0.5:
        lengths[int(rng.integers(0, B))] = 0          # force an empty row
    perm = rng.permutation(np.arange(1, num_pages)).tolist()
    bt = np.zeros((B, nb), np.int32)
    for b in range(B):
        for j in range(-(-int(lengths[b]) // page)):
            bt[b, j] = perm.pop()
    return num_pages, lengths, bt


def _relabel(pools, bt, num_pages, rng, axis=0):
    """Apply a random physical-page relabeling (defrag analogue) to the page
    axis ``axis`` of each pool: outputs must be bitwise invariant."""
    perm = np.concatenate([[0], rng.permutation(np.arange(1, num_pages))])
    inv = np.argsort(perm)
    return ([np.take(np.asarray(p), perm, axis=axis) for p in pools],
            inv[bt].astype(np.int32))


def _stacked_heads(rng, num_pages, page, KV, D):
    """Per-head pools (LAYERS, P, page, KV, D) with different contents in
    every layer and the null page poisoned in each, so a kernel that reads
    the wrong layer or the null page fails."""
    heads = rng.normal(size=(LAYERS, num_pages, page, KV, D)).astype(np.float32)
    heads[:, 0] = 1e3
    return heads


def _lane_dense(heads, dtype=jnp.float32):
    """The serving arena's form of per-head pools: (L, P, page, KV*D)."""
    return jnp.asarray(heads.reshape(heads.shape[:3] + (-1,)), dtype)


def _argmax_where_resolvable(out, ref):
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    flat_o = out.reshape(-1, out.shape[-1])
    flat_r = ref.reshape(-1, ref.shape[-1])
    top2 = np.sort(flat_r, axis=-1)
    resolvable = (top2[:, -1] - top2[:, -2]) > ARGMAX_GAP
    np.testing.assert_array_equal(flat_o.argmax(-1)[resolvable],
                                  flat_r.argmax(-1)[resolvable])


# ------------------------------------------------------------- dense / flash


def check_paged_flash(seed, page, nb, B, KV, g, Dh, dtype=jnp.float32,
                      layer=0):
    """The kernel reads layer ``layer`` of a stacked lane-dense arena; the
    oracle reads that layer's per-head pools."""
    from repro.kernels.flash_attn.ops import paged_flash_decode_tpu
    from repro.kernels.flash_attn.ref import paged_flash_decode_ref

    rng = np.random.default_rng(seed)
    num_pages, lengths, bt = _pool_layout(rng, B, nb, page)
    kh = _stacked_heads(rng, num_pages, page, KV, Dh)
    vh = _stacked_heads(rng, num_pages, page, KV, Dh)
    q = jnp.asarray(rng.normal(size=(B, 1, KV * g, Dh)).astype(np.float32),
                    dtype)
    out = paged_flash_decode_tpu(q, _lane_dense(kh, dtype),
                                 _lane_dense(vh, dtype), jnp.asarray(bt),
                                 jnp.asarray(lengths), layer, Dh ** -0.5)
    ref = paged_flash_decode_ref(q, jnp.asarray(kh[layer], dtype),
                                 jnp.asarray(vh[layer], dtype), jnp.asarray(bt),
                                 jnp.asarray(lengths), Dh ** -0.5)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)
    _argmax_where_resolvable(out, ref)

    (kh2, vh2), bt2 = _relabel([kh, vh], bt, num_pages, rng, axis=1)
    out2 = paged_flash_decode_tpu(q, _lane_dense(kh2, dtype),
                                  _lane_dense(vh2, dtype), jnp.asarray(bt2),
                                  jnp.asarray(lengths), layer, Dh ** -0.5)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(out2, np.float32))


@pytest.mark.parametrize("layer", [0, 1, LAYERS - 1])
@pytest.mark.parametrize("seed,page,nb,B,KV,g,Dh,dtype", [
    (0, 4, 4, 3, 2, 2, 16, jnp.float32),
    (1, 1, 3, 2, 1, 4, 8, jnp.float32),   # page_size 1: one token per page
    (2, 8, 2, 2, 4, 1, 32, jnp.float32),
    (3, 5, 4, 4, 2, 3, 16, jnp.float32),  # odd page size, partial last pages
    (4, 4, 1, 1, 1, 1, 8, jnp.float32),   # single block
    (5, 4, 3, 2, 2, 2, 16, jnp.bfloat16),  # the engine's default cache dtype
    # k pages a grid step (k = 16, 16, 16, 8, 2, 16); each layout holds an
    # empty row and a row that ends inside a k-page step
    (6, 2, 32, 3, 2, 1, 16, jnp.float32),   # MHA, two steps: lengths 34, 0, 22
    (12, 2, 32, 3, 2, 1, 16, jnp.bfloat16),  # MHA: 0, 63, 61
    (8, 2, 16, 3, 2, 4, 16, jnp.float32),   # GQA, one step: 10, 0, 32
    (15, 4, 8, 3, 1, 8, 16, jnp.bfloat16),  # GQA, g 8: 0, 23, 26
    (7, 4, 6, 4, 2, 2, 16, jnp.float32),    # k 2, three steps: 15, 17, 22, 0
    (16, 1, 48, 3, 4, 2, 8, jnp.bfloat16),  # one token a page: 27, 0, 21
    # two empty rows in a row, so no step prefetches row 3: 24, 0, 0, 14, 31
    (40, 2, 16, 5, 2, 1, 16, jnp.float32),
])
def test_paged_flash_sweep(seed, page, nb, B, KV, g, Dh, dtype, layer):
    check_paged_flash(seed, page, nb, B, KV, g, Dh, dtype, layer)


@pytest.mark.parametrize("seed,page,nb,B,KV,g", [
    (6, 2, 32, 3, 2, 1),    # two steps a row, an empty row between
    (15, 4, 8, 3, 1, 8),    # an empty first row
    (7, 4, 6, 4, 2, 2),     # three steps a row
    (40, 2, 16, 5, 2, 1),   # two empty rows in a row
])
def test_paged_flash_decode_under_simulated_dmas(seed, page, nb, B, KV, g):
    """The decode kernel DMAs its pages itself, a live step ahead. The TPU
    interpreter runs a DMA only when it is waited on and fills scratch with
    NaN, so a step that reads a buffer before its own pages are in fails."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.flash_attn.kernel import paged_flash_decode_fwd
    from repro.kernels.flash_attn.ref import paged_flash_decode_ref

    rng = np.random.default_rng(seed)
    num_pages, lengths, bt = _pool_layout(rng, B, nb, page)
    kh = _stacked_heads(rng, num_pages, page, KV, 16)
    vh = _stacked_heads(rng, num_pages, page, KV, 16)
    q = jnp.asarray(rng.normal(size=(B, 1, KV * g, 16)).astype(np.float32))
    params = pltpu.InterpretParams(dma_execution_mode="on_wait",
                                   uninitialized_memory="nan")
    try:
        out = paged_flash_decode_fwd(
            q, _lane_dense(kh), _lane_dense(vh), jnp.asarray(bt),
            jnp.asarray(lengths), LAYERS - 1, scale=0.25, interpret=params)
    finally:
        pltpu.reset_tpu_interpret_mode_state()
    ref = paged_flash_decode_ref(q, jnp.asarray(kh[-1]), jnp.asarray(vh[-1]),
                                 jnp.asarray(bt), jnp.asarray(lengths), 0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@hypothesis.given(
    seed=st.integers(0, 2 ** 16),
    page=st.integers(1, 8),
    nb=st.integers(1, 4),
    B=st.integers(1, 3),
    KV=st.sampled_from([1, 2]),
    g=st.sampled_from([1, 2, 4]),
    layer=st.integers(0, LAYERS - 1),
)
@hypothesis.settings(max_examples=10, deadline=None)
def test_paged_flash_property(seed, page, nb, B, KV, g, layer):
    check_paged_flash(seed, page, nb, B, KV, g, Dh=16, layer=layer)


# ------------------------------------------------------------------ T2 / CPQ


def check_paged_cpq(seed, page, nb, B, KV, g, Dh, bits):
    from repro.kernels.cpq_dequant_attn.kernel import paged_cpq_decode_fwd
    from repro.kernels.cpq_dequant_attn.ref import paged_cpq_decode_ref

    rng = np.random.default_rng(seed)
    cfg = CPQCfg(prune_ratio=0.3, bits=bits, max_levels=4)
    num_pages, lengths, bt = _pool_layout(rng, B, nb, page)
    cap = nb * page
    # per-row CPQ compression (the real serving construction), then scatter
    # codes/levels into the permuted physical pool
    S = max(int(lengths.max()), 1)
    kx = jnp.asarray(rng.normal(size=(B, S, KV, Dh)).astype(np.float32))
    vx = jnp.asarray(rng.normal(size=(B, S, KV, Dh)).astype(np.float32))
    tk = C.cpq_compress_prefill(kx, cfg, cap)
    tv = C.cpq_compress_prefill(vx, cfg, cap)
    ck = rng.integers(-128, 128, size=(num_pages, page, KV, Dh)).astype(np.int8)
    cv = rng.integers(-128, 128, size=(num_pages, page, KV, Dh)).astype(np.int8)
    lk = rng.integers(0, 4, size=(num_pages, page, KV)).astype(np.int32)
    lv = rng.integers(0, 4, size=(num_pages, page, KV)).astype(np.int32)
    for b in range(B):
        for j in range(-(-int(lengths[b]) // page)):
            sl = slice(j * page, (j + 1) * page)
            ck[bt[b, j]] = np.asarray(tk.codes)[b, sl]
            cv[bt[b, j]] = np.asarray(tv.codes)[b, sl]
            lk[bt[b, j]] = np.asarray(tk.level)[b, sl]
            lv[bt[b, j]] = np.asarray(tv.level)[b, sl]
    q = rng.normal(size=(B, KV, g, Dh)).astype(np.float32)
    args = (jnp.asarray(q), jnp.asarray(ck), jnp.asarray(cv),
            tk.scale, tk.zero, tv.scale, tv.zero,
            jnp.asarray(lk), jnp.asarray(lv), jnp.asarray(bt),
            jnp.asarray(lengths))
    out = paged_cpq_decode_fwd(*args, scale=0.17, interpret=True)
    ref = paged_cpq_decode_ref(*args, 0.17)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    _argmax_where_resolvable(out, ref)

    (ck2, cv2, lk2, lv2), bt2 = _relabel([ck, cv, lk, lv], bt, num_pages, rng)
    out2 = paged_cpq_decode_fwd(
        jnp.asarray(q), jnp.asarray(ck2), jnp.asarray(cv2),
        tk.scale, tk.zero, tv.scale, tv.zero,
        jnp.asarray(lk2), jnp.asarray(lv2), jnp.asarray(bt2),
        jnp.asarray(lengths), scale=0.17, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


@pytest.mark.parametrize("seed,page,nb,B,KV,g,Dh,bits", [
    (0, 4, 4, 2, 2, 2, 16, 8),
    (1, 2, 3, 3, 1, 4, 8, 4),
    (2, 8, 2, 2, 4, 1, 32, 8),
    (3, 3, 4, 2, 2, 1, 16, 4),  # odd page size
])
def test_paged_cpq_sweep(seed, page, nb, B, KV, g, Dh, bits):
    check_paged_cpq(seed, page, nb, B, KV, g, Dh, bits)


@hypothesis.given(
    seed=st.integers(0, 2 ** 16),
    page=st.integers(1, 8),
    nb=st.integers(1, 4),
    B=st.integers(1, 3),
    bits=st.sampled_from([4, 8]),
)
@hypothesis.settings(max_examples=10, deadline=None)
def test_paged_cpq_property(seed, page, nb, B, bits):
    check_paged_cpq(seed, page, nb, B, KV=2, g=2, Dh=16, bits=bits)


# ---------------------------------------------------------- T1 / decomposed


def check_paged_decomposed(seed, page, nb, B, H, Dm, kv_r, Rr,
                           dtype=jnp.float32):
    from repro.kernels.decomposed_attn.kernel import paged_decomposed_decode_fwd
    from repro.kernels.decomposed_attn.ref import paged_decomposed_decode_ref

    rng = np.random.default_rng(seed)
    num_pages, lengths, bt = _pool_layout(rng, B, nb, page)
    xp = rng.normal(size=(num_pages, page, Dm)).astype(np.float32)
    krp = rng.normal(size=(num_pages, page, kv_r, max(Rr, 1))).astype(np.float32)
    xp[0] = krp[0] = 1e3                              # poison the null page
    r = rng.normal(size=(B, H, Dm)).astype(np.float32)
    qr = rng.normal(size=(B, H, Rr)).astype(np.float32)
    args = (jnp.asarray(r, dtype), jnp.asarray(qr, dtype),
            jnp.asarray(xp, dtype), jnp.asarray(krp[..., :Rr], dtype),
            jnp.asarray(bt), jnp.asarray(lengths))
    out = paged_decomposed_decode_fwd(*args, scale=0.2, interpret=True)
    ref = paged_decomposed_decode_ref(*args, 0.2)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)
    _argmax_where_resolvable(out, ref)

    (xp2, krp2), bt2 = _relabel([xp, krp], bt, num_pages, rng)
    out2 = paged_decomposed_decode_fwd(
        jnp.asarray(r, dtype), jnp.asarray(qr, dtype), jnp.asarray(xp2, dtype),
        jnp.asarray(krp2[..., :Rr], dtype), jnp.asarray(bt2),
        jnp.asarray(lengths), scale=0.2, interpret=True)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(out2, np.float32))


@pytest.mark.parametrize("seed,page,nb,B,H,Dm,kv_r,Rr,dtype", [
    (0, 4, 4, 2, 4, 16, 1, 8, jnp.float32),   # MLA layout: shared rope head
    (1, 4, 3, 3, 4, 16, 2, 8, jnp.float32),   # per-kv-head rope (decoupled T1)
    (2, 2, 4, 2, 8, 32, 4, 4, jnp.float32),
    (3, 8, 2, 2, 4, 16, 1, 0, jnp.float32),   # absolute positions: no rope
    (4, 5, 3, 1, 2, 8, 2, 8, jnp.float32),    # odd page size
    (5, 4, 3, 2, 4, 16, 1, 8, jnp.bfloat16),  # engine's default cache dtype
])
def test_paged_decomposed_sweep(seed, page, nb, B, H, Dm, kv_r, Rr, dtype):
    check_paged_decomposed(seed, page, nb, B, H, Dm, kv_r, Rr, dtype)


@hypothesis.given(
    seed=st.integers(0, 2 ** 16),
    page=st.integers(1, 8),
    nb=st.integers(1, 4),
    B=st.integers(1, 3),
    kv_r=st.sampled_from([1, 2, 4]),
    Rr=st.sampled_from([0, 8]),
)
@hypothesis.settings(max_examples=10, deadline=None)
def test_paged_decomposed_property(seed, page, nb, B, kv_r, Rr):
    check_paged_decomposed(seed, page, nb, B, H=4, Dm=16, kv_r=kv_r, Rr=Rr)


# ------------------------------------------------- engine-level greedy parity


def test_paged_kernels_greedy_exact_vs_gather_f32():
    """Property satellite's exactness anchor at the kernel level: one decode
    step through the fused dense kernel and through the gather path on the
    SAME paged cache state agree on greedy argmax for every resolvable row
    (f32; both are reduction-order-epsilon realizations of the same math)."""
    from repro.core import attention as core_attn
    from repro.kernels.flash_attn.ops import paged_flash_decode_tpu
    from repro.serving import paged_cache as pgc

    rng = np.random.default_rng(9)
    B, KV, g, Dh, page, nb, layer = 3, 2, 2, 16, 4, 4, 1
    num_pages, lengths, bt = _pool_layout(rng, B, nb, page)
    kp = _lane_dense(_stacked_heads(rng, num_pages, page, KV, Dh))
    vp = _lane_dense(_stacked_heads(rng, num_pages, page, KV, Dh))
    q = jnp.asarray(rng.normal(size=(B, 1, KV * g, Dh)).astype(np.float32))
    out_k = paged_flash_decode_tpu(q, kp, vp, jnp.asarray(bt),
                                   jnp.asarray(lengths), layer, Dh ** -0.5)
    out_g = core_attn.dense_attention(
        q, pgc.gather_layer_heads(kp, layer, jnp.asarray(bt), KV),
        pgc.gather_layer_heads(vp, layer, jnp.asarray(bt), KV), Dh ** -0.5,
        causal=False, kv_length=jnp.asarray(lengths))
    live = lengths > 0
    np.testing.assert_allclose(np.asarray(out_k)[live], np.asarray(out_g)[live],
                               atol=2e-5)
    _argmax_where_resolvable(np.asarray(out_k)[live], np.asarray(out_g)[live])


# ------------------------------------------- chunked prefill vs ref.py oracles


def _prefill_layout(rng, page, nb, offset, valid):
    """One slot's block row: the pages holding positions < offset + valid
    mapped in PERMUTED physical order, the rest left on the poisoned null
    page. Returns (num_pages, block_row)."""
    used = -(-(offset + valid) // page)
    num_pages = 1 + nb + 2
    row = np.zeros((nb,), np.int32)
    row[:used] = rng.permutation(np.arange(1, num_pages))[:used]
    return num_pages, row


def _f32_case(*case):
    """A float32 case, under the id it had before the dtype was a field."""
    return pytest.param(*case, jnp.float32, id="-".join(map(str, case)))


@pytest.mark.parametrize("layer", [0, 1, LAYERS - 1])
@pytest.mark.parametrize("seed,page,nb,C,KV,g,offset,valid,dtype", [
    _f32_case(0, 4, 6, 8, 2, 2, 0, 8),
    _f32_case(1, 4, 6, 8, 2, 1, 8, 5),    # continuation chunk, jit padding
    _f32_case(2, 2, 8, 4, 4, 1, 6, 4),
    # k pages a grid step (k = 16, 16, 8, 2, 16)
    (3, 2, 16, 8, 2, 1, 13, 8, jnp.float32),    # MHA, offset mid-step
    (4, 2, 32, 16, 2, 1, 40, 12, jnp.bfloat16),  # second step, jit padding
    (5, 4, 8, 8, 2, 2, 0, 8, jnp.float32),      # GQA, first chunk
    (6, 2, 6, 4, 1, 4, 5, 3, jnp.bfloat16),     # GQA, g 4, three steps
    (7, 1, 48, 8, 2, 2, 21, 8, jnp.float32),    # one token a page
])
def test_paged_flash_prefill_vs_ref(seed, page, nb, C, KV, g, offset, valid,
                                    dtype, layer):
    from repro.kernels.flash_attn.kernel import paged_flash_prefill_fwd
    from repro.kernels.flash_attn.ref import paged_flash_prefill_ref

    rng = np.random.default_rng(seed)
    num_pages, row = _prefill_layout(rng, page, nb, offset, valid)
    kh = _stacked_heads(rng, num_pages, page, KV, 16)
    vh = _stacked_heads(rng, num_pages, page, KV, 16)
    q = jnp.asarray(rng.normal(size=(1, C, KV * g, 16)).astype(np.float32),
                    dtype)
    pos = (jnp.asarray(row), jnp.asarray(offset, jnp.int32),
           jnp.asarray(valid, jnp.int32))
    out = paged_flash_prefill_fwd(q, _lane_dense(kh, dtype),
                                  _lane_dense(vh, dtype), *pos, layer,
                                  scale=0.25, interpret=True)
    ref = paged_flash_prefill_ref(q, jnp.asarray(kh[layer], dtype),
                                  jnp.asarray(vh[layer], dtype), *pos, 0.25)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32)[0, :valid],
                               np.asarray(ref, np.float32)[0, :valid], atol=tol)


@pytest.mark.parametrize("seed,page,nb,C,KV,g,offset,valid", [
    (0, 4, 6, 8, 2, 2, 0, 8),    # first chunk: raw tail only
    (1, 4, 6, 8, 2, 1, 12, 3),
])
def test_paged_cpq_prefill_vs_ref(seed, page, nb, C, KV, g, offset, valid):
    from repro.kernels.cpq_dequant_attn.kernel import paged_cpq_prefill_fwd
    from repro.kernels.cpq_dequant_attn.ref import paged_cpq_prefill_ref

    rng = np.random.default_rng(seed)
    num_pages, row = _prefill_layout(rng, page, nb, offset, valid)
    Dh, L = 16, 3
    codes = lambda: jnp.asarray(rng.integers(  # noqa: E731
        -128, 128, size=(num_pages, page, KV, Dh)).astype(np.int8))
    levels = lambda: jnp.asarray(rng.integers(  # noqa: E731
        0, L, size=(num_pages, page, KV)).astype(np.int32))
    side = lambda: jnp.asarray(  # noqa: E731
        np.abs(rng.normal(size=(1, L, KV, Dh))).astype(np.float32) + 0.05)
    args = (jnp.asarray(rng.normal(size=(1, KV, C * g, Dh)).astype(np.float32)),
            codes(), codes(), side(), side(), side(), side(), levels(),
            levels(),
            jnp.asarray(rng.normal(size=(C, KV, Dh)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(C, KV, Dh)).astype(np.float32)),
            jnp.asarray(row), jnp.asarray(offset, jnp.int32),
            jnp.asarray(valid, jnp.int32))
    out = paged_cpq_prefill_fwd(*args, scale=0.2, interpret=True)
    ref = paged_cpq_prefill_ref(*args, 0.2)
    rows = np.arange(C * g) // g < valid
    np.testing.assert_allclose(np.asarray(out)[0][:, rows],
                               np.asarray(ref)[0][:, rows], atol=3e-5)


@pytest.mark.parametrize("seed,page,nb,C,H,kv_r,Rr,offset,valid", [
    (0, 4, 6, 8, 4, 1, 8, 0, 8),     # MLA layout: shared rope head
    (1, 4, 6, 8, 4, 4, 8, 8, 6),     # per-kv-head rope (decoupled T1)
    (2, 2, 8, 4, 2, 1, 0, 4, 4),     # no rope
])
def test_paged_decomposed_prefill_vs_ref(seed, page, nb, C, H, kv_r, Rr,
                                         offset, valid):
    from repro.kernels.decomposed_attn.kernel import paged_decomposed_prefill_fwd
    from repro.kernels.decomposed_attn.ref import paged_decomposed_prefill_ref

    rng = np.random.default_rng(seed)
    num_pages, row = _prefill_layout(rng, page, nb, offset, valid)
    Dm = 16
    xp = rng.normal(size=(num_pages, page, Dm)).astype(np.float32)
    krp = rng.normal(size=(num_pages, page, kv_r, Rr)).astype(np.float32)
    xp[0] = 1e3                                       # poison the null page
    args = (jnp.asarray(rng.normal(size=(C, H, Dm)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(C, H, Rr)).astype(np.float32)),
            jnp.asarray(xp), jnp.asarray(krp), jnp.asarray(row),
            jnp.asarray(offset, jnp.int32), jnp.asarray(valid, jnp.int32))
    out = paged_decomposed_prefill_fwd(*args, scale=0.2, interpret=True)
    ref = paged_decomposed_prefill_ref(*args, 0.2)
    np.testing.assert_allclose(np.asarray(out)[:valid],
                               np.asarray(ref)[:valid], atol=2e-4)


# ------------------------------------------------------- MLA latent arena


def _latent_arena(rng, num_pages, page, Dc, R):
    """A stacked latent arena (LAYERS, ...) in the packed rope layout of
    ``PagedLatentCache``, every layer different and the null page poisoned,
    and the same rope pages unpacked to (LAYERS, P, page, 1, R) for the
    oracle."""
    from repro.serving.paged_cache import rope_pack

    f = rope_pack(page, R)
    c = rng.normal(size=(LAYERS, num_pages, page, Dc)).astype(np.float32)
    kr = rng.normal(size=(LAYERS, num_pages, page, 1, R)).astype(np.float32)
    c[:, 0] = kr[:, 0] = 1e3
    packed = kr[:, :, :, 0].reshape(LAYERS, num_pages, f, page // f, R)
    packed = packed.transpose(0, 1, 3, 2, 4).reshape(LAYERS, num_pages,
                                                      page // f, f * R)
    return c, packed, kr


@pytest.mark.parametrize("layer", [0, LAYERS - 1])
@pytest.mark.parametrize("seed,page,nb,B,H,Dc,R,dtype", [
    (0, 16, 3, 2, 4, 32, 64, jnp.float32),    # the published rope: 2 per row
    (1, 16, 2, 3, 4, 16, 16, jnp.float32),    # 8 tokens per row
    (2, 4, 4, 2, 2, 16, 8, jnp.float32),      # page too short to pack: 1
    (3, 16, 3, 2, 4, 32, 64, jnp.bfloat16),   # the served dtype
    (4, 16, 32, 2, 4, 32, 64, jnp.float32),   # 16 pages a grid step
])
def test_paged_latent_decode_vs_ref(seed, page, nb, B, H, Dc, R, dtype, layer):
    from repro.kernels.decomposed_attn.kernel import paged_latent_decode_fwd
    from repro.kernels.decomposed_attn.ref import paged_decomposed_decode_ref

    rng = np.random.default_rng(seed)
    num_pages, lengths, bt = _pool_layout(rng, B, nb, page)
    c, packed, kr = _latent_arena(rng, num_pages, page, Dc, R)
    r = jnp.asarray(rng.normal(size=(B, H, Dc)), dtype)
    qr = jnp.asarray(rng.normal(size=(B, H, R)), dtype)
    out = paged_latent_decode_fwd(
        r, qr, jnp.asarray(c, dtype), jnp.asarray(packed, dtype),
        jnp.asarray(bt), jnp.asarray(lengths), jnp.asarray(layer, jnp.int32),
        scale=0.2, interpret=True)
    ref = paged_decomposed_decode_ref(
        r, qr, jnp.asarray(c[layer], dtype), jnp.asarray(kr[layer], dtype),
        jnp.asarray(bt), jnp.asarray(lengths), 0.2)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("seed,page,nb,C,H,R,offset,valid", [
    (0, 16, 4, 16, 4, 64, 0, 16),    # first chunk, two tokens per rope row
    (1, 16, 4, 16, 2, 64, 16, 11),   # continuation chunk, jit padding
    (2, 4, 6, 8, 4, 8, 8, 6),        # unpacked rope
    (3, 16, 32, 16, 2, 64, 272, 13),  # 16 pages a grid step, 18 of them live
])
def test_paged_latent_prefill_vs_ref(seed, page, nb, C, H, R, offset, valid):
    from repro.kernels.decomposed_attn.kernel import paged_latent_prefill_fwd
    from repro.kernels.decomposed_attn.ref import paged_decomposed_prefill_ref

    rng = np.random.default_rng(seed)
    num_pages, row = _prefill_layout(rng, page, nb, offset, valid)
    c, packed, kr = _latent_arena(rng, num_pages, page, 16, R)
    r = jnp.asarray(rng.normal(size=(C, H, 16)).astype(np.float32))
    qr = jnp.asarray(rng.normal(size=(C, H, R)).astype(np.float32))
    layer = LAYERS - 1
    out = paged_latent_prefill_fwd(
        r, qr, jnp.asarray(c), jnp.asarray(packed), jnp.asarray(row),
        jnp.asarray(offset, jnp.int32), jnp.asarray(valid, jnp.int32),
        jnp.asarray(layer, jnp.int32), scale=0.2, interpret=True)
    ref = paged_decomposed_prefill_ref(
        r, qr, jnp.asarray(c[layer]), jnp.asarray(kr[layer]), jnp.asarray(row),
        jnp.asarray(offset, jnp.int32), jnp.asarray(valid, jnp.int32), 0.2)
    np.testing.assert_allclose(np.asarray(out)[:valid],
                               np.asarray(ref)[:valid], atol=2e-4)


@pytest.mark.parametrize("page,R,offset,n", [(16, 64, 0, 16), (16, 64, 5, 13),
                                             (16, 16, 3, 30), (4, 8, 1, 6)])
def test_latent_writes_land_where_the_gather_reads(page, R, offset, n):
    """Chunk and decode writes into the packed rope rows: every position
    written reads back through ``gather_latent``, tokens that share a row
    (in one chunk) included, and positions not written keep their
    contents."""
    from repro.serving import paged_cache as pgc

    rng = np.random.default_rng(page + R + offset)
    nb, Dc = 4, 8
    cache = pgc.init_paged_latent(1 + nb, page, Dc, R, jnp.float32)
    cache = cache._replace(c=cache.c + 7.0, rope=cache.rope + 7.0)
    row = jnp.asarray(rng.permutation(np.arange(1, 1 + nb)), jnp.int32)
    cv = rng.normal(size=(n, Dc)).astype(np.float32)
    rv = rng.normal(size=(n, R)).astype(np.float32)
    cache = pgc.write_latent_chunk(cache, row, jnp.asarray(offset, jnp.int32),
                                   jnp.asarray(n - 1, jnp.int32), jnp.asarray(cv),
                                   jnp.asarray(rv))
    rows = pgc.RowState(lengths=jnp.asarray([offset + n - 1], jnp.int32),
                        block_table=row[None], active=jnp.asarray([True]),
                        tier=jnp.zeros((1,), jnp.int32))
    cache = pgc.append_latent(cache, rows, jnp.asarray(cv[None, -1:]),
                              jnp.asarray(rv[None, -1:, None]))
    cg, rg = pgc.gather_latent(cache, row[None])
    cg, rg = np.asarray(cg[0]), np.asarray(rg[0, :, 0])
    np.testing.assert_array_equal(cg[offset:offset + n], cv)
    np.testing.assert_array_equal(rg[offset:offset + n], rv)
    np.testing.assert_array_equal(rg[:offset], 7.0)
    np.testing.assert_array_equal(rg[offset + n:], 7.0)


# ------------------------------------------------- interpret mode by platform


def test_interpret_mode_follows_the_platform(monkeypatch):
    """A kernel call on CPU arrays interprets (the compiled Mosaic kernel
    cannot run on the CPU backend), whatever the environment says: the
    package has no interpret switch left to read."""
    import importlib

    import repro.kernels as K
    from repro.kernels.flash_attn.ops import paged_flash_decode_tpu

    for var in ("REPRO_INTERPRET", "INTERPRET"):
        monkeypatch.setenv(var, "0")
    importlib.reload(K)
    assert not hasattr(K, "INTERPRET")
    rng = np.random.default_rng(0)
    num_pages, lengths, bt = _pool_layout(rng, 2, 3, 4)
    kp = _lane_dense(_stacked_heads(rng, num_pages, 4, 2, 8))
    q = jnp.asarray(rng.normal(size=(2, 1, 4, 8)).astype(np.float32))
    args = (q, kp, kp, jnp.asarray(bt), jnp.asarray(lengths), LAYERS - 1)
    assert q.devices().pop().platform == "cpu"
    hlo = paged_flash_decode_tpu.lower(*args, scale=0.3).compile().as_text()
    assert "tpu_custom_call" not in hlo
    out = paged_flash_decode_tpu(*args, scale=0.3)
    ref = paged_flash_decode_tpu(*args, scale=0.3, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
