"""T1 — fused decomposed-attention decode kernel (paper §III), Pallas TPU.

Computes, for one new-token query against the X cache:

    s_b   = R X_b^T (+ q_rope k_rope_b^T)     (score stage,  MXU)
    P    += softmax-online(s_b) X_b           (value stage,  MXU)

per X block b — i.e. BOTH cascaded MatMuls of the paper's decomposition
stream through VMEM on one X read. This is the sub-matrix pipeline of
Fig. 3(b) realized as a single kernel: stage 2 consumes stage-1 tiles as
they are produced, and neither the scores nor P round-trip HBM.

R = q_nope W_K^T is computed outside (a (H, Dn) x (Dn, Dm) matmul, tiny for
one token), as is the final out = P W_V. The kernel owns the O(N) part.

Grid: (B, nn) — nn innermost; online-softmax state (m, l, P) in VMEM scratch.
The rope path covers the shared-rope layout (MLA: one k_rope per token).
``length`` arrives via scalar prefetch (SMEM) and masks unwritten slots.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, r_ref, qr_ref, x_ref, kr_ref, p_ref,
            m_sc, l_sc, acc_sc, *, scale: float, block_n: int, nn: int,
            rope_dims: int):
    ib = pl.program_id(1)

    @pl.when(ib == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    r = r_ref[0]                    # (H, Dm)
    x = x_ref[0]                    # (bn, Dm)
    # --- score stage: s = R X^T (the first cascaded MatMul)
    s = jax.lax.dot_general(r, x, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (H, bn)
    if rope_dims > 0:
        qr = qr_ref[0]              # (H, Rr)
        kr = kr_ref[0]              # (bn, Rr)
        s = s + jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    s = s * scale
    pos = ib * block_n + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < len_ref[0], s, NEG_INF)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)          # (H, bn)
    l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_sc[...] = m_new
    # --- value stage: P += p X (the second cascaded MatMul, same X tile)
    acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
        p.astype(x.dtype), x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ib == nn - 1)
    def _finish():
        p_ref[0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(p_ref.dtype)


def _paged_kernel(bt_ref, len_ref, r_ref, qr_ref, x_ref, kr_ref, p_ref,
                  m_sc, l_sc, acc_sc, *, scale: float, page_size: int,
                  nb: int, rope_dims: int, kv_r: int):
    """One (b, ib) step over physical X page bt[b, ib] (resolved by the
    BlockSpec index maps from the scalar-prefetched block table). Both
    cascaded MatMuls of the decomposition consume the page on ONE read while
    it sits in VMEM; rope keys may be shared (kv_r == 1, MLA) or
    per-kv-head; softmax state is carried online in f32 scratch."""
    b = pl.program_id(0)
    ib = pl.program_id(1)

    @pl.when(ib == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # unmapped (null) pages sit wholly past the row's length: skip
    @pl.when(ib * page_size < len_ref[b])
    def _compute():
        r = r_ref[0].astype(jnp.float32)           # (H, Dm)
        x = x_ref[0].astype(jnp.float32)           # (page, Dm)
        # --- score stage: s = R X^T on the in-VMEM page
        s = jax.lax.dot_general(r, x, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (H, page)
        if rope_dims > 0:
            H = r.shape[0]
            g_r = H // kv_r
            rope_rows = []
            for j in range(kv_r):       # static, tiny: per-kv-head rope slice
                qj = qr_ref[0, j * g_r:(j + 1) * g_r, :].astype(jnp.float32)
                kj = kr_ref[0, :, j, :].astype(jnp.float32)   # (page, Rr)
                rope_rows.append(jax.lax.dot_general(
                    qj, kj, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32))
            s = s + jnp.concatenate(rope_rows, axis=0)
        s = s * scale
        pos = ib * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < len_ref[b], s, NEG_INF)        # partial last page

        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        # --- value stage: P += p X, same page still in VMEM
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p, x, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ib == nb - 1)
    def _finish():
        p_ref[0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(p_ref.dtype)


def _paged_prefill_kernel(bt_ref, lens_ref, r_ref, qr_ref, x_ref, kr_ref, p_ref,
                          m_sc, l_sc, acc_sc, *, scale: float, page_size: int,
                          nb: int, rope_dims: int, kv_r: int, chunk: int):
    """One ib step of the Q-chunk>1 paged decomposed sweep for ONE slot being
    admitted: both cascaded MatMuls consume physical X page bt[ib] on one
    read. Query rows are HEAD-MAJOR (row = h * C + i) so the per-kv-head rope
    slices stay contiguous; row r is chunk token r % C at absolute position
    lens[0] + r % C; lens[1] = offset + valid masks the chunk's jit padding."""
    ib = pl.program_id(0)

    @pl.when(ib == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # pages wholly past the slot's post-chunk length are unmapped: skip
    @pl.when(ib * page_size < lens_ref[1])
    def _compute():
        r = r_ref[0].astype(jnp.float32)           # (H*C, Dm)
        x = x_ref[0].astype(jnp.float32)           # (page, Dm)
        # --- score stage: s = R X^T on the in-VMEM page
        s = jax.lax.dot_general(r, x, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (H*C, page)
        if rope_dims > 0:
            HC = r.shape[0]
            g_r = HC // (kv_r * chunk)             # heads per kv_r, in rows of C
            rope_rows = []
            for j in range(kv_r):   # static, tiny: per-kv-head rope slice
                qj = qr_ref[0, j * g_r * chunk:(j + 1) * g_r * chunk, :].astype(
                    jnp.float32)
                kj = kr_ref[0, :, j, :].astype(jnp.float32)   # (page, Rr)
                rope_rows.append(jax.lax.dot_general(
                    qj, kj, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32))
            s = s + jnp.concatenate(rope_rows, axis=0)
        s = s * scale
        pos = ib * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qtok = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % chunk
        ok = (pos < lens_ref[1]) & (pos <= lens_ref[0] + qtok)  # valid & causal
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        # --- value stage: P += p X, same page still in VMEM
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p, x, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ib == nb - 1)
    def _finish():
        p_ref[0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(p_ref.dtype)


def _vmem_limit(rows: int, dm: int, itemsize: int) -> int:
    """Scoped-VMEM budget of the chunk prefill kernel: f32 accumulator,
    double-buffered query and output blocks, and the lane-padded (rows, 1)
    softmax statistics, with 8 MiB of headroom for page tiles and temporaries
    (never below Mosaic's 16 MiB default)."""
    need = rows * (4 * dm + 4 * dm * itemsize + 2 * 4 * 128)
    return max(16 << 20, need + (8 << 20))


def paged_decomposed_prefill_fwd(r: jax.Array, q_rope: jax.Array,
                                 x_pages: jax.Array, kr_pages: jax.Array,
                                 block_row: jax.Array, offset: jax.Array,
                                 valid: jax.Array, *, scale: float,
                                 interpret: bool = True) -> jax.Array:
    """Chunked paged T1/MLA prefill for one slot: the admission chunk's C
    queries sweep the slot's X (+roped key) pages [0, offset + valid) — the
    chunk's own X rows were just written into those pages, so the decomposed
    score/value stages serve intra-chunk causal attention too and no
    contiguous scratch cache exists.

    r: (C, H, Dm) = q_nope W_K^T; q_rope: (C, H, Rr) (Rr may be 0);
    x_pages: (P, page, Dm); kr_pages: (P, page, KV_r, Rr), KV_r == 1 for the
    MLA shared rope; block_row: (max_blocks,) int32 (0 = null page);
    offset/valid: () int32. Returns P: (C, H, Dm) — caller applies W_V; rows
    past ``valid`` are jit-padding garbage."""
    C, H, Dm = r.shape
    page = x_pages.shape[1]
    Rr = q_rope.shape[-1]
    kv_r = kr_pages.shape[2] if Rr else 1
    nb = block_row.shape[0]
    if not Rr:  # keep a well-formed (non-0-width) operand for the BlockSpec
        q_rope = jnp.zeros((C, H, 1), r.dtype)
        kr_pages = jnp.zeros((x_pages.shape[0], page, 1, 1), x_pages.dtype)
    Rp = q_rope.shape[-1]
    # head-major rows (h * C + i): kv_r slices contiguous, token = row % C
    r2 = r.transpose(1, 0, 2).reshape(1, H * C, Dm)
    qr2 = q_rope.transpose(1, 0, 2).reshape(1, H * C, Rp)
    lens = jnp.stack([offset, offset + valid]).astype(jnp.int32)

    kern = functools.partial(_paged_prefill_kernel, scale=scale, page_size=page,
                             nb=nb, rope_dims=Rr, kv_r=kv_r, chunk=C)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # block_row, (offset, total)
            grid=(nb,),             # sweeps the slot's block-table entries
            in_specs=[
                pl.BlockSpec((1, H * C, Dm), lambda ib, bt, ln: (0, 0, 0)),
                pl.BlockSpec((1, H * C, Rp), lambda ib, bt, ln: (0, 0, 0)),
                pl.BlockSpec((1, page, Dm), lambda ib, bt, ln: (bt[ib], 0, 0)),
                pl.BlockSpec((1, page, kv_r, Rp),
                             lambda ib, bt, ln: (bt[ib], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, H * C, Dm), lambda ib, bt, ln: (0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H * C, 1), jnp.float32),
                pltpu.VMEM((H * C, 1), jnp.float32),
                pltpu.VMEM((H * C, Dm), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((1, H * C, Dm), x_pages.dtype),
        # every query row of the chunk stays resident: the f32 accumulator
        # plus double-buffered query/output blocks outgrow the default scoped
        # VMEM (16 MiB) from H*C = 2048 rows at Dm = 1024
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(H * C, Dm, x_pages.dtype.itemsize)),
        interpret=interpret,
    )(block_row.astype(jnp.int32), lens, r2, qr2, x_pages, kr_pages)
    return out.reshape(H, C, Dm).transpose(1, 0, 2)


def paged_decomposed_decode_fwd(r: jax.Array, q_rope: jax.Array,
                                x_pages: jax.Array, kr_pages: jax.Array,
                                block_table: jax.Array, lengths: jax.Array, *,
                                scale: float, interpret: bool = True) -> jax.Array:
    """Paged T1/MLA decode: the grid's innermost axis iterates block-table
    entries and each mapped X (+roped key) page is DMA'd from the arena into
    VMEM — no contiguous logical X view is materialized.

    r: (B, H, Dm) = q_nope W_K^T; q_rope: (B, H, Rr) (Rr may be 0);
    x_pages: (P, page, Dm) pool; kr_pages: (P, page, KV_r, Rr) pool with
    KV_r == 1 (MLA shared rope) or per-kv-head; block_table: (B, max_blocks)
    int32 (0 = null page); lengths: (B,) int32. Returns P: (B, H, Dm) —
    caller applies W_V.

    Masking convention: positions >= lengths[b] (null pages, partial last
    page) are dead; lengths[b] == 0 rows return zeros."""
    B, H, Dm = r.shape
    page = x_pages.shape[1]
    Rr = q_rope.shape[-1]
    kv_r = kr_pages.shape[2] if Rr else 1
    nb = block_table.shape[1]
    if not Rr:  # keep a well-formed (non-0-width) operand for the BlockSpec
        q_rope = jnp.zeros((B, H, 1), r.dtype)
        kr_pages = jnp.zeros((x_pages.shape[0], page, 1, 1), x_pages.dtype)
    Rp = q_rope.shape[-1]

    kern = functools.partial(_paged_kernel, scale=scale, page_size=page,
                             nb=nb, rope_dims=Rr, kv_r=kv_r)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # block_table, lengths
            grid=(B, nb),           # innermost axis sweeps block-table entries
            in_specs=[
                pl.BlockSpec((1, H, Dm), lambda b, ib, bt, ln: (b, 0, 0)),
                pl.BlockSpec((1, H, Rp), lambda b, ib, bt, ln: (b, 0, 0)),
                pl.BlockSpec((1, page, Dm),
                             lambda b, ib, bt, ln: (bt[b, ib], 0, 0)),
                pl.BlockSpec((1, page, kv_r, Rp),
                             lambda b, ib, bt, ln: (bt[b, ib], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, H, Dm), lambda b, ib, bt, ln: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, Dm), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Dm), x_pages.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      r, q_rope, x_pages, kr_pages)


def decomposed_decode_fwd(r: jax.Array, q_rope: jax.Array, x: jax.Array,
                          k_rope: jax.Array, length: jax.Array, *,
                          scale: float, block_n: int = 512,
                          interpret: bool = True) -> jax.Array:
    """r: (B, H, Dm); q_rope: (B, H, Rr); x: (B, N, Dm); k_rope: (B, N, Rr);
    length: () int32. Returns P: (B, H, Dm) — caller applies W_V."""
    B, H, Dm = r.shape
    N = x.shape[1]
    Rr = q_rope.shape[-1]
    bn = min(block_n, N)
    pad = (-N) % bn
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))
    nn = (N + pad) // bn

    grid = (B, nn)
    kern = functools.partial(_kernel, scale=scale, block_n=bn, nn=nn,
                             rope_dims=Rr)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # length (1,)
            pl.BlockSpec((1, H, Dm), lambda b, ib: (b, 0, 0)),
            pl.BlockSpec((1, H, max(Rr, 1)), lambda b, ib: (b, 0, 0)),
            pl.BlockSpec((1, bn, Dm), lambda b, ib: (b, ib, 0)),
            pl.BlockSpec((1, bn, max(Rr, 1)), lambda b, ib: (b, ib, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, Dm), lambda b, ib: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Dm), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, Dm), jnp.float32),
        ],
        interpret=interpret,
    )(length.reshape(1).astype(jnp.int32),
      r,
      q_rope if Rr else jnp.zeros((B, H, 1), r.dtype),
      x,
      k_rope if Rr else jnp.zeros((B, N + pad, 1), x.dtype))
