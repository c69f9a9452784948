"""T2 — decode attention directly over CPQ int8 codes (paper §IV), Pallas TPU.

The hardware DQU (dequantization unit) analogue: HBM moves only the int8/int4
codes + per-(level, channel) scale/zero + per-token HQE level; dequantization
happens in VMEM/registers inside the attention kernel, so the cache traffic
is the compressed bytes (4-8x less than bf16 K/V).

HQE level lookup is MXU-friendly: the per-token level id becomes a one-hot
(bn, L) matrix multiplied against the (L, D) scale/zero tables — no gathers.
Pruned elements (stored code 0, i.e. int8 -128) dequantize to exactly 0,
which realizes the paper's "transfer only non-zero" semantics as
zero-contribution MACs.

Grid: (B, KV, nn) — nn innermost; online softmax in VMEM scratch; one sweep
dequantizes K and V blocks and runs both attention matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attn.kernel import online_update

NEG_INF = -1e30


def _dequant(codes, lv_oh, scale_tab, zero_tab):
    """codes: (bn, D) i8 (stored = code - 128); lv_oh: (bn, L) f32;
    scale_tab/zero_tab: (L, D) f32."""
    s = jax.lax.dot_general(lv_oh, scale_tab, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bn, D)
    z = jax.lax.dot_general(lv_oh, zero_tab, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    c = codes.astype(jnp.float32) + 128.0
    return jnp.where(c == 0.0, 0.0, (c - 1.0) * s + z)


def _kernel(len_ref, q_ref, ck_ref, cv_ref, sk_ref, zk_ref, sv_ref, zv_ref,
            lvk_ref, lvv_ref, o_ref, m_sc, l_sc, acc_sc, *, scale: float,
            block_n: int, nn: int, num_levels: int):
    ib = pl.program_id(2)

    @pl.when(ib == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0, 0]                                  # (G, Dh)
    ck = ck_ref[0, :, 0, :]                          # (bn, Dh) i8
    cv = cv_ref[0, :, 0, :]                          # (bn, Dv) i8

    def onehot(lv):                                  # (bn,) i32 -> (bn, L) f32
        return (lv[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (lv.shape[0], num_levels), 1)).astype(jnp.float32)

    lvk_oh = onehot(lvk_ref[0, :, 0])
    lvv_oh = onehot(lvv_ref[0, :, 0])

    k_hat = _dequant(ck, lvk_oh, sk_ref[0, :, 0, :], zk_ref[0, :, 0, :])
    s = jax.lax.dot_general(q.astype(jnp.float32), k_hat,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (G, bn)
    pos = ib * block_n + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < len_ref[0], s, NEG_INF)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_sc[...] = m_new
    v_hat = _dequant(cv, lvv_oh, sv_ref[0, :, 0, :], zv_ref[0, :, 0, :])
    acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
        p, v_hat, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ib == nn - 1)
    def _finish():
        o_ref[0, 0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


def _page_dequant(codes_ref, lv_ref, s_ref, z_ref, h, num_levels):
    """Dequantize kv head ``h`` of the page in VMEM: codes (page, KV, D)
    i8, levels (page, KV) i32, per-slot scale/zero (L, KV, D) f32. Tiles
    are rounded to bf16 like the jnp gather path
    (cpq_chunked_decode_attention) so paged-kernel decode stays token-exact
    vs it under greedy sampling."""
    lv = lv_ref[0, :, h:h + 1]                           # (page, 1)
    lv_oh = (lv == jax.lax.broadcasted_iota(
        jnp.int32, (lv.shape[0], num_levels), 1)).astype(jnp.float32)
    return _dequant(codes_ref[0, :, h, :], lv_oh, s_ref[0, :, h, :],
                    z_ref[0, :, h, :]).astype(jnp.bfloat16).astype(jnp.float32)


def _paged_kernel(bt_ref, len_ref, q_ref, ck_ref, cv_ref, sk_ref, zk_ref,
                  sv_ref, zv_ref, lvk_ref, lvv_ref, o_ref, m_sc, l_sc, acc_sc,
                  *, scale: float, page_size: int, nb: int, num_levels: int,
                  kv_heads: int):
    """Paged T2 step: code/level tiles ARE physical page bt[b, ib] with all
    kv heads (resolved by the BlockSpec index maps from the scalar-prefetched
    block table); per-slot HQE scale/zero stay slot-indexed by b.
    Dequantization happens in VMEM on the page — HBM moved only the
    compressed bytes of mapped pages."""
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
        for h in range(kv_heads):                        # static: per kv head
            q = q_ref[0, h].astype(jnp.float32)          # (G, Dh)
            k_hat = _page_dequant(ck_ref, lvk_ref, sk_ref, zk_ref, h,
                                  num_levels)
            s = jax.lax.dot_general(q, k_hat, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            pos = ib * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(pos < len_ref[b], s, NEG_INF)  # partial last page
            v_hat = _page_dequant(cv_ref, lvv_ref, sv_ref, zv_ref, h,
                                  num_levels)
            online_update(s, v_hat, m_sc, l_sc, acc_sc, h)

    @pl.when(ib == nb - 1)
    def _finish():
        o_ref[0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


def _paged_prefill_kernel(bt_ref, lens_ref, q_ref, ck_ref, cv_ref, sk_ref,
                          zk_ref, sv_ref, zv_ref, lvk_ref, lvv_ref, kraw_ref,
                          vraw_ref, o_ref, m_sc, l_sc, acc_sc, *, scale: float,
                          page_size: int, nb: int, num_levels: int, group: int,
                          kv_heads: int):
    """One ib step of the Q-chunk>1 paged T2 prefill sweep for the slot
    being admitted, all kv heads of a page at once. Grid steps ib < nb
    dequantize the slot's EARLIER code pages (positions < offset —
    cross-chunk keys read exactly what decode will read); the extra final
    step ib == nb attends the chunk's RAW roped K/V tile causally, so a
    single-chunk admission reproduces the one-shot prefill's raw-attention
    numerics bit-for-bit. lens = (offset, valid)."""
    ib = pl.program_id(0)

    @pl.when(ib == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # earlier-chunk pages: dequantize in VMEM, positions >= offset are dead
    # (the current chunk's keys are served raw by the final grid step)
    @pl.when((ib < nb) & (ib * page_size < lens_ref[0]))
    def _pages():
        for h in range(kv_heads):                        # static: per kv head
            q = q_ref[0, h].astype(jnp.float32)          # (C*G, Dh)
            k_hat = _page_dequant(ck_ref, lvk_ref, sk_ref, zk_ref, h,
                                  num_levels)
            s = jax.lax.dot_general(q, k_hat, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            pos = ib * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(pos < lens_ref[0], s, NEG_INF)  # earlier tokens only
            v_hat = _page_dequant(cv_ref, lvv_ref, sv_ref, zv_ref, h,
                                  num_levels)
            online_update(s, v_hat, m_sc, l_sc, acc_sc, h)

    # final step: the chunk's raw roped K/V, causal within the chunk
    @pl.when(ib == nb)
    def _raw_tail():
        for h in range(kv_heads):
            q = q_ref[0, h].astype(jnp.float32)          # (C*G, Dh)
            k = kraw_ref[:, h, :].astype(jnp.float32)    # (C, Dh)
            v = vraw_ref[:, h, :].astype(jnp.float32)    # (C, Dv)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            qtok = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
            ok = (col < lens_ref[1]) & (col <= qtok)     # valid & causal
            s = jnp.where(ok, s, NEG_INF)
            online_update(s, v, m_sc, l_sc, acc_sc, h)
        o_ref[0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(
            o_ref.dtype)


def paged_cpq_prefill_fwd(q, codes_k, codes_v, scale_k, zero_k, scale_v,
                          zero_v, level_k, level_v, k_raw, v_raw, block_row,
                          offset, valid, *, scale: float,
                          interpret: bool = True):
    """Chunked paged T2 prefill for one slot: the admission chunk's C queries
    attend the slot's earlier code/level pages (dequantized in VMEM — HBM
    moves only compressed bytes) plus the chunk's raw roped K/V causally.
    No contiguous scratch cache and no logical CPQ view is materialized.

    q: (1, KV, C*G, Dh) token-major rows (row r = chunk token r // G);
    codes_*/level_*: (P, page, KV, D*) i8 / (P, page, KV) i32 pools;
    scale_/zero_*: (1, L, KV, D*) f32 HQE side state of THIS slot;
    k_raw/v_raw: (C, KV, Dh|Dv) the chunk's raw roped keys/values;
    block_row: (max_blocks,) int32 (0 = null page); offset/valid: () int32.
    Returns (1, KV, C*G, Dv) f32; rows past ``valid`` are jit-padding
    garbage."""
    _, KV, CG, Dh = q.shape
    C = k_raw.shape[0]
    G = CG // C
    page = codes_k.shape[1]
    Dv = codes_v.shape[-1]
    L = scale_k.shape[1]
    nb = block_row.shape[0]
    lens = jnp.stack([offset, valid]).astype(jnp.int32)

    kern = functools.partial(_paged_prefill_kernel, scale=scale,
                             page_size=page, nb=nb, num_levels=L, group=G,
                             kv_heads=KV)
    # page index maps clamp ib to nb-1 so the extra raw-tail grid step keeps
    # well-formed (dummy) page operands
    pg = lambda ib, bt: bt[jnp.minimum(ib, nb - 1)]  # noqa: E731
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # block_row, (offset, valid)
            grid=(nb + 1,),         # block-table sweep + raw-chunk tail
            in_specs=[
                pl.BlockSpec((1, KV, CG, Dh), lambda ib, bt, ln: (0, 0, 0, 0)),
                pl.BlockSpec((1, page, KV, Dh),
                             lambda ib, bt, ln: (pg(ib, bt), 0, 0, 0)),
                pl.BlockSpec((1, page, KV, Dv),
                             lambda ib, bt, ln: (pg(ib, bt), 0, 0, 0)),
                pl.BlockSpec((1, L, KV, Dh), lambda ib, bt, ln: (0, 0, 0, 0)),
                pl.BlockSpec((1, L, KV, Dh), lambda ib, bt, ln: (0, 0, 0, 0)),
                pl.BlockSpec((1, L, KV, Dv), lambda ib, bt, ln: (0, 0, 0, 0)),
                pl.BlockSpec((1, L, KV, Dv), lambda ib, bt, ln: (0, 0, 0, 0)),
                pl.BlockSpec((1, page, KV),
                             lambda ib, bt, ln: (pg(ib, bt), 0, 0)),
                pl.BlockSpec((1, page, KV),
                             lambda ib, bt, ln: (pg(ib, bt), 0, 0)),
                pl.BlockSpec((C, KV, Dh), lambda ib, bt, ln: (0, 0, 0)),
                pl.BlockSpec((C, KV, Dv), lambda ib, bt, ln: (0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, KV, CG, Dv),
                                   lambda ib, bt, ln: (0, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((KV, CG, 1), jnp.float32),
                pltpu.VMEM((KV, CG, 1), jnp.float32),
                pltpu.VMEM((KV, CG, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((1, KV, CG, Dv), jnp.float32),
        name="paged_cpq_prefill",
        interpret=interpret,
    )(block_row.astype(jnp.int32), lens,
      q, codes_k, codes_v, scale_k, zero_k, scale_v, zero_v,
      level_k.astype(jnp.int32), level_v.astype(jnp.int32), k_raw, v_raw)


def paged_cpq_decode_fwd(q, codes_k, codes_v, scale_k, zero_k, scale_v, zero_v,
                         level_k, level_v, block_table, lengths, *,
                         scale: float, interpret: bool = True):
    """Paged T2 decode: the grid's innermost axis iterates block-table entries
    and each mapped code/level page is DMA'd from the arena into VMEM — no
    contiguous logical CPQ view is materialized.

    q: (B, KV, G, Dh); codes_*: (P, page, KV, D*) i8 pools; level_*:
    (P, page, KV) i32 pools; scale_/zero_*: (B, L, KV, D*) f32 per-SLOT HQE
    side state; block_table: (B, max_blocks) int32 (0 = null page);
    lengths: (B,) int32. Returns (B, KV, G, Dv) f32.

    Masking convention: positions >= lengths[b] (null pages, partial last
    page) are dead; lengths[b] == 0 rows return zeros."""
    B, KV, G, Dh = q.shape
    page = codes_k.shape[1]
    Dv = codes_v.shape[-1]
    L = scale_k.shape[1]
    nb = block_table.shape[1]

    kern = functools.partial(_paged_kernel, scale=scale, page_size=page,
                             nb=nb, num_levels=L, kv_heads=KV)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # block_table, lengths
            grid=(B, nb),
            in_specs=[
                pl.BlockSpec((1, KV, G, Dh), lambda b, ib, bt, ln: (b, 0, 0, 0)),
                pl.BlockSpec((1, page, KV, Dh),
                             lambda b, ib, bt, ln: (bt[b, ib], 0, 0, 0)),
                pl.BlockSpec((1, page, KV, Dv),
                             lambda b, ib, bt, ln: (bt[b, ib], 0, 0, 0)),
                pl.BlockSpec((1, L, KV, Dh), lambda b, ib, bt, ln: (b, 0, 0, 0)),
                pl.BlockSpec((1, L, KV, Dh), lambda b, ib, bt, ln: (b, 0, 0, 0)),
                pl.BlockSpec((1, L, KV, Dv), lambda b, ib, bt, ln: (b, 0, 0, 0)),
                pl.BlockSpec((1, L, KV, Dv), lambda b, ib, bt, ln: (b, 0, 0, 0)),
                pl.BlockSpec((1, page, KV),
                             lambda b, ib, bt, ln: (bt[b, ib], 0, 0)),
                pl.BlockSpec((1, page, KV),
                             lambda b, ib, bt, ln: (bt[b, ib], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, KV, G, Dv),
                                   lambda b, ib, bt, ln: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((KV, G, 1), jnp.float32),
                pltpu.VMEM((KV, G, 1), jnp.float32),
                pltpu.VMEM((KV, G, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Dv), jnp.float32),
        name="paged_cpq_decode",
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      q, codes_k, codes_v, scale_k, zero_k, scale_v, zero_v,
      level_k.astype(jnp.int32), level_v.astype(jnp.int32))


def cpq_decode_fwd(q, codes_k, codes_v, scale_k, zero_k, scale_v, zero_v,
                   level_k, level_v, length, *, scale: float,
                   block_n: int = 512, interpret: bool = True):
    """q: (B, KV, G, Dh); codes_*: (B, N, KV, D*) i8; scale_/zero_*:
    (B, L, KV, D*) f32; level_*: (B, N, KV) i32; length: () int32.
    Returns (B, KV, G, Dv)."""
    B, KV, G, Dh = q.shape
    N = codes_k.shape[1]
    Dv = codes_v.shape[-1]
    L = scale_k.shape[1]
    bn = min(block_n, N)
    pad = (-N) % bn
    if pad:
        codes_k = jnp.pad(codes_k, ((0, 0), (0, pad), (0, 0), (0, 0)),
                          constant_values=-128)
        codes_v = jnp.pad(codes_v, ((0, 0), (0, pad), (0, 0), (0, 0)),
                          constant_values=-128)
        level_k = jnp.pad(level_k, ((0, 0), (0, pad), (0, 0)))
        level_v = jnp.pad(level_v, ((0, 0), (0, pad), (0, 0)))
    nn = (N + pad) // bn

    kern = functools.partial(_kernel, scale=scale, block_n=bn, nn=nn,
                             num_levels=L)
    return pl.pallas_call(
        kern,
        grid=(B, KV, nn),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, Dh), lambda b, kv, ib: (b, kv, 0, 0)),
            pl.BlockSpec((1, bn, 1, Dh), lambda b, kv, ib: (b, ib, kv, 0)),
            pl.BlockSpec((1, bn, 1, Dv), lambda b, kv, ib: (b, ib, kv, 0)),
            pl.BlockSpec((1, L, 1, Dh), lambda b, kv, ib: (b, 0, kv, 0)),
            pl.BlockSpec((1, L, 1, Dh), lambda b, kv, ib: (b, 0, kv, 0)),
            pl.BlockSpec((1, L, 1, Dv), lambda b, kv, ib: (b, 0, kv, 0)),
            pl.BlockSpec((1, L, 1, Dv), lambda b, kv, ib: (b, 0, kv, 0)),
            pl.BlockSpec((1, bn, 1), lambda b, kv, ib: (b, ib, kv)),
            pl.BlockSpec((1, bn, 1), lambda b, kv, ib: (b, ib, kv)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dv), lambda b, kv, ib: (b, kv, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Dv), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, Dv), jnp.float32),
        ],
        name="cpq_decode",
        interpret=interpret,
    )(length.reshape(1).astype(jnp.int32), q, codes_k, codes_v,
      scale_k, zero_k, scale_v, zero_v,
      level_k.astype(jnp.int32), level_v.astype(jnp.int32))
