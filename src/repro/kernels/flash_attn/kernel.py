"""Flash attention forward, Pallas TPU.

Grid: (B, H, nq, nk) — nk is the innermost (sequential on-core) axis, so the
online-softmax state for one (b, h, iq) lives in VMEM scratch across the nk
sweep; the (T x S) score matrix never exists. Tiles are MXU-aligned
(block_q x head_dim and block_k x head_dim, head_dim a multiple of 128 on the
lane axis is ideal; 64 also maps cleanly on v5e).

Causal blocks that are fully masked are skipped with pl.when (no MXU work).
GQA: the kv-head index for query head h is h // (H // KV), computed in the
BlockSpec index_map so K/V tiles are fetched per kv head.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            scale: float, causal: bool, block_q: int, block_k: int,
            nk: int, seq_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q_start = iq * block_q
    k_start = ik * block_k
    # skip fully-masked causal blocks (first row of q tile vs last k row)
    live = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, :, 0, :]                      # (bq, D)
        k = k_ref[0, :, 0, :]                      # (bk, D)
        v = v_ref[0, :, 0, :]                      # (bk, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < seq_k
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = mask & (kpos <= qpos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0, :, 0, :] = (
            acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


def online_update(s, v, m_sc, l_sc, acc_sc, h):
    """One online-softmax step for kv head ``h``: fold scores ``s`` (rows,
    n) and values ``v`` (n, Dv) into the f32 scratch state of that head."""
    m_prev = m_sc[h]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_sc[h] = l_sc[h] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_sc[h] = m_new
    acc_sc[h] = acc_sc[h] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _paged_decode_kernel(bt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref,
                         o_ref, m_sc, l_sc, acc_sc, *, scale: float,
                         page_size: int, nb: int, kv_heads: int, head_dim: int,
                         v_dim: int):
    """One (b, ib) step: the K/V tile IS physical page bt[b, ib] of layer
    ``layer`` in the stacked lane-dense arena (block (1, page, KV*Dh): the
    last two block dims span whole array dims, which Mosaic's (8, 128)
    tiling accepts at any head count) — the BlockSpec index map resolved the
    layer and the block table before the body ran, so the page was DMA'd
    straight from the arena into VMEM. Head h is lanes [h*Dh, (h+1)*Dh).

    One sweep serves both attention matmuls of every head per page (scores
    AND weighted-V accumulate while the page sits in VMEM); softmax state is
    carried online in f32 scratch across the block-table sweep."""
    b = pl.program_id(0)
    ib = pl.program_id(1)

    @pl.when(ib == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # pages wholly past the row's length are unmapped (null page 0, garbage
    # contents by convention) — skip them entirely: no MXU work
    @pl.when(ib * page_size < len_ref[b])
    def _compute():
        for h in range(kv_heads):                      # static: per kv head
            q = q_ref[0, h].astype(jnp.float32)        # (G, Dh)
            k = k_ref[0, :, h * head_dim:(h + 1) * head_dim].astype(
                jnp.float32)                           # (page, Dh)
            v = v_ref[0, :, h * v_dim:(h + 1) * v_dim].astype(
                jnp.float32)                           # (page, Dv)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale      # (G, page)
            # null-page / partial-last-page masking: position vs row length
            pos = ib * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(pos < len_ref[b], s, NEG_INF)
            online_update(s, v, m_sc, l_sc, acc_sc, h)

    @pl.when(ib == nb - 1)
    def _finish():
        o_ref[0] = (
            acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


def _paged_prefill_kernel(bt_ref, lens_ref, layer_ref, q_ref, k_ref, v_ref,
                          o_ref, m_sc, l_sc, acc_sc, *, scale: float,
                          page_size: int, nb: int, group: int, kv_heads: int,
                          head_dim: int, v_dim: int):
    """One ib step of the Q-chunk>1 paged prefill sweep: queries are the
    admission chunk's C tokens (flattened (C*G) rows per kv head), the K/V
    tile IS physical page bt[ib] of layer ``layer`` in the stacked
    lane-dense arena, all kv heads at once (head h: lanes
    [h*Dh, (h+1)*Dh)). lens holds (offset, total): ``offset`` tokens
    preceded this chunk, ``total`` = offset + valid masks the chunk's jit
    padding. Causal masking
    is per query ROW: row r is chunk token r // G at absolute position
    offset + r // G."""
    ib = pl.program_id(0)

    @pl.when(ib == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # pages wholly past the row's post-chunk length are unmapped: skip
    @pl.when(ib * page_size < lens_ref[1])
    def _compute():
        for h in range(kv_heads):                      # static: per kv head
            q = q_ref[h].astype(jnp.float32)           # (C*G, Dh)
            k = k_ref[0, :, h * head_dim:(h + 1) * head_dim].astype(
                jnp.float32)                           # (page, Dh)
            v = v_ref[0, :, h * v_dim:(h + 1) * v_dim].astype(
                jnp.float32)                           # (page, Dv)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale      # (C*G, page)
            pos = ib * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            qtok = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
            ok = (pos < lens_ref[1]) & (pos <= lens_ref[0] + qtok)  # causal
            s = jnp.where(ok, s, NEG_INF)
            online_update(s, v, m_sc, l_sc, acc_sc, h)

    @pl.when(ib == nb - 1)
    def _finish():
        o_ref[...] = (
            acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


def _layer_index(layer) -> jax.Array:
    """The stacked arena's layer to read, as a (1,) int32 scalar-prefetch
    operand."""
    return jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))


def paged_flash_prefill_fwd(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                            block_row: jax.Array, offset: jax.Array,
                            valid: jax.Array, layer: jax.Array, *, scale: float,
                            interpret: bool = True) -> jax.Array:
    """Chunked paged prefill attention for ONE request slot: the chunk's C
    queries attend over the slot's pages [0, offset + valid) — the chunk's own
    K/V were just written into those pages, so no contiguous scratch cache
    exists. Same scalar-prefetch construction as the decode kernel, with a
    per-query-row causal mask (query i sits at absolute position offset + i).

    q: (1, C, H, Dh); k_pages/v_pages: (L, P, page, KV*Dh|KV*Dv) stacked
    lane-dense pools (head h at lanes [h*Dh, (h+1)*Dh)), read at ``layer``;
    block_row: (max_blocks,) int32 (0 = null page); offset/valid/layer: ()
    int32 — tokens already in the slot before this chunk / real tokens in
    this chunk (the tail up to C is jit padding whose output is garbage) /
    the layer of the stack. Returns (1, C, H, Dv)."""
    _, C, H, Dh = q.shape
    page = k_pages.shape[2]
    KV = k_pages.shape[3] // Dh
    Dv = v_pages.shape[3] // KV
    g = H // KV
    nb = block_row.shape[0]
    # (KV, C*G, Dh), token-major rows within each kv head: row r = token r // g
    qg = q[0].reshape(C, KV, g, Dh).transpose(1, 0, 2, 3).reshape(KV, C * g, Dh)
    lens = jnp.stack([offset, offset + valid]).astype(jnp.int32)

    kern = functools.partial(_paged_prefill_kernel, scale=scale,
                             page_size=page, nb=nb, group=g, kv_heads=KV,
                             head_dim=Dh, v_dim=Dv)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # block_row, (offset, total), layer
            grid=(nb,),             # sweeps the slot's block-table entries
            in_specs=[
                pl.BlockSpec((KV, C * g, Dh), lambda ib, bt, ln, ly: (0, 0, 0)),
                pl.BlockSpec((pl.squeezed, 1, page, KV * Dh),
                             lambda ib, bt, ln, ly: (ly[0], bt[ib], 0, 0)),
                pl.BlockSpec((pl.squeezed, 1, page, KV * Dv),
                             lambda ib, bt, ln, ly: (ly[0], bt[ib], 0, 0)),
            ],
            out_specs=pl.BlockSpec((KV, C * g, Dv),
                                   lambda ib, bt, ln, ly: (0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((KV, C * g, 1), jnp.float32),
                pltpu.VMEM((KV, C * g, 1), jnp.float32),
                pltpu.VMEM((KV, C * g, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((KV, C * g, Dv), q.dtype),
        name="paged_flash_prefill",
        interpret=interpret,
    )(block_row.astype(jnp.int32), lens, _layer_index(layer), qg, k_pages,
      v_pages)
    return out.reshape(KV, C, g, Dv).transpose(1, 0, 2, 3).reshape(1, C, H, Dv)


def paged_flash_decode_fwd(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                           block_table: jax.Array, lengths: jax.Array,
                           layer: jax.Array, *, scale: float,
                           interpret: bool = True) -> jax.Array:
    """Paged single-token flash decode: grid iterates block-table entries and
    DMAs each mapped page of layer ``layer`` from the stacked arena into VMEM
    via the BlockSpec index map (scalar-prefetched block table and layer) —
    neither the layer's pool nor the contiguous logical K/V view is ever
    materialized.

    q: (B, 1, H, Dh); k_pages/v_pages: (L, P, page, KV*Dh|KV*Dv) stacked
    lane-dense pools (head h at lanes [h*Dh, (h+1)*Dh)); block_table:
    (B, max_blocks) int32, 0 = unmapped (null page); lengths: (B,) int32
    valid tokens per row; layer: () int32. Returns (B, 1, H, Dv).

    Masking convention (shared with serving/paged_cache.py): positions >=
    lengths[b] — including every slot of an unmapped/null page and the tail of
    a partial last page — contribute nothing; a row with lengths[b] == 0
    returns zeros."""
    B, _, H, Dh = q.shape
    page = k_pages.shape[2]
    KV = k_pages.shape[3] // Dh
    Dv = v_pages.shape[3] // KV
    g = H // KV
    nb = block_table.shape[1]
    qg = q[:, 0].reshape(B, KV, g, Dh)

    kern = functools.partial(_paged_decode_kernel, scale=scale,
                             page_size=page, nb=nb, kv_heads=KV, head_dim=Dh,
                             v_dim=Dv)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # block_table, lengths, layer
            grid=(B, nb),           # innermost axis sweeps block-table entries
            in_specs=[
                pl.BlockSpec((1, KV, g, Dh),
                             lambda b, ib, bt, ln, ly: (b, 0, 0, 0)),
                pl.BlockSpec((pl.squeezed, 1, page, KV * Dh),
                             lambda b, ib, bt, ln, ly: (ly[0], bt[b, ib], 0, 0)),
                pl.BlockSpec((pl.squeezed, 1, page, KV * Dv),
                             lambda b, ib, bt, ln, ly: (ly[0], bt[b, ib], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, KV, g, Dv),
                                   lambda b, ib, bt, ln, ly: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((KV, g, 1), jnp.float32),
                pltpu.VMEM((KV, g, 1), jnp.float32),
                pltpu.VMEM((KV, g, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, g, Dv), q.dtype),
        name="paged_flash_decode",
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      _layer_index(layer), qg, k_pages, v_pages)
    return out.reshape(B, 1, H, Dv)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        scale: float, causal: bool = True,
                        block_q: int = 512, block_k: int = 512,
                        interpret: bool = True) -> jax.Array:
    """q: (B, T, H, D), k/v: (B, S, KV, D/Dv) -> (B, T, H, Dv)."""
    B, T, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    g = H // KV
    bq = min(block_q, T)
    bk = min(block_k, S)
    pad_q = (-T) % bq
    pad_k = (-S) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq = (T + pad_q) // bq
    nk = (S + pad_k) // bk

    grid = (B, H, nq, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, block_q=bq,
                          block_k=bk, nk=nk, seq_k=S),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1, D), lambda b, h, iq, ik: (b, iq, h, 0)),
            pl.BlockSpec((1, bk, 1, D), lambda b, h, iq, ik: (b, ik, h // g, 0)),
            pl.BlockSpec((1, bk, 1, Dv), lambda b, h, iq, ik: (b, ik, h // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, Dv), lambda b, h, iq, ik: (b, iq, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T + pad_q, H, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
    return out[:, :T]
