"""Pure-jnp oracle: dequantize the whole CPQ arena, run dense attention."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _dequant_full(codes, scale, zero, level):
    """codes: (B,N,KV,D) i8; scale/zero: (B,L,KV,D); level: (B,N,KV)."""
    lvl = level[..., None]
    s = jnp.take_along_axis(scale, jnp.broadcast_to(lvl, codes.shape), axis=1)
    z = jnp.take_along_axis(zero, jnp.broadcast_to(lvl, codes.shape), axis=1)
    c = codes.astype(jnp.float32) + 128.0
    return jnp.where(c == 0.0, 0.0, (c - 1.0) * s + z)


def paged_cpq_decode_ref(q, codes_k, codes_v, scale_k, zero_k, scale_v, zero_v,
                         level_k, level_v, block_table, lengths, scale):
    """Oracle for the paged T2 kernel, straight from the paged layout:
    q: (B, KV, G, Dh); codes_*: (P, page, KV, D*) i8 pools; level_*:
    (P, page, KV) i32 pools; scale_/zero_*: (B, L, KV, D*) per-slot HQE side
    state; block_table: (B, max_blocks) (0 = null page); lengths: (B,).
    -> (B, KV, G, Dv) f32; positions >= lengths[b] masked, empty rows zero."""
    B = q.shape[0]
    page, KV = codes_k.shape[1], codes_k.shape[2]
    nb = block_table.shape[1]
    ck = jnp.take(codes_k, block_table, axis=0).reshape(
        B, nb * page, KV, codes_k.shape[-1])
    cv = jnp.take(codes_v, block_table, axis=0).reshape(
        B, nb * page, KV, codes_v.shape[-1])
    lk = jnp.take(level_k, block_table, axis=0).reshape(B, nb * page, KV)
    lv = jnp.take(level_v, block_table, axis=0).reshape(B, nb * page, KV)
    # null-page levels may be arbitrary garbage: clamp so the gather in
    # _dequant_full stays in range (the positions are masked below anyway)
    L = scale_k.shape[1]
    lk = jnp.clip(lk, 0, L - 1)
    lv = jnp.clip(lv, 0, L - 1)
    # same bf16 rounding of dequantized tiles as the serving gather path
    k_hat = _dequant_full(ck, scale_k, zero_k, lk).astype(
        jnp.bfloat16).astype(jnp.float32)
    v_hat = _dequant_full(cv, scale_v, zero_v, lv).astype(
        jnp.bfloat16).astype(jnp.float32)
    s = jnp.einsum("bkgd,bnkd->bkgn", q.astype(jnp.float32), k_hat) * scale
    pos = jnp.arange(nb * page, dtype=jnp.int32)
    live = pos[None, :] < lengths[:, None]
    s = jnp.where(live[:, None, None, :], s, NEG_INF)
    w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    l = jnp.sum(w, axis=-1, keepdims=True)
    o = jnp.einsum("bkgn,bnkd->bkgd", w, v_hat) / jnp.maximum(l, 1e-30)
    return jnp.where((lengths > 0)[:, None, None, None], o, 0.0)


def paged_cpq_prefill_ref(q, codes_k, codes_v, scale_k, zero_k, scale_v,
                          zero_v, level_k, level_v, k_raw, v_raw, block_row,
                          offset, valid, scale):
    """Oracle for the chunked paged T2 prefill kernel, straight from the
    paged layout: the slot's earlier code pages (positions < offset,
    dequantized with this slot's HQE state and rounded to bf16) plus the
    chunk's raw keys/values (positions offset + i, i < valid), causal.
    q: (1, KV, C*G, Dh) token-major rows (row r = chunk token r // G);
    codes_*/level_*: (P, page, KV, D*) / (P, page, KV) pools; scale_/zero_*:
    (1, L, KV, D*); k_raw/v_raw: (C, KV, Dh|Dv); block_row: (max_blocks,).
    -> (1, KV, C*G, Dv) f32; rows past ``valid`` are padding."""
    _, KV, CG, _ = q.shape
    C = k_raw.shape[0]
    G = CG // C
    N = block_row.shape[0] * codes_k.shape[1]
    L = scale_k.shape[1]

    def logical(codes, level, sc, zr):
        c = jnp.take(codes, block_row, axis=0).reshape(1, N, KV, -1)
        lv = jnp.clip(jnp.take(level, block_row, axis=0).reshape(1, N, KV),
                      0, L - 1)
        return _dequant_full(c, sc, zr, lv)[0].astype(
            jnp.bfloat16).astype(jnp.float32)                   # (N, KV, D)

    k_all = jnp.concatenate([logical(codes_k, level_k, scale_k, zero_k),
                             k_raw.astype(jnp.float32)])
    v_all = jnp.concatenate([logical(codes_v, level_v, scale_v, zero_v),
                             v_raw.astype(jnp.float32)])
    kpos = jnp.concatenate([jnp.arange(N, dtype=jnp.int32),
                            offset + jnp.arange(C, dtype=jnp.int32)])
    live = jnp.concatenate([jnp.arange(N) < offset, jnp.arange(C) < valid])
    qpos = offset + jnp.arange(CG, dtype=jnp.int32) // G
    ok = live[None, :] & (kpos[None, :] <= qpos[:, None])       # (CG, N + C)
    s = jnp.einsum("kqd,nkd->kqn", q[0].astype(jnp.float32), k_all) * scale
    s = jnp.where(ok[None], s, NEG_INF)
    w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    l = jnp.sum(w, axis=-1, keepdims=True)
    return (jnp.einsum("kqn,nkd->kqd", w, v_all) / jnp.maximum(l, 1e-30))[None]


def cpq_decode_ref(q, codes_k, codes_v, scale_k, zero_k, scale_v, zero_v,
                   level_k, level_v, length, scale):
    """q: (B, KV, G, Dh) -> (B, KV, G, Dv) f32."""
    k_hat = _dequant_full(codes_k, scale_k, zero_k, level_k)
    v_hat = _dequant_full(codes_v, scale_v, zero_v, level_v)
    s = jnp.einsum("bkgd,bnkd->bkgn", q.astype(jnp.float32), k_hat) * scale
    pos = jnp.arange(codes_k.shape[1], dtype=jnp.int32)
    s = jnp.where((pos < length)[None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgn,bnkd->bkgd", w, v_hat)
