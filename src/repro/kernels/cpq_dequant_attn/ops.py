"""Public op: decode attention over a CPQKVCache via the fused dequant kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import kernels as K
from repro.core.kv_cache import CPQKVCache
from repro.kernels.cpq_dequant_attn.kernel import (cpq_decode_fwd,
                                                   paged_cpq_decode_fwd,
                                                   paged_cpq_prefill_fwd)


@partial(jax.jit, static_argnames=("scale", "block_n", "interpret"))
def cpq_decode_tpu(q, cache: CPQKVCache, scale: float, block_n: int = 512,
                   interpret: bool | None = None):
    """q: (B, 1, H, Dh) roped query; cache: CPQKVCache. -> (B, 1, H, Dv)."""
    B, _, H, Dh = q.shape
    KV = cache.k.codes.shape[2]
    g = H // KV
    qg = q[:, 0].reshape(B, KV, g, Dh)
    out = K.platform_call(
        cpq_decode_fwd, qg, cache.k.codes, cache.v.codes,
        cache.k.scale, cache.k.zero, cache.v.scale, cache.v.zero,
        cache.k.level, cache.v.level, cache.length, scale=scale,
        block_n=block_n, interpret=interpret)
    return out.reshape(B, 1, H, -1).astype(q.dtype)


@partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_cpq_prefill_tpu(q, kt, vt, k_raw, v_raw, slot, block_row, offset,
                          valid, scale: float, interpret: bool | None = None):
    """Chunked paged T2 prefill for one slot: the admission chunk's C queries
    attend the slot's earlier code/level pages (in-VMEM dequant) plus the
    chunk's raw roped K/V causally. q: (1, C, H, Dh) roped chunk queries;
    kt/vt: PagedCPQTensor arenas; k_raw/v_raw: (1, C, KV, Dh|Dv);
    slot/offset/valid: () int32; block_row: (max_blocks,) int32.
    -> (1, C, H, Dv); rows past ``valid`` are jit-padding garbage."""
    _, C, H, Dh = q.shape
    KV = kt.codes.shape[2]
    g = H // KV
    # (1, KV, C*G, Dh), token-major rows within each kv head
    qg = q[0].reshape(C, KV, g, Dh).transpose(1, 0, 2, 3).reshape(1, KV, C * g, Dh)
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0)  # noqa: E731
    out = K.platform_call(
        paged_cpq_prefill_fwd, qg, kt.codes, vt.codes, sl(kt.scale),
        sl(kt.zero), sl(vt.scale), sl(vt.zero), kt.level, vt.level, k_raw[0],
        v_raw[0], block_row, offset, valid, scale=scale, interpret=interpret)
    Dv = out.shape[-1]
    return (out.reshape(KV, C, g, Dv).transpose(1, 0, 2, 3)
            .reshape(1, C, H, Dv).astype(q.dtype))


@partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_cpq_decode_tpu(q, kt, vt, block_table, lengths, scale: float,
                         interpret: bool | None = None):
    """Paged T2 decode over PagedCPQTensor arenas (serving/paged_cache.py)
    through their block table — no contiguous logical CPQ view. q: (B, 1, H,
    Dh) roped query; kt/vt: PagedCPQTensor (code/level pages + per-slot HQE
    scale/zero); block_table: (B, max_blocks) int32 (0 = null page);
    lengths: (B,) int32. -> (B, 1, H, Dv)."""
    B, _, H, Dh = q.shape
    KV = kt.codes.shape[2]
    g = H // KV
    qg = q[:, 0].reshape(B, KV, g, Dh)
    out = K.platform_call(
        paged_cpq_decode_fwd, qg, kt.codes, vt.codes, kt.scale, kt.zero,
        vt.scale, vt.zero, kt.level, vt.level, block_table, lengths,
        scale=scale, interpret=interpret)
    return out.reshape(B, 1, H, -1).astype(q.dtype)
