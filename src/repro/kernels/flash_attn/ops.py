"""Jit'd public wrapper for the flash attention kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro import kernels as K
from repro.kernels.flash_attn.kernel import (flash_attention_fwd,
                                             paged_flash_decode_fwd,
                                             paged_flash_prefill_fwd)


@partial(jax.jit, static_argnames=("scale", "causal", "block_q", "block_k",
                                   "interpret"))
def flash_attention_tpu(q, k, v, scale: float, causal: bool = True,
                        block_q: int = 512, block_k: int = 512,
                        interpret: bool | None = None):
    return K.platform_call(flash_attention_fwd, q, k, v, scale=scale,
                           causal=causal, block_q=block_q, block_k=block_k,
                           interpret=interpret)


@partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_flash_prefill_tpu(q, k_pages, v_pages, block_row, offset, valid,
                            scale: float, interpret: bool | None = None):
    """Chunked paged prefill for one slot: the admission chunk's C queries
    attend the slot's pages [0, offset + valid) through its block-table row
    (the chunk's K/V already live in those pages). q: (1, C, H, Dh);
    block_row: (max_blocks,) int32 (0 = null page); offset/valid: () int32.
    -> (1, C, H, Dv); rows past ``valid`` are jit-padding garbage."""
    return K.platform_call(paged_flash_prefill_fwd, q, k_pages, v_pages,
                           block_row, offset, valid, scale=scale,
                           interpret=interpret)


@partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_flash_decode_tpu(q, k_pages, v_pages, block_table, lengths,
                           scale: float, interpret: bool | None = None):
    """Paged dense decode over a (P, page, KV, Dh) arena through its block
    table — no contiguous logical view. q: (B, 1, H, Dh); block_table:
    (B, max_blocks) int32 (0 = null page); lengths: (B,) int32 valid tokens
    per row. -> (B, 1, H, Dv)."""
    return K.platform_call(paged_flash_decode_fwd, q, k_pages, v_pages,
                           block_table, lengths, scale=scale,
                           interpret=interpret)
