"""T3 — proxy-similarity scoring kernel (paper §V), Pallas TPU.

The CAM analogue: an associative lookup over ALL cached keys realized as an
int8-code matmul on the MXU. Per-channel affine codes give

    score ~ q . k_hat = (q * scale) . code + q . zero

The per-head query-side factors (qs = q * scale[kv(h)], qz = q . zero[kv(h)])
are precomputed outside (O(Dp) per head); the kernel does the O(N) sweep:
one int8 code block load -> one MXU matmul -> masked score block. HBM traffic
is 1 byte per (key, channel) instead of 2 (bf16), and V is not touched at all
during candidate search.

Grid: (B, KV, nn). Output: proxy scores (B, H, N) f32 for lax.top_k outside.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, qs_ref, qz_ref, c_ref, o_ref, *, block_n: int):
    ib = pl.program_id(2)
    qs = qs_ref[0, 0]                                # (G, Dp)
    qz = qz_ref[0, 0]                                # (G, 1)
    c = c_ref[0, :, 0, :].astype(jnp.float32) + 128.0  # (bn, Dp)
    s = jax.lax.dot_general(qs, c, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) + qz  # (G, bn)
    pos = ib * block_n + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    o_ref[0, 0] = jnp.where(pos < len_ref[0], s, NEG_INF)


def proxy_scores_fwd(qs, qz, codes, length, *, block_n: int = 1024,
                     interpret: bool = True):
    """qs: (B, KV, G, Dp) f32 (= q * scale); qz: (B, KV, G, 1) f32
    (= q . zero); codes: (B, N, KV, Dp) i8 (stored code-128).
    Returns (B, KV, G, N) f32 masked proxy scores."""
    B, KV, G, Dp = qs.shape
    N = codes.shape[1]
    bn = min(block_n, N)
    pad = (-N) % bn
    if pad:
        codes = jnp.pad(codes, ((0, 0), (0, pad), (0, 0), (0, 0)),
                        constant_values=-128)
    nn = (N + pad) // bn

    out = pl.pallas_call(
        functools.partial(_kernel, block_n=bn),
        grid=(B, KV, nn),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, Dp), lambda b, kv, ib: (b, kv, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, kv, ib: (b, kv, 0, 0)),
            pl.BlockSpec((1, bn, 1, Dp), lambda b, kv, ib: (b, ib, kv, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, bn), lambda b, kv, ib: (b, kv, 0, ib)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, N + pad), jnp.float32),
        name="proxy_scores",
        interpret=interpret,
    )(length.reshape(1).astype(jnp.int32), qs, qz, codes)
    return out[..., :N]
