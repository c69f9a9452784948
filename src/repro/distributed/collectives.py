"""Manual collective patterns (shard_map) for the hot distributed paths.

1. ``flash_decoding_attention`` — decode attention over a SEQUENCE-SHARDED
   cache: each shard computes (m, l, o) over its local tokens, then a single
   psum-based softmax combine merges shards. One small collective instead of
   all-gathering the cache. This is the distributed analogue of the paper's
   sub-matrix pipeline: partial attention results stream out of each memory
   shard and are merged, instead of centralizing the operand.

2. ``ring_decomposed_scores`` — T1 score stage over a sequence-sharded
   X-cache with a ppermute ring: compute on the resident block while the next
   block's owner index rotates — per-step overlap of collective and compute
   (paper Fig. 3(b) across chips).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

NEG_INF = -1e30


def _local_flash(q, k, v, scale, base, length):
    """q: (B,H,Dh); k/v: (B,n,KV,Dh) local shard starting at global ``base``.
    Returns (m, l, o) partial softmax stats, f32."""
    B, H, Dh = q.shape
    KV = k.shape[2]
    g = H // KV
    qg = q.reshape(B, KV, g, Dh)
    s = jnp.einsum("bkgd,bnkd->bkgn", qg, k).astype(jnp.float32) * scale
    pos = base + jnp.arange(k.shape[1], dtype=jnp.int32)
    s = jnp.where((pos < length)[None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                      # (B,KV,g)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgn,bnkd->bkgd", p.astype(v.dtype), v).astype(jnp.float32)
    return m, l, o


def flash_decoding_attention(mesh: Mesh, seq_axis: str):
    """Returns fn(q (B,1,H,Dh), k, v (B,N,KV,Dh) seq-sharded, length) ->
    (B,1,H,Dh); softmax combine via psum over ``seq_axis``."""

    def inner(q, k, v, length, scale):
        ax = jax.lax.axis_index(seq_axis)
        n_local = k.shape[1]
        base = ax * n_local
        m, l, o = _local_flash(q[:, 0], k, v, scale, base, length)
        m_g = jax.lax.pmax(m, seq_axis)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, seq_axis)
        o_g = jax.lax.psum(o * corr[..., None], seq_axis)
        out = o_g / jnp.maximum(l_g, 1e-30)[..., None]
        B, KV, g, Dh = out.shape
        return out.reshape(B, 1, KV * g, Dh).astype(q.dtype)

    def fn(q, k, v, length, scale: float):
        return shard_map(
            partial(inner, scale=scale),
            mesh=mesh,
            in_specs=(P(None, None, None, None), P(None, seq_axis, None, None),
                      P(None, seq_axis, None, None), P()),
            out_specs=P(None, None, None, None),
        )(q, k, v, length)

    return fn


def ring_decomposed_scores(mesh: Mesh, axis: str):
    """T1 score stage R X^T with HEADS sharded over ``axis`` and the X cache
    SEQUENCE-sharded over the same axis — the classic ring matmul: each shard
    computes its heads' scores against the resident X block while blocks
    rotate via ppermute, overlapping transfer with compute (the paper's
    sub-matrix pipeline across chips).

    Returns fn(r (B,H,Dm) heads-sharded, x (B,N,Dm) seq-sharded)
    -> scores (B,H,N) with H sharded over ``axis``."""
    n_dev = mesh.shape[axis]

    def inner(r, x):
        idx = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

        def step(carry, _):
            xb, src = carry  # resident block, owner index of that block
            s = jnp.einsum("bhm,bnm->bhn", r, xb).astype(jnp.float32)
            xb = jax.lax.ppermute(xb, axis, perm)
            nxt = (src - 1) % n_dev
            return (xb, nxt), (s, src)

        (_, _), (ss, srcs) = jax.lax.scan(step, (x, idx), None, length=n_dev)
        # chunk computed at step t came from shard srcs[t]; restore global order
        order = jnp.argsort(srcs)
        ss = jnp.take(ss, order, axis=0)          # (n_dev, B, H_loc, n_local)
        return jnp.moveaxis(ss, 0, 2).reshape(r.shape[0], r.shape[1], -1)

    def fn(r, x):
        return shard_map(
            inner, mesh=mesh,
            in_specs=(P(None, axis, None), P(None, axis, None)),
            out_specs=P(None, axis, None),
        )(r, x)

    return fn
