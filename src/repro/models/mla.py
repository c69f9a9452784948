"""Multi-head Latent Attention (DeepSeek-V2) — implemented as an instance of
the paper's T1 matrix decomposition.

MLA caches a learned 512-d latent ``c_kv = Norm(X W_DKV)`` (plus one shared
64-d roped key) instead of per-head K/V. Decode uses the ABSORBED form:

    score_h = (q_nope_h W_UK_h^T) c^T + q_rope k_rope^T
    out_h   = (S c) W_UV_h

which is literally ``(Q W_K^T) X^T`` / ``(S X) W_V`` with X replaced by the
learned latent — i.e. the paper's decomposition with a compressed operand.
Both stages reuse one cached c read; the roped slice is the decoupled cache.
We therefore route MLA decode through ``core.decomposed_attention`` and reuse
the XCache container (x := c_kv, KV_r := 1 shared rope head).

Modes: "decomposed" (native, default for MLA regardless of the global mode)
and "cpq" (T2 on the latent cache via CPQXCache).

Paged serving keeps the latent in ``PagedLatentCache``: stacked per layer
kind, carried through the layer scan and written in place, read by the paged
MLA kernels at a layer index (``kernels/decomposed_attn``).

Published equations (DeepSeek-V2, arXiv:2405.04434; the HF modeling code):
YaRN rope (``ModelConfig.rope_scaling``) on the 64 rope dims, and the softmax
scale ``(Dn + Dr)^-0.5 * mscale(factor, mscale_all_dim)^2``. Rope pairs
dimension i with i + Dr/2 (halves), where the published code pairs adjacent
columns: the same function after a fixed permutation of the rope output
columns of ``wq`` and ``wdkv``. ``wuk``/``wuv`` are stored (H, Dc, D), the
contracted latent axis second to last.

Profiler scopes: ``attn.qkv``, ``attn.kernel`` (its arena writes under
``kv_write``), ``attn.out``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common.param import ParamDef
from repro.configs.base import AttentionRuntime, ModelConfig
from repro.core import cpq as cpq_lib
from repro.core import kv_cache as kvc
from repro.core.decomposed_attention import decomposed_attention
from repro.core.flash_ref import attention_auto
from repro.distributed.sharding import constrain
from repro.models.layers import apply_rope, apply_rope_rows, rope_tables, yarn_mscale


def _dims(cfg: ModelConfig):
    m = cfg.mla
    return m.kv_lora_rank, m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim


def mla_defs(cfg: ModelConfig):
    d, H = cfg.d_model, cfg.num_heads
    L, Dn, Dr, Dv = _dims(cfg)
    dt = cfg.param_dtype
    return {
        "wq": ParamDef((d, H * (Dn + Dr)), dt, ("embed", "heads"), init="fan_in"),
        "wdkv": ParamDef((d, L + Dr), dt, ("embed", None), init="fan_in"),
        "kv_norm": ParamDef((L,), jnp.float32, (None,), init="ones"),
        "wuk": ParamDef((H, L, Dn), dt, ("heads", None, None), init="fan_in",
                        fan_in_dim=1),
        "wuv": ParamDef((H, L, Dv), dt, ("heads", None, None), init="fan_in",
                        fan_in_dim=1),
        "wo": ParamDef((H * Dv, d), dt, ("heads", "embed"), init="fan_in"),
    }


def _latent_major(w: jax.Array) -> jax.Array:
    """(H, Dc, D) -> (Dc, H, D), the layout ``core.decomposed_attention``
    and the T1 kernels take."""
    return jnp.swapaxes(w, 0, 1)


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) * scale).astype(x.dtype)


def _q_ckv(cfg: ModelConfig, p, x: jax.Array, positions: jax.Array,
           rows: bool = False):
    """Shared projection work: q (nope, roped), the normed latent and the
    roped shared key. ``positions``: (T,), or (B,) per row when ``rows``
    (one-token continuous decode, every row at its own position)."""
    B, T, _ = x.shape
    H = cfg.num_heads
    L, Dn, Dr, Dv = _dims(cfg)
    q = (x @ p["wq"]).reshape(B, T, H, Dn + Dr)
    q = constrain(q, "act_batch", None, "act_heads", None)
    q_nope, q_rope = q[..., :Dn], q[..., Dn:]
    kv = x @ p["wdkv"]
    c = _rms(kv[..., :L], p["kv_norm"])
    k_rope = kv[..., None, L:]  # (B, T, 1, Dr) shared across heads
    cos, sin = rope_tables(positions, Dr, cfg.rope_theta, cfg.rope_scaling)
    rope = apply_rope_rows if rows else apply_rope
    return q_nope, rope(q_rope, cos, sin), c, rope(k_rope, cos, sin)


def _scale(cfg: ModelConfig) -> float:
    _, Dn, Dr, _ = _dims(cfg)
    y = cfg.rope_scaling
    m = yarn_mscale(y.factor, y.mscale_all_dim) if y is not None and y.mscale_all_dim else 1.0
    return (Dn + Dr) ** -0.5 * m * m


def _out(cfg: ModelConfig, p, o: jax.Array) -> jax.Array:
    B, T = o.shape[:2]
    y = o.reshape(B, T, -1) @ p["wo"]
    return constrain(y, "act_batch", None, None)


def _naive(cfg: ModelConfig, p, q_nope, q_rope, c, k_rope):
    """Per-head K/V expanded from the latent; exact causal attention."""
    H = cfg.num_heads
    k_nope = jnp.einsum("btl,hld->bthd", c, p["wuk"])
    v = jnp.einsum("btl,hld->bthd", c, p["wuv"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (*k_rope.shape[:2], H, k_rope.shape[-1]))],
        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    return attention_auto(q, k, v, _scale(cfg), causal=True)


# -------------------------------------------------------------------- train


def mla_train(cfg: ModelConfig, p, x: jax.Array, positions: jax.Array) -> jax.Array:
    """Naive (non-absorbed) path: materialize per-head K/V — best for large-T
    prefill/train where the N*H*Dn score math beats the absorbed extra FLOPs."""
    q_nope, q_rope, c, k_rope = _q_ckv(cfg, p, x, positions)
    return _out(cfg, p, _naive(cfg, p, q_nope, q_rope, c, k_rope))


# ------------------------------------------------------------------ serving


def init_mla_cache(cfg: ModelConfig, rt: AttentionRuntime, batch: int, n_max: int):
    L, _, Dr, _ = _dims(cfg)
    if rt.mode == "cpq":
        return kvc.init_cpq_x(batch, n_max, L, 1, Dr, rt.cpq, cfg.param_dtype)
    return kvc.init_x(batch, n_max, L, 1, Dr, cfg.param_dtype)


def mla_prefill(cfg: ModelConfig, rt: AttentionRuntime, p, x: jax.Array,
                positions: jax.Array, cache):
    T = x.shape[1]
    q_nope, q_rope, c, k_rope = _q_ckv(cfg, p, x, positions)
    o = _naive(cfg, p, q_nope, q_rope, c, k_rope)

    length = jnp.asarray(T, jnp.int32)
    if isinstance(cache, kvc.CPQXCache):
        xt = cpq_lib.cpq_compress_prefill(c[:, :, None, :], rt.cpq, cache.x.n_max)
        cache = kvc.CPQXCache(xt, kvc.append_tokens(cache.k_rope, k_rope, 0), length)
    else:
        cache = kvc.XCache(kvc.append_tokens(cache.x, c, 0),
                           kvc.append_tokens(cache.k_rope, k_rope, 0), length)
    return _out(cfg, p, o), cache


def mla_decode(cfg: ModelConfig, rt: AttentionRuntime, p, x_t: jax.Array,
               pos: jax.Array, cache):
    """Absorbed decode — the paper's decomposition over the latent cache."""
    q_nope, q_rope, c_t, k_rope_t = _q_ckv(cfg, p, x_t, pos[None])
    slot = cache.length
    new_len = cache.length + 1

    if isinstance(cache, kvc.CPQXCache):
        xt = cpq_lib.cpq_append_decode(cache.x, c_t[:, :, None, :], slot, rt.cpq)
        cache = kvc.CPQXCache(xt, kvc.append_tokens(cache.k_rope, k_rope_t, slot), new_len)
        c_arena = cpq_lib.cpq_dequant(xt, x_t.dtype)[:, :, 0, :]  # fused in kernel path
    else:
        cache = kvc.XCache(kvc.append_tokens(cache.x, c_t, slot),
                           kvc.append_tokens(cache.k_rope, k_rope_t, slot), new_len)
        c_arena = cache.x

    o = decomposed_attention(
        q_nope, q_rope, c_arena, cache.k_rope,
        w_k_nope=_latent_major(p["wuk"]), w_v=_latent_major(p["wuv"]),
        length=new_len, scale=_scale(cfg))
    return _out(cfg, p, o), cache


def init_paged_mla_cache(cfg: ModelConfig, rt: AttentionRuntime, serving):
    """Paged latent arena (serving/paged_cache.py): ``PagedLatentCache``,
    or CPQ-compressed latent pages in the "cpq" mode."""
    from repro.serving import paged_cache as pgc

    L, _, Dr, _ = _dims(cfg)
    if rt.mode == "cpq":
        return pgc.init_paged_cpq_x(serving.num_pages, serving.page_size,
                                    serving.num_slots, L, 1, Dr, rt.cpq,
                                    cfg.param_dtype)
    return pgc.init_paged_latent(serving.num_pages, serving.page_size, L, Dr,
                                 cfg.param_dtype)


def mla_prefill_chunk(cfg: ModelConfig, rt: AttentionRuntime, tier: int,
                      first: bool, p, x: jax.Array, positions: jax.Array,
                      slot, block_row, offset, valid, cache):
    """Chunked paged prefill over the latent arena: the chunk's c_kv (+shared
    roped key) goes straight into slot ``slot``'s pages and its queries run
    the ABSORBED decomposition over the slot's pages — algebraically the
    one-shot prefill's dense math re-associated, so chunked admission is
    token-exact vs one-shot at f32. The CPQ latent tier compresses each chunk
    incrementally (level-0 fit on the first chunk, HQE extension after)."""
    from repro.serving import paged_cache as pgc

    with jax.named_scope("attn.qkv"):
        q_nope, q_rope, c, k_rope = _q_ckv(cfg, p, x, positions)
    wuk, wuv, scale = p["wuk"], p["wuv"], _scale(cfg)
    with jax.named_scope("attn.kernel"):   # its arena writes: kv_write
        if isinstance(cache, pgc.PagedCPQXCache):
            cache = pgc.PagedCPQXCache(
                x=pgc.chunk_cpq_tensor(cache.x, slot, block_row, offset, valid,
                                       c[:, :, None, :], rt.cpq, first),
                k_rope=pgc.write_chunk_pages(cache.k_rope, block_row, offset,
                                             valid, k_rope[0]))
            o = pgc.decomposed_cpq_chunk_prefill(
                q_nope, q_rope, cache.x, cache.k_rope, block_row, slot, c,
                k_rope, offset, valid, _latent_major(wuk), _latent_major(wuv),
                scale)
        elif getattr(rt, "mesh", None) is not None:
            # mesh-native: query heads + W_UK/W_UV shard over "model"; the
            # latent pages are storage-sharded on their feature axis and
            # reassembled locally inside the shard_map (serving/sharded.py)
            from repro.serving.sharded import latent_chunk_sharded

            o, cache = latent_chunk_sharded(
                rt, cache, block_row=block_row, offset=offset, valid=valid,
                c=c, k_rope=k_rope, q_nope=q_nope, q_rope=q_rope, w_uk=wuk,
                w_uv=wuv, scale=scale)
        else:
            cache = pgc.write_latent_chunk(cache, block_row, offset, valid,
                                           c[0], k_rope[0, :, 0])
            o = latent_chunk_read(rt, cache, block_row, offset, valid, q_nope,
                                  q_rope, wuk, wuv, scale)
    with jax.named_scope("attn.out"):
        return _out(cfg, p, o), cache


def latent_chunk_read(rt: AttentionRuntime, cache, block_row, offset, valid,
                      q_nope, q_rope, wuk, wuv, scale: float):
    """A chunk's queries over one slot's pages [0, offset + valid) of a
    ``PagedLatentCache``: the paged MLA kernel, or the gather oracle."""
    from repro.kernels.decomposed_attn.ops import paged_latent_prefill_tpu
    from repro.serving import paged_cache as pgc

    if rt.paged_kernels:
        cp, rp, layer = pgc.latent_stack(cache)
        return paged_latent_prefill_tpu(q_nope, q_rope, cp, rp, block_row,
                                        offset, valid, layer, wuk, wuv, scale)
    cg, rg = pgc.gather_latent(cache, block_row[None])
    return decomposed_attention(
        q_nope, q_rope, cg, rg, w_k_nope=_latent_major(wuk),
        w_v=_latent_major(wuv), length=offset + valid, scale=scale,
        query_positions=offset + jnp.arange(q_nope.shape[1], dtype=jnp.int32))


def latent_decode_read(rt: AttentionRuntime, cache, block_table, lengths,
                       q_nope, q_rope, wuk, wuv, scale: float):
    """One query per row over its pages [0, lengths[b]) of a
    ``PagedLatentCache``: the paged MLA kernel, or the gather oracle."""
    from repro.kernels.decomposed_attn.ops import paged_latent_decode_tpu
    from repro.serving import paged_cache as pgc

    if rt.paged_kernels:
        cp, rp, layer = pgc.latent_stack(cache)
        return paged_latent_decode_tpu(q_nope, q_rope, cp, rp, block_table,
                                       lengths, layer, wuk, wuv, scale)
    cg, rg = pgc.gather_latent(cache, block_table)
    return decomposed_attention(q_nope, q_rope, cg, rg,
                                w_k_nope=_latent_major(wuk),
                                w_v=_latent_major(wuv), length=lengths,
                                scale=scale)


def mla_decode_rows(cfg: ModelConfig, rt: AttentionRuntime, p, x_t: jax.Array,
                    rows, cache):
    """Absorbed decode over a paged latent arena with per-row positions.
    With ``rt.paged_kernels`` the latent arena runs the paged MLA kernel —
    latent pages are DMA'd straight from the arena through the block table,
    no logical view. The CPQ-compressed latent keeps the dequant-gather
    path."""
    from repro.serving import paged_cache as pgc

    with jax.named_scope("attn.qkv"):
        q_nope, q_rope, c_t, k_rope_t = _q_ckv(cfg, p, x_t, rows.lengths,
                                               rows=True)
    wuk, wuv, scale = p["wuk"], p["wuv"], _scale(cfg)
    with jax.named_scope("attn.kernel"):   # its arena writes: kv_write
        if isinstance(cache, pgc.PagedCPQXCache):
            new_len = rows.lengths + rows.active.astype(jnp.int32)
            cache = pgc.PagedCPQXCache(
                x=pgc.append_cpq_tensor(cache.x, rows, c_t[:, :, None, :], rt.cpq),
                k_rope=pgc.write_token_pages(cache.k_rope, rows.block_table,
                                             rows.lengths, rows.active,
                                             k_rope_t[:, 0]))
            xt = pgc.logical_cpq(cache.x, rows.block_table)
            o = decomposed_attention(
                q_nope, q_rope, cpq_lib.cpq_dequant(xt, x_t.dtype)[:, :, 0, :],
                pgc.gather_pages(cache.k_rope, rows.block_table),
                w_k_nope=_latent_major(wuk), w_v=_latent_major(wuv),
                length=new_len, scale=scale)
        elif getattr(rt, "mesh", None) is not None:
            # mesh-native absorbed decode: head-sharded shard_map over the
            # storage-sharded latent arena (serving/sharded.py)
            from repro.serving.sharded import latent_decode_sharded

            o, cache = latent_decode_sharded(
                rt, cache, rows, c_t=c_t, k_rope_t=k_rope_t, q_nope=q_nope,
                q_rope=q_rope, w_uk=wuk, w_uv=wuv, scale=scale)
        else:
            cache = pgc.append_latent(cache, rows, c_t, k_rope_t)
            o = latent_decode_read(rt, cache, rows.block_table,
                                   rows.lengths + rows.active.astype(jnp.int32),
                                   q_nope, q_rope, wuk, wuv, scale)
    with jax.named_scope("attn.out"):
        return _out(cfg, p, o), cache
