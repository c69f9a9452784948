"""Mesh-native paged serving attention: shard_map over the kv-head axis.

The paper's central claim is that attention should execute where the KV
lives — each PIM bank holds its slice of the cache and computes locally,
with only small per-head partials crossing the interconnect. The serving
analogue implemented here: every paged arena partitions over its KV-HEAD
axis (``distributed/cache_specs.paged_layer_cache_specs``), and the paged
decode / chunked-prefill attention calls run under ``shard_map`` so each
device sweeps only its LOCAL head shard of the page pool — block tables,
``RowState``, and scheduler state stay replicated (the allocator operates on
logical pages; a logical page is one slice per device), and the only
cross-device traffic is the concatenation of per-head attention outputs
(``out_specs`` sharded on the head axis).

Tier routing (mirrors ``decode_attend_paged``):

  dense / T2 CPQ / tiered   embarrassingly head-parallel: per-shard call of
                            the SAME fused Pallas kernel (or jnp gather
                            oracle) over the local (KV/mp)-head arena slice.
  T1 X / MLA latent         the pool has no head axis; its FEATURE axis is
                            storage-sharded for HBM capacity and all-gathered
                            locally before the absorbed attend (query heads
                            and the W_UK/W_UV slices stay sharded, so score
                            and value stages still run head-parallel).
  T3 retrieval              keeps global-semantics compute over its (still
                            head-sharded) arenas — safe because the kv-head
                            axis is batch-like in every contraction.
  T1+T2 / MLA-CPQ           replicate their code pools: feature-sharding
                            would split the attend's f32 reduction under
                            GSPMD and break single-device token parity.

With ``AttentionRuntime.mesh is None`` nothing in this module runs and the
single-device path is bit-identical to before. Numerics under a mesh: every
head's math is computed once on exactly one device from the same operands,
so sharded-vs-single-device greedy decode is token-exact at f32
(tests/test_serving_sharded.py).

Per-request sampling under a mesh: the vectorized per-row sampling
parameters (temperature / top-k / top-p / seed / stream-index arrays) cross
the mesh REPLICATED — the sampler consumes the already-concatenated (B, V)
logits after the shard_map'd attention, so every device draws the identical
token from identical operands (``replicate_on_mesh``). Sampled decode is
therefore mesh-invariant exactly like greedy decode: the categorical draw is
a deterministic function of (logits, seed, stream index), none of which
shard.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _shard_map(f, mesh, in_specs, out_specs):
    # replication checking is off: out_specs mix head-sharded attention
    # outputs with replicated cache side state that the checker cannot
    # always prove replicated
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


MODEL_AXIS = "model"


def replicate_on_mesh(mesh, tree):
    """Pin a host pytree (per-row sampling parameter arrays, scheduler-side
    scalars) onto every device of the serving mesh REPLICATED, so the jitted
    per-row sampler sees one committed layout instead of letting GSPMD infer
    placement per call site. Identity when ``mesh`` is None."""
    if mesh is None:
        return tree
    from jax.sharding import NamedSharding

    return jax.device_put(tree, NamedSharding(mesh, P()))

# intent specs for the per-call attention operands (fitted to shapes; the
# kv/query-head axis shards, everything else is replicated)
_ARG_SPECS = {
    "q": P(None, None, MODEL_AXIS, None),
    "k_t": P(None, None, MODEL_AXIS, None),
    "v_t": P(None, None, MODEL_AXIS, None),
    "k_c": P(None, None, MODEL_AXIS, None),
    "v_c": P(None, None, MODEL_AXIS, None),
    "x_t": P(None, None, MODEL_AXIS),
    "x_c": P(None, None, MODEL_AXIS),
    "k_rope_t": P(None, None, MODEL_AXIS, None),
    "k_rope_c": P(None, None, MODEL_AXIS, None),
    "q_nope": P(None, None, MODEL_AXIS, None),
    "q_rope": P(None, None, MODEL_AXIS, None),
    "w_k_nope": P(None, MODEL_AXIS, None),
    "w_v": P(None, MODEL_AXIS, None),
}


def supports(cache) -> bool:
    """Tiers routed through shard_map (per-shard kernel calls). T3 retrieval
    (top-k slot selection) and the T1+T2 CPQ(X) composition keep global-
    semantics compute, exactly as they keep the gather path."""
    from repro.serving import paged_cache as pgc

    return isinstance(cache, (pgc.PagedDenseKVCache, pgc.PagedCPQKVCache,
                              pgc.PagedXCache, pgc.TieredPagedCache))


def _fit(spec: P, shape: tuple, mesh) -> P:
    from repro.distributed.sharding import fit_spec_to_shape

    return fit_spec_to_shape(spec, shape, mesh)


def container_specs(cache, mesh):
    """Fitted PartitionSpec tree for a paged container (shard_map in/out
    specs): the SAME ``cache_specs.paged_container_specs`` intent the engine
    places arenas with, fitted to the concrete shapes — placement and
    shard_map can never disagree. Non-dividing axes (e.g. MLA's shared
    kv_r == 1 rope head) drop to replicated."""
    from repro.distributed.cache_specs import paged_container_specs

    return jax.tree.map(lambda sp, a: _fit(sp, a.shape, mesh),
                        paged_container_specs(cache), cache,
                        is_leaf=lambda x: isinstance(x, P))


def _x_is_sharded(cspec) -> bool:
    """Whether the latent pool's feature axis actually sharded (fit kept it)."""
    return tuple(cspec.x) and tuple(cspec.x)[-1] is not None


def _gather_latent(x_local: jax.Array) -> jax.Array:
    """Reassemble the full latent feature axis from the per-device storage
    shards (the absorbed attend needs every feature; queries stay sharded)."""
    return jax.lax.all_gather(x_local, MODEL_AXIS, axis=x_local.ndim - 1,
                              tiled=True)


def _gather_heads(mesh, out: jax.Array) -> jax.Array:
    """Concatenate the per-device head outputs on every device. Left
    head-sharded, the output projection would contract over sharded heads:
    GSPMD then all-reduces bf16 partial sums, which rounds differently from
    the single-device engine and flips greedy near-ties. Replicated, the
    projection runs exactly as on one device."""
    from jax.sharding import NamedSharding

    return jax.lax.with_sharding_constraint(out, NamedSharding(mesh, P()))


def _split(kw: dict, mesh):
    """(present-operands dict, fitted specs dict) — None operands stay out of
    the shard_map argument tree and are reinstated in the body."""
    present = {k: v for k, v in kw.items() if v is not None}
    specs = {k: _fit(_ARG_SPECS[k], v.shape, mesh) for k, v in present.items()}
    return present, specs


def decode_attend_sharded(
    rt, cache, rows, *, q, k_t, v_t, x_t, k_rope_t, q_nope, q_rope,
    w_k_nope, w_v, scale: float,
):
    """shard_map wrapper of ``decode_attend_paged``: per-device sweep of the
    local head shard; only per-head outputs are concatenated. Returns
    (out (B,1,H,Dv) replicated, new_cache) with cache specs preserved."""
    from repro.serving import paged_cache as pgc

    mesh = rt.mesh
    rt_local = dataclasses.replace(rt, mesh=None)
    cspecs = container_specs(cache, mesh)
    rspecs = jax.tree.map(lambda _: P(), rows)
    latent = isinstance(cache, pgc.PagedXCache)
    gather_x = latent and _x_is_sharded(cspecs)
    kw = dict(q=q, k_t=k_t, v_t=v_t, x_t=x_t, k_rope_t=k_rope_t,
              q_nope=q_nope, q_rope=q_rope, w_k_nope=w_k_nope, w_v=w_v)
    present, pspecs = _split(kw, mesh)

    def body(cache, rows, ops):
        a = {k: ops.get(k) for k in kw}
        if latent:
            # storage-sharded latent: append the local feature slice, then
            # all-gather pages for the absorbed attend (heads stay sharded)
            cache = pgc.append_x(cache, rows, a["x_t"], a["k_rope_t"])
            x_pages = _gather_latent(cache.x) if gather_x else cache.x
            new_len = rows.lengths + rows.active.astype(jnp.int32)
            if rt_local.paged_kernels:
                from repro.kernels.decomposed_attn.ops import (
                    paged_decomposed_decode_tpu)

                out = paged_decomposed_decode_tpu(
                    a["q_nope"], a["q_rope"], x_pages, cache.k_rope,
                    rows.block_table, new_len, a["w_k_nope"], a["w_v"], scale)
            else:
                from repro.core.decomposed_attention import decomposed_attention

                out = decomposed_attention(
                    a["q_nope"], a["q_rope"],
                    pgc.gather_pages(x_pages, rows.block_table),
                    pgc.gather_pages(cache.k_rope, rows.block_table),
                    a["w_k_nope"], a["w_v"], new_len, scale)
            return out, cache
        return pgc.decode_attend_paged(rt_local, cache, rows, scale=scale, **a)

    out, cache = _shard_map(
        body, mesh,
        in_specs=(cspecs, rspecs, pspecs),
        out_specs=(P(None, None, MODEL_AXIS, None), cspecs),
    )(cache, rows, present)
    return _gather_heads(mesh, out), cache


def chunk_attend_sharded(
    rt, cache, *, tier: int, first: bool, slot, block_row, offset, valid,
    q, k_c, v_c, x_c, k_rope_c, q_nope, q_rope, w_k_nope, w_v, scale: float,
):
    """shard_map wrapper of ``chunk_attend_paged`` (chunked paged prefill):
    the chunk's payload lands in each device's local arena shard and its C
    queries attend per head shard. Returns (out (1,C,H,Dv) replicated,
    new_cache).

    C is whatever the caller compiled — prompt chunks (``prefill_chunk``)
    and speculative verify chunks (``spec_len + 1``; engine._verify_fn)
    share this wrapper, so mesh serving gets speculative decoding with no
    extra collectives: the verify chunk pays exactly one prompt-chunk's
    interconnect (per-head output concat + latent pool gather)."""
    from repro.serving import paged_cache as pgc

    mesh = rt.mesh
    rt_local = dataclasses.replace(rt, mesh=None)
    cspecs = container_specs(cache, mesh)
    latent = isinstance(cache, pgc.PagedXCache)
    gather_x = latent and _x_is_sharded(cspecs)
    kw = dict(q=q, k_c=k_c, v_c=v_c, x_c=x_c, k_rope_c=k_rope_c,
              q_nope=q_nope, q_rope=q_rope, w_k_nope=w_k_nope, w_v=w_v)
    present, pspecs = _split(kw, mesh)
    scalars = (slot, block_row, offset, valid)
    sspecs = jax.tree.map(lambda _: P(), scalars)

    def body(cache, scalars, ops):
        slot, block_row, offset, valid = scalars
        a = {k: ops.get(k) for k in kw}
        if latent:
            cache = pgc.PagedXCache(
                x=pgc.write_chunk_pages(cache.x, block_row, offset, valid,
                                        a["x_c"][0]),
                k_rope=(pgc.write_chunk_pages(cache.k_rope, block_row, offset,
                                              valid, a["k_rope_c"][0])
                        if a["k_rope_c"] is not None else cache.k_rope))
            x_pages = _gather_latent(cache.x) if gather_x else cache.x
            C = a["q_nope"].shape[1]
            if rt_local.paged_kernels:
                from repro.kernels.decomposed_attn.ops import (
                    paged_decomposed_prefill_tpu)

                out = paged_decomposed_prefill_tpu(
                    a["q_nope"], a["q_rope"], x_pages, cache.k_rope,
                    block_row, offset, valid, a["w_k_nope"], a["w_v"], scale)
            else:
                from repro.core.decomposed_attention import decomposed_attention

                out = decomposed_attention(
                    a["q_nope"], a["q_rope"],
                    pgc.gather_pages(x_pages, block_row[None]),
                    pgc.gather_pages(cache.k_rope, block_row[None]),
                    a["w_k_nope"], a["w_v"], offset + valid, scale,
                    query_positions=offset + jnp.arange(C, dtype=jnp.int32))
            return out, cache
        return pgc.chunk_attend_paged(
            rt_local, cache, tier=tier, first=first, slot=slot,
            block_row=block_row, offset=offset, valid=valid, scale=scale, **a)

    out, cache = _shard_map(
        body, mesh,
        in_specs=(cspecs, sspecs, pspecs),
        out_specs=(P(None, None, MODEL_AXIS, None), cspecs),
    )(cache, scalars, present)
    return _gather_heads(mesh, out), cache


def validate_serve_mesh(cfg, rt, tiered: bool = False) -> int:
    """Engine-construction guard: the ``model`` axis must divide every axis
    it shards, or the per-shard GQA group structure breaks. Returns the
    model-axis size (1 = no model sharding)."""
    from repro.serving.scheduler import SchedulerConfigError

    mesh = rt.mesh
    if mesh is None:
        return 1
    if MODEL_AXIS not in mesh.axis_names:
        raise SchedulerConfigError(
            f"serving mesh needs a {MODEL_AXIS!r} axis; got {mesh.axis_names}")
    mp = mesh.shape[MODEL_AXIS]
    if mp == 1:
        return 1
    kinds = set(m for m, _ in cfg.layer_kinds)
    if cfg.num_heads % mp:
        raise SchedulerConfigError(
            f"model axis {mp} must divide num_heads {cfg.num_heads}")
    head_paged = "attn" in kinds and (tiered or rt.mode in (
        "dense", "cpq", "retrieval", "decomposed"))
    if head_paged and cfg.num_kv_heads % mp:
        raise SchedulerConfigError(
            f"model axis {mp} must divide num_kv_heads {cfg.num_kv_heads}")
    # CPQ-X latent tiers (decomposed_cpq / MLA-CPQ) replicate their code
    # pools (see cache_specs._paged_cpq_specs), so only the shard_map'd
    # latent pools constrain the mesh
    if "attn" in kinds and rt.mode == "decomposed" and cfg.d_model % mp:
        raise SchedulerConfigError(
            f"model axis {mp} must divide d_model {cfg.d_model} (T1 X pages)")
    if "mla" in kinds and rt.mode != "cpq" and cfg.mla is not None \
            and cfg.mla.kv_lora_rank % mp:
        raise SchedulerConfigError(
            f"model axis {mp} must divide kv_lora_rank {cfg.mla.kv_lora_rank}")
    return mp
