"""Full model: embed -> prefix layers (unrolled) -> scanned blocks -> norm ->
LM head. Three entry points: forward_train / prefill / decode_step.

All functions are pure; parameters/caches are pytrees declared by
``model_defs`` (see common/param.py for how init, ShapeDtypeStructs and
PartitionSpecs all derive from the same tree).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.common.param import abstract_tree, init_tree, spec_tree
from repro.configs.base import AttentionRuntime, CPQCfg, ModelConfig
from repro.core import kv_cache as kvc
from repro.models import transformer as tfm
from repro.serving import paged_cache as pgc
from repro.models.layers import embed_defs, embed_inputs, lm_logits, norm_defs, apply_norm


@jax.custom_jvp
def _barrier(tree):
    """Differentiable ``optimization_barrier``: some JAX versions ship no JVP
    rule for the primitive, which broke every train-path test. Primal keeps
    the barrier (the scan/LICM pinning it exists for); tangents pass through."""
    return jax.lax.optimization_barrier(tree)


@_barrier.defjvp
def _barrier_jvp(primals, tangents):
    (x,), (t,) = primals, tangents
    return _barrier(x), t


# --------------------------------------------------------------------- defs


def model_defs(cfg: ModelConfig):
    return {
        "embed": embed_defs(cfg),
        "prefix": [tfm.layer_defs(cfg, m, f) for m, f in cfg.prefix_pattern],
        "blocks": tfm.stacked_block_defs(cfg),
        "final_norm": norm_defs(cfg),
    }


def init_params(cfg: ModelConfig, key: jax.Array):
    return init_tree(model_defs(cfg), key)


def abstract_params(cfg: ModelConfig):
    return abstract_tree(model_defs(cfg))


def param_specs(cfg: ModelConfig, rules: dict, mesh_shape: dict | None = None):
    return spec_tree(model_defs(cfg), rules, mesh_shape)


def _patches(cfg: ModelConfig, params, batch: dict) -> Optional[jax.Array]:
    if cfg.input_kind != "text+patches":
        return None
    return batch["patches"].astype(cfg.param_dtype) @ params["embed"]["mm_proj"]


# -------------------------------------------------------------------- train


def forward_train(cfg: ModelConfig, params, batch: dict, remat: bool = True):
    """-> (logits (B,S,V) f32, aux_loss scalar)."""
    S = (batch["tokens"] if "tokens" in batch else batch["frames"]).shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    x = embed_inputs(cfg, params["embed"], batch, positions)
    patches = _patches(cfg, params, batch)
    aux = jnp.zeros((), jnp.float32)

    for kind, p in zip(cfg.prefix_pattern, params["prefix"]):
        x, a = tfm.layer_train(cfg, kind, p, x, positions, patches)
        aux = aux + a

    if cfg.num_blocks:
        def one_layer(kind):
            def f(x, p):
                return tfm.layer_train(cfg, kind, p, x, positions, patches)
            # inner remat: backward recomputes one sublayer at a time
            return jax.checkpoint(f) if remat and len(cfg.block_pattern) > 1 else f

        layer_fns = [one_layer(k) for k in cfg.block_pattern]

        def body(x, block_params):
            # pin the sliced block weights inside the loop: without this, the
            # SPMD partitioner all-gathers the WHOLE stacked (num_blocks, ...)
            # FSDP weights and LICM hoists them out of the scan (measured
            # +43GB/device on jamba train — EXPERIMENTS.md §Perf)
            block_params = _barrier(block_params)
            a_blk = jnp.zeros((), jnp.float32)
            for f, p in zip(layer_fns, block_params):
                x, a = f(x, p)
                a_blk = a_blk + a
            return x, a_blk

        if remat:
            # outer remat: scan saves only the per-block carry
            body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
        x, auxs = jax.lax.scan(body, x, tuple(params["blocks"]))
        aux = aux + jnp.sum(auxs)

    x = apply_norm(cfg, params["final_norm"], x)
    return lm_logits(cfg, params, x), aux


def loss_fn(cfg: ModelConfig, params, batch: dict, remat: bool = True,
            aux_weight: float = 0.01):
    """Next-token cross entropy (+ MoE aux). labels: (B,S) int32, -1 = pad."""
    logits, aux = forward_train(cfg, params, batch, remat)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    tgt = labels[:, 1:]
    ok = tgt >= 0
    nll = -jnp.take_along_axis(logp, jnp.maximum(tgt, 0)[..., None], axis=-1)[..., 0]
    loss = jnp.sum(nll * ok) / jnp.maximum(jnp.sum(ok), 1)
    return loss + aux_weight * aux, {"nll": loss, "aux": aux}


# ------------------------------------------------------------------ serving


def init_caches(cfg: ModelConfig, rt: AttentionRuntime, batch: int, n_max: int):
    """Cache pytree: prefix list + per-position stacked block caches."""
    npatch = cfg.num_patch_tokens
    prefix = [tfm.layer_cache_init(cfg, rt, k, batch, n_max, npatch)
              for k in cfg.prefix_pattern]

    def stacked(kind):
        one = tfm.layer_cache_init(cfg, rt, kind, batch, n_max, npatch)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.num_blocks,) + a.shape).copy(), one)

    blocks = [stacked(k) for k in cfg.block_pattern]
    return {"prefix": prefix, "blocks": blocks}


def prefill(cfg: ModelConfig, rt: AttentionRuntime, params, batch: dict, caches,
            last_index: Optional[jax.Array] = None):
    """Process the prompt; returns (logits (B,V), caches). Logits come from
    the last position, or from ``last_index`` (shared () int32) when the
    prompt is right-padded to a jit bucket (continuous-batching admission)."""
    S = (batch["tokens"] if "tokens" in batch else batch["frames"]).shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    x = embed_inputs(cfg, params["embed"], batch, positions)
    patches = _patches(cfg, params, batch)

    new_prefix = []
    for kind, p, c in zip(cfg.prefix_pattern, params["prefix"], caches["prefix"]):
        x, c2 = tfm.layer_prefill(cfg, rt, kind, p, x, positions, patches, c)
        new_prefix.append(c2)

    new_blocks = caches["blocks"]
    if cfg.num_blocks:
        def body(x, inp):
            block_params, block_caches = _barrier(inp)
            outs = []
            for kind, p, c in zip(cfg.block_pattern, block_params, block_caches):
                x, c2 = tfm.layer_prefill(cfg, rt, kind, p, x, positions, patches, c)
                outs.append(c2)
            return x, tuple(outs)

        x, new_blocks = jax.lax.scan(
            body, x, (tuple(params["blocks"]), tuple(caches["blocks"])))
        new_blocks = list(new_blocks)

    if last_index is not None:
        x = jax.lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
    else:
        x = x[:, -1:]
    x = apply_norm(cfg, params["final_norm"], x)
    logits = lm_logits(cfg, params, x)[:, 0]
    return logits, {"prefix": new_prefix, "blocks": new_blocks}


def decode_step(cfg: ModelConfig, rt: AttentionRuntime, params, tokens: jax.Array,
                pos: jax.Array, caches):
    """One decode step. tokens: (B, 1) int32; pos: () int32 (next position).
    Returns (logits (B, V), caches)."""
    x = embed_inputs(cfg, params["embed"], {"tokens": tokens}, pos[None])

    new_prefix = []
    for kind, p, c in zip(cfg.prefix_pattern, params["prefix"], caches["prefix"]):
        x, c2 = tfm.layer_decode(cfg, rt, kind, p, x, pos, c)
        new_prefix.append(c2)

    new_blocks = caches["blocks"]
    if cfg.num_blocks:
        def body(x, inp):
            block_params, block_caches = _barrier(inp)
            outs = []
            for kind, p, c in zip(cfg.block_pattern, block_params, block_caches):
                x, c2 = tfm.layer_decode(cfg, rt, kind, p, x, pos, c)
                outs.append(c2)
            return x, tuple(outs)

        x, new_blocks = jax.lax.scan(
            body, x, (tuple(params["blocks"]), tuple(caches["blocks"])))
        new_blocks = list(new_blocks)

    x = apply_norm(cfg, params["final_norm"], x)
    logits = lm_logits(cfg, params, x)[:, 0]
    return logits, {"prefix": new_prefix, "blocks": new_blocks}


# -------------------------------------------------- continuous (paged) serving


def init_paged_caches(cfg: ModelConfig, rt: AttentionRuntime, serving,
                      tiered: bool = False):
    """Paged cache pytree: one shared page pool per layer; slot-indexed
    contiguous state for recurrent/xattn mixers. ``serving`` is a ServingCfg;
    ``tiered`` adds the CPQ escalation arena (watermark policy)."""
    prefix = [tfm.layer_paged_cache_init(cfg, rt, k, serving, tiered)
              for k in cfg.prefix_pattern]

    def stacked(kind):
        init = functools.partial(tfm.layer_paged_cache_init, cfg, rt, kind,
                                 serving, tiered)
        shapes = jax.eval_shape(init)
        if isinstance(shapes, CARRIED):
            # the carried arena: each pool one zeros at its stacked shape
            return jax.tree.map(
                lambda a: jnp.zeros((cfg.num_blocks,) + a.shape, a.dtype), shapes)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.num_blocks,) + a.shape).copy(), init())

    blocks = [stacked(k) for k in cfg.block_pattern]
    return {"prefix": prefix, "blocks": blocks}


# Arenas that ride in the layer scan's carry, read and written in place at
# the layer index: the dense K/V arena and the MLA latent arena. T1, CPQ,
# retrieval, tiered and recurrent caches are sliced through xs/ys.
CARRIED = (pgc.PagedDenseKVCache, pgc.PagedLatentCache)


def carries_arenas(caches) -> bool:
    """Whether every scanned cache is an arena that rides in the layer
    scan's carry (``_scan_layers``). Only then may a step donate its caches:
    a donated cache that went through the scan's xs/ys would cost whole-arena
    copies instead of saving them."""
    return all(isinstance(c, CARRIED) for c in caches["blocks"])


def _scan_layers(cfg: ModelConfig, layer_fn, x, block_params, block_caches):
    """Run the scanned blocks: ``layer_fn(kind, p, x, cache) -> (x, cache)``.

    A carried arena (``CARRIED``) rides in the carry as its whole stacked
    (L, P, ...) pools next to a layer counter; the layer reads and writes it
    in place at that index, so no per-layer slice of it is copied out or
    back. Every other cache is sliced per layer through xs/ys, as is every
    weight. Returns (x, new block caches)."""
    carried = [isinstance(c, CARRIED) for c in block_caches]
    held0 = tuple(c for c, k in zip(block_caches, carried) if k)
    sliced0 = tuple(c for c, k in zip(block_caches, carried) if not k)

    def body(carry, inp):
        x, layer, held = carry
        block_params, sliced = _barrier(inp)
        held, sliced = list(held), list(sliced)
        new_held, outs = [], []
        for kind, p, is_held in zip(cfg.block_pattern, block_params, carried):
            if is_held:
                x, c = layer_fn(kind, p, x, held.pop(0)._replace(layer=layer))
                new_held.append(c._replace(layer=None))
            else:
                x, c = layer_fn(kind, p, x, sliced.pop(0))
                outs.append(c)
        return (x, layer + 1, tuple(new_held)), tuple(outs)

    (x, _, held), outs = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.int32), held0),
        (tuple(block_params), sliced0))
    held, outs = list(held), list(outs)
    return x, [held.pop(0) if k else outs.pop(0) for k in carried]


def decode_step_rows(cfg: ModelConfig, rt: AttentionRuntime, params,
                     tokens: jax.Array, rows: pgc.RowState, caches):
    """One continuous-batching decode step: every row at its own position
    (``rows.lengths``). With ``rt.paged_kernels`` (default) the dense, CPQ,
    and X/MLA attention tiers read their arenas through the fused paged
    Pallas kernels (pages DMA'd via the block table, no logical view);
    otherwise caches are gathered through the block table in jnp.
    tokens: (B, 1) int32. Returns (logits (B, V), caches).

    Dense and MLA latent arenas ride in the layer scan's carry and are
    updated in place (``_scan_layers``); a caller that donates ``caches``
    must not read them again.

    Profiler scopes (metadata only): ``embed``; ``layers`` around the layer
    stack, whose own ops are the scan's per-layer slices of the weights (and
    of any cache that is not carried); per layer ``norm``, ``attn.qkv``,
    ``kv_write``, ``attn.kernel``, ``attn.out``, then ``mlp`` or the expert
    layer's ``moe.router``, ``moe.dispatch``, ``moe.experts``,
    ``moe.shared``; then ``lm_head``."""
    with jax.named_scope("embed"):
        x = embed_inputs(cfg, params["embed"], {"tokens": tokens},
                         rows.lengths[:, None])

    with jax.named_scope("layers"):
        new_prefix = []
        for kind, p, c in zip(cfg.prefix_pattern, params["prefix"], caches["prefix"]):
            x, c2 = tfm.layer_decode_rows(cfg, rt, kind, p, x, rows, c)
            new_prefix.append(c2)

        new_blocks = caches["blocks"]
        if cfg.num_blocks:
            x, new_blocks = _scan_layers(
                cfg, lambda kind, p, x, c: tfm.layer_decode_rows(
                    cfg, rt, kind, p, x, rows, c),
                x, params["blocks"], caches["blocks"])

    with jax.named_scope("lm_head"):
        x = apply_norm(cfg, params["final_norm"], x)
        logits = lm_logits(cfg, params, x)[:, 0]
    return logits, {"prefix": new_prefix, "blocks": new_blocks}


def _chunk_forward(cfg: ModelConfig, rt: AttentionRuntime, tier: int,
                   first: bool, params, tokens: jax.Array, slot: jax.Array,
                   block_row: jax.Array, offset: jax.Array, valid: jax.Array,
                   caches):
    """Shared trunk of the chunked paged forward pass: embed ``tokens``
    (1, C) at absolute positions ``offset + i``, stream every layer's
    chunk step (writes land straight in slot ``slot``'s arena pages through
    ``block_row``; the chunk's queries attend ``[0, offset + i]`` via the
    per-query-row causal mask). Returns the pre-norm hidden states
    (1, C, D) and the updated caches — the prefill head keeps the last
    valid position's logits, the speculative verify head keeps them all.
    Profiler scopes as in ``decode_step_rows``; the heads add ``lm_head``."""
    C = tokens.shape[1]
    positions = offset + jnp.arange(C, dtype=jnp.int32)
    with jax.named_scope("embed"):
        x = embed_inputs(cfg, params["embed"], {"tokens": tokens}, positions)

    with jax.named_scope("layers"):
        new_prefix = []
        for kind, p, c in zip(cfg.prefix_pattern, params["prefix"], caches["prefix"]):
            x, c2 = tfm.layer_prefill_chunk(cfg, rt, tier, first, kind, p, x,
                                            positions, slot, block_row, offset,
                                            valid, c)
            new_prefix.append(c2)

        new_blocks = caches["blocks"]
        if cfg.num_blocks:
            x, new_blocks = _scan_layers(
                cfg, lambda kind, p, x, c: tfm.layer_prefill_chunk(
                    cfg, rt, tier, first, kind, p, x, positions, slot,
                    block_row, offset, valid, c),
                x, params["blocks"], caches["blocks"])

    return x, {"prefix": new_prefix, "blocks": new_blocks}


def prefill_chunk_rows(cfg: ModelConfig, rt: AttentionRuntime, tier: int,
                       first: bool, params, tokens: jax.Array,
                       slot: jax.Array, block_row: jax.Array,
                       offset: jax.Array, valid: jax.Array, caches):
    """One CHUNK of a chunked paged admission prefill: ``tokens`` (1, C) is
    the next slice of the prompt (padded to the static chunk size with the
    edge token), embedded at absolute positions ``offset + i`` and written
    straight into slot ``slot``'s arena pages — no contiguous scratch cache
    is ever allocated, and one compiled shape serves every prompt length
    (the per-(mode, padded-length) prefill variant zoo collapses to this
    function's (mode, first-chunk) pair). Returns (logits (1, V) of the
    chunk's LAST VALID position — meaningful on the final chunk only — and
    the updated paged caches)."""
    x, caches = _chunk_forward(cfg, rt, tier, first, params, tokens, slot,
                               block_row, offset, valid, caches)
    with jax.named_scope("lm_head"):
        x = jax.lax.dynamic_slice_in_dim(x, valid - 1, 1, axis=1)
        x = apply_norm(cfg, params["final_norm"], x)
        logits = lm_logits(cfg, params, x)[:, 0]
    return logits, caches


def verify_chunk_rows(cfg: ModelConfig, rt: AttentionRuntime, tier: int,
                      first: bool, params, tokens: jax.Array,
                      slot: jax.Array, block_row: jax.Array,
                      offset: jax.Array, valid: jax.Array, caches):
    """Speculative-decoding verification chunk: the SAME chunked paged
    forward pass as ``prefill_chunk_rows`` (one weight stream, Q-chunk>1
    paged attend with the per-query-row causal mask, writes into the
    draft's scratch pages through ``block_row``), but the head keeps the
    logits of EVERY chunk position — position ``offset + i`` scores
    candidate ``i+1`` — so all k drafted tokens are verified in a single
    model invocation. Returns (logits (1, C, V), updated caches); rows at
    ``i >= valid`` are jit padding (never sampled, writes masked)."""
    x, caches = _chunk_forward(cfg, rt, tier, first, params, tokens, slot,
                               block_row, offset, valid, caches)
    with jax.named_scope("lm_head"):
        x = apply_norm(cfg, params["final_norm"], x)
        logits = lm_logits(cfg, params, x)
    return logits, caches


def pack_prefill_caches(cfg: ModelConfig, rt: AttentionRuntime, caches, src,
                        block_row: jax.Array, slot: jax.Array):
    """Scatter a freshly prefilled B=1 contiguous cache pytree (``src``, from
    ``prefill``) into slot ``slot`` of the paged cache pytree (admission)."""
    def pack_layer(kind, pc, sc):
        mixer, _ = kind
        if mixer in ("attn", "mla"):
            return pgc.pack_into(rt.mode, pc, sc, block_row, slot)
        if mixer == "xattn":  # static per-request K/V, slot-indexed
            return kvc.DenseKVCache(pc.k.at[slot].set(sc.k[0]),
                                    pc.v.at[slot].set(sc.v[0]), sc.length)
        # recurrent state: all leaves are (B, ...)
        return jax.tree.map(lambda d, s: d.at[slot].set(s[0]), pc, sc)

    prefix = [pack_layer(k, pc, sc)
              for k, pc, sc in zip(cfg.prefix_pattern, caches["prefix"], src["prefix"])]
    blocks = [jax.vmap(lambda c, s, kind=kind: pack_layer(kind, c, s))(pc, sc)
              for kind, pc, sc in zip(cfg.block_pattern, caches["blocks"], src["blocks"])]
    return {"prefix": prefix, "blocks": blocks}


def defrag_caches(cfg: ModelConfig, rt: AttentionRuntime, caches,
                  perm: jax.Array):
    """Apply a scheduler defrag permutation (``Scheduler.plan_defrag``) to
    every attention layer's BASE-arena page pools: mapped pages move onto
    the lowest physical ids so each request's pages become physically
    contiguous again (locality for the fused kernels' sequential reads).
    Non-attention layer state is slot-indexed, not paged."""
    def one(kind, c):
        mixer, _ = kind
        if mixer not in ("attn", "mla"):
            return c
        return pgc.permute_pool(c, perm)

    prefix = [one(k, c) for k, c in zip(cfg.prefix_pattern, caches["prefix"])]
    blocks = [jax.vmap(lambda c, kind=kind: one(kind, c))(pc)
              for kind, pc in zip(cfg.block_pattern, caches["blocks"])]
    return {"prefix": prefix, "blocks": blocks}


def copy_page_caches(cfg: ModelConfig, rt: AttentionRuntime, caches,
                     src: jax.Array, dst: jax.Array):
    """Copy physical page ``src -> dst`` in every attention layer's BASE
    arena pools — the copy-on-write split behind prefix sharing: before a
    request's first write into a page it still shares, the scheduler remaps
    its block-table entry to a fresh page and this op duplicates the payload
    (tiered arenas copy the dense arm only; non-attention layer state is
    slot-indexed, not paged)."""
    def one(kind, c):
        mixer, _ = kind
        if mixer not in ("attn", "mla"):
            return c
        return pgc.copy_page(c, src, dst)

    prefix = [one(k, c) for k, c in zip(cfg.prefix_pattern, caches["prefix"])]
    blocks = [jax.vmap(lambda c, kind=kind: one(kind, c))(pc)
              for kind, pc in zip(cfg.block_pattern, caches["blocks"])]
    return {"prefix": prefix, "blocks": blocks}


def escalate_slot(cfg: ModelConfig, rt: AttentionRuntime, caches,
                  dense_row: jax.Array, cpq_row: jax.Array, slot: jax.Array,
                  length: jax.Array):
    """Watermark-policy tier escalation: re-compress slot ``slot``'s dense K/V
    into the CPQ arena across every tiered attention layer (the paper's
    "dynamically compress" lever, applied mid-request). The host frees the
    dense pages afterwards; ``dense_row`` is the slot's pre-escalation dense
    block row, ``cpq_row`` its freshly allocated CPQ block row."""
    cpq_cfg = rt.cpq or CPQCfg()

    def esc_layer(kind, c):
        mixer, _ = kind
        if mixer != "attn" or not isinstance(c, pgc.TieredPagedCache):
            return c
        k, v = (pgc.gather_layer_heads(pool[None], 0, dense_row[None],
                                       cfg.num_kv_heads)
                for pool in (c.dense.k, c.dense.v))
        src = pgc.compress_dense_slot(k, v, length, cpq_cfg)
        return c._replace(cpq=pgc.pack_cpq(c.cpq, src, cpq_row, slot))

    prefix = [esc_layer(k, c) for k, c in zip(cfg.prefix_pattern, caches["prefix"])]
    blocks = [jax.vmap(lambda c, kind=kind: esc_layer(kind, c))(pc)
              for kind, pc in zip(cfg.block_pattern, caches["blocks"])]
    return {"prefix": prefix, "blocks": blocks}
