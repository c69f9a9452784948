"""PartitionSpec trees for decode caches, mirroring models.model.init_caches.

Sharding strategy (see rules.py): batch over (pod,)data; feature dims (head
dim / latent dim / d_inner) over model so one-token cache writes stay local;
for batch==1 long-context shapes the token arena is sharded over the axes the
batch cannot use.
"""
from __future__ import annotations

from jax.sharding import PartitionSpec as P

from repro.configs.base import AttentionRuntime, ModelConfig
from repro.models import transformer as tfm


def _cpq_tensor_specs(b, s):
    from repro.core.cpq import CPQTensor
    return CPQTensor(
        codes=P(b, s, None, "model"),
        scale=P(b, None, None, "model"),
        zero=P(b, None, None, "model"),
        level=P(b, s, None),
        num_levels=P(b, None),
        prune_thr=P(b, None, "model"),
    )


def layer_cache_specs(cfg: ModelConfig, rt: AttentionRuntime, kind, b, s):
    """b: mesh axes for batch (str/tuple/None); s: mesh axes for token arena."""
    from repro.core import kv_cache as kvc
    from repro.models.mamba import MambaState
    from repro.models.xlstm import MLSTMState, SLSTMState

    mixer, _ = kind
    if mixer == "xattn":
        return kvc.DenseKVCache(k=P(b, None, None, "model"),
                                v=P(b, None, None, "model"), length=P())
    if mixer == "mla":
        if rt.mode == "cpq":
            return kvc.CPQXCache(x=_cpq_tensor_specs(b, s),
                                 k_rope=P(b, s, None, None), length=P())
        return kvc.XCache(x=P(b, s, "model"), k_rope=P(b, s, None, None), length=P())
    if mixer == "attn":
        if rt.mode == "dense":
            return kvc.DenseKVCache(k=P(b, s, None, "model"),
                                    v=P(b, s, None, "model"), length=P())
        if rt.mode == "decomposed":
            return kvc.XCache(x=P(b, s, "model"), k_rope=P(b, s, None, None), length=P())
        if rt.mode == "decomposed_cpq":
            return kvc.CPQXCache(x=_cpq_tensor_specs(b, s),
                                 k_rope=P(b, s, None, None), length=P())
        if rt.mode == "cpq":
            return kvc.CPQKVCache(k=_cpq_tensor_specs(b, s),
                                  v=_cpq_tensor_specs(b, s), length=P())
        if rt.mode == "retrieval":
            return kvc.RetrievalCache(
                k=P(b, s, None, "model"), v=P(b, s, None, "model"),
                proxy=P(b, s, None, "model"),
                proxy_scale=P(b, None, "model"), proxy_zero=P(b, None, "model"),
                length=P())
        raise ValueError(rt.mode)
    if mixer == "mamba":
        return MambaState(conv=P(b, None, "model"), h=P(b, "model", None))
    if mixer == "mlstm":
        return MLSTMState(C=P(b, None, None, "model"), n=P(b, None, "model"),
                          m=P(b, None), conv=P(b, None, "model"))
    if mixer == "slstm":
        return SLSTMState(c=P(b, "model"), n=P(b, "model"),
                          h=P(b, "model"), m=P(b, "model"))
    raise ValueError(mixer)


def cache_pspecs(cfg: ModelConfig, rt: AttentionRuntime, batch_axes, seq_axes):
    """Spec tree matching models.model.init_caches output."""
    import jax

    b = batch_axes if batch_axes else None
    s = seq_axes if seq_axes else None

    prefix = [layer_cache_specs(cfg, rt, k, b, s) for k in cfg.prefix_pattern]

    def stacked(kind):
        one = layer_cache_specs(cfg, rt, kind, b, s)
        return jax.tree.map(lambda sp: P(None, *sp), one,
                            is_leaf=lambda x: isinstance(x, P))

    blocks = [stacked(k) for k in cfg.block_pattern]
    return {"prefix": prefix, "blocks": blocks}


# ------------------------------------------------------- paged serving arenas


def _paged_cpq_specs(rules: dict, latent: bool):
    """Spec tree for a serving PagedCPQTensor. ``latent`` selects the
    T1+T2 / MLA-CPQ layout (H == 1, D == d_model/L), which REPLICATES: its
    attend contracts over the feature axis, and feature-sharding would make
    GSPMD split that f32 reduction — summation order changes and greedy
    parity vs the single-device engine is no longer token-exact (observed).
    Head-axis pools are safe to shard because every contraction treats the
    kv-head axis as batch-like. Sharding the CPQ-X codes behind an exact
    psum-staged attend is an open item (ROADMAP)."""
    from repro.serving.paged_cache import PagedCPQTensor
    from repro.distributed.sharding import resolve

    if latent:
        rep = P()
        return PagedCPQTensor(codes=rep, level=rep, scale=rep, zero=rep,
                              num_levels=rep, prune_thr=rep)
    r = lambda *axes: resolve(rules, axes)  # noqa: E731
    return PagedCPQTensor(
        codes=r("page_pool", "page", "kv_heads", "head_dim"),
        level=r("page_pool", "page", "kv_heads"),
        scale=r("slots", "levels", "kv_heads", "head_dim"),
        zero=r("slots", "levels", "kv_heads", "head_dim"),
        num_levels=r("slots", "kv_heads"),
        prune_thr=r("slots", "kv_heads", "head_dim"))


def paged_container_specs(container, rules: dict | None = None):
    """PartitionSpec intent tree for a paged serving container (instance or
    eval_shape skeleton — only the container TYPES matter): per-kv-head page
    pools shard their head axis over ``model``, latent pools (T1 X / MLA
    c_kv / CPQ-X codes) shard their feature axis, the page-pool / page /
    slot axes replicate (rules from distributed.rules.serve_paged_rules).
    Specs are INTENT — callers fit them to concrete shapes with
    ``sharding.fit_spec_to_shape`` (which drops non-dividing axes, e.g.
    MLA's shared kv_r == 1 rope head). The single source of truth for BOTH
    device placement (engine) and shard_map in/out specs (serving/sharded)."""
    from repro.distributed.rules import serve_paged_rules
    from repro.distributed.sharding import resolve
    from repro.serving import paged_cache as pgc

    rules = serve_paged_rules() if rules is None else rules
    r = lambda *axes: resolve(rules, axes)  # noqa: E731
    c = container
    if isinstance(c, pgc.TieredPagedCache):
        return pgc.TieredPagedCache(dense=paged_container_specs(c.dense, rules),
                                    cpq=paged_container_specs(c.cpq, rules))
    if isinstance(c, pgc.PagedDenseKVCache):
        # lane-dense pools: the merged KV*Dh axis splits into contiguous head
        # groups; inside the layer scan the pools are stacked and carry the
        # layer index
        pool = r("page_pool", "page", "kv_heads")
        if c.layer is None:
            return pgc.PagedDenseKVCache(k=pool, v=pool)
        return pgc.PagedDenseKVCache(k=P(None, *pool), v=P(None, *pool),
                                     layer=P())
    if isinstance(c, pgc.PagedXCache):
        return pgc.PagedXCache(
            x=r("page_pool", "page", "latent"),
            k_rope=r("page_pool", "page", "kv_heads", "head_dim"))
    if isinstance(c, pgc.PagedLatentCache):
        # latent pages storage-sharded on their feature axis; the one
        # shared rope head replicates
        lat, rope = r("page_pool", "page", "latent"), P(None, None, None)
        if c.layer is None:
            return pgc.PagedLatentCache(c=lat, rope=rope)
        return pgc.PagedLatentCache(c=P(None, *lat), rope=P(None, *rope),
                                    layer=P())
    if isinstance(c, pgc.PagedCPQKVCache):
        t = _paged_cpq_specs(rules, latent=False)
        return pgc.PagedCPQKVCache(k=t, v=t)
    if isinstance(c, pgc.PagedCPQXCache):
        return pgc.PagedCPQXCache(
            x=_paged_cpq_specs(rules, latent=True),
            k_rope=r("page_pool", "page", "kv_heads", "head_dim"))
    if isinstance(c, pgc.PagedRetrievalCache):
        return pgc.PagedRetrievalCache(
            k=r("page_pool", "page", "kv_heads", "head_dim"),
            v=r("page_pool", "page", "kv_heads", "head_dim"),
            proxy=r("page_pool", "page", "kv_heads", "head_dim"),
            proxy_scale=r("slots", "kv_heads", "head_dim"),
            proxy_zero=r("slots", "kv_heads", "head_dim"))
    raise TypeError(type(c))


def paged_layer_cache_specs(cfg: ModelConfig, rt: AttentionRuntime, kind,
                            serving, tiered: bool = False,
                            rules: dict | None = None):
    """PartitionSpec tree for ONE layer's paged serving container, mirroring
    models.transformer.layer_paged_cache_init (attention mixers get the
    ``paged_container_specs`` intent; recurrent / xattn state is slot-indexed
    and O(1)/request, so it replicates)."""
    import jax

    from repro.models import transformer as tfm
    from repro.serving import paged_cache as pgc

    skeleton = jax.eval_shape(
        lambda: tfm.layer_paged_cache_init(cfg, rt, kind, serving, tiered))
    if isinstance(skeleton, pgc.PagedCache):
        return paged_container_specs(skeleton, rules)
    return jax.tree.map(lambda _: P(), skeleton)


def paged_cache_pspecs(cfg: ModelConfig, rt: AttentionRuntime, serving,
                       tiered: bool = False, rules: dict | None = None):
    """Spec tree matching models.model.init_paged_caches output (prefix list
    + stacked blocks with a leading replicated layer axis)."""
    import jax

    prefix = [paged_layer_cache_specs(cfg, rt, k, serving, tiered, rules)
              for k in cfg.prefix_pattern]

    def stacked(kind):
        one = paged_layer_cache_specs(cfg, rt, kind, serving, tiered, rules)
        return jax.tree.map(lambda sp: P(None, *sp), one,
                            is_leaf=lambda x: isinstance(x, P))

    blocks = [stacked(k) for k in cfg.block_pattern]
    return {"prefix": prefix, "blocks": blocks}
