"""Block-paged KV-cache arenas for continuous-batching serving.

The static containers in ``core/kv_cache.py`` dedicate a contiguous
``(B, n_max, ...)`` arena to every request slot; a short request strands the
rest of its row. Here the token axis is cut into fixed-size **pages** owned by
a shared physical pool ``(P, page_size, ...)``, and a per-slot **block table**
``(B, max_blocks)`` maps logical token blocks to physical pages (the vLLM
construction, adapted to the paper's five cache tiers). The paper's motivating
observation — "the KV cache can grow unpredictably and even surpass the
model's weight size" — becomes an allocation problem: pages are allocated at
admission/decode, freed at retirement, and the pool utilization drives the
scheduler's watermark/tier-escalation policy.

Layout invariants (shared by every paged container):

  * Physical page 0 is the reserved **null page**: unmapped block-table
    entries are 0 and the writes of inactive rows are routed there, so decode
    steps stay branch-free under jit. Its contents are garbage by design.
  * A slot's logical view is ``pages[block_table[b]]`` flattened to
    ``(max_blocks * page_size, ...)``; slots beyond ``lengths[b]`` are masked
    by every attention mode (core attention takes per-row ``(B,)`` lengths).
  * Per-token state pages; per-SEQUENCE state (CPQ scale/zero/levels,
    retrieval proxy calibration) stays slot-indexed ``(B, ...)`` — it is
    O(1) per request and is overwritten at admission.
  * Dense K/V pools are lane-dense: ``(P, page, KV*Dh)``, head h at lanes
    ``[h*Dh, (h+1)*Dh)``; the scanned layers' pools are stacked
    ``(L, P, page, KV*Dh)``, the TPU's default row-major layout, which the
    paged flash kernels DMA pages from directly. Inside the layer scan the
    stack rides in the carry and is read and written in place at the layer
    index (``PagedDenseKVCache.layer``); no per-layer slice of it exists.
  * The MLA latent arena (``PagedLatentCache``) follows the same pattern:
    latent pages ``(L, P, page, Dc)`` and the shared roped key packed to
    whole 128-lane rows, stacked, carried and written in place.

Mode -> paged container (mirrors core/kv_cache.py):
  dense      PagedDenseKVCache   K,V pages, lane-dense (heads merged)
  decomposed PagedXCache         X pages (+ roped key pages)     (T1)
             PagedLatentCache    MLA latent + shared roped key pages
  cpq        PagedCPQKVCache     CPQ code/level pages, slot stats (T2)
  retrieval  PagedRetrievalCache K,V,proxy pages, slot calibration (T3)
  cpq+decomp PagedCPQXCache      CPQ(X) pages (+ roped key pages) (T1+T2)
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import CPQCfg, RetrievalCfg
from repro.core import cpq as cpq_lib
from repro.core import kv_cache as kvc

NULL_PAGE = 0


class RowState(NamedTuple):
    """Per-step request-row state threaded through the jitted decode step
    (the paged analogue of the scalar ``pos`` argument)."""

    lengths: jax.Array      # (B,) int32 — valid tokens per slot (= next position)
    block_table: jax.Array  # (B, max_blocks) int32 physical page ids; 0 = unmapped
    active: jax.Array       # (B,) bool — row decodes this step (writes commit)
    tier: jax.Array         # (B,) int32 — 0 = base tier, 1 = escalated tier
    alt_block_table: Optional[jax.Array] = None  # escalated-arena table (tiered)


# -------------------------------------------------------------- page plumbing


# Every write of new K/V (or X, CPQ codes and levels) into the arena runs
# under the ``kv_write`` profiler scope (metadata only; docs/serving.md).
_kv_write = jax.named_scope("kv_write")


def gather_pages(pages: jax.Array, block_table: jax.Array) -> jax.Array:
    """Materialize logical views: (P, page, ...) x (B, max_blocks)
    -> (B, max_blocks * page, ...). Unmapped blocks read the null page and
    must be masked by lengths downstream."""
    g = jnp.take(pages, block_table, axis=0)  # (B, max_blocks, page, ...)
    return g.reshape(g.shape[0], g.shape[1] * g.shape[2], *g.shape[3:])


def _at(layer, page_idx, off) -> tuple:
    """Scatter index into a pool: (page, offset), or (layer, page, offset)
    into a stacked (L, P, page, ...) pool."""
    return (page_idx, off) if layer is None else (layer, page_idx, off)


@_kv_write
def write_token_pages(pages: jax.Array, block_table: jax.Array, lengths: jax.Array,
                      active: jax.Array, val: jax.Array,
                      layer: Optional[jax.Array] = None) -> jax.Array:
    """Scatter one token per row at slot ``lengths[b]``. val: (B, ...) —
    token payload per row. Inactive rows write the null page. With
    ``layer``, ``pages`` is a stacked (L, P, page, ...) pool written in
    place at that layer."""
    page_size = pages.shape[1 if layer is None else 2]
    max_blocks = block_table.shape[1]
    blk = jnp.clip(lengths // page_size, 0, max_blocks - 1)
    page_idx = jnp.take_along_axis(block_table, blk[:, None], axis=1)[:, 0]
    page_idx = jnp.where(active, page_idx, NULL_PAGE)
    off = lengths % page_size
    return pages.at[_at(layer, page_idx, off)].set(val.astype(pages.dtype))


@_kv_write
def write_prompt_pages(pages: jax.Array, block_row: jax.Array, val: jax.Array) -> jax.Array:
    """Bulk-write a prompt into one slot's pages. block_row: (max_blocks,);
    val: (S, ...). Positions whose block is unmapped or beyond max_blocks
    (bucket padding past the slot's capacity) land on the null page — they
    must never wrap around onto mapped pages."""
    S, page_size = val.shape[0], pages.shape[1]
    pos = jnp.arange(S, dtype=jnp.int32)
    blk = pos // page_size
    in_range = blk < block_row.shape[0]
    pidx = jnp.where(in_range,
                     block_row[jnp.clip(blk, 0, block_row.shape[0] - 1)],
                     NULL_PAGE)
    return pages.at[pidx, pos % page_size].set(val.astype(pages.dtype))


@_kv_write
def write_chunk_pages(pages: jax.Array, block_row: jax.Array, offset: jax.Array,
                      valid: jax.Array, vals: jax.Array,
                      layer: Optional[jax.Array] = None) -> jax.Array:
    """Bulk-write one prompt CHUNK into one slot's pages at positions
    ``offset .. offset+C-1`` (chunked paged prefill: the chunk's payload goes
    straight into the arena — no contiguous scratch cache). vals: (C, ...).
    Positions past ``offset + valid`` (the chunk's jit padding), positions
    whose block is unmapped, and positions beyond the slot's page capacity
    all land on the null page, so page contents are independent of how a
    prompt is split into chunks (property-tested). With ``layer``,
    ``pages`` is a stacked (L, P, page, ...) pool written in place at that
    layer."""
    C, page_size = vals.shape[0], pages.shape[1 if layer is None else 2]
    idx = jnp.arange(C, dtype=jnp.int32)
    pos = offset + idx
    blk = pos // page_size
    ok = (idx < valid) & (blk < block_row.shape[0])
    pidx = jnp.where(ok,
                     block_row[jnp.clip(blk, 0, block_row.shape[0] - 1)],
                     NULL_PAGE)
    return pages.at[_at(layer, pidx, pos % page_size)].set(
        vals.astype(pages.dtype))


def _sel_rows(active: jax.Array, new, old):
    """Per-slot side-state commit: keep ``new`` on active rows only."""
    return jax.tree.map(
        lambda n, o: jnp.where(active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
        new, old)


# ----------------------------------------------------------------- allocator


class PageAllocator:
    """Host-side free-list over the physical pool (page 0 reserved as null),
    with a per-page REFCOUNT: prefix sharing maps one physical page into
    several block tables (``incref``), and the page returns to the free list
    only when the last owner releases it. ``alloc`` hands out pages at
    refcount 1, so refcount-oblivious callers see the old exclusive-ownership
    semantics unchanged.

    The scheduler owns one per arena; alloc/free are O(n). ``OutOfPages`` is
    the admission-control signal, not an error state; ``DoubleFree`` IS an
    error — releasing a page more often than it was referenced corrupts the
    free list (it used to be an ``assert``, which vanishes under ``-O``)."""

    class OutOfPages(RuntimeError):
        pass

    class DoubleFree(RuntimeError):
        """A page was released more times than it was referenced (or the
        null page was released). Raised, not asserted: a silent free-list
        corruption here double-allocates live KV pages later."""

    def __init__(self, num_pages: int):
        assert num_pages >= 2, "need >= 1 allocatable page beyond the null page"
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, 0, -1))  # pop() hands out low ids first
        self._refs = [0] * num_pages                    # [NULL_PAGE] stays 0

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    @property
    def utilization(self) -> float:
        return self.num_used / max(self.num_pages - 1, 1)

    def can_alloc(self, n: int) -> bool:
        return len(self._free) >= n

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise self.OutOfPages(f"want {n} pages, {len(self._free)} free")
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._refs[p] = 1
        return out

    def refcount(self, page: int) -> int:
        return self._refs[int(page)]

    def incref(self, page: int) -> None:
        """Add an owner to an already-allocated page (prefix sharing: the
        admission maps an existing physical page into another block table)."""
        p = int(page)
        if p == NULL_PAGE or self._refs[p] <= 0:
            raise self.DoubleFree(f"incref of unowned page {p}")
        self._refs[p] += 1

    def free(self, pages) -> list[int]:
        """Drop one reference per listed page. A page rejoins the free list
        only at refcount zero; returns the pages that did (the caller
        invalidates any prefix-index entries for exactly those)."""
        released = []
        for p in pages:
            p = int(p)
            if p == NULL_PAGE:
                raise self.DoubleFree("freeing the null page")
            if self._refs[p] <= 0:
                raise self.DoubleFree(f"double free of page {p}")
            self._refs[p] -= 1
            if self._refs[p] == 0:
                self._free.append(p)
                released.append(p)
        return released

    def reset_free(self, free: list[int]) -> None:
        """Install a rebuilt free list (defrag: page ids were relabeled) for
        a refcount-OBLIVIOUS owner: every used page is assumed exclusively
        owned (refcount 1). Shared arenas must use ``relabel`` instead."""
        assert len(free) == len(self._free), (len(free), len(self._free))
        self._free = [int(p) for p in free]
        in_free = set(self._free)
        self._refs = [0 if (p in in_free or p == NULL_PAGE) else 1
                      for p in range(self.num_pages)]

    def relabel(self, perm, free: list[int]) -> None:
        """Defrag relabeling that PRESERVES refcounts: page ``perm[new]``
        moves to id ``new`` and carries its count. Asserts the refcount
        multiset is unchanged and the new free list is exactly the zero-
        refcount pages (the invariant ``permute_pool`` relies on)."""
        new_refs = [self._refs[int(old)] for old in perm]
        if sorted(new_refs) != sorted(self._refs):
            raise self.DoubleFree("relabel dropped or duplicated refcounts")
        zero = {p for p in range(1, self.num_pages) if new_refs[p] == 0}
        if set(int(p) for p in free) != zero:
            raise self.DoubleFree("relabel free list != zero-refcount pages")
        self._refs = new_refs
        self._free = [int(p) for p in free]


def pages_needed(tokens: int, page_size: int) -> int:
    return -(-int(tokens) // page_size)


def defrag_plan(block_table, num_pages: int, shared=None):
    """Compaction plan: remap every mapped page onto the lowest physical ids,
    ordered by (slot, logical block) so each request's pages become physically
    contiguous again after a churn of retirements (locality for the fused
    kernels' sequential page reads).

    ``shared`` (optional) is the set of pages with refcount > 1 (prefix
    sharing): they are stably partitioned to the FRONT of the compacted
    range, so the pages every sharer re-reads each tick cluster on the
    lowest ids — one hot region instead of being interleaved with
    single-owner pages (and they stay put across repeated compactions,
    keeping the prefix index's physical ids maximally stable).

    ``block_table`` is a host array (B, max_blocks). Returns
    (perm, new_block_table, free): ``perm[new_id] = old_id`` — apply to every
    page-major pool with ``jnp.take(pages, perm, axis=0)`` — and ``free`` is
    the rebuilt free list (same LIFO convention as PageAllocator)."""
    import numpy as np

    bt = np.asarray(block_table)
    used: list[int] = []
    seen = set()
    for b in range(bt.shape[0]):
        for j in range(bt.shape[1]):
            p = int(bt[b, j])
            if p != NULL_PAGE and p not in seen:
                seen.add(p)
                used.append(p)
    if shared:
        used = ([p for p in used if p in shared]
                + [p for p in used if p not in shared])
    perm = [NULL_PAGE] + used
    in_front = set(perm)
    perm += [p for p in range(num_pages) if p not in in_front]  # park stale pages
    remap = {old: new for new, old in enumerate(perm)}  # total map; 0 -> 0
    new_bt = np.array([[remap[int(p)] for p in row] for row in bt], dtype=bt.dtype)
    free = list(range(num_pages - 1, len(used), -1))  # pop() hands out low ids
    return np.asarray(perm, dtype=np.int32), new_bt, free


def permute_pool(cache: "PagedCache", perm: jax.Array) -> "PagedCache":
    """Apply a defrag permutation (``perm[new_id] = old_id``) to every
    BASE-arena page pool of a paged container; per-slot side state and the
    tiered CPQ escalation arena (its own allocator/tables) are untouched.
    Works identically on sharded pools: the pool axis is never partitioned,
    so the take is local on every device."""
    def pcpq(t: PagedCPQTensor) -> PagedCPQTensor:
        return t._replace(codes=jnp.take(t.codes, perm, axis=0),
                          level=jnp.take(t.level, perm, axis=0))

    if isinstance(cache, TieredPagedCache):
        return cache._replace(dense=permute_pool(cache.dense, perm))
    if isinstance(cache, PagedDenseKVCache):
        return PagedDenseKVCache(k=jnp.take(cache.k, perm, axis=0),
                                 v=jnp.take(cache.v, perm, axis=0))
    if isinstance(cache, PagedXCache):
        return PagedXCache(x=jnp.take(cache.x, perm, axis=0),
                           k_rope=jnp.take(cache.k_rope, perm, axis=0))
    if isinstance(cache, PagedLatentCache):
        return cache._replace(c=jnp.take(cache.c, perm, axis=0),
                              rope=jnp.take(cache.rope, perm, axis=0))
    if isinstance(cache, PagedCPQKVCache):
        return PagedCPQKVCache(k=pcpq(cache.k), v=pcpq(cache.v))
    if isinstance(cache, PagedRetrievalCache):
        return cache._replace(k=jnp.take(cache.k, perm, axis=0),
                              v=jnp.take(cache.v, perm, axis=0),
                              proxy=jnp.take(cache.proxy, perm, axis=0))
    if isinstance(cache, PagedCPQXCache):
        return PagedCPQXCache(x=pcpq(cache.x),
                              k_rope=jnp.take(cache.k_rope, perm, axis=0))
    raise TypeError(type(cache))


def copy_page(cache: "PagedCache", src: jax.Array, dst: jax.Array) -> "PagedCache":
    """Copy one physical page's payload ``src -> dst`` in every BASE-arena
    pool — the copy-on-write split: a writer diverging inside a shared page
    gets a private copy before its first write. Only the positional per-token
    pools move; per-slot side state is already private to the writer. Tiered
    arenas copy the dense arm only (sharing is a tier-0 feature); the CPQ /
    retrieval tiers never share pages (their dequant reads go through per-slot
    side state fitted to one request's stream), so no copy is defined.
    Works identically on sharded pools: the pool axis is never partitioned,
    so the dynamic-index copy is local on every device."""
    cp = lambda pool: pool.at[dst].set(pool[src])  # noqa: E731

    if isinstance(cache, TieredPagedCache):
        return cache._replace(dense=copy_page(cache.dense, src, dst))
    if isinstance(cache, PagedDenseKVCache):
        return PagedDenseKVCache(k=cp(cache.k), v=cp(cache.v))
    if isinstance(cache, PagedXCache):
        return PagedXCache(x=cp(cache.x), k_rope=cp(cache.k_rope))
    if isinstance(cache, PagedLatentCache):
        return cache._replace(c=cp(cache.c), rope=cp(cache.rope))
    raise TypeError(f"copy-on-write is undefined for {type(cache).__name__}")


# ------------------------------------------------------------- paged containers


class PagedDenseKVCache(NamedTuple):
    """Lane-dense K/V pools: (P, page, KV*Dh) for one layer, stacked
    (L, P, page, KV*Dh) for the scanned layers. ``layer`` is set only inside
    the layer scan, where the container carries the whole stack and the
    index of the layer being run (``models/model.py``)."""

    k: jax.Array
    v: jax.Array
    layer: Optional[jax.Array] = None


class PagedXCache(NamedTuple):
    x: jax.Array       # (P, page, Dm)
    k_rope: jax.Array  # (P, page, KV, R)


class PagedLatentCache(NamedTuple):
    """The MLA latent arena, lane-dense. ``c``: the normed latent,
    (P, page, Dc). ``rope``: the one roped key all heads share, ``f``
    tokens to a row so that a row fills 128 lanes (``rope_pack``):
    (P, page // f, f * R), token t of a page at row ``t % (page // f)``,
    lanes ``[(t // (page // f)) * R, ...+R)``. The scanned layers' pools are
    stacked (L, P, ...); ``layer`` is set only inside the layer scan, where
    the container carries the whole stack and the index of the layer being
    run (``models/model.py``), as ``PagedDenseKVCache`` does."""

    c: jax.Array
    rope: jax.Array
    layer: Optional[jax.Array] = None


class PagedCPQTensor(NamedTuple):
    """CPQ arena split into per-token pages + per-slot HQE side state."""

    codes: jax.Array       # (P, page, H, D) int8
    level: jax.Array       # (P, page, H) int32
    scale: jax.Array       # (B, L, H, D) f32
    zero: jax.Array        # (B, L, H, D) f32
    num_levels: jax.Array  # (B, H) int32
    prune_thr: jax.Array   # (B, H, D) f32


class PagedCPQKVCache(NamedTuple):
    k: PagedCPQTensor
    v: PagedCPQTensor


class PagedRetrievalCache(NamedTuple):
    k: jax.Array            # (P, page, KV, Dh)
    v: jax.Array            # (P, page, KV, Dh)
    proxy: jax.Array        # (P, page, KV, Dp) int8
    proxy_scale: jax.Array  # (B, KV, Dp) f32
    proxy_zero: jax.Array   # (B, KV, Dp) f32


class PagedCPQXCache(NamedTuple):
    x: PagedCPQTensor       # H = 1, D = Dm
    k_rope: jax.Array       # (P, page, KV, R)


class TieredPagedCache(NamedTuple):
    """Dense base arena + CPQ escalation arena; ``RowState.tier`` selects the
    live one per row (the watermark policy's dense -> T2 migration target)."""

    dense: PagedDenseKVCache
    cpq: PagedCPQKVCache


PagedCache = (PagedDenseKVCache | PagedXCache | PagedLatentCache
              | PagedCPQKVCache | PagedRetrievalCache | PagedCPQXCache
              | TieredPagedCache)


# ------------------------------------------------------------- constructors


def init_paged_dense(num_pages: int, page_size: int, kv: int, dh: int,
                     dtype=jnp.bfloat16) -> PagedDenseKVCache:
    """Lane-dense pools; K and V are distinct buffers, so a step may donate
    both."""
    shape = (num_pages, page_size, kv * dh)
    return PagedDenseKVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def init_paged_x(num_pages: int, page_size: int, dm: int, kv: int, rope_dims: int,
                 dtype=jnp.bfloat16) -> PagedXCache:
    return PagedXCache(
        x=jnp.zeros((num_pages, page_size, dm), dtype),
        k_rope=jnp.zeros((num_pages, page_size, kv, rope_dims), dtype))


def rope_pack(page_size: int, rope_dims: int) -> int:
    """Tokens per row of a latent arena's rope pool: as many as fill 128
    lanes, where they divide the page; else 1."""
    f = 128 // rope_dims if 0 < rope_dims <= 128 and 128 % rope_dims == 0 else 1
    return f if page_size % f == 0 else 1


def init_paged_latent(num_pages: int, page_size: int, dc: int, rope_dims: int,
                      dtype=jnp.bfloat16) -> PagedLatentCache:
    f = rope_pack(page_size, rope_dims)
    return PagedLatentCache(
        c=jnp.zeros((num_pages, page_size, dc), dtype),
        rope=jnp.zeros((num_pages, page_size // f, f * rope_dims), dtype))


def _init_paged_cpq_tensor(num_pages: int, page_size: int, num_slots: int,
                           h: int, d: int, cfg: CPQCfg) -> PagedCPQTensor:
    return PagedCPQTensor(
        codes=jnp.zeros((num_pages, page_size, h, d), jnp.int8),
        level=jnp.zeros((num_pages, page_size, h), jnp.int32),
        scale=jnp.zeros((num_slots, cfg.max_levels, h, d), jnp.float32),
        zero=jnp.zeros((num_slots, cfg.max_levels, h, d), jnp.float32),
        num_levels=jnp.ones((num_slots, h), jnp.int32),
        prune_thr=jnp.zeros((num_slots, h, d), jnp.float32))


def init_paged_cpq(num_pages: int, page_size: int, num_slots: int, kv: int, dh: int,
                   cfg: CPQCfg) -> PagedCPQKVCache:
    return PagedCPQKVCache(
        k=_init_paged_cpq_tensor(num_pages, page_size, num_slots, kv, dh, cfg),
        v=_init_paged_cpq_tensor(num_pages, page_size, num_slots, kv, dh, cfg))


def init_paged_retrieval(num_pages: int, page_size: int, num_slots: int, kv: int,
                         dh: int, cfg: RetrievalCfg, dtype=jnp.bfloat16
                         ) -> PagedRetrievalCache:
    dp = cfg.proxy_dim or dh
    z = jnp.zeros((num_pages, page_size, kv, dh), dtype)
    return PagedRetrievalCache(
        k=z, v=z,
        proxy=jnp.zeros((num_pages, page_size, kv, dp), jnp.int8),
        proxy_scale=jnp.ones((num_slots, kv, dp), jnp.float32),
        proxy_zero=jnp.zeros((num_slots, kv, dp), jnp.float32))


def init_paged_cpq_x(num_pages: int, page_size: int, num_slots: int, dm: int,
                     kv: int, rope_dims: int, cfg: CPQCfg,
                     dtype=jnp.bfloat16) -> PagedCPQXCache:
    return PagedCPQXCache(
        x=_init_paged_cpq_tensor(num_pages, page_size, num_slots, 1, dm, cfg),
        k_rope=jnp.zeros((num_pages, page_size, kv, rope_dims), dtype))


# ------------------------------------------------------------ logical views


def _dense_stack(cache: PagedDenseKVCache):
    """(k, v, layer) that the dense read and write paths index: inside the
    layer scan the container already holds the stacked (L, P, page, KV*Dh)
    pools and the layer being run; a single layer's (P, page, KV*Dh) pools
    are read as a stack of one, at layer 0."""
    if cache.layer is None:
        return cache.k[None], cache.v[None], jnp.zeros((), jnp.int32)
    return cache.k, cache.v, cache.layer


def _dense_like(cache: PagedDenseKVCache, k: jax.Array,
                v: jax.Array) -> PagedDenseKVCache:
    """Put stacked pools back in the form ``cache`` came in."""
    if cache.layer is None:
        k, v = k[0], v[0]
    return cache._replace(k=k, v=v)


def gather_layer_heads(pages: jax.Array, layer: jax.Array,
                       block_table: jax.Array, kv: int) -> jax.Array:
    """Logical per-head view of one layer of a stacked lane-dense pool (the
    gather oracle of the paged flash kernels): (L, P, page, KV*D) x
    (B, max_blocks) -> (B, max_blocks * page, KV, D)."""
    g = pages[layer, block_table]             # (B, max_blocks, page, KV*D)
    B, nb, page, w = g.shape
    return g.reshape(B, nb * page, kv, w // kv)


def _one_layer(pool: jax.Array) -> jax.Array:
    """A per-head (P, page, KV, D) pool as the paged flash kernels read it:
    a lane-dense stack of one layer, (1, P, page, KV*D)."""
    return pool.reshape((1,) + pool.shape[:2] + (-1,))


def latent_stack(cache: PagedLatentCache):
    """(c, rope, layer) that the latent read and write paths index: the
    carried stack and its layer inside the layer scan, else a single
    layer's pools read as a stack of one, at layer 0."""
    if cache.layer is None:
        return cache.c[None], cache.rope[None], jnp.zeros((), jnp.int32)
    return cache.c, cache.rope, cache.layer


def _latent_like(cache: PagedLatentCache, c: jax.Array,
                 rope: jax.Array) -> PagedLatentCache:
    """Put stacked pools back in the form ``cache`` came in."""
    if cache.layer is None:
        c, rope = c[0], rope[0]
    return cache._replace(c=c, rope=rope)


@_kv_write
def _write_rope(pool: jax.Array, page_idx: jax.Array, off: jax.Array,
                val: jax.Array, layer: Optional[jax.Array] = None) -> jax.Array:
    """Write roped keys val (N, R) at (page_idx, off) (N,) into a packed
    rope pool (P, rows, f*R), or a stacked one at ``layer``. Whole 128-lane
    rows are written, as the dense K/V writes are: each token's row is read,
    and every segment that a token of this call lands in is replaced, so
    tokens that share a row write the same row."""
    N, R = val.shape
    rows = pool.shape[-2]
    f = pool.shape[-1] // R
    row, seg = off % rows, off // rows
    at = (page_idx, row) if layer is None else (layer, page_idx, row)
    old = pool[at]                                          # (N, f*R)
    same = (page_idx[:, None] == page_idx[None]) & (row[:, None] == row[None])
    parts = []
    for j in range(f):
        m = same & (seg[None, :] == j)      # token k fills segment j of i's row
        src = jnp.take(val, jnp.argmax(m, axis=1), axis=0).astype(pool.dtype)
        parts.append(jnp.where(m.any(1)[:, None], src, old[:, j * R:(j + 1) * R]))
    return pool.at[at].set(jnp.concatenate(parts, axis=-1))


def append_latent(cache: PagedLatentCache, rows: RowState, c_t: jax.Array,
                  rope_t: jax.Array) -> PagedLatentCache:
    """Write one token per row (c_t (B, 1, Dc), rope_t (B, 1, 1, R)) at
    ``rows.lengths``; inactive rows write the null page."""
    c, rope, layer = latent_stack(cache)
    page = c.shape[2]
    c = write_token_pages(c, rows.block_table, rows.lengths, rows.active,
                          c_t[:, 0], layer)
    blk = jnp.clip(rows.lengths // page, 0, rows.block_table.shape[1] - 1)
    pidx = jnp.take_along_axis(rows.block_table, blk[:, None], axis=1)[:, 0]
    pidx = jnp.where(rows.active, pidx, NULL_PAGE)
    rope = _write_rope(rope, pidx, rows.lengths % page, rope_t[:, 0, 0], layer)
    return _latent_like(cache, c, rope)


def write_latent_chunk(cache: PagedLatentCache, block_row: jax.Array,
                       offset: jax.Array, valid: jax.Array, c_c: jax.Array,
                       rope_c: jax.Array) -> PagedLatentCache:
    """Write one prompt chunk (c_c (C, Dc), rope_c (C, R)) into one slot's
    pages at positions ``offset ..``; padding and unmapped positions land on
    the null page (``write_chunk_pages``)."""
    c, rope, layer = latent_stack(cache)
    page = c.shape[2]
    c = write_chunk_pages(c, block_row, offset, valid, c_c, layer)
    idx = jnp.arange(c_c.shape[0], dtype=jnp.int32)
    pos = offset + idx
    blk = pos // page
    ok = (idx < valid) & (blk < block_row.shape[0])
    pidx = jnp.where(ok, block_row[jnp.clip(blk, 0, block_row.shape[0] - 1)],
                     NULL_PAGE)
    rope = _write_rope(rope, pidx, pos % page, rope_c, layer)
    return _latent_like(cache, c, rope)


def gather_latent(cache: PagedLatentCache, block_table: jax.Array):
    """Logical views of a latent arena's layer (the gather oracle of the
    paged MLA kernels): (B, max_blocks) -> c (B, N, Dc), rope (B, N, 1, R)."""
    c, rope, layer = latent_stack(cache)
    g = c[layer, block_table]                     # (B, nb, page, Dc)
    B, nb, page, dc = g.shape
    r = rope[layer, block_table]                  # (B, nb, rows, f*R)
    rows = r.shape[2]
    f = page // rows
    r = r.reshape(B, nb, rows, f, -1).transpose(0, 1, 3, 2, 4)
    return g.reshape(B, nb * page, dc), r.reshape(B, nb * page, 1, -1)


def logical_cpq(t: PagedCPQTensor, block_table: jax.Array) -> cpq_lib.CPQTensor:
    """Contiguous CPQTensor view of a paged CPQ arena (codes gathered through
    the block table; per-slot stats already contiguous). The chunked decode
    kernels consume this with per-row lengths."""
    return cpq_lib.CPQTensor(
        codes=gather_pages(t.codes, block_table),
        scale=t.scale, zero=t.zero,
        level=gather_pages(t.level, block_table),
        num_levels=t.num_levels, prune_thr=t.prune_thr)


# -------------------------------------------------------------- decode append


def append_x(cache: PagedXCache, rows: RowState,
             x_t: jax.Array, k_rope_t: Optional[jax.Array]) -> PagedXCache:
    return PagedXCache(
        x=write_token_pages(cache.x, rows.block_table, rows.lengths, rows.active, x_t[:, 0]),
        k_rope=(write_token_pages(cache.k_rope, rows.block_table, rows.lengths,
                                  rows.active, k_rope_t[:, 0])
                if k_rope_t is not None else cache.k_rope))


@_kv_write
def append_cpq_tensor(t: PagedCPQTensor, rows: RowState, x_t: jax.Array,
                      cfg: CPQCfg) -> PagedCPQTensor:
    """HQE-encode one token per row (shared math with the contiguous path)
    and scatter code/level through the block table. Side-state updates only
    commit on active rows."""
    code_t, level_t, scale, zero, num_levels = cpq_lib.cpq_encode_token(
        t.scale, t.zero, t.num_levels, t.prune_thr, x_t, cfg)
    scale, zero, num_levels = _sel_rows(
        rows.active, (scale, zero, num_levels), (t.scale, t.zero, t.num_levels))
    return PagedCPQTensor(
        codes=write_token_pages(t.codes, rows.block_table, rows.lengths,
                                rows.active, code_t[:, 0]),
        level=write_token_pages(t.level, rows.block_table, rows.lengths,
                                rows.active, level_t),
        scale=scale, zero=zero, num_levels=num_levels, prune_thr=t.prune_thr)


# ------------------------------------------------------------- prefill pack


def pack_dense(cache: PagedDenseKVCache, src: kvc.DenseKVCache,
               block_row: jax.Array) -> PagedDenseKVCache:
    """Scatter a freshly prefilled contiguous B=1 cache into one slot's pages."""
    S = src.k.shape[1]
    return PagedDenseKVCache(
        k=write_prompt_pages(cache.k, block_row, src.k[0].reshape(S, -1)),
        v=write_prompt_pages(cache.v, block_row, src.v[0].reshape(S, -1)))


def pack_x(cache: PagedXCache, src: kvc.XCache, block_row: jax.Array) -> PagedXCache:
    return PagedXCache(
        x=write_prompt_pages(cache.x, block_row, src.x[0]),
        k_rope=write_prompt_pages(cache.k_rope, block_row, src.k_rope[0]))


def pack_latent(cache: PagedLatentCache, src: kvc.XCache,
                block_row: jax.Array) -> PagedLatentCache:
    """Scatter a one-shot prefilled B=1 latent cache into one slot's pages."""
    S = src.x.shape[1]
    return write_latent_chunk(cache, block_row, jnp.zeros((), jnp.int32),
                              jnp.asarray(S, jnp.int32), src.x[0],
                              src.k_rope[0, :, 0])


def pack_cpq_tensor(t: PagedCPQTensor, src: cpq_lib.CPQTensor, block_row: jax.Array,
                    slot: jax.Array) -> PagedCPQTensor:
    return PagedCPQTensor(
        codes=write_prompt_pages(t.codes, block_row, src.codes[0]),
        level=write_prompt_pages(t.level, block_row, src.level[0]),
        scale=t.scale.at[slot].set(src.scale[0]),
        zero=t.zero.at[slot].set(src.zero[0]),
        num_levels=t.num_levels.at[slot].set(src.num_levels[0]),
        prune_thr=t.prune_thr.at[slot].set(src.prune_thr[0]))


def pack_cpq(cache: PagedCPQKVCache, src: kvc.CPQKVCache, block_row: jax.Array,
             slot: jax.Array) -> PagedCPQKVCache:
    return PagedCPQKVCache(
        k=pack_cpq_tensor(cache.k, src.k, block_row, slot),
        v=pack_cpq_tensor(cache.v, src.v, block_row, slot))


def pack_retrieval(cache: PagedRetrievalCache, src: kvc.RetrievalCache,
                   block_row: jax.Array, slot: jax.Array) -> PagedRetrievalCache:
    return PagedRetrievalCache(
        k=write_prompt_pages(cache.k, block_row, src.k[0]),
        v=write_prompt_pages(cache.v, block_row, src.v[0]),
        proxy=write_prompt_pages(cache.proxy, block_row, src.proxy[0]),
        proxy_scale=cache.proxy_scale.at[slot].set(src.proxy_scale[0]),
        proxy_zero=cache.proxy_zero.at[slot].set(src.proxy_zero[0]))


def pack_cpq_x(cache: PagedCPQXCache, src: kvc.CPQXCache, block_row: jax.Array,
               slot: jax.Array) -> PagedCPQXCache:
    return PagedCPQXCache(
        x=pack_cpq_tensor(cache.x, src.x, block_row, slot),
        k_rope=write_prompt_pages(cache.k_rope, block_row, src.k_rope[0]))


def pack_into(rt_mode: str, cache, src, block_row: jax.Array, slot: jax.Array):
    """Mode dispatch for admission packing (contiguous B=1 prefill -> pages)."""
    if isinstance(cache, TieredPagedCache):
        if isinstance(src, kvc.DenseKVCache):
            return cache._replace(dense=pack_dense(cache.dense, src, block_row))
        return cache._replace(cpq=pack_cpq(cache.cpq, src, block_row, slot))
    if isinstance(cache, PagedDenseKVCache):
        return pack_dense(cache, src, block_row)
    if isinstance(cache, PagedXCache):
        return pack_x(cache, src, block_row)
    if isinstance(cache, PagedLatentCache):
        return pack_latent(cache, src, block_row)
    if isinstance(cache, PagedCPQKVCache):
        return pack_cpq(cache, src, block_row, slot)
    if isinstance(cache, PagedRetrievalCache):
        return pack_retrieval(cache, src, block_row, slot)
    if isinstance(cache, PagedCPQXCache):
        return pack_cpq_x(cache, src, block_row, slot)
    raise TypeError(type(cache))


# ---------------------------------------------------------- chunked prefill


def _slot_cpq(t: PagedCPQTensor, block_row: jax.Array, slot: jax.Array
              ) -> cpq_lib.CPQTensor:
    """One slot's logical CPQTensor view (B=1): codes/levels gathered through
    the slot's block row, HQE side state sliced at ``slot``."""
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0)  # noqa: E731
    return cpq_lib.CPQTensor(
        codes=gather_pages(t.codes, block_row[None]),
        scale=sl(t.scale), zero=sl(t.zero),
        level=gather_pages(t.level, block_row[None]),
        num_levels=sl(t.num_levels), prune_thr=sl(t.prune_thr))


@_kv_write
def chunk_cpq_tensor(t: PagedCPQTensor, slot: jax.Array, block_row: jax.Array,
                     offset: jax.Array, valid: jax.Array, x_c: jax.Array,
                     cfg: CPQCfg, first: bool) -> PagedCPQTensor:
    """Incrementally CPQ-compress one prompt chunk into a slot's code pages
    (chunked paged prefill): the FIRST chunk fits the per-channel prune
    threshold and level-0 scale/zero (the role the whole prompt plays in
    ``cpq_compress_prefill``); continuation chunks HQE-extend token by token
    exactly like decode appends — no re-compression of earlier tokens, ever.
    x_c: (1, C, H, D); ``first`` is static (one compiled variant each)."""
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0)  # noqa: E731
    if first:
        codes, level, scale, zero, num_levels, thr = cpq_lib.cpq_fit_chunk(
            x_c, valid, cfg)
        prune_thr = t.prune_thr.at[slot].set(thr[0])
    else:
        codes, level, scale, zero, num_levels = cpq_lib.cpq_encode_chunk(
            sl(t.scale), sl(t.zero), sl(t.num_levels), sl(t.prune_thr),
            x_c, valid, cfg)
        prune_thr = t.prune_thr
    return PagedCPQTensor(
        codes=write_chunk_pages(t.codes, block_row, offset, valid, codes[0]),
        level=write_chunk_pages(t.level, block_row, offset, valid, level[0]),
        scale=t.scale.at[slot].set(scale[0]),
        zero=t.zero.at[slot].set(zero[0]),
        num_levels=t.num_levels.at[slot].set(num_levels[0]),
        prune_thr=prune_thr)


def _chunk_mask_bias(n_prev: int, chunk: int, offset: jax.Array,
                     valid: jax.Array) -> jax.Array:
    """(C, n_prev + C) additive mask for chunk attention over [earlier-pages
    view | raw chunk]: earlier key j is live iff j < offset (cross-chunk keys
    read what decode reads); chunk key i is live iff i < valid and i <= the
    query's chunk index (causal)."""
    kp = jnp.concatenate([jnp.arange(n_prev, dtype=jnp.int32),
                          offset + jnp.arange(chunk, dtype=jnp.int32)])
    live = jnp.concatenate([jnp.arange(n_prev, dtype=jnp.int32) < offset,
                            jnp.arange(chunk, dtype=jnp.int32) < valid])
    qp = offset + jnp.arange(chunk, dtype=jnp.int32)
    ok = live[None, :] & (kp[None, :] <= qp[:, None])
    return jnp.where(ok, 0.0, -1e30)


def cpq_chunk_prefill_attention(q, kt: PagedCPQTensor, vt: PagedCPQTensor,
                                block_row, slot, k_raw, v_raw, offset, valid,
                                scale: float) -> jax.Array:
    """jnp gather-path oracle of the fused paged T2 prefill kernel: earlier
    chunks are read back as dequantized codes (what decode reads), the
    current chunk attends its RAW roped K/V causally — a single-chunk
    admission therefore reproduces the one-shot prefill's raw-attention
    numerics. q: (1, C, H, Dh); k_raw/v_raw: (1, C, KV, Dh|Dv)."""
    from repro.core import attention as core_attn

    k_hat = cpq_lib.cpq_dequant(_slot_cpq(kt, block_row, slot))
    v_hat = cpq_lib.cpq_dequant(_slot_cpq(vt, block_row, slot))
    k_all = jnp.concatenate([k_hat.astype(q.dtype), k_raw], axis=1)
    v_all = jnp.concatenate([v_hat.astype(q.dtype), v_raw], axis=1)
    bias = _chunk_mask_bias(k_hat.shape[1], q.shape[1], offset, valid)
    return core_attn.dense_attention(
        q, k_all, v_all, scale, causal=False, logit_bias=bias[None, :, None, :])


def decomposed_cpq_chunk_prefill(q_nope, q_rope, xt: PagedCPQTensor,
                                 kr_pages, block_row, slot, x_raw, k_rope_raw,
                                 offset, valid, w_k_nope, w_v,
                                 scale: float) -> jax.Array:
    """T1+T2 / MLA-CPQ chunk prefill attention (gather path — this
    composition has no fused kernel, matching its decode path): earlier X
    codes are dequantized, the current chunk contributes its raw operand;
    both cascaded MatMuls of the decomposition run over the combined axis.
    q_nope: (1, C, H, Dn); x_raw: (1, C, Dm); k_rope_raw: (1, C, KV, R)."""
    from repro.core.decomposed_attention import (decomposed_query_transform,
                                                 decomposed_values)

    B, C, H, _ = q_nope.shape
    x_hat = cpq_lib.cpq_dequant(
        _slot_cpq(xt, block_row, slot))[:, :, 0, :]             # (1, Nprev, Dm)
    x_all = jnp.concatenate([x_hat.astype(x_raw.dtype), x_raw], axis=1)
    r = decomposed_query_transform(q_nope, w_k_nope)            # (1, C, H, Dm)
    s = jnp.einsum("bchm,bnm->bchn", r, x_all)
    if q_rope is not None and q_rope.shape[-1] > 0:
        kr_prev = gather_pages(kr_pages, block_row[None])       # (1, Nprev, KV, R)
        kr_all = jnp.concatenate([kr_prev.astype(k_rope_raw.dtype),
                                  k_rope_raw], axis=1)
        kv_r = kr_all.shape[2]
        g_r = H // kv_r
        qg = q_rope.reshape(B, C, kv_r, g_r, q_rope.shape[-1])
        s = s + jnp.einsum("bckgr,bnkr->bckgn", qg, kr_all).reshape(
            B, C, H, s.shape[-1])
    s = s.astype(jnp.float32) * scale
    s = s + _chunk_mask_bias(x_hat.shape[1], C, offset, valid)[None, :, None, :]
    w = jax.nn.softmax(s, axis=-1).astype(x_all.dtype)
    return decomposed_values(w, x_all, w_v)


def chunk_attend_paged(
    rt,
    cache: PagedCache,
    *,
    tier: int,                      # static: tiered-arena arm (0 dense, 1 CPQ)
    first: bool,                    # static: first chunk of this admission
    slot: jax.Array,                # () int32 request slot
    block_row: jax.Array,           # (max_blocks,) slot's block-table row
    offset: jax.Array,              # () int32 tokens already written
    valid: jax.Array,               # () int32 real tokens in this chunk
    q: jax.Array,                   # (1, C, H, Dh) roped chunk queries
    k_c: jax.Array,                 # (1, C, KV, Dh) roped chunk keys
    v_c: jax.Array,                 # (1, C, KV, Dh)
    x_c: Optional[jax.Array],       # (1, C, Dm) block input (T1/MLA operand)
    k_rope_c: Optional[jax.Array],  # (1, C, KV, R)
    q_nope: Optional[jax.Array],    # (1, C, H, Dn)
    q_rope: Optional[jax.Array],    # (1, C, H, R)
    w_k_nope: Optional[jax.Array],  # (Dm, KV, Dn)
    w_v: Optional[jax.Array],       # (Dm, KV, Dh)
    scale: float,
) -> tuple[jax.Array, PagedCache]:
    """Chunked paged-prefill analogue of ``decode_attend_paged``: write one
    prompt chunk's payload STRAIGHT into the slot's arena pages (no
    contiguous scratch cache, no pack copy), then attend the chunk's C
    queries over the slot's pages [0, offset + valid) — fused Q-chunk>1
    paged kernels when ``rt.paged_kernels`` (dense, CPQ, X/MLA tiers), jnp
    gather otherwise. CPQ tiers compress incrementally (level-0 fit on the
    first chunk, HQE extension after) and attend earlier chunks through
    their own codes — cross-chunk prefill reads exactly what decode reads.
    Returns (out (1, C, H, Dv), new_cache); query rows past ``valid`` are
    jit-padding garbage the caller never reads."""
    from repro.configs.base import AttentionRuntime
    from repro.core import attention as core_attn
    from repro.core import retrieval_attention as ret_lib
    from repro.core.decomposed_attention import decomposed_attention
    from repro.kernels.cpq_dequant_attn.ops import paged_cpq_prefill_tpu
    from repro.kernels.decomposed_attn.ops import paged_decomposed_prefill_tpu
    from repro.kernels.flash_attn.ops import paged_flash_prefill_tpu

    if getattr(rt, "mesh", None) is not None:
        from repro.serving import sharded

        if sharded.supports(cache):
            return sharded.chunk_attend_sharded(
                rt, cache, tier=tier, first=first, slot=slot,
                block_row=block_row, offset=offset, valid=valid, q=q, k_c=k_c,
                v_c=v_c, x_c=x_c, k_rope_c=k_rope_c, q_nope=q_nope,
                q_rope=q_rope, w_k_nope=w_k_nope, w_v=w_v, scale=scale)
        # T3 / T1+T2 keep global-semantics compute over (possibly storage-
        # sharded) arenas — GSPMD inserts the gathers
        import dataclasses as _dc
        rt = _dc.replace(rt, mesh=None)

    fused = rt.paged_kernels
    total = offset + valid
    qpos = offset + jnp.arange(q.shape[1], dtype=jnp.int32)

    if isinstance(cache, TieredPagedCache):
        # the admission tier is host-static for the whole prefill: compile
        # one chunk function per arm instead of computing both tiers
        if tier == 0:
            out, dense = chunk_attend_paged(
                rt, cache.dense, tier=0, first=first, slot=slot,
                block_row=block_row, offset=offset, valid=valid, q=q, k_c=k_c,
                v_c=v_c, x_c=x_c, k_rope_c=k_rope_c, q_nope=q_nope,
                q_rope=q_rope, w_k_nope=w_k_nope, w_v=w_v, scale=scale)
            return out, cache._replace(dense=dense)
        rt_c = AttentionRuntime(mode="cpq", cpq=rt.cpq, paged_kernels=fused)
        out, cpq = chunk_attend_paged(
            rt_c, cache.cpq, tier=0, first=first, slot=slot,
            block_row=block_row, offset=offset, valid=valid, q=q, k_c=k_c,
            v_c=v_c, x_c=x_c, k_rope_c=k_rope_c, q_nope=q_nope,
            q_rope=q_rope, w_k_nope=w_k_nope, w_v=w_v, scale=scale)
        return out, cache._replace(cpq=cpq)

    if isinstance(cache, PagedDenseKVCache):
        k, v, layer = _dense_stack(cache)
        C, KV = k_c.shape[1], k_c.shape[2]
        k = write_chunk_pages(k, block_row, offset, valid,
                              k_c[0].reshape(C, -1), layer)
        v = write_chunk_pages(v, block_row, offset, valid,
                              v_c[0].reshape(C, -1), layer)
        if fused:
            out = paged_flash_prefill_tpu(q, k, v, block_row, offset, valid,
                                          layer, scale)
        else:
            out = core_attn.dense_attention(
                q, gather_layer_heads(k, layer, block_row[None], KV),
                gather_layer_heads(v, layer, block_row[None], KV),
                scale, causal=True, q_offset=offset, kv_length=total)
        return out, _dense_like(cache, k, v)

    if isinstance(cache, PagedXCache):
        cache = PagedXCache(
            x=write_chunk_pages(cache.x, block_row, offset, valid, x_c[0]),
            k_rope=(write_chunk_pages(cache.k_rope, block_row, offset, valid,
                                      k_rope_c[0])
                    if k_rope_c is not None else cache.k_rope))
        if fused:
            out = paged_decomposed_prefill_tpu(
                q_nope, q_rope, cache.x, cache.k_rope, block_row, offset,
                valid, w_k_nope, w_v, scale)
        else:
            out = decomposed_attention(
                q_nope, q_rope, gather_pages(cache.x, block_row[None]),
                gather_pages(cache.k_rope, block_row[None]),
                w_k_nope, w_v, total, scale, query_positions=qpos)
        return out, cache

    if isinstance(cache, PagedCPQKVCache):
        cache = PagedCPQKVCache(
            k=chunk_cpq_tensor(cache.k, slot, block_row, offset, valid,
                               k_c, rt.cpq, first),
            v=chunk_cpq_tensor(cache.v, slot, block_row, offset, valid,
                               v_c, rt.cpq, first))
        if fused:
            out = paged_cpq_prefill_tpu(q, cache.k, cache.v, k_c, v_c, slot,
                                        block_row, offset, valid, scale)
        else:
            out = cpq_chunk_prefill_attention(
                q, cache.k, cache.v, block_row, slot, k_c, v_c, offset,
                valid, scale)
        return out, cache

    if isinstance(cache, PagedRetrievalCache):
        dp = rt.retrieval.proxy_dim or k_c.shape[-1]
        # proxy fit is min/max per channel: masking the chunk's jit padding
        # with the last valid key keeps the first-chunk fit exact
        idx = jnp.arange(k_c.shape[1], dtype=jnp.int32)
        edge = jax.lax.dynamic_index_in_dim(
            k_c, jnp.maximum(valid - 1, 0), axis=1)             # (1, 1, KV, Dh)
        k_fit = jnp.where((idx < valid)[None, :, None, None], k_c, edge)
        if first:
            code_c, pscale, pzero = ret_lib.fit_proxy(
                k_fit[..., :dp], rt.retrieval.proxy_bits)
            proxy_scale = cache.proxy_scale.at[slot].set(pscale[0])
            proxy_zero = cache.proxy_zero.at[slot].set(pzero[0])
        else:
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0)  # noqa: E731
            code_c = ret_lib.encode_proxy(
                k_c[..., :dp], sl(cache.proxy_scale), sl(cache.proxy_zero),
                rt.retrieval.proxy_bits)
            proxy_scale, proxy_zero = cache.proxy_scale, cache.proxy_zero
        cache = PagedRetrievalCache(
            k=write_chunk_pages(cache.k, block_row, offset, valid, k_c[0]),
            v=write_chunk_pages(cache.v, block_row, offset, valid, v_c[0]),
            proxy=write_chunk_pages(cache.proxy, block_row, offset, valid,
                                    code_c[0]),
            proxy_scale=proxy_scale, proxy_zero=proxy_zero)
        # prefill COMPUTE is dense (T3 gates decode reads only): K/V pages
        # hold raw payload, so the dense chunk kernels serve this tier too
        if fused:
            out = paged_flash_prefill_tpu(
                q, _one_layer(cache.k), _one_layer(cache.v), block_row,
                offset, valid, 0, scale)
        else:
            out = core_attn.dense_attention(
                q, gather_pages(cache.k, block_row[None]),
                gather_pages(cache.v, block_row[None]),
                scale, causal=True, q_offset=offset, kv_length=total)
        return out, cache

    if isinstance(cache, PagedCPQXCache):
        cache = PagedCPQXCache(
            x=chunk_cpq_tensor(cache.x, slot, block_row, offset, valid,
                               x_c[:, :, None, :], rt.cpq, first),
            k_rope=(write_chunk_pages(cache.k_rope, block_row, offset, valid,
                                      k_rope_c[0])
                    if k_rope_c is not None else cache.k_rope))
        out = decomposed_cpq_chunk_prefill(
            q_nope, q_rope, cache.x, cache.k_rope, block_row, slot, x_c,
            k_rope_c if k_rope_c is not None
            else jnp.zeros((1, q.shape[1], 1, 0), x_c.dtype),
            offset, valid, w_k_nope, w_v, scale)
        return out, cache

    raise TypeError(type(cache))


# ------------------------------------------------------------------- traffic


def bytes_per_token(cache: PagedCache, page_size: int,
                    cpq_cfg: Optional[CPQCfg] = None) -> float:
    """Per-token decode traffic of the paged arena: the contiguous payload
    accounting (kv_cache.bytes_per_token / cpq accounting) plus the amortized
    block-table overhead (one int32 entry per page). An input of the
    analytic model in benchmarks/bench_e2e_energy.py."""
    overhead = 4.0 / page_size
    if isinstance(cache, TieredPagedCache):  # base-tier accounting
        return bytes_per_token(cache.dense, page_size, cpq_cfg)
    if isinstance(cache, PagedDenseKVCache):
        payload = 2.0 * cache.k.shape[-1] * cache.k.dtype.itemsize
    elif isinstance(cache, PagedXCache):
        payload = (cache.x.shape[2] * cache.x.dtype.itemsize
                   + cache.k_rope.shape[2] * cache.k_rope.shape[3]
                   * cache.k_rope.dtype.itemsize)
    elif isinstance(cache, PagedLatentCache):
        page = cache.c.shape[-2]
        payload = (cache.c.shape[-1] + cache.rope.shape[-2]
                   * cache.rope.shape[-1] / page) * cache.c.dtype.itemsize
    elif isinstance(cache, PagedCPQKVCache):
        cfg = cpq_cfg or CPQCfg()
        payload = 2.0 * cpq_lib.cpq_bytes_per_token(
            cfg, cache.k.codes.shape[2], cache.k.codes.shape[3])
    elif isinstance(cache, PagedRetrievalCache):
        payload = (2.0 * cache.k.shape[2] * cache.k.shape[3] * cache.k.dtype.itemsize
                   + cache.proxy.shape[2] * cache.proxy.shape[3])
    elif isinstance(cache, PagedCPQXCache):
        cfg = cpq_cfg or CPQCfg()
        payload = (cpq_lib.cpq_bytes_per_token(cfg, 1, cache.x.codes.shape[3])
                   + cache.k_rope.shape[2] * cache.k_rope.shape[3]
                   * cache.k_rope.dtype.itemsize)
    else:
        raise TypeError(type(cache))
    return payload + overhead


def arena_bytes(cache: PagedCache) -> int:
    """Total physical bytes of the paged arena (all pools + slot side state)."""
    return int(sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache)))


# ------------------------------------------------------------- decode attend


def decode_attend_paged(
    rt,
    cache: PagedCache,
    rows: RowState,
    *,
    q: jax.Array,                   # (B, 1, H, Dh) roped query
    k_t: jax.Array,                 # (B, 1, KV, Dh) roped new key
    v_t: jax.Array,                 # (B, 1, KV, Dh)
    x_t: Optional[jax.Array],       # (B, 1, Dm)
    k_rope_t: Optional[jax.Array],  # (B, 1, KV, R)
    q_nope: Optional[jax.Array],    # (B, 1, H, Dn) content query (T1)
    q_rope: Optional[jax.Array],    # (B, 1, H, R) roped query slice (T1)
    w_k_nope: Optional[jax.Array],  # (Dm, KV, Dn) (T1)
    w_v: Optional[jax.Array],       # (Dm, KV, Dh) (T1)
    scale: float,
) -> tuple[jax.Array, PagedCache]:
    """Paged analogue of ``core.attention.decode_attend``: scatter one token
    per row through the block table, then attend with per-row lengths. With
    ``rt.paged_kernels`` (the default) the dense, CPQ, and X/MLA tiers run
    the fused paged Pallas kernels, whose grid iterates block-table entries
    and DMAs mapped pages straight from the arena into VMEM — no contiguous
    logical view is ever materialized. ``rt.paged_kernels=False`` falls back
    to the jnp gather path (the numerics oracle and benchmark foil);
    retrieval (T3, top-k slot selection) and the T1+T2 composition keep the
    gather path. Every row sits at its own position (``rows.lengths``);
    inactive rows write the null page and their output is garbage the engine
    never reads. Returns (out (B,1,H,Dv), new_cache)."""
    from repro.configs.base import AttentionRuntime
    from repro.core import attention as core_attn
    from repro.core import retrieval_attention as ret_lib
    from repro.core.decomposed_attention import decomposed_attention
    from repro.kernels.cpq_dequant_attn.ops import paged_cpq_decode_tpu
    from repro.kernels.decomposed_attn.ops import paged_decomposed_decode_tpu
    from repro.kernels.flash_attn.ops import paged_flash_decode_tpu

    if getattr(rt, "mesh", None) is not None:
        from repro.serving import sharded

        if sharded.supports(cache):
            return sharded.decode_attend_sharded(
                rt, cache, rows, q=q, k_t=k_t, v_t=v_t, x_t=x_t,
                k_rope_t=k_rope_t, q_nope=q_nope, q_rope=q_rope,
                w_k_nope=w_k_nope, w_v=w_v, scale=scale)
        # T3 / T1+T2 keep global-semantics compute over (possibly storage-
        # sharded) arenas — GSPMD inserts the gathers
        import dataclasses as _dc
        rt = _dc.replace(rt, mesh=None)

    fused = rt.paged_kernels
    new_len = rows.lengths + rows.active.astype(jnp.int32)

    if isinstance(cache, TieredPagedCache):
        # compute both tiers (each tier's appends masked to its own rows),
        # select per row — one jitted step serves a mixed dense/T2 batch
        rows_d = rows._replace(active=rows.active & (rows.tier == 0))
        rows_c = rows._replace(active=rows.active & (rows.tier == 1),
                               block_table=rows.alt_block_table)
        rt_c = AttentionRuntime(mode="cpq", cpq=rt.cpq, paged_kernels=fused)
        out_d, dense = decode_attend_paged(
            rt, cache.dense, rows_d, q=q, k_t=k_t, v_t=v_t, x_t=x_t,
            k_rope_t=k_rope_t, q_nope=q_nope, q_rope=q_rope,
            w_k_nope=w_k_nope, w_v=w_v, scale=scale)
        out_c, cpq = decode_attend_paged(
            rt_c, cache.cpq, rows_c, q=q, k_t=k_t, v_t=v_t, x_t=x_t,
            k_rope_t=k_rope_t, q_nope=q_nope, q_rope=q_rope,
            w_k_nope=w_k_nope, w_v=w_v, scale=scale)
        out = jnp.where((rows.tier == 1)[:, None, None, None], out_c, out_d)
        return out, TieredPagedCache(dense, cpq)

    if isinstance(cache, PagedDenseKVCache):
        k, v, layer = _dense_stack(cache)
        B, KV = k_t.shape[0], k_t.shape[2]
        k = write_token_pages(k, rows.block_table, rows.lengths, rows.active,
                              k_t[:, 0].reshape(B, -1), layer)
        v = write_token_pages(v, rows.block_table, rows.lengths, rows.active,
                              v_t[:, 0].reshape(B, -1), layer)
        if fused:
            out = paged_flash_decode_tpu(
                q, k, v, rows.block_table, new_len, layer, scale)
        else:
            out = core_attn.dense_attention(
                q, gather_layer_heads(k, layer, rows.block_table, KV),
                gather_layer_heads(v, layer, rows.block_table, KV),
                scale, causal=False, kv_length=new_len)
        return out, _dense_like(cache, k, v)

    if isinstance(cache, PagedXCache):
        cache = append_x(cache, rows, x_t, k_rope_t)
        if fused:
            out = paged_decomposed_decode_tpu(
                q_nope, q_rope, cache.x, cache.k_rope,
                rows.block_table, new_len, w_k_nope, w_v, scale)
        else:
            out = decomposed_attention(
                q_nope, q_rope, gather_pages(cache.x, rows.block_table),
                gather_pages(cache.k_rope, rows.block_table),
                w_k_nope, w_v, new_len, scale)
        return out, cache

    if isinstance(cache, PagedCPQKVCache):
        cache = PagedCPQKVCache(
            k=append_cpq_tensor(cache.k, rows, k_t, rt.cpq),
            v=append_cpq_tensor(cache.v, rows, v_t, rt.cpq))
        if fused:
            out = paged_cpq_decode_tpu(
                q, cache.k, cache.v, rows.block_table, new_len, scale)
        else:
            out = core_attn.cpq_chunked_decode_attention(
                q, logical_cpq(cache.k, rows.block_table),
                logical_cpq(cache.v, rows.block_table), new_len, scale)
        return out, cache

    if isinstance(cache, PagedRetrievalCache):
        dp = rt.retrieval.proxy_dim or k_t.shape[-1]
        code_t = ret_lib.encode_proxy(
            k_t[..., :dp], cache.proxy_scale, cache.proxy_zero, rt.retrieval.proxy_bits)
        cache = PagedRetrievalCache(
            k=write_token_pages(cache.k, rows.block_table, rows.lengths,
                                rows.active, k_t[:, 0]),
            v=write_token_pages(cache.v, rows.block_table, rows.lengths,
                                rows.active, v_t[:, 0]),
            proxy=write_token_pages(cache.proxy, rows.block_table, rows.lengths,
                                    rows.active, code_t[:, 0]),
            proxy_scale=cache.proxy_scale, proxy_zero=cache.proxy_zero)
        out = ret_lib.retrieval_attention(
            q, gather_pages(cache.k, rows.block_table),
            gather_pages(cache.v, rows.block_table),
            gather_pages(cache.proxy, rows.block_table),
            cache.proxy_scale, cache.proxy_zero, new_len, rt.retrieval, scale)
        return out, cache

    if isinstance(cache, PagedCPQXCache):
        cache = PagedCPQXCache(
            x=append_cpq_tensor(cache.x, rows, x_t[:, :, None, :], rt.cpq),
            k_rope=(write_token_pages(cache.k_rope, rows.block_table, rows.lengths,
                                      rows.active, k_rope_t[:, 0])
                    if k_rope_t is not None else cache.k_rope))
        out = core_attn.decomposed_cpq_chunked_decode(
            q_nope, q_rope, logical_cpq(cache.x, rows.block_table),
            gather_pages(cache.k_rope, rows.block_table),
            w_k_nope, w_v, new_len, scale)
        return out, cache

    raise TypeError(type(cache))


# ------------------------------------------------------- tier escalation (T2)


def compress_dense_slot(k_log: jax.Array, v_log: jax.Array, length: jax.Array,
                        cfg: CPQCfg) -> kvc.CPQKVCache:
    """Re-compress one slot's gathered dense K/V into CPQ tensors — the
    watermark policy's dense -> T2 migration. Only dense is escalatable
    post-hoc: T1 needs the pre-projection operand X, which a dense cache
    never stored; T2 compresses exactly what is cached.

    k_log/v_log: (1, Npad, KV, Dh) logical views; slots beyond ``length`` are
    replaced by the last valid token so the prefill statistics (prune
    quantile, level-0 range) see only real data."""
    pos = jnp.arange(k_log.shape[1], dtype=jnp.int32)
    last = jnp.clip(length - 1, 0, k_log.shape[1] - 1)

    def valid_only(a):
        edge = jax.lax.dynamic_index_in_dim(a, last, axis=1)  # (1, 1, KV, Dh)
        return jnp.where((pos < length)[None, :, None, None], a, edge)

    kt = cpq_lib.cpq_compress_prefill(valid_only(k_log), cfg, k_log.shape[1])
    vt = cpq_lib.cpq_compress_prefill(valid_only(v_log), cfg, v_log.shape[1])
    return kvc.CPQKVCache(kt, vt, length)
