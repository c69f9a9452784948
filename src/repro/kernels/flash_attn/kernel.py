"""Flash attention forward, Pallas TPU.

Grid: (B, H, nq, nk) — nk is the innermost (sequential on-core) axis, so the
online-softmax state for one (b, h, iq) lives in VMEM scratch across the nk
sweep; the (T x S) score matrix never exists. Tiles are MXU-aligned
(block_q x head_dim and block_k x head_dim, head_dim a multiple of 128 on the
lane axis is ideal; 64 also maps cleanly on v5e).

Causal blocks that are fully masked are skipped with pl.when (no MXU work).
GQA: the kv-head index for query head h is h // (H // KV), computed in the
BlockSpec index_map so K/V tiles are fetched per kv head.

Paged kernels (``paged_flash_decode`` / ``paged_flash_prefill``) read one
layer of a stacked lane-dense arena (L, P, page, KV*Dh) through a
scalar-prefetched block table. A grid step takes k pages
(``_pages_per_step``: the most of 16, 8, 4, 2, 1 dividing the table's
width) as one (k*page, KV*Dh) tile in VMEM. Decode DMAs them itself, a
live step ahead into a double buffer, and skips steps past a row's length
outright; it scores every head in one NT matmul against a block-diagonal
(H, KV*Dh) query (row h holds head h in the lanes of kv head h // g) and
weights V in one (H, k*page) x (k*page, KV*Dv) matmul, whatever the group
size g. Prefill takes each page as its own block operand, stacked in VMEM,
and loops over kv heads on the k-page-wide tile. q, K and V reach the MXU
in the arena's dtype; scores, softmax state, p and the accumulator are
f32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decomposed_attn.kernel import (_nt, _online_update,
                                                  _page_specs, _pages_per_step)

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            scale: float, causal: bool, block_q: int, block_k: int,
            nk: int, seq_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q_start = iq * block_q
    k_start = ik * block_k
    # skip fully-masked causal blocks (first row of q tile vs last k row)
    live = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, :, 0, :]                      # (bq, D)
        k = k_ref[0, :, 0, :]                      # (bk, D)
        v = v_ref[0, :, 0, :]                      # (bk, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < seq_k
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = mask & (kpos <= qpos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0, :, 0, :] = (
            acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


def online_update(s, v, m_sc, l_sc, acc_sc, h):
    """One online-softmax step for kv head ``h``: fold scores ``s`` (rows,
    n) and values ``v`` (n, Dv) into the f32 scratch state of that head."""
    m_prev = m_sc[h]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_sc[h] = l_sc[h] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_sc[h] = m_new
    acc_sc[h] = acc_sc[h] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _stacked(refs):
    """The k page blocks of one grid step stacked in VMEM into one
    (k*page, lanes) tile: row i is position ``ib*k*page + i`` of the row."""
    return jnp.concatenate([r[0, 0] for r in refs], axis=0)


def _paged_decode_kernel(bt_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm,
                         o_ref, k_buf, v_buf, sem, done, m_sc, l_sc, acc_sc,
                         *, scale: float, page: int, nb: int, k: int,
                         group: int, v_dim: int):
    """One (b, ib) step: pages bt[b, ib*k .. ib*k+k-1] of layer ``layer``
    of the stacked lane-dense arena, DMA'd by the kernel itself from the
    arena (left in HBM) into one (k*page, KV*Dh) tile of a double buffer,
    page j at rows [j*page, (j+1)*page).

    Only live steps (first position < the row's length) fetch and compute,
    in grid order, so a step past the row's end costs nothing. Each live
    step starts the DMAs of the next live step, (b, ib+1) or else (b+1, 0),
    into the other buffer before it waits on its own; ``done`` counts the
    live steps so far, and its parity names the buffer. A step nobody
    prefetched (the first live step of a row after an empty row, or of row
    0) starts its own DMAs.

    The query arrives block-diagonal, (H, KV*Dh): row h holds query head h
    in the lanes of its kv head h // group, zeros elsewhere. One NT matmul
    then scores every head over the k pages, (H, k*page) lane-dense, and one
    matmul weights the values, (H, KV*Dv); head h keeps the lanes of its kv
    head in the finish step. Softmax state is carried online in f32 scratch
    across the block-table sweep."""
    b = pl.program_id(0)
    ib = pl.program_id(1)
    n_rows = pl.num_programs(0)
    span = k * page
    first = ib * span

    def copies(row, step, slot):
        """The 2k DMAs of step ``step`` of row ``row`` into buffer ``slot``:
        its K and V pages of the layer."""
        out = []
        for j in range(k):
            pg = bt_ref[row, step * k + j]
            dst = pl.ds(j * page, page)
            out.append(pltpu.make_async_copy(
                k_hbm.at[layer_ref[0], pg], k_buf.at[slot, dst],
                sem.at[0, slot]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[layer_ref[0], pg], v_buf.at[slot, dst],
                sem.at[1, slot]))
        return out

    def start(row, step, slot):
        for c in copies(row, step, slot):
            c.start()

    @pl.when((b == 0) & (ib == 0))
    def _first():
        done[0] = 0

    @pl.when(ib == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # steps whose pages all sit past the row's length would read the null
    # page (garbage contents by convention): no DMA, no MXU work
    @pl.when(first < len_ref[b])
    def _compute():
        slot = done[0] % 2
        prev = jnp.maximum(b - 1, 0)
        prefetched = (ib > 0) | ((b > 0) & (len_ref[prev] > 0))
        # row b goes on (and the table does: no entry past it is read)
        more = (first + span < len_ref[b]) & (ib + 1 < nb // k)
        nxt = jnp.minimum(b + 1, n_rows - 1)
        next_row = (jnp.logical_not(more) & (b + 1 < n_rows)
                    & (len_ref[nxt] > 0))

        @pl.when(jnp.logical_not(prefetched))
        def _own():
            start(b, ib, slot)

        @pl.when(more)
        def _next_step():
            start(b, ib + 1, 1 - slot)

        @pl.when(next_row)
        def _next_row():
            start(nxt, 0, 1 - slot)

        for c in copies(b, ib, slot):
            c.wait()
        done[0] += 1

        q = q_ref[0]                                       # (H, KV*Dh)
        s = _nt(q, k_buf[slot].astype(q.dtype)) * scale    # (H, k*page)
        # null-page / partial-last-page masking: position vs row length
        pos = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _online_update(m_sc, l_sc, acc_sc,
                       jnp.where(pos < len_ref[b], s, NEG_INF),
                       v_buf[slot].astype(jnp.float32))

    @pl.when(ib == nb // k - 1)
    def _finish():
        o = acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)    # (H, KV*Dv)
        for h in range(o.shape[0] // group):               # static: kv head
            rows = slice(h * group, (h + 1) * group)
            o_ref[0, rows] = o[rows, h * v_dim:(h + 1) * v_dim].astype(
                o_ref.dtype)


def _paged_prefill_kernel(bt_ref, lens_ref, layer_ref, q_ref, *refs,
                          scale: float, page: int, nb: int, k: int,
                          group: int, head_dim: int, v_dim: int):
    """One ib step of the Q-chunk>1 paged prefill sweep: queries are the
    admission chunk's C tokens (flattened (C*G) rows per kv head); pages
    bt[ib*k .. ib*k+k-1] of layer ``layer`` of the stacked lane-dense arena
    arrive one block operand each and are stacked into one (k*page, KV*Dh)
    tile, whose lanes [h*Dh, (h+1)*Dh) are kv head h's keys. lens holds
    (offset, total): ``offset`` tokens preceded this chunk, ``total`` =
    offset + valid masks the chunk's jit padding. Causal masking is per
    query ROW: row r is chunk token r // G at absolute position
    offset + r // G."""
    k_refs, v_refs = refs[:k], refs[k:2 * k]
    o_ref, m_sc, l_sc, acc_sc = refs[2 * k:]
    ib = pl.program_id(0)
    first = ib * k * page

    @pl.when(ib == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # steps whose pages all sit past the row's post-chunk length: skip
    @pl.when(first < lens_ref[1])
    def _compute():
        keys, vals = _stacked(k_refs), _stacked(v_refs)
        shape = (q_ref.shape[1], keys.shape[0])            # (C*G, k*page)
        pos = first + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        qtok = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // group
        ok = (pos < lens_ref[1]) & (pos <= lens_ref[0] + qtok)  # causal
        for h in range(m_sc.shape[0]):                     # static: kv head
            q = q_ref[h]                                   # (C*G, Dh)
            s = _nt(q, keys[:, h * head_dim:(h + 1) * head_dim].astype(
                q.dtype)) * scale
            online_update(jnp.where(ok, s, NEG_INF),
                          vals[:, h * v_dim:(h + 1) * v_dim].astype(
                              jnp.float32), m_sc, l_sc, acc_sc, h)

    @pl.when(ib == nb // k - 1)
    def _finish():
        o_ref[...] = (
            acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


def _layer_index(layer) -> jax.Array:
    """The stacked arena's layer to read, as a (1,) int32 scalar-prefetch
    operand."""
    return jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))


def paged_flash_prefill_fwd(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                            block_row: jax.Array, offset: jax.Array,
                            valid: jax.Array, layer: jax.Array, *, scale: float,
                            interpret: bool = True) -> jax.Array:
    """Chunked paged prefill attention for ONE request slot: the chunk's C
    queries attend over the slot's pages [0, offset + valid) — the chunk's own
    K/V were just written into those pages, so no contiguous scratch cache
    exists. Same scalar-prefetch construction as the decode kernel, k pages
    a grid step (``_pages_per_step``), with a per-query-row causal mask
    (query i sits at absolute position offset + i).

    q: (1, C, H, Dh); k_pages/v_pages: (L, P, page, KV*Dh|KV*Dv) stacked
    lane-dense pools (head h at lanes [h*Dh, (h+1)*Dh)), read at ``layer``;
    block_row: (max_blocks,) int32 (0 = null page); offset/valid/layer: ()
    int32 — tokens already in the slot before this chunk / real tokens in
    this chunk (the tail up to C is jit padding whose output is garbage) /
    the layer of the stack. Returns (1, C, H, Dv)."""
    _, C, H, Dh = q.shape
    page = k_pages.shape[2]
    KV = k_pages.shape[3] // Dh
    Dv = v_pages.shape[3] // KV
    g = H // KV
    nb = block_row.shape[0]
    k = _pages_per_step(nb)
    # (KV, C*G, Dh), token-major rows within each kv head: row r = token r // g
    qg = q[0].reshape(C, KV, g, Dh).transpose(1, 0, 2, 3).reshape(KV, C * g, Dh)
    qg = qg.astype(jnp.promote_types(q.dtype, k_pages.dtype))
    lens = jnp.stack([offset, offset + valid]).astype(jnp.int32)

    kern = functools.partial(_paged_prefill_kernel, scale=scale, page=page,
                             nb=nb, k=k, group=g, head_dim=Dh, v_dim=Dv)
    # the default scoped VMEM (16 MiB) of a one-page step, plus the k - 1
    # more double-buffered K and V pages a k-page step holds
    wider = 2 * (k - 1) * page * KV * (Dh + Dv) * k_pages.dtype.itemsize
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # block_row, (offset, total), layer
            grid=(nb // k,),        # sweeps the slot's block-table entries
            in_specs=[
                pl.BlockSpec((KV, C * g, Dh), lambda ib, bt, ln, ly: (0, 0, 0)),
            ] + _page_specs(k, page, KV * Dh, page, KV * Dv,
                            lambda j, ib, bt, ln, ly: bt[ib * k + j]),
            out_specs=pl.BlockSpec((KV, C * g, Dv),
                                   lambda ib, bt, ln, ly: (0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((KV, C * g, 1), jnp.float32),
                pltpu.VMEM((KV, C * g, 1), jnp.float32),
                pltpu.VMEM((KV, C * g, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((KV, C * g, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=(16 << 20) + wider),
        name="paged_flash_prefill",
        interpret=interpret,
    )(block_row.astype(jnp.int32), lens, _layer_index(layer), qg,
      *([k_pages] * k + [v_pages] * k))
    return out.reshape(KV, C, g, Dv).transpose(1, 0, 2, 3).reshape(1, C, H, Dv)


def paged_flash_decode_fwd(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                           block_table: jax.Array, lengths: jax.Array,
                           layer: jax.Array, *, scale: float,
                           interpret: bool = True) -> jax.Array:
    """Paged single-token flash decode: grid step (b, ib) reads the k pages
    bt[b, ib*k .. ib*k+k-1] of layer ``layer`` (``_pages_per_step``), which
    the kernel DMAs from the stacked arena into VMEM one step ahead
    (scalar-prefetched block table and layer) — neither the layer's pool nor
    the contiguous logical K/V view is ever materialized — and scores every
    head over them in one matmul against the block-diagonal query
    (``_paged_decode_kernel``).

    q: (B, 1, H, Dh); k_pages/v_pages: (L, P, page, KV*Dh|KV*Dv) stacked
    lane-dense pools (head h at lanes [h*Dh, (h+1)*Dh)); block_table:
    (B, max_blocks) int32, 0 = unmapped (null page); lengths: (B,) int32
    valid tokens per row; layer: () int32. Returns (B, 1, H, Dv).

    Masking convention (shared with serving/paged_cache.py): positions >=
    lengths[b] — including every slot of an unmapped/null page and the tail of
    a partial last page — contribute nothing; a row with lengths[b] == 0
    returns zeros."""
    B, _, H, Dh = q.shape
    page = k_pages.shape[2]
    KV = k_pages.shape[3] // Dh
    Dv = v_pages.shape[3] // KV
    g = H // KV
    nb = block_table.shape[1]
    k = _pages_per_step(nb)
    # block-diagonal query (B, H, KV*Dh): head h in kv head h // g's lanes
    eye = jnp.eye(KV, dtype=jnp.promote_types(q.dtype, k_pages.dtype))
    qb = (eye[None, :, None, :, None]
          * q[:, 0].reshape(B, KV, g, 1, Dh)).reshape(B, H, KV * Dh)

    kern = functools.partial(_paged_decode_kernel, scale=scale, page=page,
                             nb=nb, k=k, group=g, v_dim=Dv)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # block_table, lengths, layer
            grid=(B, nb // k),      # innermost axis sweeps block-table entries
            in_specs=[
                pl.BlockSpec((1, H, KV * Dh),
                             lambda b, ib, bt, ln, ly: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),   # the arena stays in
                pl.BlockSpec(memory_space=pl.ANY),   # HBM: DMA'd by hand
            ],
            out_specs=pl.BlockSpec((1, H, Dv),
                                   lambda b, ib, bt, ln, ly: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, k * page, KV * Dh), k_pages.dtype),
                pltpu.VMEM((2, k * page, KV * Dv), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),   # (K | V, buffer)
                pltpu.SMEM((1,), jnp.int32),       # live steps so far
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, KV * Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Dv), q.dtype),
        # a step prefetches the next row's first step: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="paged_flash_decode",
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      _layer_index(layer), qb, k_pages, v_pages)
    return out.reshape(B, 1, H, Dv)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        scale: float, causal: bool = True,
                        block_q: int = 512, block_k: int = 512,
                        interpret: bool = True) -> jax.Array:
    """q: (B, T, H, D), k/v: (B, S, KV, D/Dv) -> (B, T, H, Dv)."""
    B, T, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    g = H // KV
    bq = min(block_q, T)
    bk = min(block_k, S)
    pad_q = (-T) % bq
    pad_k = (-S) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq = (T + pad_q) // bq
    nk = (S + pad_k) // bk

    grid = (B, H, nq, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, block_q=bq,
                          block_k=bk, nk=nk, seq_k=S),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1, D), lambda b, h, iq, ik: (b, iq, h, 0)),
            pl.BlockSpec((1, bk, 1, D), lambda b, h, iq, ik: (b, ik, h // g, 0)),
            pl.BlockSpec((1, bk, 1, Dv), lambda b, h, iq, ik: (b, ik, h // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, Dv), lambda b, h, iq, ik: (b, iq, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T + pad_q, H, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
    return out[:, :T]
