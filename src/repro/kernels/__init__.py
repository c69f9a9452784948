"""Pallas TPU kernels for the paper's compute hot spots.

  flash_attn/        baseline dense flash attention (train/prefill) +
                     ``paged_flash_decode_*``: paged single-token decode over
                     a (P, page, KV, Dh) arena
  decomposed_attn/   T1: fused two-stage (Q W_K^T) X^T decode attention —
                     the sub-matrix pipeline realized as one VMEM-resident
                     streaming kernel over the X cache +
                     ``paged_decomposed_decode_*``: same sweep over X pages
                     (covers the MLA latent cache: shared-rope kv_r == 1)
  cpq_dequant_attn/  T2: decode attention directly over int8 CPQ codes with
                     in-register HQE dequantization (HBM moves only codes) +
                     ``paged_cpq_decode_*``: code/level pages + per-slot HQE
                     side state
  topk_retrieval/    T3: int8 proxy-similarity scoring (the CAM analogue)

Each directory: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit wrapper
through ``platform_call``), ref.py (pure-jnp oracle).

Paged decode entry points (serving/paged_cache.py arenas)
---------------------------------------------------------
The ``paged_*`` kernels take ``(pages, block_table, lengths)`` directly: the
block table is a scalar-prefetch operand, so each grid step's BlockSpec index
map resolves ``block_table[b, ib]`` and DMAs that PHYSICAL page, every kv
head of it, from the arena into VMEM — the contiguous logical view the jnp
gather path materializes never exists. Masking convention (shared with
serving/paged_cache.py): block-table entry 0 is the reserved null page whose
contents are garbage by design; every position >= lengths[b] — all slots of
an unmapped/null page and the tail of a partial last page — is masked to
-inf before the online softmax, pages wholly past lengths[b] are skipped
without issuing MXU work, and a row with lengths[b] == 0 returns zeros.
``ops.py`` wrappers select the engine-facing defaults; the serving dispatch
(``decode_attend_paged``) routes dense, CPQ, and X/MLA tiers through them
when ``AttentionRuntime.paged_kernels`` is set (retrieval T3 keeps the
gather for its top-k slot selection).

Paged prefill entry points (chunked admission)
----------------------------------------------
The ``paged_*_prefill_*`` variants generalize the decode kernels to
Q-chunk>1: the C queries of one admission chunk sweep ONE slot's
block-table row with an additional per-query-row causal mask (query i sits
at absolute position ``offset + i``; positions past ``offset + valid`` are
the chunk's jit padding). The chunk's own payload is written into the pages
first, so the same sweep serves intra-chunk causal attention — serving
admission never materializes a contiguous scratch cache. The CPQ variant
adds one extra grid step that attends the chunk's RAW roped K/V causally
(earlier pages are dequantized in VMEM, reading exactly what decode reads).
``chunk_attend_paged`` in serving/paged_cache.py is the dispatch.

Interpret mode
--------------
Kernels TARGET TPU v5e (VMEM-resident accumulators, page blocks whose last
two dims are whole array dims so Mosaic's (8, 128) tiling accepts them) and
are VALIDATED in interpret mode on CPU. The mode is not an option: every
``ops.py`` wrapper goes through ``platform_call``, which stages both
variants and lets lowering pick the one for the platform the arrays live
on — interpreted on the CPU backend, compiled Mosaic on a TPU. A per-call
``interpret=`` argument still wins (tests pass it). Nothing here touches a
backend at import time. Interpret mode checks semantics, not speed.
"""
import functools

import jax


def platform_call(fwd, *args, interpret: bool | None = None, **static):
    """Call a kernel forward ``fwd(*args, interpret=..., **static)``.

    ``interpret=None`` defers the choice to lowering
    (``jax.lax.platform_dependent``): the CPU lowering runs the kernel in
    the Pallas interpreter, every other platform compiles it. ``args`` are
    arrays (or pytrees of arrays); hashable options go in ``static``."""
    if interpret is not None:
        return fwd(*args, interpret=interpret, **static)
    return jax.lax.platform_dependent(
        *args,
        cpu=functools.partial(fwd, interpret=True, **static),
        default=functools.partial(fwd, interpret=False, **static))
