"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

Scope: forward pipeline for the scanned decoder stack — the deployment case
where a deep model's layers are split across pods and DCN bandwidth makes
cross-pod FSDP gathers unattractive (serving, or as a stage within other
schedules). Training in this framework uses DP/FSDP/TP (+ the compressed
cross-pod gradient path in optim/compression.py); wiring a full backward
pipeline schedule (1F1B) is future work and noted in DESIGN.md.

Schedule: M microbatches, S stages, T = M + S - 1 ticks; at tick t stage s
works on microbatch t - s. Each tick overlaps compute with a single
ppermute hop of activations to the next stage. Bubble fraction is
(S - 1) / T — reported by ``bubble_fraction`` and benchmarked in
benchmarks/bench_pipeline.py alongside the paper's sub-matrix analysis.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def gpipe_forward(mesh: Mesh, axis: str, block_fn):
    """Build a pipelined forward over ``axis``.

    block_fn(params_block, x) -> x applies ONE block; each stage scans it
    over its local slice of the stacked block params.

    Returns fn(stacked_params, x_mb) where stacked_params leaves have leading
    dim num_blocks (sharded over ``axis``) and x_mb is (M, mb, ...) input
    microbatches (replicated). Output: (M, mb, ...) after ALL blocks.
    """
    n_stage = mesh.shape[axis]

    def stage_apply(params_loc, x):
        def body(h, p_one):
            return block_fn(p_one, h), None
        h, _ = jax.lax.scan(body, x, params_loc)
        return h

    def inner(params_loc, x_mb):
        stage = jax.lax.axis_index(axis)
        M = x_mb.shape[0]
        T = M + n_stage - 1
        fwd_perm = [(i, i + 1) for i in range(n_stage - 1)]

        def tick(carry, t):
            buf, outs = carry
            mb = t - stage
            active = (mb >= 0) & (mb < M)
            mb_c = jnp.clip(mb, 0, M - 1)
            x_in = jnp.where(stage == 0, x_mb[mb_c], buf)
            y = stage_apply(params_loc, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            is_last = stage == n_stage - 1
            outs = jnp.where(active & is_last, outs.at[mb_c].set(y), outs)
            buf_next = jax.lax.ppermute(y, axis, fwd_perm)
            return (buf_next, outs), None

        # initial carries must be marked pod-varying for shard_map's vma check
        buf0 = jax.lax.pcast(jnp.zeros_like(x_mb[0]), (axis,), to="varying")
        outs0 = jax.lax.pcast(jnp.zeros_like(x_mb), (axis,), to="varying")
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(T, dtype=jnp.int32))
        # outputs live on the last stage only (zeros elsewhere); replicate
        return jax.lax.psum(outs, axis)

    def fn(stacked_params, x_mb):
        in_specs = (jax.tree.map(lambda _: P(axis), stacked_params), P())
        return shard_map(inner, mesh=mesh, in_specs=in_specs, out_specs=P())(
            stacked_params, x_mb)

    return fn
