"""Mixture-of-Experts layer: shared + routed experts, softmax top-k routing.

DeepSeek-style fine-grained MoE: ``num_shared`` always-on experts (fused into
one dense GLU of width num_shared*d_ff_expert) + ``num_experts`` routed,
``top_k`` active; whether the top-k weights are renormalised is the
config's (``MoECfg.norm_topk``).

Two dispatches over the same weights:

- ``serve_moe`` (every serving path): dropless and independent of the batch.
  The router scores all ``num_experts``; this chip computes only the experts
  it holds (``ModelConfig.experts_held``, expert parallelism), for exactly
  the (token, expert) pairs routed to them: pairs sorted by expert, one
  grouped matmul per weight (``lax.ragged_dot``), each held expert's weights
  read once per call. A token's output does not depend on the other tokens
  of the call, so chunked prefill, decode and one-shot prefill agree.
- ``apply_moe`` (training): GShard capacity dispatch with the switch-style
  load-balance loss; it drops tokens at capacity and needs every expert.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common.param import ParamDef
from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain


def held_experts(cfg: ModelConfig) -> int:
    """Routed experts whose weights live here (the first of them)."""
    return cfg.experts_held or cfg.moe.num_experts


def moe_defs(cfg: ModelConfig):
    m = cfg.moe
    d, dt = cfg.d_model, cfg.param_dtype
    E, F = held_experts(cfg), m.d_ff_expert
    p = {
        # (experts, d_model), as DeepSeek publishes its gate weight
        "router": ParamDef((m.num_experts, d), jnp.float32, (None, "embed"),
                           init="fan_in", fan_in_dim=1),
        "w_gate": ParamDef((E, d, F), dt, ("experts", "embed", "expert_mlp"), init="fan_in"),
        "w_up": ParamDef((E, d, F), dt, ("experts", "embed", "expert_mlp"), init="fan_in"),
        "w_down": ParamDef((E, F, d), dt, ("experts", "expert_mlp", "embed"), init="fan_in"),
    }
    if m.num_shared > 0:
        FS = m.num_shared * F
        p["shared"] = {
            "w_gate": ParamDef((d, FS), dt, ("embed", "mlp"), init="fan_in"),
            "w_up": ParamDef((d, FS), dt, ("embed", "mlp"), init="fan_in"),
            "w_down": ParamDef((FS, d), dt, ("mlp", "embed"), init="fan_in"),
        }
    return p


def _glu(x, wg, wu, wd):
    h = jax.nn.silu(x @ wg) * (x @ wu)
    return h @ wd


def route(cfg: ModelConfig, p, xf: jax.Array):
    """Softmax gate over all ``num_experts``: xf (S, D) -> (probs (S, E) f32,
    top-k weights (S, K) f32, top-k expert ids (S, K) int32). The router's
    matmul runs at full f32 precision (it is tiny, and a near-tie decides
    which experts a token reaches)."""
    m = cfg.moe
    logits = jnp.matmul(xf.astype(jnp.float32), p["router"].T,
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk:
        top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    return probs, top_w, top_e


def serve_moe(cfg: ModelConfig, p, x: jax.Array) -> jax.Array:
    """Dropless expert layer for serving. x: (B, T, D) -> (B, T, D).

    ``p`` holds routed experts ``0 .. n - 1`` (n = the leading axis of its
    expert weights) and the shared experts; the output is this share's part
    of the routed sum plus the shared experts. What experts held
    elsewhere add is left out: summed over every share, with the shared
    experts counted once, the parts give the whole layer.

    Profiler scopes: ``moe.router``, ``moe.dispatch`` (sort, gather and
    un-sort), ``moe.experts`` (the grouped matmuls), ``moe.shared``."""
    m = cfg.moe
    B, T, D = x.shape
    S, K = B * T, m.top_k
    n = p["w_gate"].shape[0]
    xf = x.reshape(S, D)
    with jax.named_scope("moe.router"):
        _, top_w, top_e = route(cfg, p, xf)
    with jax.named_scope("moe.dispatch"):
        expert = top_e.reshape(-1)                        # (S*K,) pair -> expert
        here = expert < n
        key = jnp.where(here, expert, n)                  # pairs held elsewhere last
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(key[None, :] == jnp.arange(n, dtype=key.dtype)[:, None],
                        axis=1, dtype=jnp.int32)
        xs = jnp.take(xf, order // K, axis=0)             # (S*K, D), by expert
    with jax.named_scope("moe.experts"):
        f32 = jnp.float32
        g = jax.lax.ragged_dot(xs, p["w_gate"], sizes, preferred_element_type=f32)
        u = jax.lax.ragged_dot(xs, p["w_up"], sizes, preferred_element_type=f32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        y = jax.lax.ragged_dot(h, p["w_down"], sizes, preferred_element_type=f32)
    with jax.named_scope("moe.dispatch"):
        # rows past the held pairs belong to no group: select, never
        # multiply, so whatever they hold cannot reach the sum
        y = jnp.where(jnp.take(here, order)[:, None], y, 0.0)
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(S * K, dtype=order.dtype))
        y = jnp.take(y, back, axis=0).reshape(S, K, D)    # un-sort: (token, choice)
        out = jnp.sum(y * top_w[:, :, None], axis=1)
    if m.num_shared > 0:
        with jax.named_scope("moe.shared"):
            sp = p["shared"]
            out = out + _glu(xf, sp["w_gate"], sp["w_up"], sp["w_down"]).astype(f32)
    return out.astype(x.dtype).reshape(B, T, D)


GROUP = 1024  # tokens per dispatch group (GShard-style); groups ride the batch sharding


def apply_moe(cfg: ModelConfig, p, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x: (B, T, D) -> (out, aux_loss).

    GShard-style grouped one-hot dispatch: tokens are split into groups of
    GROUP (the group axis inherits the data sharding); capacity
    C = cf * GROUP * k / E per (group, expert). Dispatch/combine are einsums
    (no scatter), so XLA SPMD turns the (group-sharded) -> (expert-sharded)
    boundary into an all_to_all instead of replicating buffers.
    """
    m = cfg.moe
    B, T, D = x.shape
    S = B * T
    E, K = m.num_experts, m.top_k
    if held_experts(cfg) != E:
        raise ValueError("the training dispatch needs every routed expert; "
                         f"this config holds {held_experts(cfg)} of {E}")

    g = min(GROUP, S)
    pad = (-S) % g
    xf = x.reshape(S, D)
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    G = xf.shape[0] // g
    xg = xf.reshape(G, g, D)
    xg = constrain(xg, "act_batch", None, None)
    C = max(int(m.capacity_factor * g * K / E), 4)
    C = min(C, g)

    probs, top_w, top_e = route(cfg, p, xg.reshape(-1, D))
    probs, top_w, top_e = (a.reshape(G, g, -1) for a in (probs, top_w, top_e))

    # switch-style load-balance aux loss
    oh_all = jax.nn.one_hot(top_e, E, dtype=jnp.float32)   # (G, g, K, E)
    aux = E * jnp.sum(jnp.mean(jnp.sum(oh_all, 2), axis=(0, 1)) *
                      jnp.mean(probs, axis=(0, 1))) / K

    # position of each (token, choice) within its expert, FIFO over (g*K)
    ohf = oh_all.reshape(G, g * K, E)
    pos = jnp.cumsum(ohf, axis=1) - 1.0                    # (G, g*K, E)
    pos_choice = jnp.sum(pos * ohf, axis=-1).reshape(G, g, K)

    combine = jnp.zeros((G, g, E, C), jnp.float32)
    for j in range(K):
        keep = (pos_choice[:, :, j] < C)
        w = jnp.where(keep, top_w[:, :, j], 0.0)
        oh_e = oh_all[:, :, j]                             # (G, g, E)
        oh_c = jax.nn.one_hot(pos_choice[:, :, j], C, dtype=jnp.float32)
        combine = combine + (w[..., None] * oh_e)[..., None] * oh_c[:, :, None, :]
    dispatch = (combine > 0).astype(x.dtype)               # (G, g, E, C)

    ein = jnp.einsum("GgEC,Ggd->GECd", dispatch, xg)
    ein = constrain(ein, "act_batch", "act_experts", None, None)
    h = jax.nn.silu(jnp.einsum("GECd,Edf->GECf", ein, p["w_gate"]))
    h = h * jnp.einsum("GECd,Edf->GECf", ein, p["w_up"])
    h = constrain(h, "act_batch", "act_experts", None, "act_expert_mlp")
    eout = jnp.einsum("GECf,Efd->GECd", h, p["w_down"])
    out = jnp.einsum("GgEC,GECd->Ggd", combine.astype(x.dtype), eout)

    out = out.reshape(-1, D)
    if pad:
        out = out[:S]
    if m.num_shared > 0:
        sp = p["shared"]
        out = out + _glu(xf[:S] if pad else xf, sp["w_gate"], sp["w_up"], sp["w_down"])
    return out.reshape(B, T, D), aux
