"""Plain reference of DeepSeek-V2's decoder: multi-head latent attention
(MLA) and a fine-grained mixture of experts, straight jax.numpy over the whole
sequence, no kernels, no cache, no batching. It follows the published
modeling code (DeepSeek-V2, arXiv:2405.04434):

- MLA in its naive form: per-head keys and values expanded from the normed
  512-d latent, one roped 64-d key shared by every head, YaRN rope
  (``rope_scaling``) and the softmax scale ``(nope + rope)^-0.5 *
  mscale(factor, mscale_all_dim)^2``. Rope pairs dimension i with i + R/2:
  the published adjacent pairing after the fixed column permutation the
  configuration file's ``assumed`` states;
- gating: softmax over every routed expert (the router's published width;
  its weight stored (experts, d_model), as published),
  top ``num_experts_per_tok``, renormalised only if ``norm_topk_prob``,
  times ``routed_scaling_factor``; the routed experts held here (the leading
  axis of the expert weights) computed densely over every token, each
  weighted by its gate where chosen; the shared experts; a dense MLP in the
  first ``first_k_dense_replace`` layers; an untied head.

Sizes come from the weights' shapes and the configuration's published keys,
never from the program. Attention runs in query blocks, so that 4096
positions fit.

``precision="f32"`` is the reference: every operand in float32, every
matmul at ``highest``. ``precision="fp8"`` is the control: each weight
matmul's operands rounded to float8_e4m3 with a per-tensor scale for the
weights and a per-row scale for the activations, accumulated in float32, the
step below the bfloat16 the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
Q_BLOCK = 512


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    """x (..., k) @ w (k, n), both float32."""
    if fp8:
        x = _q8(x, -1)
        w = _q8(w, None)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps: float):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _glu(h, p, fp8: bool):
    return _mm(jax.nn.silu(_mm(h, p["w_gate"], fp8)) * _mm(h, p["w_up"], fp8),
               p["w_down"], fp8)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _inv_freq(dim: int, theta: float, yarn) -> jax.Array:
    """Rotary frequencies; with YaRN, interpolated by ``factor`` above the
    correction range, extrapolated below it, a linear ramp across."""
    freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if yarn is None:
        return freq
    factor, orig, fast, slow = yarn[:4]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(fast)), 0)
    high = min(math.ceil(corr(slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    extra = 1.0 - ramp
    return freq / factor * (1.0 - extra) + freq * extra


def _rope(x, pos, theta: float, yarn):
    """x (L, ..., R): rotate the two halves of the last axis."""
    dh = x.shape[-1]
    ang = pos[:, None].astype(jnp.float32) * _inv_freq(dh, theta, yarn)[None]
    mag = 1.0 if yarn is None else _yarn_mscale(yarn[0], yarn[4]) / _yarn_mscale(yarn[0], yarn[5])
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (dh,)
    cos = (jnp.concatenate([jnp.cos(ang)] * 2, -1) * mag).reshape(shape)
    sin = (jnp.concatenate([jnp.sin(ang)] * 2, -1) * mag).reshape(shape)
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def switches(conf: dict) -> tuple:
    """The hashable facts of the architecture the forward pass needs."""
    rs = conf.get("rope_scaling")
    yarn = None if not rs else (
        float(rs["factor"]), float(rs["original_max_position_embeddings"]),
        float(rs["beta_fast"]), float(rs["beta_slow"]), float(rs["mscale"]),
        float(rs["mscale_all_dim"]))
    return (conf["num_attention_heads"], float(conf["rms_norm_eps"]),
            float(conf["rope_theta"]), yarn, conf["num_experts_per_tok"],
            bool(conf["norm_topk_prob"]), float(conf["routed_scaling_factor"]),
            conf["scoring_func"], conf["topk_method"])


def _attention(q, k, v, scale: float):
    """Causal softmax attention, q/k (L, H, Dqk), v (L, H, Dv), in blocks of
    Q_BLOCK queries."""
    L = q.shape[0]
    nq = -(-L // Q_BLOCK)
    qp = jnp.pad(q, ((0, nq * Q_BLOCK - L), (0, 0), (0, 0)))
    kpos = jnp.arange(L)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * Q_BLOCK, Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where((qpos[:, None] >= kpos[None, :])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, precision=HI)

    o = jax.lax.map(block, jnp.arange(nq))
    return o.reshape(nq * Q_BLOCK, *o.shape[2:])[:L]


def _mla(x, a, pos, arch, fp8: bool):
    H, eps, theta, yarn = arch[:4]
    L = x.shape[0]
    Dc, Dn = a["wuk"].shape[1:]
    Dv = a["wuv"].shape[2]
    Dr = a["wdkv"].shape[1] - Dc
    q = _mm(x, a["wq"], fp8).reshape(L, H, Dn + Dr)
    kv = _mm(x, a["wdkv"], fp8)
    c = _rms(kv[:, :Dc], a["kv_norm"], eps)
    k_rope = _rope(kv[:, None, Dc:], pos, theta, yarn)            # (L, 1, Dr)
    q_rope = _rope(q[..., Dn:], pos, theta, yarn)
    # per-head keys and values expanded from the latent: c @ W_UK[h], c @ W_UV[h]
    wuk = a["wuk"].transpose(1, 0, 2).reshape(Dc, H * Dn)
    wuv = a["wuv"].transpose(1, 0, 2).reshape(Dc, H * Dv)
    k_nope = _mm(c, wuk, fp8).reshape(L, H, Dn)
    v = _mm(c, wuv, fp8).reshape(L, H, Dv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (L, H, Dr))], -1)
    qf = jnp.concatenate([q[..., :Dn], q_rope], -1)
    scale = (Dn + Dr) ** -0.5
    if yarn is not None and yarn[5]:
        scale *= _yarn_mscale(yarn[0], yarn[5]) ** 2
    o = _attention(qf, k, v, scale)
    return _mm(o.reshape(L, H * Dv), a["wo"], fp8)


def _moe(h, m, arch, fp8: bool):
    top_k, norm_topk, routed_scale, scoring, method = arch[4:]
    assert scoring == "softmax" and method == "greedy", (scoring, method)
    probs = jax.nn.softmax(_mm(h, m["router"].T, fp8), axis=-1)   # (L, E)
    top_w, top_e = jax.lax.top_k(probs, top_k)
    if norm_topk:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    top_w = top_w * routed_scale

    def expert(out, e):
        gate = jnp.sum(jnp.where(top_e == e, top_w, 0.0), -1)     # (L,)
        w = {k: m[k][e] for k in ("w_gate", "w_up", "w_down")}
        return out + gate[:, None] * _glu(h, w, fp8), None

    held = m["w_gate"].shape[0]                 # routed experts 0 .. held-1
    out, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(held))
    if "shared" in m:
        out = out + _glu(h, m["shared"], fp8)
    return out


@functools.partial(jax.jit, static_argnames=("arch", "precision"))
def logits_at(params, tokens, read, *, arch: tuple, precision: str):
    """Logits (P, V) float32 at positions ``read`` (P,) of the causal forward
    pass over ``tokens`` (L,). Positions past the true length only see
    padding that the causal mask keeps out of the positions read."""
    fp8 = precision == "fp8"
    eps = arch[1]
    pos = jnp.arange(tokens.shape[0])
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)  # noqa: E731
    x = params["embed"]["tok"][tokens].astype(jnp.float32)

    def layer(x, p, moe: bool):
        p = f32(p)
        x = x + _mla(_rms(x, p["norm1"]["scale"], eps), p["mixer"], pos, arch, fp8)
        h = _rms(x, p["norm2"]["scale"], eps)
        return x + (_moe(h, p["mlp"], arch, fp8) if moe else _glu(h, p["mlp"], fp8))

    for p in params["prefix"]:                  # the leading dense layers
        x = layer(x, p, moe=False)
    x, _ = jax.lax.scan(lambda x, p: (layer(x, p, moe=True), None), x,
                        params["blocks"][0])
    x = _rms(x[read], params["final_norm"]["scale"].astype(jnp.float32), eps)
    return _mm(x, params["embed"]["lm_head"].astype(jnp.float32), fp8)
