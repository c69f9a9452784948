"""Plain reference of a pre-norm decoder-only transformer, the family both
configurations belong to: straightforward jax.numpy over the whole sequence,
no kernels, no cache, no batching. Its switches come from the
configuration file's published keys (norm, positions, MLP, biases), not from
the program. Weights are the benchmark's seeded ones, read by the tree keys
the program stores them under.

``precision="f32"`` is the reference: every operand in float32, every
matmul at ``highest``. ``precision="fp8"`` is the control: each matmul's
operands rounded to float8_e4m3 with a per-tensor scale for weights and a
per-row scale for activations, accumulated in float32, the step below the
bfloat16 the configurations state.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


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


def _norm(x, p, kind: str, eps: float):
    if kind == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def _rope(x, pos, theta: float):
    """x (L, H, Dh); rotate the two halves of each head (GPT-NeoX pairing)."""
    dh = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _sinusoid(pos, d: int):
    half = d // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq[None]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def switches(conf: dict) -> tuple:
    """The hashable facts of the architecture the forward pass needs."""
    act = conf.get("hidden_act") or conf["activation_function"]
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return (d, h, conf.get("num_key_value_heads", h), conf.get("head_dim", d // h),
            "layernorm" if "rms_norm_eps" not in conf else "rmsnorm",
            float(conf.get("rms_norm_eps", conf.get("layer_norm_eps", 1e-5))),
            "rope" if "rope_theta" in conf else "sinusoid",
            float(conf.get("rope_theta", 0.0)),
            act in ("silu", "swiglu"))


@functools.partial(jax.jit, static_argnames=("arch", "precision"))
def logits_at(params, tokens, read, *, arch: tuple, precision: str):
    """Logits (P, V) float32 at positions ``read`` (P,) of the causal forward
    pass over ``tokens`` (L,). Positions past the true length only see
    padding that the causal mask keeps out of the positions read."""
    d, H, KV, Dh, norm, eps, posk, theta, gated = arch
    fp8 = precision == "fp8"
    L = tokens.shape[0]
    pos = jnp.arange(L)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    emb = params["embed"]["tok"]
    x = emb[tokens].astype(jnp.float32)
    if posk == "sinusoid":
        x = x + _sinusoid(pos, d)
    causal = pos[:, None] >= pos[None, :]

    def layer(x, p):
        p = f32(p)
        a = p["mixer"]
        h = _norm(x, p["norm1"], norm, eps)
        q = _mm(h, a["wq"], fp8) + a.get("bq", 0.0)
        k = _mm(h, a["wk"], fp8) + a.get("bk", 0.0)
        v = _mm(h, a["wv"], fp8) + a.get("bv", 0.0)
        q, k, v = q.reshape(L, H, Dh), k.reshape(L, KV, Dh), v.reshape(L, KV, Dh)
        if posk == "rope":
            q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * Dh ** -0.5
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, precision=HI)
        x = x + _mm(o.reshape(L, H * Dh), a["wo"], fp8)
        m = p["mlp"]
        h = _norm(x, p["norm2"], norm, eps)
        if gated:
            u = jax.nn.silu(_mm(h, m["w_gate"], fp8)) * _mm(h, m["w_up"], fp8)
            x = x + _mm(u, m["w_down"], fp8)
        else:
            u = _gelu_tanh(_mm(h, m["w_in"], fp8) + m["b_in"])
            x = x + _mm(u, m["w_out"], fp8) + m["b_out"]
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"][0])
    x = _norm(x[read], f32(params["final_norm"]), norm, eps)
    head = params["embed"].get("lm_head")
    w = emb.T if head is None else head
    return _mm(x, w.astype(jnp.float32), fp8)
