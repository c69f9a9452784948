"""Shared model layers: norms, RoPE / sinusoidal positions, MLP variants,
embeddings. Pure functions over ParamDef-declared parameter pytrees."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.common.param import ParamDef
from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain


# -------------------------------------------------------------------- norms


def norm_defs(cfg: ModelConfig, dim: int | None = None):
    d = dim or cfg.d_model
    if cfg.norm == "layernorm":
        return {
            "scale": ParamDef((d,), jnp.float32, (None,), init="ones"),
            "bias": ParamDef((d,), jnp.float32, (None,), init="zeros"),
        }
    return {"scale": ParamDef((d,), jnp.float32, (None,), init="ones")}


def apply_norm(cfg: ModelConfig, p, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:  # rmsnorm
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return y.astype(x.dtype)


def rms_norm_vec(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMS-norm along the last axis with an explicit scale vector (qk-norm etc.)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


# --------------------------------------------------------------------- rope


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor, ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(dim: int, theta: float, yarn) -> tuple[int, int]:
    """First and last rotary pair index of YaRN's ramp between the
    extrapolated (fast, ``beta_fast`` rotations over the original context)
    and the interpolated (slow, ``beta_slow``) frequencies."""
    def pair(rotations):
        n = yarn.original_max_position_embeddings / (rotations * 2 * math.pi)
        return dim * math.log(n) / (2 * math.log(theta))

    return (max(math.floor(pair(yarn.beta_fast)), 0),
            min(math.ceil(pair(yarn.beta_slow)), dim - 1))


def rope_inv_freq(dim: int, theta: float, yarn=None) -> jax.Array:
    """(dim/2,) f32 rotary frequencies ``theta^(-2i/dim)``; with YaRN,
    ``f * m + f / factor * (1 - m)``, where the mask ``m`` is 1 below the
    correction range, 0 above it, and linear across it."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if yarn is None:
        return inv
    low, high = yarn_correction_range(dim, theta, yarn)
    ramp = (jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3)
    m = 1.0 - jnp.clip(ramp, 0.0, 1.0)
    return inv / yarn.factor * (1.0 - m) + inv * m


def rope_tables(positions: jax.Array, dim: int, theta: float,
                yarn=None) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables. positions: (T,) or (B,) int32 -> (T, dim/2) each, f32.
    With YaRN (``yarn``, a ``YarnCfg``) the frequencies are rescaled and
    cos/sin carry ``mscale(mscale) / mscale(mscale_all_dim)``."""
    assert dim % 2 == 0, dim
    inv = rope_inv_freq(dim, theta, yarn)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    if yarn is None:
        return jnp.cos(ang), jnp.sin(ang)
    mag = (yarn_mscale(yarn.factor, yarn.mscale)
           / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    return jnp.cos(ang) * mag, jnp.sin(ang) * mag


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, T, H, D) with D even; cos/sin: (T, D/2). Pairing: (x1, x2) halves."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = cos[None, :, None, :].astype(x.dtype)
    s = sin[None, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def apply_rope_rows(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Per-row rope for one-token decode: x: (B, 1, H, D); cos/sin: (B, D/2)
    built from per-row positions (continuous-batching serving, where every
    request sits at its own position)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = cos[:, None, None, :].astype(x.dtype)
    s = sin[:, None, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def sinusoidal_embedding(positions: jax.Array, dim: int) -> jax.Array:
    """Absolute sinusoidal position embeddings (musicgen/opt): (T, dim)."""
    half = dim // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freq[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------- MLP


def mlp_defs(cfg: ModelConfig, d_ff: int | None = None):
    ff = d_ff or cfg.d_ff
    d = cfg.d_model
    dt = cfg.param_dtype
    if cfg.mlp_act in ("swiglu", "geglu"):
        return {
            "w_gate": ParamDef((d, ff), dt, ("embed", "mlp"), init="fan_in"),
            "w_up": ParamDef((d, ff), dt, ("embed", "mlp"), init="fan_in"),
            "w_down": ParamDef((ff, d), dt, ("mlp", "embed"), init="fan_in"),
        }
    return {  # plain gelu MLP
        "w_in": ParamDef((d, ff), dt, ("embed", "mlp"), init="fan_in"),
        "b_in": ParamDef((ff,), jnp.float32, (None,), init="zeros"),
        "w_out": ParamDef((ff, d), dt, ("mlp", "embed"), init="fan_in"),
        "b_out": ParamDef((d,), jnp.float32, (None,), init="zeros"),
    }


def apply_mlp(cfg: ModelConfig, p, x: jax.Array) -> jax.Array:
    if cfg.mlp_act in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp_act == "swiglu" else jax.nn.gelu
        h = act(x @ p["w_gate"]) * (x @ p["w_up"])
        h = constrain(h, "act_batch", None, "act_mlp")
        return h @ p["w_down"]
    h = jax.nn.gelu(x @ p["w_in"] + p["b_in"].astype(x.dtype))
    h = constrain(h, "act_batch", None, "act_mlp")
    return (h @ p["w_out"] + p["b_out"].astype(x.dtype)).astype(x.dtype)


# --------------------------------------------------------------- embeddings


def embed_defs(cfg: ModelConfig):
    dt = cfg.param_dtype
    out = {
        # audio archs keep a code-embedding table too (decode feeds tokens;
        # the EnCodec frontend stub supplies "frames" at train/prefill)
        "tok": ParamDef((cfg.vocab_size, cfg.d_model), dt, ("vocab", "embed"),
                        init="normal", scale=0.02)
    }
    if cfg.input_kind == "text+patches":
        # stub frontend adapter: patches arrive pre-projected to d_model
        out["mm_proj"] = ParamDef((cfg.d_model, cfg.d_model), dt, ("embed", "mlp"),
                                  init="fan_in")
    if not cfg.tie_embeddings:
        out["lm_head"] = ParamDef((cfg.d_model, cfg.vocab_size), dt, ("embed", "vocab"),
                                  init="fan_in")
    return out


def embed_inputs(cfg: ModelConfig, p, batch: dict, positions: jax.Array) -> jax.Array:
    """batch: {'tokens': (B,S) i32} and/or {'frames': (B,S,D)} / {'patches': (B,P,D)}."""
    if "frames" in batch:
        x = batch["frames"].astype(cfg.param_dtype)
    else:
        x = p["tok"][batch["tokens"]]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.pos_embedding == "absolute":
        if positions.ndim == 2:  # per-row positions (B, T): continuous batching
            emb = sinusoidal_embedding(positions.reshape(-1), cfg.d_model)
            x = x + emb.reshape(*positions.shape, cfg.d_model).astype(x.dtype)
        else:
            x = x + sinusoidal_embedding(positions, cfg.d_model).astype(x.dtype)[None]
    return constrain(x, "act_batch", None, None)


def lm_logits(cfg: ModelConfig, params, x: jax.Array) -> jax.Array:
    w = params["embed"]["tok"].T if cfg.tie_embeddings else params["embed"]["lm_head"]
    logits = (x @ w).astype(jnp.float32)
    if cfg.logit_softcap > 0:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return constrain(logits, "act_batch", None, "act_vocab")
