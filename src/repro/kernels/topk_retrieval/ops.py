"""Public ops: proxy scoring via the kernel + full T3 retrieval decode
(kernel proxy pass -> lax.top_k -> exact gather re-score)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import kernels as K
from repro.configs.base import RetrievalCfg
from repro.core import retrieval_attention as ret_lib
from repro.core.kv_cache import RetrievalCache
from repro.kernels.topk_retrieval.kernel import proxy_scores_fwd


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def proxy_scores_tpu(q, proxy_scale, proxy_zero, codes, length,
                     block_n: int = 1024, interpret: bool | None = None):
    """q: (B, H, Dp) pre-scaled query (incl. attention scale);
    proxy_scale/zero: (B, KV, Dp); codes: (B, N, KV, Dp) i8.
    Returns (B, H, N) f32."""
    B, H, Dp = q.shape
    KV = codes.shape[2]
    g = H // KV
    qf = q.astype(jnp.float32).reshape(B, KV, g, Dp)
    qs = qf * proxy_scale[:, :, None, :]
    qz = jnp.einsum("bkgd,bkd->bkg", qf, proxy_zero)[..., None]
    s = K.platform_call(proxy_scores_fwd, qs, qz, codes, length,
                        block_n=block_n, interpret=interpret)
    return s.reshape(B, H, codes.shape[1])


def retrieval_decode_tpu(q, cache: RetrievalCache, cfg: RetrievalCfg,
                         scale: float, interpret: bool | None = None):
    """Full T3 decode: kernel proxy sweep, then top-k + exact re-score.
    q: (B, 1, H, Dh) -> (B, 1, H, Dh)."""
    dp = cfg.proxy_dim or q.shape[-1]
    qp = (q[:, 0, :, :dp] * scale)
    sp = proxy_scores_tpu(qp, cache.proxy_scale, cache.proxy_zero,
                          cache.proxy, cache.length, interpret=interpret)
    # sp: (B, H, N) -> select_topk expects (B, T=1, H, N)
    idx = ret_lib.select_topk(sp[:, None], cache.length, cfg)
    k_sel, v_sel = ret_lib.gather_kv(cache.k, cache.v, idx)
    s = jnp.einsum("bthd,bthkd->bthk", q, k_sel).astype(jnp.float32) * scale
    ok = idx < cache.length
    s = jnp.where(ok, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bthk,bthkd->bthd", w.astype(v_sel.dtype), v_sel)
