"""Operations and bytes the model's work needs, counted from shapes and live
lengths: the yardstick for kernel roofline shares and model FLOP/s
utilization. What a kernel happens to DMA or recompute does not count."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int          # hidden size
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    layers: int
    gated_mlp: bool  # three MLP matrices (SwiGLU) or two
    kv_bytes: int = 2  # bytes per cached K/V element (bf16)
    act_bytes: int = 2

    @property
    def matmul_params(self) -> int:
        """Weights of every matmul a token passes through in the decoder
        stack (not the embedding lookup, not the LM head)."""
        attn = self.d * self.head_dim * (2 * self.heads + 2 * self.kv_heads)
        mlp = (3 if self.gated_mlp else 2) * self.d * self.ff
        return self.layers * (attn + mlp)


def dims_of(conf: dict) -> Dims:
    """Dims from a configuration file's published keys."""
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return Dims(d=d, heads=h, kv_heads=conf.get("num_key_value_heads", h),
                head_dim=conf.get("head_dim", d // h),
                ff=conf.get("intermediate_size", conf.get("ffn_dim")),
                vocab=conf["vocab_size"], layers=conf["num_hidden_layers"],
                gated_mlp=act(conf) in ("silu", "swiglu"))


# ----------------------------------------------------------------- attention


def decode_attn(dims: Dims, kv_lens) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step's attention over all layers: each
    row's live K and V read once, its q read and o written; QK^T and PV at
    2*H*Dh FLOPs per key each."""
    n = float(sum(kv_lens))
    rows = len(kv_lens)
    flops = 4.0 * dims.heads * dims.head_dim * n
    kv = 2.0 * dims.kv_heads * dims.head_dim * dims.kv_bytes * n
    qo = 2.0 * dims.heads * dims.head_dim * dims.act_bytes * rows
    return dims.layers * flops, dims.layers * (kv + qo)


def chunk_attn(dims: Dims, offset: int, valid: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill chunk's attention over all layers:
    query i (absolute position offset+i) attends offset+i+1 keys (the causal
    triangle); K and V of [0, offset+valid) read once; q read, o written."""
    keys = valid * offset + valid * (valid + 1) / 2.0
    flops = 4.0 * dims.heads * dims.head_dim * keys
    kv = 2.0 * dims.kv_heads * dims.head_dim * dims.kv_bytes * (offset + valid)
    qo = 2.0 * dims.heads * dims.head_dim * dims.act_bytes * valid
    return dims.layers * flops, dims.layers * (kv + qo)


def min_time(flops: float, nbytes: float, peak_flops: float,
             peak_bw: float) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    tc, tm = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")


# --------------------------------------------------------------------- model


def model_flops(dims: Dims, tokens: int, sampled: int) -> float:
    """Matmul FLOPs the model needs: 2*N_matmul per processed token (chunk
    padding excluded by the caller) plus 2*d*V per sampled position. Add
    the attention FLOPs of ``decode_attn`` / ``chunk_attn`` for the whole."""
    return 2.0 * dims.matmul_params * tokens + 2.0 * dims.d * dims.vocab * sampled


def act(conf: dict) -> str:
    return conf.get("hidden_act") or conf["activation_function"]
