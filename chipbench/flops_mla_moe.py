"""Operations and bytes of a DeepSeek-V2 decoder (multi-head latent
attention and a fine-grained mixture of experts), counted from the
configuration file's published keys and live lengths: the yardstick of the
MLA decode kernel's roofline share and of the whole step's model FLOP/s
utilization in the cells of such a configuration. ``flops.Dims`` counts a
dense decoder and does not apply. What a kernel happens to DMA or recompute
does not count."""
from __future__ import annotations

import dataclasses
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int              # hidden size
    heads: int
    latent: int         # kv_lora_rank: the cached latent
    nope: int           # qk_nope_head_dim
    rope: int           # qk_rope_head_dim: the one shared roped key
    v: int              # v_head_dim
    layers: int
    dense_layers: int   # first_k_dense_replace: dense MLP of width ``ff``
    ff: int
    expert_ff: int      # moe_intermediate_size
    experts: int        # routed experts the router scores (published)
    held: int           # routed experts held on this chip
    top_k: int
    shared: int         # shared experts, each of width ``expert_ff``
    vocab: int
    kv_bytes: int = 2   # bytes per cached latent / rope element (bf16)
    act_bytes: int = 2

    @property
    def token_flops(self) -> float:
        """Matmul FLOPs one processed token needs in the decoder stack, in
        the absorbed form the serving path runs: per layer q (d x H(nope +
        rope)), the latent and roped key (d x (latent + rope)), q_nope into
        the latent (H nope x latent), the attention output out of it
        (H latent x v), o (H v x d); the dense MLP, or the router (d x
        experts), the shared experts and the held experts at the pairs they
        are expected to get: top_k * held / experts per token (6 * 8 / 64 =
        0.75 here), each 3 d x expert_ff. Not the LM head."""
        H, d = self.heads, self.d
        attn = (d * H * (self.nope + self.rope) + d * (self.latent + self.rope)
                + H * self.nope * self.latent + H * self.latent * self.v
                + H * self.v * d)
        dense = 3 * d * self.ff
        moe = (d * self.experts + 3 * d * self.expert_ff * self.shared
               + 3 * d * self.expert_ff * self.top_k * self.held / self.experts)
        moe_layers = self.layers - self.dense_layers
        return 2.0 * (self.layers * attn + self.dense_layers * dense
                      + moe_layers * moe)

    @property
    def flops_per_key(self) -> float:
        """Absorbed attention FLOPs per (query, key, layer): the score
        against latent and roped key, 2 H (latent + rope), and the weighted
        sum of latents, 2 H latent."""
        return 2.0 * self.heads * (self.latent + self.rope) + 2.0 * self.heads * self.latent

    @property
    def bytes_per_key(self) -> int:
        """Cached bytes per token and layer: the latent and the roped key."""
        return (self.latent + self.rope) * self.kv_bytes

    @property
    def qo_bytes(self) -> int:
        """Bytes of one query's operands and result per layer: q in the
        latent (H latent) and its rope part (H rope) read, the per-head
        latent sum (H latent) written."""
        return self.heads * (2 * self.latent + self.rope) * self.act_bytes


def dims_of(conf: dict) -> Dims:
    """Dims from a configuration file's published keys; ``published``
    holds the router's width where the file keeps only a chip's share."""
    experts = conf.get("published", {}).get("n_routed_experts",
                                            conf["n_routed_experts"])
    return Dims(d=conf["hidden_size"], heads=conf["num_attention_heads"],
                latent=conf["kv_lora_rank"], nope=conf["qk_nope_head_dim"],
                rope=conf["qk_rope_head_dim"], v=conf["v_head_dim"],
                layers=conf["num_hidden_layers"],
                dense_layers=conf["first_k_dense_replace"],
                ff=conf["intermediate_size"],
                expert_ff=conf["moe_intermediate_size"], experts=experts,
                held=conf["n_routed_experts"], top_k=conf["num_experts_per_tok"],
                shared=conf["n_shared_experts"], vocab=conf["vocab_size"])


def load(name: str = "deepseek-v2-lite") -> Dims:
    """Dims of the configuration file ``configs/<name>.json``."""
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return dims_of(json.load(f))


def decode_attn(dims: Dims, kv_lens) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step's absorbed attention over all
    layers: each row's live latent and roped key read once, its query
    operands read and its latent sum written."""
    n = float(sum(kv_lens))
    return (dims.layers * dims.flops_per_key * n,
            dims.layers * (dims.bytes_per_key * n + dims.qo_bytes * len(kv_lens)))


def chunk_attn(dims: Dims, offset: int, valid: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill chunk's attention over all layers:
    query i (position offset + i) attends offset + i + 1 keys; the latent of
    [0, offset + valid) read once."""
    keys = valid * offset + valid * (valid + 1) / 2.0
    return (dims.layers * dims.flops_per_key * keys,
            dims.layers * (dims.bytes_per_key * (offset + valid)
                           + dims.qo_bytes * valid))


def model_flops(dims: Dims, tokens: int, sampled: int) -> float:
    """Matmul FLOPs the model needs for ``tokens`` processed tokens (chunk
    padding excluded by the caller) and ``sampled`` LM-head positions, 2 d V
    each. Add ``decode_attn`` / ``chunk_attn`` for the whole."""
    return dims.token_flops * tokens + 2.0 * dims.d * dims.vocab * sampled


def step_flops(dims: Dims, kv_lens, chunk) -> float:
    """Model FLOPs of one engine tick: its decode rows (``kv_lens``) and
    its prompt chunk (``chunk`` = (offset, valid, first token sampled), or
    None), attention included."""
    tokens = len(kv_lens) + (chunk[1] if chunk else 0)
    sampled = len(kv_lens) + (1 if chunk and chunk[2] else 0)
    total = model_flops(dims, tokens, sampled)
    if kv_lens:
        total += decode_attn(dims, kv_lens)[0]
    if chunk:
        total += chunk_attn(dims, chunk[0], chunk[1])[0]
    return total
