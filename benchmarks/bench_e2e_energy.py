"""End-to-end OPT-6.7B inference energy/throughput model (paper §IV table).

Reproduces the paper's comparison METHODOLOGY (their numbers come from a
ReRAM-PIM simulator; ours from an analytical latency/energy model with
published device constants — see benchmarks/hw.py):

  energy/token = moved_bytes * pj_per_byte + MACs * pj_per_mac
  time/token   = max(MACs*2 / peak_flops, moved_bytes / mem_bw)

Configurations:
  a100-dense          weights + bf16 KV over HBM (the paper's GPU baseline)
  flightllm           FPGA baseline (paper's accelerator baseline)
  pim-t1t2            the paper's design: weights stationary in CIM,
                      T1 decomposition (no K/V rewrite; X cache), T2 CPQ
                      4-bit+prune cache, sparse CE
  tpu-v5e-dense       our target hardware, vanilla serving
  tpu-v5e-t1t2        our TPU-native adaptation (X-cache + CPQ cache)

Paper's headline: PIM vs A100 = 159.9x energy / 49.6x throughput;
vs FlightLLM = 34.8x / 29.2x. We print ours next to those.
"""
from __future__ import annotations

import dataclasses

from benchmarks.hw import A100, FLIGHTLLM, PIM, TPU_V5E, Device
from repro.common.param import count_params
from repro.configs import get_config
from repro.configs.base import CPQCfg
from repro.core.cpq import cpq_bytes_per_token
from repro.models.model import model_defs


@dataclasses.dataclass
class TrafficCfg:
    """Per-variant traffic knobs of the analytical model (renamed from the
    old ``ServingCfg`` — that name now means the continuous-batching serving
    config in configs/base.py)."""

    ctx: int = 2048
    batch: int = 1
    weights_stationary: bool = False   # PIM: weights never leave the macros
    kv_bytes_per_token_layer: float = 0.0  # set per variant
    extra_kv_write_penalty: float = 0.0    # CWC rewrite energy (ReRAM baseline)
    # paged serving: chunked prefill writes the prompt's cache payload into
    # the arena exactly once; amortized here over the generated tokens
    # (prompt_ctx tokens written per gen_tokens generated). 0 = not modeled —
    # the pre-serving variants charge decode reads only.
    prefill_ctx: int = 0
    gen_tokens: int = 256
    prefill_write_bytes_per_token_layer: float = 0.0
    # mesh-sharded paged serving: per-head attention-output partials
    # concatenated across the model axis — (mp-1)/mp of the head outputs
    # cross the interconnect per generated token per cache layer (the
    # paper's "only small per-head partials cross the interconnect")
    interconnect_bytes_per_token_layer: float = 0.0
    # idle-vs-active serving utilization: the mean fraction of batch slots
    # that emit a useful token per decode step, the engine's
    # ``slot_utilization`` stat. Below 1.0 a
    # useful token pays (a) the 1/u amplification of the per-step weight
    # stream (idle rows ride the same step) and (b) the idle share of the
    # board's static power. 1.0 (the default) reproduces the pre-trace
    # model exactly.
    slot_util: float = 1.0


def decode_token_cost(dev: Device, n_params: float, L: int, cfg: TrafficCfg):
    """Per generated token (per sequence), amortized over the batch."""
    u = min(max(cfg.slot_util, 1e-6), 1.0)
    macs = n_params + 0.0  # linear layers: one MAC per weight per token
    kv_bytes = cfg.kv_bytes_per_token_layer * L * cfg.ctx
    attn_macs = cfg.kv_bytes_per_token_layer / 2 * L * cfg.ctx  # ~1 MAC/elem
    # weight streaming is a PER-STEP cost: idle slots still ride the step,
    # so per USEFUL token it amortizes over batch * slot_util live rows
    w_bytes = 0.0 if cfg.weights_stationary else 2.0 * n_params / (cfg.batch * u)
    # chunked-prefill arena writes: one write per prompt token per layer,
    # amortized per generated token
    pf_bytes = (cfg.prefill_write_bytes_per_token_layer * L * cfg.prefill_ctx
                / max(cfg.gen_tokens, 1))
    # caveat: interconnect bytes are charged at HBM bandwidth/energy — an
    # OPTIMISTIC lower bound (v5e ICI is slower and costlier per byte than
    # HBM); the column exists for the movement accounting, and the partial
    # concat is small enough that the ranking is insensitive to the constant
    icnx_bytes = cfg.interconnect_bytes_per_token_layer * L
    bytes_moved = (w_bytes + kv_bytes + pf_bytes + icnx_bytes
                   + cfg.extra_kv_write_penalty)
    t = max(2.0 * (macs + attn_macs) / dev.peak_flops,
            bytes_moved / dev.hbm_bw)
    e = (bytes_moved * dev.mem_pj_per_byte + (macs + attn_macs) * dev.mac_pj) * 1e-12
    # the idle rows' share of static board power over the token's time
    # slice (zero at full occupancy — pre-trace rows are unchanged)
    e += dev.idle_w * t * (1.0 - u)
    return t, e


# Slot and arena utilization of the continuous engine, recorded once from a
# CPU smoke run on a mixed-length Poisson trace: model inputs, not
# measurements of any device.
SMOKE_SLOT_UTIL, SMOKE_ARENA_UTIL = 0.72, 0.55


def main(emit):
    cfg = get_config("opt-6.7b")
    n_params = count_params(model_defs(cfg))
    L = cfg.num_layers
    kv_dense = 2.0 * cfg.num_kv_heads * cfg.head_dim * 2       # K+V bf16
    kv_x = float(cfg.d_model * 2)                              # T1 X-cache (no rope)
    kv_cpq = 2 * cpq_bytes_per_token(CPQCfg(prune_ratio=0.4, bits=4),
                                     cfg.num_kv_heads, cfg.head_dim)
    kv_x_cpq = cpq_bytes_per_token(CPQCfg(prune_ratio=0.4, bits=4), 1,
                                   cfg.d_model)
    # paged-arena accounting (serving subsystem): same payload through the
    # same API, plus the amortized block-table entry per page
    from repro.serving import paged_cache as pgc
    page_size = 16
    paged_dense = pgc.init_paged_dense(2, page_size, cfg.num_kv_heads, cfg.head_dim)
    kv_paged = pgc.bytes_per_token(paged_dense, page_size)

    # idle-vs-active utilization: the paged rows charge the 1/u
    # weight-stream amplification and the idle static-power share instead of
    # assuming every slot emits a token every step
    slot_u = SMOKE_SLOT_UTIL
    emit("e2e_paged_utilization", 0.0,
         f"slot_util={slot_u:.3f};arena_util={SMOKE_ARENA_UTIL:.3f};RECORDED")

    for batch in (1, 8):
        variants = {
            "a100-dense": (A100, TrafficCfg(batch=batch,
                                            kv_bytes_per_token_layer=kv_dense)),
            "flightllm": (FLIGHTLLM, TrafficCfg(batch=batch,
                                                kv_bytes_per_token_layer=kv_dense)),
            "pim-t1t2": (PIM, TrafficCfg(batch=batch, weights_stationary=True,
                                         kv_bytes_per_token_layer=kv_x_cpq)),
            "tpu-v5e-dense": (TPU_V5E, TrafficCfg(batch=batch,
                                                  kv_bytes_per_token_layer=kv_dense)),
            "tpu-v5e-t1": (TPU_V5E, TrafficCfg(batch=batch,
                                               kv_bytes_per_token_layer=kv_x)),
            "tpu-v5e-t1t2": (TPU_V5E, TrafficCfg(batch=batch,
                                                 kv_bytes_per_token_layer=kv_x_cpq)),
            # continuous-batching serving: paged dense arena (block-table
            # overhead included; the serving win is utilization, not bytes).
            # Decode reads PLUS the chunked-prefill arena writes: every
            # prompt token's K/V lands in the pages exactly once (no scratch
            # cache and no pack re-copy), amortized per generated token —
            # the serving-level half of the energy story. Charged at the
            # recorded slot utilization: idle slots amplify the per-step
            # weight stream 1/u and bill their share of static board power.
            "tpu-v5e-paged": (TPU_V5E, TrafficCfg(
                batch=batch, kv_bytes_per_token_layer=kv_paged,
                prefill_ctx=2048, gen_tokens=256,
                prefill_write_bytes_per_token_layer=kv_paged,
                slot_util=slot_u)),
            # mesh-sharded paged serving (PER-DEVICE traffic, mp=4 model
            # sharding): each device sweeps only
            # its kv-head quarter of the arena (reads AND prefill writes
            # shrink 1/mp) and in exchange ships (mp-1)/mp of the per-head
            # output partials over the interconnect per generated token —
            # the paper's off-chip-movement accounting applied to the
            # partial concat. Weights stay replicated (engine places params
            # with P()), so w_bytes is unchanged per device.
            "tpu-v5e-paged-mp4": (TPU_V5E, TrafficCfg(
                batch=batch, kv_bytes_per_token_layer=kv_paged / 4,
                prefill_ctx=2048, gen_tokens=256,
                prefill_write_bytes_per_token_layer=kv_paged / 4,
                interconnect_bytes_per_token_layer=(
                    3 / 4 * cfg.num_heads * cfg.head_dim * 2),
                slot_util=slot_u)),
        }
        res = {}
        for name, (dev, sc) in variants.items():
            t, e = decode_token_cost(dev, n_params, L, sc)
            res[name] = (t, e)
            emit(f"e2e_b{batch}_{name}", t * 1e6,
                 f"tok_per_s={1 / t:.1f};mJ_per_tok={e * 1e3:.3f};"
                 f"icnx_B_per_tok={sc.interconnect_bytes_per_token_layer * L:.0f};"
                 f"slot_util={sc.slot_util:.2f}")
        ee = lambda a, b: (res[b][1] / res[a][1], res[b][0] / res[a][0])  # noqa: E731
        e_a, th_a = ee("pim-t1t2", "a100-dense")
        e_f, th_f = ee("pim-t1t2", "flightllm")
        emit(f"e2e_b{batch}_pim_vs_a100", 0.0,
             f"energy_eff={e_a:.1f}x(paper:159.9x);throughput={th_a:.1f}x(paper:49.6x)")
        emit(f"e2e_b{batch}_pim_vs_flightllm", 0.0,
             f"energy_eff={e_f:.1f}x(paper:34.8x);throughput={th_f:.1f}x(paper:29.2x)")
        e_t, th_t = ee("tpu-v5e-t1t2", "tpu-v5e-dense")
        emit(f"e2e_b{batch}_tpu_t1t2_vs_dense", 0.0,
             f"energy_eff={e_t:.2f}x;throughput={th_t:.2f}x (beyond-paper TPU adaptation)")
