"""Continuous-batching serving demo: mixed-length Poisson traffic through the
paged-arena engine, next to the static batch engine, plus the watermark
tier-escalation path under a deliberately tiny dense arena.

  PYTHONPATH=src python examples/serve_continuous.py
"""
import numpy as np

import jax

from repro.configs import ARCHS, ServingCfg, smoke_config
from repro.models import model as M
from repro.serving import ContinuousServeEngine, GenerationConfig, Request
from repro.serving.paged_cache import pages_needed


def main():
    cfg = smoke_config(ARCHS["qwen3-4b"])
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    # mixed prompts + heavy-tailed targets, Poisson arrivals (decode-step units)
    reqs, t = [], 0.0
    for i in range(10):
        t += rng.exponential(2.0)
        tgt = int(rng.integers(24, 48)) if rng.random() < 0.3 else int(rng.integers(3, 10))
        reqs.append(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size,
                                size=int(rng.integers(4, 20))).astype(np.int32),
            max_new_tokens=tgt, arrival=t))

    max_len = max(len(r.prompt) + r.max_new_tokens for r in reqs)
    serving = ServingCfg(num_slots=4, page_size=8,
                         num_pages=4 * pages_needed(max_len, 8) + 1,
                         max_blocks_per_slot=pages_needed(max_len, 8),
                         prefill_bucket=8)
    eng = ContinuousServeEngine(cfg, params, serving=serving)
    res, stats = eng.serve(reqs, GenerationConfig(max_new_tokens=max_len))

    print(f"[continuous] {stats['generated_tokens']} tokens in "
          f"{stats['decode_steps']} decode steps "
          f"({stats['tokens_per_step']:.2f} tok/step, "
          f"slot util {stats['slot_utilization']:.2f}, "
          f"arena util mean {stats['arena_utilization_mean']:.2f})")
    print(f"[continuous] chunked prefill: {stats['prefill_chunks']} chunks "
          f"streamed into arena pages ({stats['prefill_tokens']} prompt "
          "tokens)")
    for i in sorted(res):
        r = res[i]
        print(f"  req {i}: arrival {r['arrival']:5.1f} admitted {r['admitted_step']:3d} "
              f"done {r['done_step']:3d} ({len(r['tokens'])} tokens, "
              f"{r['finish_reason']})")

    # memory-pressure story: tiny dense arena + CPQ escalation arena
    pressured = ServingCfg(num_slots=4, page_size=8, num_pages=17,
                           escalated_pages=65, max_blocks_per_slot=8,
                           low_watermark=0.5, critical_watermark=0.25,
                           enable_escalation=True, prefill_bucket=8)
    eng2 = ContinuousServeEngine(cfg, params, serving=pressured)
    reqs2 = [Request(rid=100 + i,
                     prompt=rng.integers(0, cfg.vocab_size, size=12).astype(np.int32),
                     max_new_tokens=16) for i in range(6)]
    res2, stats2 = eng2.serve(reqs2, GenerationConfig(max_new_tokens=16))
    print(f"[escalation] escalations={stats2['escalations']} "
          f"preemptions={stats2['preemptions']} "
          f"(dense arena {pressured.num_pages - 1} pages, "
          f"CPQ arena {pressured.escalated_pages - 1} pages)")
    esc = [i for i in res2 if res2[i]["escalated"]]
    print(f"  escalated requests {esc} still finished: "
          f"{[res2[i]['finish_reason'] for i in esc]}")


if __name__ == "__main__":
    main()
