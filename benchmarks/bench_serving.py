"""Continuous-batching vs static serving benchmark.

Workload: mixed prompt lengths + mixed target generation lengths, Poisson
arrivals (arrival gaps exponential in decode-step units). Both engines get
EQUAL ARENA BYTES: the static engine provisions ``num_slots`` contiguous rows
of the worst-case request length; the continuous engine gets the same token
capacity as a shared page pool.

Metrics per arrival rate:
  * token throughput (useful generated tokens per decode step, and per second)
  * mean/p90 completion latency in decode steps (arrival -> last token)
  * time-to-first-token and inter-token-latency p50/p95 in engine ticks —
    the head-of-line metrics chunked paged prefill exists to fix: a one-shot
    admission stalls every running row for the whole prompt's
    chunk-equivalents, a chunked admission interleaves one chunk per tick
  * arena utilization (valid tokens / provisioned tokens)

The static engine is the paper-baseline batch server: FIFO batches of
``num_slots`` requests, right-padded prompts, each batch runs until its
LONGEST target finishes (rows past their own target produce waste tokens).
Continuous batching retires rows at their target and refills the slot.

Workload builders and the continuous-run harness live in
``repro.serving.trace`` (importable: the auto-tuner and tests reuse them);
this file is the comparison/reporting CLI on top — plus the static-engine
baseline, which only the benchmarks care about. ``run_continuous`` /
``make_workload`` / ``equal_arena_serving`` etc. stay re-exported here for
back-compat.

  PYTHONPATH=src python benchmarks/bench_serving.py [--smoke]
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

from repro.launch._bootstrap import ensure_host_devices_for_mesh

# --mesh needs emulated host devices BEFORE the jax backend initializes
ensure_host_devices_for_mesh(sys.argv)

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, ServingCfg, smoke_config
from repro.models import model as M
from repro.serving import paged_cache as pgc
from repro.serving.engine import ContinuousServeEngine, GenerationConfig, ServeEngine
from repro.serving.paged_cache import pages_needed
from repro.serving.scheduler import Request
from repro.serving.trace import (WorkItem, class_tails, equal_arena_serving,
                                 make_burst_workload, make_loopy_workload,
                                 make_slo_workload, make_templated_workload,
                                 make_workload, run_trace)

# back-compat alias: the continuous-run harness moved to repro.serving.trace
run_continuous = run_trace

__all__ = [
    "WorkItem", "class_tails", "equal_arena_serving", "make_burst_workload",
    "make_loopy_workload", "make_slo_workload", "make_templated_workload",
    "make_workload", "run_trace", "run_continuous", "run_static", "compare",
    "compare_admission", "templated_compare", "speculate_compare",
    "policy_sweep", "score_policy_run", "replica_sweep", "run_router",
    "failure_drill", "mesh_sweep", "main",
]


def run_static(cfg, params, work: list[WorkItem], num_slots: int, max_len: int,
               mode_rt=None):
    """FIFO batches of ``num_slots``; each batch decodes to its longest
    target. Useful tokens = per-request targets; the rest is padding waste."""
    eng = ServeEngine(cfg, params, rt=mode_rt, max_len=max_len)
    useful = waste = decode_steps = 0
    latencies = []
    clock = 0.0  # decode-step clock
    t0 = time.time()
    for i in range(0, len(work), num_slots):
        batch = work[i:i + num_slots]
        S = max(len(w.prompt) for w in batch)
        toks = np.stack([np.pad(w.prompt, (0, S - len(w.prompt)), mode="edge")
                         for w in batch])
        max_t = max(w.target for w in batch)
        gen = GenerationConfig(max_new_tokens=max_t)
        # the batch cannot start before its last member arrives
        clock = max(clock, max(w.arrival for w in batch))
        out, stats = eng.generate({"tokens": jnp.asarray(toks)}, gen)
        decode_steps += stats["decode_steps"]
        clock += stats["decode_steps"]
        for w in batch:
            useful += w.target
            waste += max_t - w.target
            latencies.append(clock - w.arrival)
    wall = time.time() - t0
    provisioned = num_slots * max_len
    return {
        "engine": "static",
        "useful_tokens": useful,
        "waste_tokens": waste,
        "decode_steps": decode_steps,
        "tokens_per_step": useful / max(decode_steps, 1),
        "latency_mean": float(np.mean(latencies)),
        "latency_p90": float(np.percentile(latencies, 90)),
        "arena_utilization": useful / max(decode_steps * provisioned, 1) * num_slots,
        "wall_time_s": wall,
        "tokens_per_s": useful / max(wall, 1e-9),
    }


def compare(cfg, params, *, rate: float, n_requests: int, num_slots: int,
            seed: int = 0, mode_rt=None, prefill_chunk: int = 16,
            long_prompts: bool = False):
    kw = dict(long_prompt=(40, 72), p_long_prompt=0.3) if long_prompts else {}
    work = make_workload(seed, n_requests, cfg.vocab_size, rate, **kw)
    max_len = max(len(w.prompt) + w.target for w in work)
    serving = equal_arena_serving(num_slots, max_len, page_size=8,
                                  prefill_chunk=prefill_chunk)
    st = run_static(cfg, params, work, num_slots, max_len, mode_rt)
    ct = run_continuous(cfg, params, work, serving, mode_rt)
    return st, ct


def compare_admission(cfg, params, *, rate: float, n_requests: int,
                      num_slots: int, seed: int = 0, prefill_chunk: int = 16):
    """Chunked vs one-shot admission on the SAME long-prompt Poisson workload
    at equal arena bytes: the interleaving win shows up as lower tail
    inter-token latency (p95 ITL) for the rows that keep decoding while a
    long prompt streams in."""
    work = make_workload(seed, n_requests, cfg.vocab_size, rate,
                         long_prompt=(40, 72), p_long_prompt=0.3)
    max_len = max(len(w.prompt) + w.target for w in work)
    chunked = run_continuous(cfg, params, work, equal_arena_serving(
        num_slots, max_len, page_size=8, prefill_chunk=prefill_chunk))
    oneshot = run_continuous(cfg, params, work, equal_arena_serving(
        num_slots, max_len, page_size=8, prefill_chunk=0,
        bucket=prefill_chunk))
    return chunked, oneshot


def templated_compare(cfg, params, emit, *, rate: float = 1.0,
                      n_sessions: int = 4, num_slots: int = 4, seed: int = 0,
                      smoke: bool = False):
    """Prefix sharing on the shared-system-prompt multi-turn trace: the SAME
    continuous engine with sharing ON vs OFF (token-exact by construction),
    plus the static baseline for the acceptance bar. Reported per arm:
    prefill bytes actually written per request (mounted pages write nothing),
    the fraction of prompt pages served from the index instead of recomputed,
    and tail TTFT — the turns that resend a resident conversation start
    decoding after prefilling only their unshared tail."""
    work = make_templated_workload(seed, n_sessions, cfg.vocab_size, rate)
    max_len = max(len(w.prompt) + w.target for w in work)
    base = equal_arena_serving(num_slots, max_len, page_size=8,
                               prefill_chunk=16)
    on = run_continuous(cfg, params, work,
                        dataclasses.replace(base, share_prefix=True))
    off = run_continuous(cfg, params, work, base)
    st = run_static(cfg, params, work, num_slots, max_len)
    prompt_pages = sum(pages_needed(len(w.prompt), base.page_size)
                       for w in work)
    for tag, r in (("shared", on), ("unshared", off)):
        frac = r["shared_prefix_pages"] / max(prompt_pages, 1)
        emit(f"serving_templated_{tag}", r["wall_time_s"] * 1e6,
             f"tok_per_step={r['tokens_per_step']:.2f};"
             f"prefill_write_bytes_per_req="
             f"{r['prefill_write_bytes'] / len(work):.0f};"
             f"shared_page_fraction={frac:.3f};"
             f"prefix_hits={r['prefix_hits']};cow={r['cow_copies']};"
             f"ttft_p50={r['ttft_p50']:.1f};ttft_p95={r['ttft_p95']:.1f}")
    emit("serving_templated_static", st["wall_time_s"] * 1e6,
         f"tok_per_step={st['tokens_per_step']:.2f};"
         f"lat_p90={st['latency_p90']:.1f}")
    ratio = on["tokens_per_step"] / max(st["tokens_per_step"], 1e-9)
    emit("serving_templated_speedup", 0.0,
         f"continuous_vs_static={ratio:.2f}x (target >= 1.5x)")
    if smoke:
        # sharing is an allocator optimization, not a model change: the
        # streams must be bit-identical with it on or off
        assert np.array_equal(on["tokens"], off["tokens"]), (
            "prefix sharing changed generated tokens on the templated trace")
        assert on["prefix_hits"] > 0, (
            "templated trace produced no prefix hits with sharing on")
        assert on["prefill_write_bytes"] < off["prefill_write_bytes"], (
            f"sharing did not reduce prefill writes: "
            f"{on['prefill_write_bytes']} vs {off['prefill_write_bytes']}")
        assert on["ttft_p95"] < off["ttft_p95"], (
            f"shared TTFT p95 {on['ttft_p95']:.1f} not better than "
            f"unshared {off['ttft_p95']:.1f}")
        assert ratio >= 1.5, (
            f"templated continuous-vs-static {ratio:.2f}x < 1.5x floor")
        emit("serving_templated_smoke", 0.0,
             f"PASS ttft_p95 {on['ttft_p95']:.1f} < {off['ttft_p95']:.1f}; "
             f"write_bytes {on['prefill_write_bytes']} < "
             f"{off['prefill_write_bytes']}; speedup={ratio:.2f}x")
    return on, off, st


def speculate_compare(cfg, params, emit, *, seed: int = 0, spec_k: int = 4,
                      smoke: bool = False):
    """Speculative decoding on vs off at equal arena bytes, at the two
    occupancy extremes the clock model distinguishes:

    * LOW occupancy (serialized trace, 1 resident row): decode is
      weight-stream-bound — one model invocation per token. The verify
      chunk scores ``k`` drafted tokens in that same single invocation, so
      every acceptance is a free token: ITL (ticks between committed
      tokens) drops below 1 and tokens/step rises by the accept rate.
    * HIGH occupancy (Poisson trace filling all slots): the batched decode
      already amortizes the weight stream over the resident rows, while
      each speculative row pays a PRIVATE verify invocation — speculation
      is reported honestly as a loss here (the engine-level takeaway:
      gate speculation on occupancy; ``SamplingParams.speculate`` is the
      per-request switch).

    Both arms assert greedy bit-parity speculative on-vs-off (f32 — same
    recast contract as ``mesh_sweep``); ``--smoke`` additionally asserts
    the low-occupancy ITL win and that continuous serving keeps the 1.5x
    over static on the high-occupancy trace."""
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = jax.tree.map(lambda a: a.astype(jnp.float32)
                          if a.dtype == jnp.bfloat16 else a, params)

    def pair(work, num_slots):
        max_len = max(len(w.prompt) + w.target for w in work)
        base = equal_arena_serving(num_slots, max_len, page_size=8)
        off = run_continuous(cfg, params, work, base)
        on = run_continuous(cfg, params, work,
                            dataclasses.replace(base, spec_len=spec_k))
        assert np.array_equal(on["tokens"], off["tokens"]), (
            "speculative decoding changed greedy tokens (verify draws must "
            "be bit-identical to the decode path)")
        return off, on, max_len

    def row(tag, r):
        emit(f"serving_spec_{tag}", r["wall_time_s"] * 1e6,
             f"tok_per_step={r['tokens_per_step']:.2f};"
             f"itl_mean={r['itl_mean']:.2f};itl_p50={r['itl_p50']:.1f};"
             f"itl_p95={r['itl_p95']:.1f};"
             f"accept_rate={r['spec_accept_rate']:.2f};"
             f"accepted_per_step={r['spec_accepted_per_step']:.2f};"
             f"verify_steps={r['spec_steps']}")

    # low occupancy: arrivals spaced far past each request's lifetime
    work_low = make_loopy_workload(seed, 3, cfg.vocab_size, gap=400.0)
    low_off, low_on, _ = pair(work_low, num_slots=4)
    row("low_off", low_off)
    row("low_on", low_on)

    # high occupancy: the acceptance suite's mixed heavy-tailed Poisson
    # trace keeping all 4 slots busy (and the static engine padding)
    work_high = make_workload(seed, 24, cfg.vocab_size, rate=4.0)
    high_off, high_on, max_len = pair(work_high, num_slots=4)
    st = run_static(cfg, params, work_high, 4, max_len)
    row("high_off", high_off)
    row("high_on", high_on)
    emit("serving_spec_static", st["wall_time_s"] * 1e6,
         f"tok_per_step={st['tokens_per_step']:.2f}")
    bar = high_off["tokens_per_step"] / max(st["tokens_per_step"], 1e-9)
    bar_on = high_on["tokens_per_step"] / max(st["tokens_per_step"], 1e-9)
    emit("serving_spec_bar", 0.0,
         f"continuous_vs_static={bar:.2f}x;spec_arm={bar_on:.2f}x "
         f"(target >= 1.5x)")

    if smoke:
        assert low_on["itl_p95"] <= low_off["itl_p95"], (
            f"spec p95 ITL {low_on['itl_p95']:.2f} worse than baseline "
            f"{low_off['itl_p95']:.2f} at low occupancy")
        assert low_on["itl_mean"] < low_off["itl_mean"], (
            f"spec mean ITL {low_on['itl_mean']:.2f} not better than "
            f"baseline {low_off['itl_mean']:.2f} at low occupancy")
        assert low_on["spec_accept_rate"] > 0, (
            "loopy trace produced zero accepted draft tokens")
        assert bar >= 1.5, (
            f"continuous-vs-static {bar:.2f}x < 1.5x floor on the "
            f"speculative high-occupancy trace")
        emit("serving_spec_smoke", 0.0,
             f"PASS itl_mean {low_on['itl_mean']:.2f} < "
             f"{low_off['itl_mean']:.2f}; itl_p95 {low_on['itl_p95']:.1f} "
             f"<= {low_off['itl_p95']:.1f}; "
             f"accept_rate={low_on['spec_accept_rate']:.2f}; "
             f"bar={bar:.2f}x >= 1.5x")
    return low_off, low_on, high_off, high_on


def score_policy_run(run: dict, work: list[WorkItem], slos) -> dict:
    """Per-class latency + SLO-attainment % + Jain fairness for one policy
    run. A request attains its SLO when its TTFT meets ``ttft_target`` AND
    its p95 inter-token gap meets ``itl_target`` (both in engine ticks).
    Jain's index is computed over per-request service rates
    (tokens / resident time): 1.0 = perfectly even service, 1/n = one
    request got everything."""
    res = run["results"]
    ttft_by_class: dict[str, list] = {}
    attained = 0
    rates = []
    for w, slo in zip(work, slos):
        r = res[w.rid]
        if r["first_token_step"] < 0:
            # never produced a token (oom / unschedulable): a hard SLO miss
            # and zero service — excluded from the TTFT percentiles (its
            # sentinel -1 stamp is not a latency), counted everywhere else
            rates.append(0.0)
            continue
        ttft = r["first_token_step"] - w.arrival
        gaps = (np.diff(r["token_steps"])
                if len(r["token_steps"]) > 1 else np.zeros(1))
        ok = (ttft <= slo.ttft_target
              and float(np.percentile(gaps, 95)) <= slo.itl_target)
        attained += bool(ok)
        ttft_by_class.setdefault(slo.name, []).append(ttft)
        rates.append(len(r["tokens"]) / max(r["done_step"] - w.arrival, 1e-9))
    x = np.asarray(rates, np.float64)
    out = {
        "policy": run["policy"],
        "tokens_per_step": run["tokens_per_step"],
        "slo_attained_pct": 100.0 * attained / len(work),
        "jain_fairness": float(x.sum() ** 2 / (len(x) * (x ** 2).sum() + 1e-12)),
        "preemptions": run["preemptions"],
        "deescalations": run["deescalations"],
    }
    for name, vals in ttft_by_class.items():
        out[f"ttft_p50_{name}"] = float(np.percentile(vals, 50))
        out[f"ttft_p95_{name}"] = float(np.percentile(vals, 95))
    return out


def policy_sweep(cfg, params, emit, *, rate: float = 2.0,
                 n_requests: int = 24, num_slots: int = 4, seed: int = 0,
                 policies=("fifo", "priority", "slo")):
    """``--policy`` comparison table: the same mixed-class Poisson trace
    through each scheduler policy at equal arena bytes, scored on per-class
    p95 TTFT, SLO-attainment %, and Jain fairness — plus the static-engine
    baseline for the throughput bar. Returns {policy: scores} + 'static'."""
    work, slos = make_slo_workload(seed, n_requests, cfg.vocab_size, rate)
    max_len = max(len(w.prompt) + w.target for w in work)
    serving = equal_arena_serving(num_slots, max_len, page_size=8)
    st = run_static(cfg, params, work, num_slots, max_len)
    rows = {"static": st}
    for pol in policies:
        run = run_continuous(cfg, params, work, serving, policy=pol,
                             slos=slos)
        s = rows[pol] = score_policy_run(run, work, slos)
        emit(f"serving_policy_{pol}", run["wall_time_s"] * 1e6,
             f"tok_per_step={s['tokens_per_step']:.2f};"
             f"slo_attained={s['slo_attained_pct']:.0f}%;"
             f"jain={s['jain_fairness']:.3f};"
             f"ttft_p95_hi={s.get('ttft_p95_interactive', 0.0):.1f};"
             f"ttft_p95_lo={s.get('ttft_p95_batch', 0.0):.1f};"
             f"preempt={s['preemptions']}")
    emit("serving_policy_static", st["wall_time_s"] * 1e6,
         f"tok_per_step={st['tokens_per_step']:.2f} (baseline)")
    return rows


def run_router(cfg, params, work: list[WorkItem], serving: ServingCfg, *,
               num_replicas: int, placement: str = "load", slos=None,
               donor=None):
    """One ``ReplicaRouter`` run over the trace. Every replica gets its own
    ``serving`` arena (data-parallel scale-out: capacity grows with replica
    count, the paper's add-a-DIMM story). ``donor`` (any engine of the same
    (cfg, rt)) shares its jitted step functions with every replica —
    sweeping replica counts compiles once."""
    from repro.serving.router import ReplicaRouter

    router = ReplicaRouter(cfg, params, num_replicas=num_replicas,
                           serving=serving, placement=placement)
    if donor is not None:
        for eng in router.engines:
            eng.adopt_compiled(donor)
    reqs = [Request(rid=w.rid, prompt=w.prompt, max_new_tokens=w.target,
                    arrival=w.arrival,
                    slo=None if slos is None else slos[i])
            for i, w in enumerate(work)]
    res, stats = router.serve(reqs, GenerationConfig(max_new_tokens=max(
        w.target for w in work)))
    out = {
        "replicas": num_replicas,
        "placement": stats["placement"],
        "useful_tokens": stats["generated_tokens"],
        "decode_steps_max": stats["decode_steps_max"],
        "tokens_per_step": stats["tokens_per_step"],
        "wall_time_s": stats["wall_time_s"],
        "tokens_per_s": stats["tokens_per_s"],
        "preemptions": stats["preemptions"],
        "defrags": stats["defrags"],
        "arena_bytes_total": stats["arena_bytes_total"],
        "interconnect_bytes_per_token": stats["interconnect_bytes_per_token"],
        "migrated_requests": stats["migrated_requests"],
        "per_replica": stats["per_replica"],
        "tokens": np.concatenate([res[w.rid]["tokens"] for w in work]),
        "results": res,
    }
    # per-SLO-class tail TTFT on each replica's own tick clock (replicas
    # tick in lockstep, so the clocks are comparable)
    if slos is not None:
        by_class: dict[str, list] = {}
        for w, slo in zip(work, slos):
            r = res[w.rid]
            if r["first_token_step"] >= 0:
                by_class.setdefault(slo.name, []).append(
                    r["first_token_step"] - w.arrival)
        for name, vals in by_class.items():
            out[f"ttft_p95_{name}"] = float(np.percentile(vals, 95))
    return out


def replica_sweep(cfg, params, emit, *, counts=(1, 2, 4),
                  placement: str = "load", rate: float = 6.0,
                  n_requests: int = 64, num_slots: int = 4, seed: int = 0):
    """Throughput-vs-replica-count table on ONE heavy-tailed burst trace:
    aggregate tokens/step (total generated over the busiest replica's decode
    clock) and per-SLO-class p95 TTFT at each count, with the per-replica
    breakdown inline. Greedy decoding is asserted token-identical across
    counts — placement moves requests between replicas, never changes what
    they generate. Returns {count: run}."""
    work, slos = make_burst_workload(seed, n_requests, cfg.vocab_size, rate)
    max_len = max(len(w.prompt) + w.target for w in work)
    serving = equal_arena_serving(num_slots, max_len, page_size=8)
    # one never-served engine donates its jit wrappers to every replica of
    # every count — the whole sweep compiles each step function once
    donor = ContinuousServeEngine(cfg, params, serving=serving)
    rows = {}
    for n in counts:
        r = rows[n] = run_router(cfg, params, work, serving, num_replicas=n,
                                 placement=placement, slos=slos, donor=donor)
        assert np.array_equal(rows[counts[0]]["tokens"], r["tokens"]), (
            f"replicas={n} broke greedy token parity vs replicas={counts[0]}")
        breakdown = "|".join(
            f"r{p['replica']}:{p['generated_tokens']}tok"
            f"@{p['tokens_per_step']:.2f}/step" for p in r["per_replica"])
        emit(f"serving_router_n{n}", r["wall_time_s"] * 1e6,
             f"placement={placement};"
             f"agg_tok_per_step={r['tokens_per_step']:.2f};"
             f"steps_max={r['decode_steps_max']};"
             f"ttft_p95_hi={r.get('ttft_p95_interactive', 0.0):.1f};"
             f"ttft_p95_lo={r.get('ttft_p95_batch', 0.0):.1f};"
             f"arena_MiB_total={r['arena_bytes_total'] / 2**20:.3f};"
             f"per_replica={breakdown}")
    base = rows[counts[0]]
    for n in counts[1:]:
        emit(f"serving_router_scaling_n{n}", 0.0,
             f"agg_vs_single={rows[n]['tokens_per_step'] / max(base['tokens_per_step'], 1e-9):.2f}x"
             f" (ideal {n}.0x)")
    return rows


def failure_drill(cfg, params, emit, *, seed: int = 0, rate: float = 6.0,
                  n_requests: int = 48, num_slots: int = 4,
                  smoke: bool = False):
    """Kill a replica mid-burst and measure the recovery: the SAME heavy-
    tailed burst trace through a 2-replica router fault-free (the reference)
    and with an injected crash window on replica 0 (probe auto-drain ->
    snapshot migration -> backoff recovery probe -> re-admission). Reported:
    ticks from auto-drain to re-admission, the goodput dip while degraded
    (tokens/tick at 1 replica vs the fault-free mean), and the robustness
    counters. With ``smoke``: every output delivered exactly once, token
    streams bit-identical to the fault-free run, nothing timed out or shed
    (deadlines off), and the fault-FREE arm keeps the 1.5x
    continuous-vs-static bar on this trace."""
    from repro.serving.faults import FaultEvent, FaultPlan
    from repro.serving.router import ReplicaRouter

    work, slos = make_burst_workload(seed, n_requests, cfg.vocab_size, rate)
    max_len = max(len(w.prompt) + w.target for w in work)
    serving = dataclasses.replace(
        equal_arena_serving(num_slots, max_len, page_size=8),
        probe_interval=2, probe_failures=2, probe_backoff=2, auto_drain=True)
    donor = ContinuousServeEngine(cfg, params, serving=serving)

    def run(plans):
        router = ReplicaRouter(cfg, params, num_replicas=2, serving=serving,
                               placement="load", fault_plans=plans)
        for eng in router.engines:
            eng.adopt_compiled(donor)
        router.reset()
        reqs = [Request(rid=w.rid, prompt=w.prompt, max_new_tokens=w.target,
                        arrival=w.arrival, slo=slos[i])
                for i, w in enumerate(work)]
        t0 = time.time()
        for r in sorted(reqs, key=lambda r: r.arrival):
            router.add_request(r)
        trace = []                    # useful tokens emitted per router tick
        drain_tick = recover_tick = -1
        for t in range(4000):
            if not router.has_unfinished():
                break
            evs = router.step()
            trace.append(sum(1 for e in evs if e.token >= 0))
            if drain_tick < 0 and router._draining:
                drain_tick = t
            if drain_tick >= 0 and recover_tick < 0 and not router._draining:
                recover_tick = t
        else:
            raise AssertionError("failure drill did not converge")
        wall = time.time() - t0
        return router, np.asarray(trace), drain_tick, recover_tick, wall

    # fault-free reference (and the static baseline for the acceptance bar)
    ref_router, ref_trace, _, _, ref_wall = run(None)
    ref_res = ref_router.results()
    st = run_static(cfg, params, work, num_slots, max_len)
    ref_stats = ref_router.stats()
    bar = ref_stats["tokens_per_step"] / max(st["tokens_per_step"], 1e-9)
    emit("serving_failures_reference", ref_wall * 1e6,
         f"agg_tok_per_step={ref_stats['tokens_per_step']:.2f};"
         f"vs_static={bar:.2f}x (target >= 1.5x);"
         f"ticks={len(ref_trace)}")

    # injected run: a crash window opens on replica 0 mid-burst, long enough
    # for the monitor to hit its threshold and short enough to recover
    plan = FaultPlan((FaultEvent(6, "crash", 6),))
    router, trace, drain_tick, recover_tick, wall = run([plan, None])
    res = router.results()
    stats = router.stats()
    assert drain_tick >= 0, "crash window never tripped the auto-drain"
    assert recover_tick > drain_tick, "replica never re-admitted"
    recovery_ticks = recover_tick - drain_tick
    degraded = trace[drain_tick:recover_tick]
    dip = (float(np.mean(degraded)) / max(float(np.mean(ref_trace)), 1e-9)
           if len(degraded) else 1.0)
    emit("serving_failures_injected", wall * 1e6,
         f"recovery_ticks={recovery_ticks};"
         f"goodput_degraded_vs_ref={dip:.2f}x;"
         f"auto_drains={stats['auto_drains']};"
         f"recoveries={stats['recoveries']};"
         f"migrated={stats['migrated_requests']};"
         f"ticks={len(trace)} (+{len(trace) - len(ref_trace)} vs ref)")

    if smoke:
        # exactly-once delivery under the crash: every generated token index
        # seen once and gapless, one finished event per request
        seen: dict[int, list] = {}
        finished: dict[int, int] = {}
        for ev in router.pending_outputs():
            if ev.token >= 0:
                seen.setdefault(ev.rid, []).append(ev.index)
            if ev.finished:
                finished[ev.rid] = finished.get(ev.rid, 0) + 1
        assert set(res) == set(ref_res), "lost or phantom requests"
        for w in work:
            toks = list(ref_res[w.rid]["tokens"])
            assert list(res[w.rid]["tokens"]) == toks, (
                f"rid {w.rid} diverged across the crash (replay not exact)")
            assert sorted(seen.get(w.rid, [])) == list(range(len(toks))), (
                f"rid {w.rid} outputs lost or duplicated")
            assert finished.get(w.rid, 0) == 1
        assert stats["timeouts"] == 0 and stats["shed"] == 0
        assert stats["dense_pages_leaked"] == 0
        assert bar >= 1.5, (
            f"fault-free router {bar:.2f}x vs static < 1.5x floor")
        emit("serving_failures_smoke", 0.0,
             f"PASS exactly-once x{len(work)}; parity bit-exact; "
             f"recovery={recovery_ticks} ticks; bar={bar:.2f}x >= 1.5x")
    return stats


def paged_decode_step_latency(cfg, params, serving: ServingCfg, *,
                              use_paged_kernels: bool, n_iters: int = 30
                              ) -> float:
    """Median per-step decode latency (s) of the jitted continuous decode
    step on a FULL machine: every slot occupied at near-capacity length, so
    the measured work is the per-token cache sweep — fused paged kernels vs
    the jnp gather path at identical arena bytes (same ServingCfg, only the
    kernel flag differs)."""
    rt = dataclasses.replace(cfg.attention, paged_kernels=use_paged_kernels)
    caches = M.init_paged_caches(cfg, rt, serving)
    B, mb = serving.num_slots, serving.max_blocks_per_slot
    assert serving.num_pages > B * mb, "latency probe wants a full machine"
    bt = np.arange(1, B * mb + 1, dtype=np.int32).reshape(B, mb)
    rows = pgc.RowState(
        lengths=jnp.full((B,), serving.page_size * mb - 1, jnp.int32),
        block_table=jnp.asarray(bt),
        active=jnp.ones((B,), bool),
        tier=jnp.zeros((B,), jnp.int32))
    from functools import partial
    decode = jax.jit(partial(M.decode_step_rows, cfg, rt))
    tok = jnp.zeros((B, 1), jnp.int32)
    logits, _ = decode(params, tok, rows, caches)   # compile
    jax.block_until_ready(logits)
    times = []
    for _ in range(n_iters):
        t0 = time.perf_counter()
        logits, _ = decode(params, tok, rows, caches)
        jax.block_until_ready(logits)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def compare_decode_latency(cfg, params, *, num_slots: int = 4,
                           max_len: int = 128, page_size: int = 8,
                           n_iters: int = 30) -> tuple[float, float]:
    """(fused, gather) median decode-step latency at equal arena bytes."""
    serving = equal_arena_serving(num_slots, max_len, page_size)
    fused = paged_decode_step_latency(cfg, params, serving,
                                      use_paged_kernels=True, n_iters=n_iters)
    gather = paged_decode_step_latency(cfg, params, serving,
                                       use_paged_kernels=False, n_iters=n_iters)
    return fused, gather


def mesh_sweep(cfg, params, emit, *, n_requests: int = 10, rate: float = 1.0):
    """1/2/4-way model sharding of the paged arenas on emulated host devices
    (--mesh): per-device arena bytes shrink ~1/mp (each device holds its
    kv-head slice of every page) while tokens/step stays flat — plus the
    interconnect cost (per-head partial concat bytes per generated token),
    mirroring the paper's off-chip-movement accounting. The throughput
    acceptance bar stays on the unsharded path (CPU emulation serializes
    shards, so sharded wall clock is not meaningful here)."""
    from repro.launch.mesh import make_serve_mesh

    # f32: the greedy-parity assert below is token-exact at f32 (the same
    # contract tests/test_serving_sharded.py pins); bf16 argmax ties can flip
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = jax.tree.map(lambda a: a.astype(jnp.float32)
                          if a.dtype == jnp.bfloat16 else a, params)
    work = make_workload(0, n_requests, cfg.vocab_size, rate)
    max_len = max(len(w.prompt) + w.target for w in work)
    serving = equal_arena_serving(4, max_len, page_size=8)
    base_tokens = None
    for mp in (1, 2, 4):
        mesh = make_serve_mesh(1, mp) if mp > 1 else None
        r = run_continuous(cfg, params, work, serving,
                           mode_rt=dataclasses.replace(cfg.attention, mesh=mesh))
        if base_tokens is None:
            base_tokens = r["tokens"]
        else:
            assert np.array_equal(base_tokens, r["tokens"]), (
                f"mesh mp={mp} broke greedy parity vs single device")
        emit(f"serving_mesh_mp{mp}", r["wall_time_s"] * 1e6,
             f"tok_per_step={r['tokens_per_step']:.2f};"
             f"arena_MiB_per_device={r['arena_bytes_per_device'] / 2**20:.3f};"
             f"arena_MiB_total={r['arena_bytes_total'] / 2**20:.3f};"
             f"icnx_B_per_tok={r['interconnect_bytes_per_token']:.1f}")


def main(emit, smoke: bool = False, mesh: bool = False,
         policies=("fifo", "priority", "slo"), replicas: int = 0,
         placement: str = "load", workload: str = "mixed",
         failures: bool = False, speculate: bool = False):
    # kernels interpret exactly when the arrays live on the CPU backend
    interpreted = jax.devices()[0].platform == "cpu"

    cfg = smoke_config(ARCHS["qwen1.5-0.5b"])
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    if speculate:
        # speculative-decoding measurement (low vs high occupancy, on vs
        # off); the throughput suite below is a separate invocation
        speculate_compare(cfg, params, emit, smoke=smoke)
        return
    if failures:
        # fault-injection drill (kill a replica mid-burst, measure recovery);
        # the throughput suite below is a separate invocation
        failure_drill(cfg, params, emit, smoke=smoke)
        return
    if workload == "templated":
        # prefix-sharing measurement on the shared-system-prompt trace; the
        # mixed-traffic suite below is a separate invocation
        templated_compare(cfg, params, emit, smoke=smoke)
        return
    if mesh:
        mesh_sweep(cfg, params, emit)

    # multi-replica router sweep on the heavy-tailed burst trace: aggregate
    # tokens/step and per-class tail TTFT vs replica count
    router_rows = None
    if replicas:
        counts = tuple(sorted({c for c in (1, 2, 4) if c <= replicas}
                              | {replicas}))
        # 96 requests: enough depth per replica that the end-of-trace drain
        # (a ~fixed straggler cost) doesn't cap the measured scaling
        router_rows = replica_sweep(cfg, params, emit, counts=counts,
                                    placement=placement, n_requests=96)
    rates = (1.0,) if smoke else (0.25, 1.0, 4.0)
    n_requests = 12 if smoke else 32
    worst = 0.0
    for rate in rates:
        st, ct = compare(cfg, params, rate=rate, n_requests=n_requests,
                         num_slots=4)
        ratio = ct["tokens_per_step"] / max(st["tokens_per_step"], 1e-9)
        worst = ratio if worst == 0 else min(worst, ratio)
        for r in (st, ct):
            lat = ""
            if "itl_p95" in r:
                lat = (f";ttft_p50={r['ttft_p50']:.1f};ttft_p95={r['ttft_p95']:.1f}"
                       f";itl_p50={r['itl_p50']:.1f};itl_p95={r['itl_p95']:.1f}")
            emit(f"serving_rate{rate}_{r['engine']}", r["wall_time_s"] * 1e6,
                 f"tok_per_step={r['tokens_per_step']:.2f};"
                 f"tok_per_s={r['tokens_per_s']:.1f};"
                 f"lat_mean={r['latency_mean']:.1f};lat_p90={r['latency_p90']:.1f};"
                 f"arena_util={r['arena_utilization']:.3f}" + lat)
        emit(f"serving_rate{rate}_speedup", 0.0,
             f"continuous_vs_static={ratio:.2f}x (target >= 1.5x)")

    # idle-vs-active utilization summary (rate=1.0 run): the measured means
    # bench_e2e_energy folds into its device model so the paged rows charge
    # idle energy honestly (not peak-utilization)
    emit("serving_util_trace", 0.0,
         f"slot_util={ct['slot_utilization']:.3f};"
         f"arena_util_mean={ct['arena_utilization']:.3f};"
         f"ticks={ct['decode_steps']}")

    # scheduler-policy comparison on the mixed-class (interactive vs batch)
    # trace: SLO-attainment %, Jain fairness, per-class tail TTFT
    policy_rows = policy_sweep(cfg, params, emit,
                               n_requests=16 if smoke else 32,
                               policies=policies)

    # chunked vs one-shot admission on long-prompt traffic at equal arena
    # bytes and equal clock quantum — the head-of-line removal measurement
    chunked, oneshot = compare_admission(cfg, params, rate=1.0,
                                         n_requests=n_requests, num_slots=4)
    for r in (chunked, oneshot):
        emit(f"serving_admission_{r['engine']}", r["wall_time_s"] * 1e6,
             f"tok_per_step={r['tokens_per_step']:.2f};"
             f"ttft_p50={r['ttft_p50']:.1f};ttft_p95={r['ttft_p95']:.1f};"
             f"itl_p50={r['itl_p50']:.1f};itl_p95={r['itl_p95']:.1f};"
             f"chunks={r['prefill_chunks']}")
    emit("serving_admission_itl", 0.0,
         f"chunked_vs_oneshot_p95_itl={chunked['itl_p95']:.1f}/"
         f"{oneshot['itl_p95']:.1f} (target <=)")

    # per-step decode latency with/without the fused paged kernels at equal
    # arena bytes — the gather-overhead-removal measurement
    fused, gather = compare_decode_latency(cfg, params, num_slots=4,
                                           max_len=128, page_size=8,
                                           n_iters=10 if smoke else 30)
    emit("serving_decode_step_fused", fused * 1e6,
         f"interpret={interpreted}")
    emit("serving_decode_step_gather", gather * 1e6,
         f"fused_vs_gather={fused / gather:.2f}x (target <= 1.0x on TPU)")

    if smoke:
        assert worst >= 1.5, (
            f"continuous batching speedup {worst:.2f}x < 1.5x acceptance floor")
        # chunked admission must improve the decode tail (p95 ITL) on the
        # mixed-length Poisson workload — the interleave is the whole point
        assert chunked["itl_p95"] <= oneshot["itl_p95"], (
            f"chunked p95 ITL {chunked['itl_p95']:.1f} worse than one-shot "
            f"{oneshot['itl_p95']:.1f}")
        emit("serving_admission_smoke", 0.0,
             f"PASS itl_p95 {chunked['itl_p95']:.1f} <= {oneshot['itl_p95']:.1f}")
        if {"fifo", "priority"} <= set(policy_rows):
            # priority scheduling must strictly improve the high class's
            # tail TTFT over FIFO on the mixed trace — without giving back
            # the continuous-batching throughput bar vs the static engine
            hi_f = policy_rows["fifo"]["ttft_p95_interactive"]
            hi_p = policy_rows["priority"]["ttft_p95_interactive"]
            assert hi_p < hi_f, (
                f"priority p95 interactive TTFT {hi_p:.1f} not better than "
                f"fifo {hi_f:.1f}")
            bar = (policy_rows["priority"]["tokens_per_step"]
                   / max(policy_rows["static"]["tokens_per_step"], 1e-9))
            assert bar >= 1.5, (
                f"priority policy throughput {bar:.2f}x vs static < 1.5x")
            emit("serving_policy_smoke", 0.0,
                 f"PASS ttft_p95_hi {hi_p:.1f} < {hi_f:.1f} (fifo); "
                 f"throughput {bar:.2f}x >= 1.5x")
        if not interpreted:
            # compiled kernels: fused decode must not be slower than
            # materializing the logical views (small timer slack)
            assert fused <= gather * 1.05, (
                f"fused paged-kernel decode {fused * 1e3:.2f}ms slower than "
                f"gather path {gather * 1e3:.2f}ms at equal arena bytes")
            emit("serving_kernel_smoke", 0.0,
                 f"PASS fused_vs_gather={fused / gather:.2f}x")
        else:
            # interpret mode emulates the kernel op-by-op — timing it would
            # benchmark the emulator, not the kernel; report only
            emit("serving_kernel_smoke", 0.0,
                 "SKIP latency bar (interpret mode; compiled-TPU only)")
        if router_rows is not None and len(router_rows) > 1:
            counts = sorted(router_rows)
            hi, lo = counts[-1], counts[0]
            scale = (router_rows[hi]["tokens_per_step"]
                     / max(router_rows[lo]["tokens_per_step"], 1e-9))
            # data-parallel scale-out bar: 4 replicas must deliver >= 3x the
            # single-replica aggregate tokens/step on the burst trace
            floor = 3.0 if hi >= 4 * max(lo, 1) else 0.75 * hi / max(lo, 1)
            assert scale >= floor, (
                f"router scaling {scale:.2f}x at {hi} replicas < "
                f"{floor:.1f}x floor")
            emit("serving_router_smoke", 0.0,
                 f"PASS n{hi}_vs_n{lo}={scale:.2f}x >= {floor:.1f}x; "
                 f"ttft_p95_hi={router_rows[hi].get('ttft_p95_interactive', 0.0):.1f}")
        emit("serving_smoke", 0.0, f"PASS speedup={worst:.2f}x")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="one small rate; asserts the >=1.5x acceptance bar")
    ap.add_argument("--mesh", action="store_true",
                    help="sweep 1/2/4-way model sharding of the paged arenas "
                         "on emulated host devices (reports per-device arena "
                         "bytes, tokens/step, interconnect bytes/token)")
    ap.add_argument("--policy", default="all",
                    choices=["all", "fifo", "priority", "slo"],
                    help="scheduler policies to compare on the mixed-class "
                         "trace (SLO-attainment %% / Jain fairness table); "
                         "default runs all three")
    ap.add_argument("--replicas", type=int, default=0,
                    help="sweep the multi-replica router at 1..N replicas "
                         "(subset of {1,2,4} plus N) on a heavy-tailed burst "
                         "trace; with --smoke, 4 replicas must hit >= 3x the "
                         "single-replica aggregate tokens/step (0 = skip)")
    ap.add_argument("--placement", default="load",
                    choices=["rr", "load", "slo"],
                    help="router placement policy for --replicas")
    ap.add_argument("--failures", action="store_true",
                    help="fault-injection drill: the burst trace through a "
                         "2-replica router fault-free vs with a crash window "
                         "on replica 0 (auto-drain -> migrate -> recover); "
                         "reports recovery ticks + goodput dip; with --smoke "
                         "asserts exactly-once delivery, bit-exact parity "
                         "with the fault-free run, and the 1.5x bar on the "
                         "fault-free arm")
    ap.add_argument("--speculate", action="store_true",
                    help="speculative-decoding arm: spec on vs off at equal "
                         "arena bytes on a serialized low-occupancy trace "
                         "(where decode is weight-stream-bound and accepted "
                         "drafts cut ITL) and the mixed high-occupancy trace "
                         "(reported honestly as a loss — batching already "
                         "amortizes the weight stream); with --smoke asserts "
                         "greedy bit-parity on-vs-off, the low-occupancy ITL "
                         "win, and the 1.5x continuous-vs-static bar")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump every emitted row (name, us, parsed "
                         "derived metrics) as JSON to PATH")
    ap.add_argument("--workload", default="mixed",
                    choices=["mixed", "templated"],
                    help="'templated' runs the shared-system-prompt "
                         "multi-turn trace with prefix sharing on vs off "
                         "(prefill bytes written/request, shared-page "
                         "fraction, TTFT p95); with --smoke the shared arm "
                         "must strictly improve TTFT p95 and prefill bytes "
                         "and keep the 1.5x continuous-vs-static bar")
    args = ap.parse_args()

    rows = []

    def emit(name, us, derived=""):
        print(f"{name},{us:.2f},{derived}")
        rows.append({"name": name, "us": round(us, 2), "derived": derived})

    def _parse_derived(derived: str) -> dict:
        """'k=v;k=v' derived strings -> {k: float|str} (units like 'x' or
        trailing prose stripped where the value parses as a number)."""
        out = {}
        for part in derived.split(";"):
            if "=" not in part:
                continue
            k, v = part.split("=", 1)
            k = k.strip()
            if not k.isidentifier():
                continue    # trailing prose like "(target >= 1.5x)"
            v = v.strip().split()[0] if v.strip() else ""
            try:
                out[k] = float(v.rstrip("x%"))
            except ValueError:
                out[k] = v
        return out

    pols = (("fifo", "priority", "slo") if args.policy == "all"
            else (args.policy,))
    main(emit, smoke=args.smoke, mesh=args.mesh, policies=pols,
         replicas=args.replicas, placement=args.placement,
         workload=args.workload, failures=args.failures,
         speculate=args.speculate)

    if args.json:
        import json

        for r in rows:
            r["metrics"] = _parse_derived(r["derived"])
        with open(args.json, "w") as f:
            json.dump({"bench": "serving", "argv": sys.argv[1:],
                       "rows": rows}, f, indent=1)
        print(f"[bench_serving] wrote {len(rows)} rows to {args.json}")
