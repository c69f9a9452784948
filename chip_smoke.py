#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU: Qwen1.5-0.5B at its published
widths (24 layers, d_model 1024, 16 MHA heads, Dh 64, FF 2816, vocab
151936, bf16, random weights from ``--seed``) served by
``ContinuousServeEngine`` with the fused paged Pallas kernels compiled.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the sharded path on four chips

One chip: every paged kernel on the serving path (flash, CPQ and decomposed;
decode and chunked prefill) is compiled at these widths and checked against
its ``ref.py`` oracle; then 8 greedy requests (prompts of 128-512 tokens, 32
new tokens each, 8 slots, page 16, prefill chunks of 128) are served in each
of the dense, decomposed (T1) and cpq (T2) modes, twice. Four chips: the
dense engine on a (1, 4) mesh, arenas sharded over the 16 kv heads, against
the one-chip engine in the same process; nothing else runs.

Everything runs in this one process. It fails (nonzero exit, no result
line) when JAX finds no TPU, when run without the repository around it,
when a request does not finish, when any logits are NaN or Inf, when a
kernel misses its oracle, or when token streams disagree. Times printed are
a smoke run's, compilation included where labelled; they are not a
benchmark. The last line of standard output is the JSON result.

The JAX compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR`` when that
is set, else in ``.jax_cache/`` next to this script.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen1.5-0.5b"
SLOTS, PAGE, CHUNK, NEW = 8, 16, 128, 32
PROMPT_MIN, PROMPT_MAX = 128, 512
MODES = ("dense", "decomposed", "cpq")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Seconds XLA spends compiling (JAX's backend-compile monitoring
    event; a persistent-cache hit records none)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **_):
        if event == self.EVENT:
            self.total += secs


# ---------------------------------------------------------------- kernels


def check_kernels(jax, seed: int) -> None:
    """Each paged kernel of the serving path, compiled through the same
    platform dispatch the engine uses, against its pure-jnp oracle on
    random bf16 operands at the model's widths."""
    import jax.numpy as jnp
    import numpy as np

    from repro import kernels as K
    from repro.kernels.cpq_dequant_attn import kernel as ck, ref as cr
    from repro.kernels.decomposed_attn import kernel as dk, ref as dr
    from repro.kernels.flash_attn import kernel as fk, ref as fr

    H = KV = 16
    Dh, Dm, R, L = 64, 1024, 32, 4
    nb = -(-(PROMPT_MAX + NEW) // PAGE)
    P = SLOTS * nb + 1
    rng = np.random.default_rng(seed)
    bf = jnp.bfloat16

    def normal(*shape, dtype=bf):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32), dtype)

    # ragged rows over permuted physical pages, one empty row
    lengths = rng.integers(1, nb * PAGE + 1, size=SLOTS).astype(np.int32)
    lengths[-1] = 0
    perm = rng.permutation(np.arange(1, P))
    table = np.zeros((SLOTS, nb), np.int32)
    for b in range(SLOTS):
        used = -(-int(lengths[b]) // PAGE)
        table[b, :used] = perm[b * nb:b * nb + used]
    offset, valid = 256, 100            # a continuation chunk with padding
    row = np.zeros((nb,), np.int32)
    row[:-(-(offset + valid) // PAGE)] = perm[:-(-(offset + valid) // PAGE)]
    table, lengths, row = map(jnp.asarray, (table, lengths, row))
    off, val = jnp.asarray(offset, jnp.int32), jnp.asarray(valid, jnp.int32)

    def codes():
        return jnp.asarray(rng.integers(-128, 128, size=(P, PAGE, KV, Dh)),
                           jnp.int8)

    def levels():
        return jnp.asarray(rng.integers(0, L, size=(P, PAGE, KV)), jnp.int32)

    def side(b, zero=False):
        # HQE scale/zero: dequantized values stay O(1)
        x = rng.normal(size=(b, L, KV, Dh))
        return jnp.asarray(x * 0.1 if zero else np.abs(x) * 4e-3 + 1e-3,
                           jnp.float32)

    kp, vp = normal(P, PAGE, KV, Dh), normal(P, PAGE, KV, Dh)
    xp, krp = normal(P, PAGE, Dm), normal(P, PAGE, KV, R)
    cases = {
        "flash_decode": (fk.paged_flash_decode_fwd, fr.paged_flash_decode_ref,
                         (normal(SLOTS, 1, H, Dh), kp, vp, table, lengths),
                         Dh ** -0.5, lambda o: o),
        "flash_prefill": (fk.paged_flash_prefill_fwd,
                          fr.paged_flash_prefill_ref,
                          (normal(1, CHUNK, H, Dh), kp, vp, row, off, val),
                          Dh ** -0.5, lambda o: o[0, :valid]),
        "cpq_decode": (ck.paged_cpq_decode_fwd, cr.paged_cpq_decode_ref,
                       (normal(SLOTS, KV, H // KV, Dh), codes(), codes(),
                        side(SLOTS), side(SLOTS, True), side(SLOTS),
                        side(SLOTS, True),
                        levels(), levels(), table, lengths),
                       Dh ** -0.5, lambda o: o),
        "cpq_prefill": (ck.paged_cpq_prefill_fwd, cr.paged_cpq_prefill_ref,
                        (normal(1, KV, CHUNK * H // KV, Dh), codes(), codes(),
                         side(1), side(1, True), side(1), side(1, True),
                         levels(),
                         levels(), normal(CHUNK, KV, Dh),
                         normal(CHUNK, KV, Dh), row, off, val),
                        Dh ** -0.5, lambda o: o[0, :, :valid]),
        "decomposed_decode": (dk.paged_decomposed_decode_fwd,
                              dr.paged_decomposed_decode_ref,
                              (normal(SLOTS, H, Dm), normal(SLOTS, H, R), xp,
                               krp, table, lengths),
                              Dm ** -0.5, lambda o: o),
        "decomposed_prefill": (dk.paged_decomposed_prefill_fwd,
                               dr.paged_decomposed_prefill_ref,
                               (normal(CHUNK, H, Dm), normal(CHUNK, H, R), xp,
                                krp, row, off, val),
                               Dm ** -0.5, lambda o: o[:valid]),
    }
    for name, (fwd, ref, args, scale, live) in cases.items():
        step = jax.jit(lambda *a, fwd=fwd, scale=scale: K.platform_call(
            fwd, *a, scale=scale))
        compiled = step.lower(*args).compile()
        if "tpu_custom_call" not in compiled.as_text():
            raise RuntimeError(f"{name}: no compiled Mosaic kernel in the "
                               "program (interpreted?)")
        got = np.asarray(live(compiled(*args)), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(live(jax.jit(
                lambda *a, ref=ref, scale=scale: ref(*a, scale))(*args)),
                np.float32)
        if not np.isfinite(got).all():
            raise RuntimeError(f"{name}: non-finite kernel output")
        err = np.abs(got - want)
        tol = 2e-2 + 2e-2 * np.abs(want)
        if not (err <= tol).all():
            raise RuntimeError(f"{name}: max |kernel - oracle| "
                               f"{float(err.max()):.3e} misses the bf16 "
                               "tolerance 2e-2 + 2e-2*|oracle|")
        log(f"kernel {name}: compiled (Mosaic), max |kernel - oracle| "
            f"{float(err.max()):.3e} within 2e-2 + 2e-2*|oracle|")


# ---------------------------------------------------------------- serving


def make_prompts(seed: int, vocab: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    lengths = [PROMPT_MIN, PROMPT_MAX] + rng.integers(
        PROMPT_MIN, PROMPT_MAX + 1, size=SLOTS - 2).tolist()
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lengths]


def serve_once(eng, prompts, vocab: int, label: str, clock):
    """Serve every prompt greedily to NEW tokens; check each finished with
    NEW in-vocabulary tokens from finite logits and that no page leaked.
    Returns the token streams in request order."""
    import numpy as np

    from repro.serving import GenerationConfig
    from repro.serving.scheduler import Request

    reqs = [Request(rid=i, prompt=p, max_new_tokens=NEW)
            for i, p in enumerate(prompts)]
    c0, t0 = clock.total, time.perf_counter()
    results, stats = eng.serve(reqs, GenerationConfig(max_new_tokens=NEW))
    wall = time.perf_counter() - t0
    streams = []
    for i in range(len(prompts)):
        r = results.get(i)
        if r is None or r["finish_reason"] != "max_tokens" \
                or len(r["tokens"]) != NEW:
            raise RuntimeError(f"{label}: request {i} did not finish "
                               f"({None if r is None else r['finish_reason']})")
        toks = np.asarray(r["tokens"])
        if ((toks < 0) | (toks >= vocab)).any():
            raise RuntimeError(f"{label}: request {i} emitted ids outside "
                               "the vocabulary")
        streams.append(toks)
    if stats["nonfinite_logit_rows"]:
        raise RuntimeError(f"{label}: {stats['nonfinite_logit_rows']} "
                           "sampled rows had NaN/Inf logits")
    if stats["dense_pages_leaked"] or stats["cpq_pages_leaked"]:
        raise RuntimeError(f"{label}: arena pages leaked")
    log(f"{label}: {len(prompts)}/{len(prompts)} requests finished, "
        f"{stats['generated_tokens']} tokens, {stats['prefill_chunks']} "
        f"prefill chunks, {stats['decode_steps']} decode steps; wall "
        f"{wall:.2f} s, XLA compile {clock.total - c0:.2f} s "
        "(smoke run, not a benchmark)")
    return streams


def serve_twice(eng, prompts, vocab: int, label: str, clock):
    """Cold then warm: the warm pass must reproduce the greedy streams."""
    cold = serve_once(eng, prompts, vocab, f"{label} (cold)", clock)
    warm = serve_once(eng, prompts, vocab, f"{label} (warm)", clock)
    if any((a != b).any() for a, b in zip(cold, warm)):
        raise RuntimeError(f"{label}: warm greedy streams differ from cold")
    return warm


def common_prefix(a, b) -> int:
    """How many leading tokens two equal-length greedy streams share."""
    diff = (a != b).nonzero()[0]
    return int(diff[0]) if diff.size else len(a)


def serving_cfg(n_prompts: int):
    from repro.configs import ServingCfg
    from repro.serving.paged_cache import pages_needed

    nb = pages_needed(PROMPT_MAX + NEW, PAGE)
    return ServingCfg(num_slots=SLOTS, page_size=PAGE,
                      num_pages=n_prompts * nb + 1, max_blocks_per_slot=nb,
                      prefill_bucket=PAGE, prefill_chunk=CHUNK)


def one_chip(jax, seed: int, clock) -> None:
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serving import ContinuousServeEngine

    check_kernels(jax, seed)
    cfg = get_config(ARCH)
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    prompts = make_prompts(seed, cfg.vocab_size)
    log(f"prompt lengths {[len(p) for p in prompts]}, {NEW} new tokens "
        f"each, {SLOTS} slots, page {PAGE}, prefill chunk {CHUNK}")
    streams = {}
    for mode in MODES:
        eng = ContinuousServeEngine(cfg.with_attention(mode), params,
                                    serving=serving_cfg(len(prompts)))
        streams[mode] = serve_twice(eng, prompts, cfg.vocab_size,
                                    f"serve {mode}", clock)
    for mode in MODES[1:]:
        lead = [common_prefix(a, b)
                for a, b in zip(streams[mode], streams["dense"])]
        log(f"{mode} vs dense: leading greedy tokens in common per request "
            f"{lead} of {NEW} (information only: on a RoPE model T1 ropes "
            "a 32-dim slice of each head (decoupled rope), T2 is lossy)")


def four_chips(jax, seed: int, clock) -> None:
    from repro.configs import get_config
    from repro.launch.mesh import make_serve_mesh
    from repro.models import model as M
    from repro.serving import ContinuousServeEngine

    cfg = get_config(ARCH).with_attention("dense")
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    prompts = make_prompts(seed, cfg.vocab_size)
    serving = serving_cfg(len(prompts))
    ref = serve_twice(ContinuousServeEngine(cfg, params, serving=serving),
                      prompts, cfg.vocab_size, "serve dense, one chip", clock)
    eng = ContinuousServeEngine(cfg, params, serving=serving,
                                mesh=make_serve_mesh(1, 4))
    got = serve_twice(eng, prompts, cfg.vocab_size,
                      "serve dense, mesh (1,4)", clock)
    st = eng.stats()
    log(f"mesh (1,4): arena {st['arena_bytes_per_device'] / 2**20:.1f} MiB "
        f"per device of {st['arena_bytes_total'] / 2**20:.1f} MiB")
    lead = [common_prefix(a, b) for a, b in zip(got, ref)]
    same = sum(n == NEW for n in lead)
    log(f"mesh (1,4) vs one chip: {same}/{len(lead)} identical greedy "
        f"streams; leading tokens in common per request {lead}")
    if same != len(lead):
        raise RuntimeError("sharded token streams differ from the one-chip "
                           "engine")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro beside {Path(__file__).name}; run it "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this script has no CPU fallback", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    log(f"device {dev.platform} kind={dev.device_kind!r} count={len(devices)}; "
        "paged kernels run compiled (interpreted only on the CPU backend)")
    clock = CompileClock(jax)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(jax, args.seed, clock)
    else:
        one_chip(jax, args.seed, clock)
    log(f"total wall {time.perf_counter() - t0:.1f} s, XLA compile "
        f"{clock.total:.1f} s (smoke run, not a benchmark)")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
