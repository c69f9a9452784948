"""Serving engines: the paper's end-to-end inference path.

``ServeEngine`` — the original static-batch engine (kept as the back-compat
baseline and as the tests' reference): one right-padded batch runs prefill then
a jitted decode loop to completion; every row owns a contiguous
``(n_max, ...)`` arena slice for the whole run.

``ContinuousServeEngine`` — continuous batching over block-paged arenas
(serving/paged_cache.py) driven by the host-side scheduler
(serving/scheduler.py): requests are admitted into vacated slots as soon as
pages are free, every row decodes at its own position (one jitted step over
per-row lengths), rows retire at EOS / stop tokens and free their pages
immediately, and the memory watermark policy escalates cache tiers
(dense -> T2 CPQ) under pressure — the paper's "dynamically compress and
prune" story operationalized at the request level.

The continuous engine's primary interface is request-centric (vLLM-style):

    eng.add_request(ServeRequest(prompt, sampling=SamplingParams(...),
                                 slo=INTERACTIVE), stream=callback)
    while eng.has_unfinished():
        for out in eng.step():        # one tick; incremental RequestOutputs
            ...

Sampling is per request — ``SamplingParams`` vectorize into per-row
temperature/top-k/top-p/seed arrays consumed by ONE jitted sampler
(``sample_token_rows``); greedy rows take the same argmax as ever,
bit-identically. Scheduling decisions (admission order, tier assignment,
preemption victims, escalation / de-escalation) come from the pluggable
``SchedulerPolicy`` (serving/policies.py). ``serve(requests, gen)`` and
``generate(batch, gen)`` remain as thin batch-shaped wrappers over
add_request()/step() — their greedy outputs are token-identical to the
pre-request-API engine.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AttentionRuntime, CPQCfg, ModelConfig, ServingCfg
from repro.models import model as M
from repro.serving import paged_cache as pgc
from repro.serving.request import RequestOutput, SamplingParams, ServeRequest
from repro.serving.scheduler import Request, Scheduler, SchedulerConfigError

# Host spans of the serving loop. A span costs about half a microsecond
# while no profiler session is open, and lands on the trace's host plane, on
# the device planes' clock, when one is.
_span = jax.profiler.TraceAnnotation


def _named_jit(fn, *bound, donate: bool = False):
    """``jax.jit(partial(fn, *bound))`` that keeps ``fn``'s name: the
    compiled program reaches the profiler as ``jit_<fn name>(...)``, where a
    bare ``partial`` shows as ``jit__unknown``. With ``donate`` the call may
    reuse the buffers of its ``caches`` argument for its output; the caller
    must not read what it passed there again."""
    f = partial(fn, *bound)
    f.__name__ = f.__qualname__ = fn.__name__
    return jax.jit(f, donate_argnames="caches" if donate else None)


def _device_memory() -> dict:
    """Device 0's ``bytes_in_use`` and ``peak_bytes_in_use``, the args of the
    once-a-session ``serve.reset``/``serve.release`` spans; empty where the
    backend keeps no memory stats (the CPU)."""
    m = jax.local_devices()[0].memory_stats() or {}
    return {k: m[k] for k in ("bytes_in_use", "peak_bytes_in_use") if k in m}


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0      # 0 => greedy
    top_p: float = 1.0
    eos_id: int = -1              # -1 => never stop early
    seed: int = 0


def sample_tokens(logits: jax.Array, key, gen: GenerationConfig) -> jax.Array:
    """(B, V) logits -> (B,) int32 samples (greedy / temperature / top-p)."""
    if gen.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / gen.temperature
    if gen.top_p < 1.0:
        sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        k = jnp.sum(cum < gen.top_p, axis=-1, keepdims=True)
        thresh = jnp.take_along_axis(sorted_l, k, axis=-1)
        logits = jnp.where(logits < thresh, -1e30, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def sample_token_rows(logits: jax.Array, temps: jax.Array, top_ks: jax.Array,
                      top_ps: jax.Array, seeds: jax.Array,
                      indices: jax.Array) -> jax.Array:
    """Vectorized per-request sampler: (B, V) logits + per-row (B,) arrays of
    temperature / top-k / top-p / seed -> (B,) int32 tokens, one jitted call
    for the whole mixed batch.

    Greedy rows (``temps <= 0``) take ``jnp.argmax`` over the unmodified
    logits — bit-identical to the engine-global greedy path. Sampled rows
    filter per row (``top_k == 0`` / ``top_p == 1`` disable a filter) and
    draw with ``fold_in(PRNGKey(seed_r), index_r)`` where ``indices`` is the
    token's position in the request's generated stream: the draw is a
    function of the request alone — independent of slot placement, the
    co-resident batch, and preemption history (recompute replays the same
    keys)."""
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    l = logits.astype(jnp.float32) / jnp.where(temps > 0, temps, 1.0)[:, None]
    # per-row top-k: mask everything below the k-th largest (k = V when off)
    desc = jnp.sort(l, axis=-1)[:, ::-1]
    k = jnp.clip(jnp.where(top_ks > 0, top_ks, V), 1, V)
    kth = jnp.take_along_axis(desc, (k - 1)[:, None], axis=-1)
    l = jnp.where(l < kth, -1e30, l)
    # per-row top-p over the top-k-filtered distribution (same nucleus
    # construction as the legacy global sampler)
    desc = jnp.sort(l, axis=-1)[:, ::-1]
    cum = jnp.cumsum(jax.nn.softmax(desc, axis=-1), axis=-1)
    j = jnp.sum(cum < top_ps[:, None], axis=-1, keepdims=True)
    thresh = jnp.take_along_axis(desc, j, axis=-1)  # jax clamps j == V
    l = jnp.where(l < thresh, -1e30, l)
    keys = jax.vmap(lambda s, i: jax.random.fold_in(jax.random.PRNGKey(s), i))(
        seeds, indices)
    sampled = jax.vmap(jax.random.categorical)(keys, l).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, sampled)


def greedy_token_rows(logits: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(B, V) logits -> ((B,) int32 argmax, (B,) bool all-finite): the
    greedy draw and the logits health check in one device call."""
    return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
            jnp.isfinite(logits).all(axis=-1))


# --------------------------------------------------------------- static engine


class ServeEngine:
    """Static-batch engine: fixed batch, right-padded prompts, run to
    completion. Kept as the contiguous-arena baseline."""

    def __init__(self, cfg: ModelConfig, params, rt: Optional[AttentionRuntime] = None,
                 max_len: int = 4096):
        self.cfg = cfg
        self.rt = rt or cfg.attention
        self.params = params
        self.max_len = max_len
        self._prefill = _named_jit(M.prefill, cfg, self.rt)
        self._decode = _named_jit(M.decode_step, cfg, self.rt)

    def _sample(self, logits: jax.Array, key, gen: GenerationConfig) -> jax.Array:
        return sample_tokens(logits, key, gen)

    def generate(self, batch: dict, gen: GenerationConfig = GenerationConfig()):
        """batch: {'tokens': (B, S)} (+frames/patches per input_kind).
        Returns (generated (B, max_new_tokens) int32, stats dict)."""
        cfg = self.cfg
        prompt = batch.get("tokens", batch.get("frames"))
        B, S = prompt.shape[0], prompt.shape[1]
        n_max = S + gen.max_new_tokens
        assert n_max <= self.max_len + gen.max_new_tokens

        caches = M.init_caches(cfg, self.rt, B, n_max)
        logits, caches = self._prefill(self.params, batch, caches)

        key = jax.random.PRNGKey(gen.seed)
        toks = []
        done = jnp.zeros((B,), bool)
        live_tokens = 0
        decode_calls = 0
        tok = self._sample(logits, key, gen)
        for t in range(gen.max_new_tokens):
            if gen.eos_id >= 0:
                # rows past their EOS emit eos_id, not fresh samples
                tok = jnp.where(done, gen.eos_id, tok)
            toks.append(np.asarray(tok))
            live_tokens += int(jnp.sum(~done))  # EOS itself counts; padding doesn't
            if gen.eos_id >= 0:
                done = done | (tok == gen.eos_id)
                if bool(jnp.all(done)):
                    break
            if t == gen.max_new_tokens - 1:
                break  # the last appended token needs no further decode
            key, sub = jax.random.split(key)
            logits, caches = self._decode(self.params, tok[:, None],
                                          jnp.asarray(S + t, jnp.int32), caches)
            decode_calls += 1
            tok = self._sample(logits, sub, gen)
        out = np.stack(toks, axis=1)
        stats = {
            "prompt_tokens": int(B * S),
            "generated_tokens": live_tokens,
            "decode_steps": decode_calls,
            "cache_mode": self.rt.mode,
        }
        return out, stats


# ----------------------------------------------------------- continuous engine


class _ServeState:
    """Mutable per-session serving state behind ``add_request()``/``step()``:
    the scheduler, the paged cache pytree, per-slot sampling-parameter
    arrays, the tick clock, counters, and the pending-output buffer. One
    ``serve()`` call owns exactly one (it resets); step-API users keep one
    across calls until ``reset()``."""

    def __init__(self, eng: "ContinuousServeEngine", gen: "GenerationConfig"):
        B = eng.serving.num_slots
        self.gen = gen
        self.sched = Scheduler(eng.serving, eng.tiered,
                               policy=eng.make_policy(),
                               share_prefix=eng.share_prefix)
        self.caches = M.init_paged_caches(eng.cfg, eng.rt, eng.serving,
                                          eng.tiered)
        if eng.mesh is not None:
            # place the arenas per the paged cache specs: kv-head / latent
            # feature axes over "model", pools and slot state replicated
            self.caches = jax.device_put(self.caches, eng._cache_shardings)
        self.last_tok = np.zeros((B,), np.int32)
        # per-slot sampling parameters, vectorized for the jitted sampler
        # (rows overwritten on admission; inactive rows' samples are unused)
        self.temp = np.zeros((B,), np.float32)
        self.top_k = np.zeros((B,), np.int32)
        self.top_p = np.ones((B,), np.float32)
        self.seed = np.zeros((B,), np.int32)
        self.results: dict[int, dict] = {}
        self.outputs: list[RequestOutput] = []       # pending (undrained)
        self.step_outputs: list[RequestOutput] = []  # this tick's events
        self.next_rid = 0
        self.step = 0                 # model-invocation tick clock
        # ticks a one-shot admission charges per quantum of padded prompt
        self.quantum = eng.serving.prefill_chunk or eng.serving.prefill_bucket
        self.decode_steps = self.live_steps = self.prefill_chunks = 0
        self.prefill_tokens = self.generated = 0
        self.nonfinite_rows = 0       # sampled rows whose logits had NaN/Inf
        self.util_peak = self.util_sum = 0.0
        self.util_n = 0
        self.defrag_mark = 0          # retirements at the last compaction
        self.has_deadlines = False    # any finite request deadline admitted
        self.t0 = time.time()


class ContinuousServeEngine:
    """Continuous batching over block-paged arenas.

    One engine instance holds the jitted step functions. The request-centric
    interface is ``add_request()`` + ``step()`` (one engine tick per call,
    returning that tick's incremental ``RequestOutput`` events);
    ``serve(requests, gen)`` wraps it batch-style — it resets the session,
    submits everything, and drains. The decode clock is the simulation time
    base: a request with ``arrival=t`` becomes admissible after t decode
    steps (tests feed simulated arrivals in these units; online use passes
    0.0). ``policy`` (object, or via ``ServingCfg.policy`` name)
    selects the scheduling policy; the default FIFO policy plus greedy
    sampling reproduces the pre-request-API engine token-exactly.
    """

    def __init__(self, cfg: ModelConfig, params, rt: Optional[AttentionRuntime] = None,
                 serving: ServingCfg = ServingCfg(), mesh=None, policy=None):
        self.cfg = cfg
        self.params = params
        self.serving = serving
        try:
            # full cross-knob validation up front: a bad combination fails
            # HERE with the knob names spelled out, not deep in the scheduler
            serving.validate()
        except ValueError as e:
            raise SchedulerConfigError(str(e)) from None
        rt = rt or cfg.attention
        if mesh is not None:
            if getattr(rt, "mesh", None) is not None and rt.mesh != mesh:
                raise SchedulerConfigError(
                    "conflicting device meshes: rt.mesh and the mesh= "
                    "argument disagree — set one or make them equal")
            rt = dataclasses.replace(rt, mesh=mesh)
        self.mesh = getattr(rt, "mesh", None)
        self.tiered = bool(serving.enable_escalation and rt.mode == "dense")
        if self.tiered and rt.cpq is None:
            rt = dataclasses.replace(rt, cpq=CPQCfg())
        if self.tiered and any(m == "mla" for m, _ in cfg.layer_kinds):
            raise SchedulerConfigError(
                "tier escalation supports plain-attention stacks only "
                "(MLA already caches the compressed latent)")
        if cfg.input_kind != "tokens":
            raise SchedulerConfigError(
                "continuous serving drives token prompts; "
                f"input_kind={cfg.input_kind!r} needs the static engine")
        self.rt = rt
        # mesh-native serving: validate the model axis divides every head /
        # latent axis it shards, pin the replicated params once, and build
        # the fitted NamedSharding tree the paged arenas are placed with
        from repro.serving import sharded as _sharded

        self.model_shards = _sharded.validate_serve_mesh(cfg, rt, self.tiered)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as PS

            from repro.distributed.cache_specs import paged_cache_pspecs
            from repro.distributed.sharding import fit_spec_to_shape

            self.params = jax.device_put(
                params, NamedSharding(self.mesh, PS()))
            shapes = jax.eval_shape(partial(M.init_paged_caches, cfg, rt,
                                            serving, self.tiered))
            specs = paged_cache_pspecs(cfg, rt, serving, self.tiered)
            self._cache_shardings = jax.tree.map(
                lambda sp, a: NamedSharding(
                    self.mesh, fit_spec_to_shape(sp, a.shape, self.mesh)),
                specs, shapes, is_leaf=lambda x: isinstance(x, PS))
        # recurrent mixers integrate every prefill token into their state, so
        # bucket padding would pollute it (attention only masks); those archs
        # prefill at exact lengths (more jit variants, exact math)
        self._exact_prefill = any(m in ("mamba", "mlstm", "slstm")
                                  for m, _ in cfg.layer_kinds)
        # the steps donate the arenas only where every scanned cache rides in
        # the layer scan's carry: then each call updates the arena in place;
        # donating a cache that is sliced through the scan would add copies
        self.donates = M.carries_arenas(jax.eval_shape(partial(
            M.init_paged_caches, cfg, rt, serving, self.tiered)))
        self._decode = _named_jit(M.decode_step_rows, cfg, rt,
                                  donate=self.donates)
        self._pack = _named_jit(M.pack_prefill_caches, cfg, rt,
                                donate=self.donates)
        self._escalate = _named_jit(M.escalate_slot, cfg, rt,
                                    donate=self.donates)
        self._defrag = _named_jit(M.defrag_caches, cfg, rt,
                                  donate=self.donates)
        self._prefills: dict[str, object] = {}   # one-shot oracle path only
        self._chunk_fns: dict[tuple[int, bool], object] = {}
        # recurrent mixers keep the exact one-shot admission: they integrate
        # every token into O(1) state that cannot be cut at page boundaries.
        # Everything else streams chunks into the arena (the serving expert
        # layer is dropless and batch-independent, so MoE stacks chunk too).
        self.chunked = bool(serving.prefill_chunk) and not self._exact_prefill
        # prefix sharing + copy-on-write: chunked admissions only (the tail
        # streams from a mid-context offset), and only for modes whose BASE
        # arena payload is purely positional — dense, decomposed (T1), MLA
        # latent, and the tiered engine's dense arm. CPQ / retrieval pages
        # read through per-slot side state fitted to ONE request's stream,
        # so mounting them under another slot would break bit-parity.
        self.share_prefix = (bool(getattr(serving, "share_prefix", False))
                             and self.chunked
                             and rt.mode in ("dense", "decomposed"))
        # speculative decoding (serving/speculative.py): same gate family as
        # prefix sharing — the verify chunk IS a chunked paged forward pass,
        # and draft scratch pages carry purely positional payload. Tiered
        # engines speculate on tier-0 rows only (_spec_eligible).
        self.spec_on = (serving.spec_len > 0 and self.chunked
                        and rt.mode in ("dense", "decomposed"))
        self._verify_fns: dict[int, object] = {}
        self._copy_page = _named_jit(M.copy_page_caches, cfg, rt,
                                     donate=self.donates)
        self.policy = policy          # object/str override of serving.policy
        self._sample_rows = jax.jit(sample_token_rows)
        self._greedy_rows = jax.jit(greedy_token_rows)
        self._st: Optional[_ServeState] = None

    def make_policy(self):
        """Resolve the scheduling policy: an explicit object wins, a string
        (constructor arg or ``ServingCfg.policy``) goes through the
        factory. Called once per serving session (``reset``)."""
        from repro.serving.policies import make_policy

        if self.policy is None:
            return make_policy(self.serving.policy)
        if isinstance(self.policy, str):
            return make_policy(self.policy)
        return self.policy

    # ------------------------------------------------------------- helpers

    def _rt_for_tier(self, tier: int) -> AttentionRuntime:
        if tier == 0:
            return self.rt
        return AttentionRuntime(mode="cpq", cpq=self.rt.cpq,
                                paged_kernels=self.rt.paged_kernels,
                                mesh=self.mesh)

    def _prefill_for(self, rt: AttentionRuntime):
        if rt.mode not in self._prefills:
            self._prefills[rt.mode] = _named_jit(M.prefill, self.cfg, rt)
        return self._prefills[rt.mode]

    def _chunk_fn(self, tier: int, first: bool):
        """Jitted chunk-prefill step: ONE compiled shape per (tier mode,
        first-chunk) pair — every prompt length reuses it (the old
        per-(mode x padded-length) prefill variant zoo is gone)."""
        key = (tier, first)
        if key not in self._chunk_fns:
            rt_t = self._rt_for_tier(tier)
            self._chunk_fns[key] = _named_jit(
                M.prefill_chunk_rows, self.cfg, rt_t, tier, first,
                donate=self.donates)
        return self._chunk_fns[key]

    def _verify_fn(self, tier: int):
        """Jitted speculative-verify step (the chunk forward pass with
        logits kept at EVERY position): ONE compiled shape —
        ``spec_len + 1`` wide — serves every draft, every request
        (``first=False``: a running row always has history)."""
        if tier not in self._verify_fns:
            rt_t = self._rt_for_tier(tier)
            self._verify_fns[tier] = _named_jit(
                M.verify_chunk_rows, self.cfg, rt_t, tier, False,
                donate=self.donates)
        return self._verify_fns[tier]

    def _bucketed(self, ctx: np.ndarray) -> tuple[np.ndarray, int]:
        """Right-pad to the prefill bucket with the edge token (padding never
        enters attention: causal mask + true-length logits index; cache slots
        beyond the true length map to the null page)."""
        S = len(ctx)
        b = 1 if self._exact_prefill else self.serving.prefill_bucket
        S_pad = max(b, -(-S // b) * b)
        if S_pad == S:
            return ctx, S
        return np.concatenate([ctx, np.full((S_pad - S,), ctx[-1], np.int32)]), S

    def _admit(self, req: Request, st: _ServeState):
        """ONE-SHOT admission (the construction-exact oracle path, selected
        by ``prefill_chunk == 0`` and kept for recurrent stacks): B=1 prefill
        of the whole context into a contiguous scratch cache, scatter-packed
        into the slot's pages. Samples the request's first token with its
        own SamplingParams. Returns (first_token, padded_len)."""
        sched = st.sched
        padded, S = self._bucketed(req.context)
        rt_t = self._rt_for_tier(req.tier)
        ctg = M.init_caches(self.cfg, rt_t, 1, len(padded))
        logits, ctg = self._prefill_for(rt_t)(
            self.params, {"tokens": jnp.asarray(padded[None])}, ctg,
            jnp.asarray(S - 1, jnp.int32))
        tables = sched.alt_block_tables if req.tier == 1 else sched.block_tables
        st.caches = self._pack(st.caches, ctg, jnp.asarray(tables[req.slot]),
                               jnp.asarray(req.slot, jnp.int32))
        sched.finish_prefill(req)
        return self._sample_one(req, logits), len(padded)

    def _prefill_chunk(self, req: Request, st: _ServeState):
        """Stream the next ``prefill_chunk`` prompt tokens STRAIGHT into the
        request's arena pages (no scratch cache, no pack copy); on the final
        chunk, samples the first token from the last valid position's logits.
        Returns (first_token | None, valid_tokens_this_chunk)."""
        sched = st.sched
        C = self.serving.prefill_chunk
        ctx = req.context
        off = req.length
        valid = min(C, req.prefill_target - off)
        chunk = ctx[off:off + valid]
        if valid < C:  # jit padding with the edge token (masked everywhere)
            chunk = np.concatenate(
                [chunk, np.full((C - valid,), chunk[-1], np.int32)])
        tables = sched.alt_block_tables if req.tier == 1 else sched.block_tables
        logits, st.caches = self._chunk_fn(req.tier, off == 0)(
            self.params, jnp.asarray(chunk[None]),
            jnp.asarray(req.slot, jnp.int32),
            jnp.asarray(tables[req.slot]),
            jnp.asarray(off, jnp.int32), jnp.asarray(valid, jnp.int32),
            st.caches)
        sched.note_chunk(req, valid)
        if req.length < req.prefill_target:
            return None, valid
        sched.finish_prefill(req)
        return self._sample_one(req, logits), valid

    # ---------------------------------------------------- per-row sampling

    def _resolve_sampling(self, req: Request, st: _ServeState) -> None:
        """Pin the request's SamplingParams (legacy Requests derive them from
        the session GenerationConfig once, on first admission) and load them
        into the slot's row of the vectorized sampler arrays."""
        if req.sampling is None:
            g = st.gen
            req.sampling = SamplingParams(
                temperature=g.temperature, top_p=g.top_p,
                max_tokens=req.max_new_tokens,
                seed=(g.seed + req.rid) & 0x7fffffff)
        s = req.slot
        st.temp[s] = req.sampling.temperature
        st.top_k[s] = req.sampling.top_k
        st.top_p[s] = req.sampling.top_p
        st.seed[s] = req.sampling.seed & 0x7fffffff

    def _place_replicated(self, tree):
        """Sampling-parameter arrays cross a serving mesh REPLICATED (the
        sampler runs on the already-concatenated logits; see
        serving/sharded.py)."""
        if self.mesh is None:
            return tree
        from repro.serving.sharded import replicate_on_mesh

        return replicate_on_mesh(self.mesh, tree)

    def _sample_one(self, req: Request, logits: jax.Array) -> int:
        """First-token sampling at the end of a prefill: the (1, V) call of
        the same jitted per-row sampler, at stream index ``num_generated``
        (0 on fresh admission; the replay index after preemption, so
        recompute re-draws identical keys). Greedy requests short-circuit
        to the plain argmax. Either way the logits' finite check feeds the
        ``nonfinite_logit_rows`` stat."""
        sp = req.sampling
        out, finite = self._greedy_rows(logits)
        self._st.nonfinite_rows += int(not np.asarray(finite)[0])
        if sp.temperature > 0.0:
            args = (jnp.full((1,), sp.temperature, jnp.float32),
                    jnp.full((1,), sp.top_k, jnp.int32),
                    jnp.full((1,), sp.top_p, jnp.float32),
                    jnp.full((1,), sp.seed & 0x7fffffff, jnp.int32),
                    jnp.full((1,), req.num_generated, jnp.int32))
            out = self._sample_rows(logits, *self._place_replicated(args))
        return int(np.asarray(out)[0])

    def _sample_active(self, st: _ServeState, logits: jax.Array
                       ) -> tuple[np.ndarray, np.ndarray]:
        """One jitted per-row sampling call over the decode batch, plus the
        per-row all-finite flags of the logits. Row r's stream index is its
        request's ``num_generated`` (the index of the token being drawn);
        inactive rows sample garbage that the caller masks out, exactly as
        their logits always were. An all-greedy batch (the default, and
        every legacy suite) skips the sampler entirely for the single argmax
        the old engine ran — ``temps`` is host state, so the check costs
        nothing and the jitted sort/softmax/categorical machinery never
        enters the greedy hot path."""
        greedy, finite = self._greedy_rows(logits)
        finite = np.asarray(finite)
        if (st.temp <= 0.0).all():
            return np.asarray(greedy), finite
        sched = st.sched
        idx = np.array([r.num_generated if (r := sched.slots[s]) is not None
                        else 0 for s in range(self.serving.num_slots)],
                       np.int32)
        args = (jnp.asarray(st.temp), jnp.asarray(st.top_k),
                jnp.asarray(st.top_p), jnp.asarray(st.seed),
                jnp.asarray(idx))
        return np.asarray(self._sample_rows(
            logits, *self._place_replicated(args))), finite

    def _row_state(self, sched: Scheduler, active=None) -> pgc.RowState:
        return pgc.RowState(
            lengths=jnp.asarray(sched.lengths),
            block_table=jnp.asarray(sched.block_tables),
            active=jnp.asarray(sched.active_mask() if active is None else active),
            tier=jnp.asarray(sched.tiers),
            alt_block_table=(jnp.asarray(sched.alt_block_tables)
                             if sched.tiered else None))

    # ------------------------------------------------- request-centric API

    def reset(self, gen: GenerationConfig = GenerationConfig()) -> None:
        """Start a fresh serving session: new scheduler (fresh policy
        instance), empty arenas, empty output buffer. ``gen`` supplies
        session-wide legacy defaults — ``eos_id`` and the SamplingParams
        derived for plain scheduler ``Request`` objects. Traced as one
        ``serve.reset`` span carrying device 0's memory once the new arenas
        are placed."""
        with _span("serve.reset") as span:
            self._st = _ServeState(self, gen)
            span.set_metadata(**_device_memory())

    def _ensure_state(self) -> _ServeState:
        if self._st is None:
            self.reset()
        return self._st

    def add_request(self, req: Union[ServeRequest, Request], *,
                    stream=None) -> int:
        """Submit one request to the live session (created on first use; see
        ``reset``). Accepts the public ``ServeRequest`` spec or a raw
        scheduler ``Request`` (legacy). ``stream`` overrides the request's
        per-token ``RequestOutput`` callback. Returns the request id.
        Traced as one ``serve.add_request`` span with the request's ``rid``."""
        with _span("serve.add_request") as span:
            rid = self._add_request(req, stream)
            span.set_metadata(rid=rid)
        return rid

    def _add_request(self, req: Union[ServeRequest, Request], stream) -> int:
        st = self._ensure_state()
        if isinstance(req, ServeRequest):
            rid = req.rid if req.rid is not None else st.next_rid
            req = Request(rid=rid, prompt=req.prompt,
                          max_new_tokens=req.sampling.max_tokens,
                          arrival=req.arrival, sampling=req.sampling,
                          slo=req.slo, stream=stream or req.stream,
                          session_id=req.session_id)
        elif stream is not None:
            req.stream = stream
        if (req.rid in st.results
                or any(r.rid == req.rid for r in st.sched.queue)
                or any(r is not None and r.rid == req.rid
                       for r in st.sched.slots)):
            # results and scheduler bookkeeping key on rid — a collision
            # would silently clobber another request's record
            raise SchedulerConfigError(
                f"request id {req.rid} already in use this session "
                "(omit ServeRequest.rid to auto-assign)")
        st.next_rid = max(st.next_rid, req.rid + 1)
        self._assign_deadlines(req, st)
        st.sched.submit(req)
        return req.rid

    def _assign_deadlines(self, req: Request, st: _ServeState) -> None:
        """Derive the request's absolute timeout ticks (policies
        .derive_deadlines): an explicit ``SamplingParams.deadline`` budget,
        or — with ``ServingCfg.deadline_scale > 0`` — the SLO class's
        scaled TTFT/total targets. Deterministic in the request alone, so a
        migrated snapshot re-derives identical deadlines."""
        from repro.serving.policies import derive_deadlines, slo_of

        scale = self.serving.deadline_scale
        sp = req.sampling
        if sp is None:
            if scale <= 0:
                return  # legacy request, deadlines off: nothing to derive
            sp = SamplingParams(max_tokens=req.max_new_tokens)
        req.ttft_deadline, req.deadline = derive_deadlines(
            sp, slo_of(req), req.arrival, scale)
        if np.isfinite(req.deadline) or np.isfinite(req.ttft_deadline):
            st.has_deadlines = True

    def has_unfinished(self) -> bool:
        """Whether the session still holds queued or in-flight requests."""
        return self._st is not None and self._st.sched.has_work()

    def pending_outputs(self) -> list[RequestOutput]:
        """Drain the buffered ``RequestOutput`` events (everything committed
        since the last drain; ``step()`` also returns its tick's events
        directly, and per-request ``stream`` callbacks fire inline)."""
        st = self._ensure_state()
        out, st.outputs = st.outputs, []
        return out

    def results(self) -> dict[int, dict]:
        """Finished-request records so far: rid -> {tokens, finish_reason,
        admitted_step, token_steps, slo/priority metadata, ...}. Empty
        when no session is live (does not build one)."""
        return dict(self._st.results) if self._st is not None else {}

    # ------------------------------------------------ router support surface

    def adopt_compiled(self, other: "ContinuousServeEngine") -> None:
        """Share ``other``'s jitted step functions and compile caches.
        Data-parallel replicas of the same (cfg, rt) run the same
        executables — N replicas, one compile. ``ServingCfg`` may differ
        (the jitted functions never close over it; shape changes retrace
        inside the shared jit wrappers)."""
        assert other.cfg == self.cfg and other.rt == self.rt, (
            "adopt_compiled requires an identical (cfg, rt) pair")
        for name in ("_decode", "_pack", "_escalate", "_defrag",
                     "_copy_page", "_sample_rows"):
            setattr(self, name, getattr(other, name))
        self._prefills = other._prefills
        self._chunk_fns = other._chunk_fns
        self._verify_fns = other._verify_fns

    def arena_stats(self) -> dict:
        """Public allocator surface (``Scheduler.arena_stats()``) plus the
        dense free-page fraction — the arena-pressure signal placement
        policies read before assigning a request to this engine."""
        sched = self._ensure_state().sched
        return {**sched.arena_stats(), "free_frac": sched.free_frac()}

    def health(self) -> dict:
        """Cheap liveness/progress/pressure probe surface for the router's
        ``HealthMonitor``: no device work, pure host bookkeeping.
        ``progress`` is a counter that moves whenever the engine does
        anything (tick clock + admissions + retirements) — two consecutive
        probes seeing the same value on an engine that HAS work is a stall.
        ``exhausted`` is always False here; fault injection
        (``FaultyReplica``) overrides it."""
        st = self._st
        if st is None:
            return {"alive": True, "has_work": False, "queued": 0,
                    "progress": 0, "free_frac": 1.0, "exhausted": False}
        sched = st.sched
        return {"alive": True,
                "has_work": sched.has_work(),
                "queued": len(sched.queue),
                "progress": (st.step + sched.stats["admitted"]
                             + sched.stats["retired"]),
                "free_frac": sched.free_frac(),
                "exhausted": False}

    def queued_requests(self) -> list[Request]:
        """The admission queue, in order (read-only view for the router)."""
        st = self._st
        return list(st.sched.queue) if st is not None else []

    def drain_request(self, rid: int) -> Optional[Request]:
        """Snapshot ONE incomplete request for replay elsewhere and free its
        pages — the single-request form of ``drain()`` (the router's
        ``rebalance`` migrate-without-drain primitive rides on it). A
        resident row (decoding or mid-prefill) leaves through the same
        recompute-preemption path full drain uses; a queued request is
        simply removed. Returns the Request record (context = prompt +
        generated so far, pinned SamplingParams intact) or None when the
        rid is not incomplete here."""
        st = self._st
        if st is None:
            return None
        sched = st.sched
        for req in sched.occupied():
            if req.rid == rid:
                slot = req.slot
                sched.preempt(req)          # pages freed, state -> queued
                self._clear_row_sampling(st, slot)
                sched.queue.remove(req)     # preempt requeued at the front
                return req
        for req in list(sched.queue):
            if req.rid == rid:
                sched.queue.remove(req)
                return req
        return None

    def outstanding_tokens(self) -> int:
        """Work still owed across queued and resident requests: prefill
        tokens not yet streamed into the arena plus undelivered generation
        budget. The load signal least-outstanding placement balances on."""
        st = self._st
        if st is None:
            return 0
        total = 0
        for r in list(st.sched.queue) + st.sched.occupied():
            total += max(len(r.prompt) + r.num_generated - r.length, 0)
            total += max(r.max_new_tokens - r.num_generated, 0)
        return total

    def drain(self) -> list[Request]:
        """Snapshot every incomplete request (queued, mid-prefill, or
        decoding) for replay re-admission elsewhere and free their pages.

        Slot holders leave through the existing recompute-preemption path
        (``Scheduler.preempt``: pages freed, state back to queued, context
        = prompt + generated-so-far, pinned ``SamplingParams`` preserved),
        then the whole queue is handed over. Feeding the returned records
        to ``add_request`` on another engine replays each context exactly:
        greedy rows are deterministic and seeded rows re-draw
        ``fold_in(seed, token_index)`` keys, so the remaining stream
        reproduces token-for-token after migration. Finished-request
        results and session counters stay on this engine (``results()`` /
        ``stats()``); call ``release()`` to drop the arenas afterwards."""
        st = self._st
        if st is None:
            return []
        sched = st.sched
        for req in sorted(sched.occupied(), key=lambda r: r.admitted_step):
            slot = req.slot
            sched.preempt(req)
            self._clear_row_sampling(st, slot)
        out = sorted(sched.queue, key=lambda r: (r.arrival, r.rid))
        sched.queue.clear()
        return out

    def release(self) -> None:
        """Drop the live serving session — scheduler, arenas (device
        memory goes with them), sampling arrays, output buffers. The next
        ``add_request()`` / ``reset()`` starts a fresh session. Traced as
        one ``serve.release`` span carrying device 0's memory as the session
        ends, before its arenas go."""
        with _span("serve.release") as span:
            span.set_metadata(**_device_memory())
            self._st = None

    # ----------------------------------------------------- result plumbing

    def _result_of(self, req: Request) -> dict:
        slo = req.slo
        return {
            "tokens": np.asarray(req.generated, np.int32),
            "session": req.session_id,
            "finish_reason": req.finish_reason,
            "arrival": req.arrival,
            "admitted_step": req.admitted_step,
            "first_token_step": req.first_token_step,
            "token_steps": np.asarray(req.token_steps, np.int64),
            "done_step": req.done_step,
            "preemptions": req.preemptions,
            "escalated": req.escalated,
            "deescalations": req.deescalations,
            "slo": slo.name if slo is not None else "standard",
            "priority": slo.priority if slo is not None else 1,
            "ttft_target": slo.ttft_target if slo is not None else float("inf"),
            "itl_target": slo.itl_target if slo is not None else float("inf"),
        }

    def _clear_row_sampling(self, st: _ServeState, slot: int) -> None:
        """Reset a vacated slot's sampler rows to greedy defaults so a
        retired sampled request cannot keep defeating the all-greedy
        argmax fast path (the next admission overwrites them anyway)."""
        if slot < 0:
            return
        st.temp[slot] = 0.0
        st.top_k[slot] = 0
        st.top_p[slot] = 1.0
        st.seed[slot] = 0

    def _finish(self, st: _ServeState, req: Request, reason: str) -> None:
        slot = req.slot
        st.sched.retire(req, st.step, reason)
        self._clear_row_sampling(st, slot)
        st.results[req.rid] = self._result_of(req)

    def _emit_token(self, st: _ServeState, req: Request, tok: int, tick: int,
                    grow: bool = False) -> None:
        """Commit one emitted token. ``tick`` is the clock value at which
        the token became available (end-of-work convention: a token
        produced during tick T is stamped T+1; a one-shot admission's
        first token is stamped at the end of its charged stall).
        ``grow`` extends the cache bookkeeping (decode tokens only —
        the first token's position is written by its decode step).
        A stop-token / EOS / budget hit retires the request HERE — pages
        free immediately and the slot refills on the next tick — and the
        final ``RequestOutput`` carries the finish reason."""
        req.generated.append(tok)
        req.token_steps.append(tick)
        if grow:
            req.length += 1
            st.sched.lengths[req.slot] += 1
        st.last_tok[req.slot] = tok
        st.generated += 1
        if req.first_token_step < 0:
            req.first_token_step = tick
        reason = ""
        if st.gen.eos_id >= 0 and tok == st.gen.eos_id:
            reason = "eos"
        elif tok in req.stop_ids:
            reason = "stop"
        elif req.num_generated >= req.max_new_tokens:
            reason = "max_tokens"
        if reason:
            self._finish(st, req, reason)
        ev = RequestOutput(rid=req.rid, token=int(tok),
                           index=req.num_generated - 1, step=tick,
                           finished=bool(reason), finish_reason=reason)
        st.step_outputs.append(ev)
        st.outputs.append(ev)
        if req.stream is not None:
            req.stream(ev)

    def _emit_finish(self, st: _ServeState, req: Request, reason: str) -> None:
        """Finish-only event (no token payload): ``token == -1`` with
        ``index`` at the stream length — timeout/shed retirements, where the
        gapless token stream simply ends early."""
        ev = RequestOutput(rid=req.rid, token=-1, index=req.num_generated,
                           step=st.step, finished=True, finish_reason=reason)
        st.step_outputs.append(ev)
        st.outputs.append(ev)
        if req.stream is not None:
            req.stream(ev)

    def _deadline_blown(self, req: Request, now: int) -> bool:
        return (now >= req.deadline
                or (req.first_token_step < 0 and now >= req.ttft_deadline))

    def _expire_deadlines(self, st: _ServeState) -> None:
        """Tick-boundary deadline enforcement: any queued or resident
        request past its absolute deadline (or TTFT deadline with no first
        token yet) retires with finish_reason ``timeout`` — pages freed
        immediately, a finish-only event emitted, the ``timeouts`` stat
        bumped. Skipped entirely when no admitted request carries a finite
        deadline (the default: zero overhead)."""
        if not st.has_deadlines:
            return
        sched = st.sched
        now = st.step
        for req in list(sched.occupied()):
            if self._deadline_blown(req, now):
                self._finish(st, req, "timeout")
                sched.stats["timeouts"] += 1
                self._emit_finish(st, req, "timeout")
        for req in [r for r in sched.queue if self._deadline_blown(r, now)]:
            sched.queue.remove(req)
            req.state, req.done_step = "done", now
            req.finish_reason = "timeout"
            st.results[req.rid] = self._result_of(req)
            sched.stats["timeouts"] += 1
            self._emit_finish(st, req, "timeout")

    def _cow_guard(self, st: _ServeState, req: Request) -> bool:
        """Copy-on-write valve before ``req``'s next cache write (tail chunk
        or decode token): if the target block maps a SHARED page, the
        scheduler splits it (alloc + remap + decref) and the jitted page
        copy duplicates the payload across every attention layer's base
        pools. Page pressure applies the growth loop's valves — preempt the
        policy's victim, or ``req`` itself as the last resort. Returns False
        iff ``req`` was preempted (skip its write this tick)."""
        sched = st.sched
        while True:
            try:
                plan = sched.cow_plan(req)
            except pgc.PageAllocator.OutOfPages:
                victim = sched.preemption_victim(exclude=req)
                if victim is None:
                    vslot = req.slot
                    sched.preempt(req)
                    self._clear_row_sampling(st, vslot)
                    return False
                vslot = victim.slot
                sched.preempt(victim)
                self._clear_row_sampling(st, vslot)
                continue
            if plan is not None:
                src, dst = plan
                st.caches = self._copy_page(st.caches,
                                            jnp.asarray(src, jnp.int32),
                                            jnp.asarray(dst, jnp.int32))
            return True

    # ------------------------------------------------- speculative decoding

    def _spec_eligible(self, req: Request) -> bool:
        """Whether a row can take a speculative step this tick: running on
        tier 0 (drafts alias DENSE pages), not opted out, with generation
        budget for at least the verify draw plus one accepted candidate
        (``budget >= 2`` — a 1-token budget speculates nothing and just
        decodes)."""
        sp = req.sampling
        return (req.state == "running" and req.tier == 0
                and req.draft is None
                and (sp is None or sp.speculate)
                and req.max_new_tokens - req.num_generated >= 2)

    def _verify_draws(self, req: Request, logits: jax.Array) -> np.ndarray:
        """The request's OWN sampler draws at every chunk position: row i
        (absolute position ``length + i``) is drawn at stream index
        ``num_generated + i`` through the same jitted ``sample_token_rows``
        the normal decode path uses — a committed token is ALWAYS
        ``fold_in(seed, token_index)``'s draw (argmax for greedy rows),
        bit-identical speculative on-vs-off. ``logits`` is (C, V); padding
        rows produce garbage draws the caller never reads."""
        sp = req.sampling
        if sp is None or sp.temperature <= 0.0:
            return np.asarray(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        C = logits.shape[0]
        args = (jnp.full((C,), sp.temperature, jnp.float32),
                jnp.full((C,), sp.top_k, jnp.int32),
                jnp.full((C,), sp.top_p, jnp.float32),
                jnp.full((C,), sp.seed & 0x7fffffff, jnp.int32),
                jnp.asarray(req.num_generated
                            + np.arange(C, dtype=np.int32)))
        return np.asarray(self._sample_rows(logits,
                                            *self._place_replicated(args)))

    def _speculate_row(self, st: _ServeState, req: Request) -> bool:
        """One speculative decode step for a single running row: draft up to
        ``spec_len`` candidates from the row's own context (prompt lookup),
        alias its pages + allocate scratch (``Scheduler.begin_draft``), run
        ONE verify chunk over [last_tok, draft...] at positions
        ``length..length+k``, and commit the longest prefix of candidates
        that EQUALS the request's own sampler draws — every committed token
        lands this tick (ITL 0 between them). Returns True iff the row was
        handled speculatively (the caller masks it out of the batched
        decode); False falls back to the normal decode step with the draft
        fully unwound.

        Clock model: the verify chunk is ONE model invocation and costs one
        tick — the win is tokens-per-invocation (up to k+1 per weight
        stream), never free ticks."""
        sched = st.sched
        serving = self.serving
        L = req.length
        budget = req.max_new_tokens - req.num_generated
        cap = serving.max_blocks_per_slot * serving.page_size - 1 - L
        k = min(serving.spec_len, budget - 1, cap)
        if k < 1:
            return False
        from repro.serving.speculative import propose_ngram

        # req.context ends with last_tok (length L+1 for a running row):
        # the draft continues the stream the verify chunk's first query
        # position (L, carrying last_tok) extends
        draft = propose_ngram(req.context, serving.spec_ngram, k)
        k = int(len(draft))
        if k < 1:
            return False
        d = sched.begin_draft(req, k)
        if d is None:
            return False  # arena pressure / block ceiling: normal decode
        d.tokens = [int(t) for t in draft]
        if d.copy_src >= 0:
            # partial frontier: seed the replacing scratch page's payload
            # (the same jitted copy the COW split uses)
            st.caches = self._copy_page(st.caches,
                                        jnp.asarray(d.copy_src, jnp.int32),
                                        jnp.asarray(d.scratch[0], jnp.int32))
        row = sched.draft_block_row(req)
        C = serving.spec_len + 1
        toks = np.full((C,), int(st.last_tok[req.slot]), np.int32)
        toks[1:1 + k] = draft
        valid = k + 1
        logits, st.caches = self._verify_fn(req.tier)(
            self.params, jnp.asarray(toks[None]),
            jnp.asarray(req.slot, jnp.int32), jnp.asarray(row),
            jnp.asarray(L, jnp.int32), jnp.asarray(valid, jnp.int32),
            st.caches)
        # clock: one model invocation
        st.decode_steps += 1
        st.live_steps += 1
        util = sched.dense_alloc.utilization
        st.util_peak = max(st.util_peak, util)
        st.util_sum += util
        st.util_n += 1
        st.step += 1

        draws = self._verify_draws(req, logits[0])
        n_accept = 1  # position L's draw is this tick's own next token
        for j in range(k):
            if int(draws[j]) == int(draft[j]):
                n_accept += 1
            else:
                break
        sched.commit_draft(req, n_accept)
        for j in range(n_accept):
            if req.state != "running":
                break  # a draw hit eos/stop/budget: the rest never emits
            self._emit_token(st, req, int(draws[j]), st.step, grow=True)
            sched.register_prefix(req)
        return True

    # ----------------------------------------------------------------- run

    def step(self) -> list[RequestOutput]:
        """Run ONE engine tick: admissions, the watermark escalation /
        recovery policy, at most one streamed prompt chunk, page growth
        (preemption on exhaustion), and one jitted decode step + per-row
        sampling over the running rows. Returns this tick's incremental
        ``RequestOutput`` events (also buffered for ``pending_outputs``).

        Clock model: ``step`` counts model-invocation ticks. A tick that
        runs the jitted decode step costs 1, and one prompt chunk rides
        along for free (the chunked-prefill interleave). The one-shot
        oracle path charges a monolithic admission its chunk-equivalents up
        front — ``ceil(padded_len / quantum)`` ticks during which no row
        decodes — which is exactly the head-of-line stall chunked admission
        removes (quantum = ``prefill_chunk`` or, on the one-shot path,
        ``prefill_bucket``).

        A tick that does work is one ``serve.tick`` profiler span whose
        children name its host phases, in order: ``serve.admit``,
        ``serve.prefill_chunk``, ``serve.grow``, ``serve.speculate``,
        ``serve.decode``, ``serve.sample``, ``serve.emit`` (docs/serving.md,
        Observability)."""
        st = self._ensure_state()
        st.step_outputs = []
        if not st.sched.has_work():
            return []
        with _span("serve.tick"):
            self._tick(st)
        return st.step_outputs

    def _tick(self, st: _ServeState) -> None:
        """The body of ``step()``; its events land in ``st.step_outputs``."""
        sched = st.sched
        B = self.serving.num_slots

        with _span("serve.admit") as span:
            # -1) deadline-aware shedding: blown budgets retire BEFORE this
            #     tick's admissions, so their freed slots/pages refill now
            self._expire_deadlines(st)
            if not sched.has_work():
                return

            # 0) periodic base-arena compaction (defrag_every retirements):
            #    the scheduler relabels mapped pages onto the lowest ids and
            #    the jitted permutation moves every base page pool to match
            if (self.serving.defrag_every
                    and sched.stats["retired"] - st.defrag_mark
                    >= self.serving.defrag_every):
                st.defrag_mark = sched.stats["retired"]
                perm = sched.plan_defrag()
                if perm is not None:
                    st.caches = self._defrag(st.caches, jnp.asarray(perm))

            # 1) admissions into vacated slots (the POLICY picks who and
            #    which tier). Chunked (default): the slot enters the
            #    prefilling state and its prompt streams below. One-shot
            #    oracle: prefill the whole context now and charge the clock
            #    its chunk-equivalents (the head-of-line stall).
            admitted = []
            while (req := sched.admit_next(now=st.step, step=st.step)) is not None:
                admitted.append(req.rid)
                self._resolve_sampling(req, st)
                if self.chunked:
                    continue  # pump below interleaves one chunk per tick
                tok, padded = self._admit(req, st)
                st.step += -(-padded // st.quantum)  # monolithic prefill stall
                st.prefill_tokens += req.length
                self._emit_token(st, req, tok, st.step)  # ready after the stall
            if admitted:
                # space-separated: the profiler splits span args at commas
                span.set_metadata(rid=" ".join(map(str, admitted)))

            # 2) watermark policy: escalate running dense requests under
            #    critical memory pressure (dense -> T2, pages freed)
            while (cand := sched.escalation_candidate()) is not None:
                slot, length = cand.slot, cand.length
                dense_row, cpq_row = sched.apply_escalation(cand)
                st.caches = self._escalate(st.caches, jnp.asarray(dense_row),
                                           jnp.asarray(cpq_row),
                                           jnp.asarray(slot, jnp.int32),
                                           jnp.asarray(length, jnp.int32))

            # 2b) recovery: when the dense free fraction sits above the HIGH
            #     watermark, the policy may de-escalate ONE T2 row per tick
            #     back to dense via chunked re-admission (bounded churn; CPQ
            #     codes are lossy, so the dense K/V is rebuilt by exact
            #     context replay through the admission path)
            if (cand := sched.deescalation_candidate()) is not None:
                slot = cand.slot
                sched.deescalate(cand)
                self._clear_row_sampling(st, slot)

        # 3) chunked-prefill pump: at most ONE prompt chunk per tick
        #    (the per-step prefill token budget), written straight into
        #    the slot's arena pages and interleaved with the decode step
        #    below — long prompts no longer freeze running rows
        did_chunk, fresh_slot = (self._pump_chunk(st) if self.chunked
                                 else (False, -1))

        # 4) growth: map a page for every running row's next write.
        #    Out of pages: a dense grower first escalates itself to the
        #    CPQ arena (frees its dense pages), else the policy's victim
        #    (default: youngest same-arena) is preempted (recompute)
        with _span("serve.grow"):
            for req in sorted(sched.running(), key=lambda r: r.admitted_step):
                if req.state != "running":
                    continue
                while not sched.ensure_writable(req):
                    if req.length // self.serving.page_size >= \
                            self.serving.max_blocks_per_slot:
                        self._finish(st, req, "length_cap")
                        break
                    if self.tiered and req.tier == 0 and sched.cpq_alloc.can_alloc(
                            pgc.pages_needed(req.length + 1,
                                             self.serving.page_size)):
                        slot, length = req.slot, req.length
                        dense_row, cpq_row = sched.apply_escalation(req)
                        st.caches = self._escalate(st.caches,
                                                   jnp.asarray(dense_row),
                                                   jnp.asarray(cpq_row),
                                                   jnp.asarray(slot, jnp.int32),
                                                   jnp.asarray(length, jnp.int32))
                        continue
                    victim = sched.preemption_victim(exclude=req)
                    if victim is None:
                        self._finish(st, req, "oom")
                        break
                    vslot = victim.slot
                    sched.preempt(victim)
                    self._clear_row_sampling(st, vslot)
                if req.state == "running":
                    # a decode write into a still-shared page splits it first
                    # (reachable only via adversarial schedules — tail chunks
                    # normally privatize the write frontier — but the
                    # refcount invariant must hold for ANY interleaving)
                    self._cow_guard(st, req)

            active = sched.active_mask()
            if fresh_slot >= 0:
                active[fresh_slot] = False

        # 4b) speculative decoding: eligible rows take a per-row verify
        #     chunk instead of joining the batched decode (each verify is
        #     its own model invocation / tick — see _speculate_row). A row
        #     whose draft cannot open (no recurring n-gram, arena pressure)
        #     stays in ``active`` and decodes normally below.
        did_spec = False
        if self.spec_on:
            with _span("serve.speculate"):
                for req in sorted(sched.running(), key=lambda r: r.admitted_step):
                    slot = req.slot
                    if slot < 0 or not active[slot]:
                        continue
                    if not self._spec_eligible(req):
                        continue
                    if self._speculate_row(st, req):
                        active[slot] = False
                        did_spec = True

        if not active.any():
            if did_spec:
                # the verify invocations already charged their ticks (and
                # this tick's prompt chunk, if any, rode along with them)
                return
            if did_chunk:
                st.step += 1     # prefill-only tick still costs a tick
                return
            if not sched.occupied():
                # a slot may have been vacated AFTER this tick's admission
                # phase (growth-cap retirement, de-escalation requeue): if
                # the policy can place someone NOW, just end the tick — the
                # next tick's admission phase admits them normally
                if sched.queue and sched.policy.select_admission(
                        sched, st.step) is not None:
                    return
                cands = sched.policy.admission_order(sched, st.step)
                if cands and cands[0].arrival <= st.step:
                    # empty machine (every page free) and the policy's pick
                    # STILL does not fit => it can never fit
                    req = cands[0]
                    sched.queue.remove(req)
                    req.state, req.done_step = "done", st.step
                    req.finish_reason = "unschedulable"
                    st.results[req.rid] = self._result_of(req)
                    return
                # idle: jump the clock to the arrival that unblocks
                # admission — the policy's blocked pick if it has one
                # (a no-bypass FIFO head gates everyone behind it), else
                # the earliest arrival in the queue
                if sched.queue:
                    nxt = (cands[0].arrival if cands
                           else min(r.arrival for r in sched.queue))
                    st.step = max(st.step + 1, int(np.ceil(nxt)))
            return

        # 5) one jitted decode step over per-row positions (rows still
        #    prefilling — and a row whose final chunk landed this very
        #    tick — are inactive: their writes hit the null page), then
        #    ONE jitted per-row sampling call for the whole mixed batch
        n_active = int(active.sum())
        with _span("serve.decode", rows=n_active):
            rows = self._row_state(sched, active)
            logits, st.caches = self._decode(self.params,
                                             jnp.asarray(st.last_tok[:, None]),
                                             rows, st.caches)
        with _span("serve.sample"):
            toks, finite = self._sample_active(st, logits)

        with _span("serve.emit"):
            st.nonfinite_rows += int((active & ~finite).sum())
            st.decode_steps += 1
            st.live_steps += n_active
            util = sched.dense_alloc.utilization
            st.util_peak = max(st.util_peak, util)
            st.util_sum += util
            st.util_n += 1
            st.step += 1

            for slot in range(B):
                if not active[slot]:
                    continue
                req = sched.slots[slot]
                self._emit_token(st, req, int(toks[slot]), st.step, grow=True)
                # decode just completed a page? register it — multi-turn
                # follow-ups then mount this request's whole history
                sched.register_prefix(req)

    def _pump_chunk(self, st: _ServeState) -> tuple[bool, int]:
        """A tick's prompt chunk for the oldest prefilling row, under the
        ``serve.prefill_chunk`` span. Returns whether a chunk ran, and the
        slot whose prefill it finished (-1 for none): that row joins the
        decode batch from the NEXT tick."""
        sched = st.sched
        pre = sched.prefilling()
        if not pre:
            return False, -1
        req = pre[0]
        with _span("serve.prefill_chunk", rid=req.rid) as span:
            # the first tail write of a shared-prefix admission may land
            # inside a shared page (divergence mid-page): split it first
            if not self._cow_guard(st, req):
                return False, -1
            tok, valid = self._prefill_chunk(req, st)
            span.set_metadata(tokens=valid)
            st.prefill_chunks += 1
            st.prefill_tokens += valid
            # every page the chunk just FILLED is immutable from here on
            # (later chunks write strictly past req.length), so register
            # eagerly — concurrent admissions can mount a prefix that is
            # still mid-prefill, and the entries outlive this request's
            # retirement for as long as any borrower keeps them resident
            sched.register_prefix(req)
            if tok is None:
                return True, -1
            # the final chunk runs during THIS tick: its first token is
            # available at the tick's end (step + 1)
            self._emit_token(st, req, tok, st.step + 1)
            return True, req.slot if req.state == "running" else -1

    def stats(self) -> dict:
        """Session counters in the same shape ``serve`` has always returned
        (throughput, latency inputs, allocator surface),
        plus the policy name."""
        st = self._ensure_state()
        sched = st.sched
        B = self.serving.num_slots
        wall = time.time() - st.t0
        total_bytes = pgc.arena_bytes(st.caches)
        device_bytes = self._per_device_arena_bytes(st.caches, total_bytes)
        return {
            "cache_mode": self.rt.mode,
            "tiered": self.tiered,
            "chunked_prefill": self.chunked,
            "prefix_sharing": self.share_prefix,
            "spec_on": self.spec_on,
            "spec_accept_rate": (sched.stats["spec_accepted"]
                                 / max(sched.stats["spec_drafted"], 1)),
            "policy": sched.policy.name,
            "model_shards": self.model_shards,
            "arena_bytes_total": total_bytes,
            "arena_bytes_per_device": device_bytes,
            "decode_steps": st.decode_steps,
            "prefill_chunks": st.prefill_chunks,
            "prefill_tokens": st.prefill_tokens,
            "generated_tokens": st.generated,
            "nonfinite_logit_rows": st.nonfinite_rows,
            "tokens_per_step": st.generated / max(st.decode_steps, 1),
            "slot_utilization": st.live_steps / max(st.decode_steps * B, 1),
            "arena_utilization_mean": st.util_sum / max(st.util_n, 1),
            "arena_utilization_peak": st.util_peak,
            "wall_time_s": wall,
            "tokens_per_s": st.generated / max(wall, 1e-9),
            # invariant: every page freed once all requests retired
            "dense_pages_leaked": sched.dense_alloc.num_used,
            "cpq_pages_leaked": sched.cpq_alloc.num_used if sched.cpq_alloc else 0,
            **sched.stats,
            # public allocator surface (utilization + defrag counts), in
            # place of the private dense_alloc/cpq_alloc state
            **sched.arena_stats(),
        }

    def serve(self, requests: list[Union[Request, ServeRequest]],
              gen: GenerationConfig = GenerationConfig()):
        """Batch-shaped wrapper over the request-centric API (kept for
        backward compatibility): resets the session, submits every request
        in arrival order, and drains with ``step()``. Returns
        (results, stats) exactly as before; ``ServeRequest`` specs are
        accepted alongside scheduler ``Request`` records, and greedy FIFO
        serving is token-identical to the pre-request-API engine."""
        self.reset(gen)
        st = self._st
        for r in sorted(requests, key=lambda r: r.arrival):
            self.add_request(r)
        while st.sched.has_work():
            self.step()
        return dict(st.results), self.stats()

    def _per_device_arena_bytes(self, caches, total_bytes: int) -> float:
        """Physical arena bytes each device holds (sharded leaves shrink,
        replicated leaves don't) — the HBM-capacity win the kv-head
        partitioning exists for."""
        if self.mesh is None:
            return float(total_bytes)
        import math

        def leaf_bytes(a, ns) -> float:
            return math.prod(ns.shard_shape(a.shape)) * a.dtype.itemsize

        return float(sum(jax.tree.leaves(
            jax.tree.map(leaf_bytes, caches, self._cache_shardings))))

    def generate(self, batch: dict, gen: GenerationConfig = GenerationConfig()):
        """Static-engine-compatible convenience: one batch of equal-priority
        requests; returns (tokens (B, max_new) right-padded with eos/last,
        stats)."""
        prompt = np.asarray(batch["tokens"])
        reqs = [Request(rid=i, prompt=prompt[i], max_new_tokens=gen.max_new_tokens)
                for i in range(prompt.shape[0])]
        results, stats = self.serve(reqs, gen)
        pad = gen.eos_id if gen.eos_id >= 0 else 0
        out = np.full((prompt.shape[0], gen.max_new_tokens), pad, np.int32)
        for i in range(prompt.shape[0]):
            t = results[i]["tokens"]
            out[i, :len(t)] = t[:gen.max_new_tokens]
        return out, stats
