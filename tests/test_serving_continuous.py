"""Continuous-batching engine + scheduler behaviour tests: page-leak
invariants, admission/retirement/resume correctness, preemption recompute,
watermark tier escalation, and ``ServingCfg.validate``'s named-knob errors,
at construction and at engine construction."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, ServingCfg, smoke_config
from repro.models import model as M
from repro.serving.engine import ContinuousServeEngine, GenerationConfig, ServeEngine
from repro.serving.scheduler import Request, Scheduler, SchedulerConfigError


@pytest.fixture(scope="module")
def model():
    cfg = smoke_config(ARCHS["qwen1.5-0.5b"])
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _reqs(cfg, sizes, max_new, seed=0, arrivals=None):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=s).astype(np.int32),
                    max_new_tokens=max_new,
                    arrival=0.0 if arrivals is None else arrivals[i])
            for i, s in enumerate(sizes)]


# ----------------------------------------------------------- scheduler unit


def test_scheduler_admission_and_leak_free():
    serving = ServingCfg(num_slots=2, page_size=4, num_pages=9,
                         max_blocks_per_slot=4)
    sched = Scheduler(serving)
    reqs = [Request(rid=i, prompt=np.arange(6, dtype=np.int32), max_new_tokens=4)
            for i in range(3)]
    for r in reqs:
        sched.submit(r)
    a = sched.admit_next(now=0, step=0)
    b = sched.admit_next(now=0, step=0)
    assert a is reqs[0] and b is reqs[1]
    assert sched.admit_next(now=0, step=0) is None  # no free slot
    # admission enters the prefilling window: pages owned, nothing valid yet
    assert a.state == "prefilling" and a.prefill_target == 6
    assert sched.lengths[a.slot] == 0 and len(a.pages) == 2
    sched.note_chunk(a, 4)
    assert sched.lengths[a.slot] == 4 and a.state == "prefilling"
    sched.finish_prefill(a)
    sched.finish_prefill(b)
    assert a.state == "running" and sched.lengths[a.slot] == 6
    # block table maps exactly the prompt's pages; rest is null
    assert (sched.block_tables[a.slot, :2] > 0).all()
    assert (sched.block_tables[a.slot, 2:] == 0).all()
    a_slot = a.slot
    sched.retire(a, step=1, reason="eos")
    assert sched.slots[a_slot] is None and sched.lengths[a_slot] == 0
    c = sched.admit_next(now=0, step=1)          # vacated slot is refilled
    assert c is reqs[2] and c.slot == a_slot
    sched.retire(b, step=2, reason="eos")
    sched.retire(c, step=2, reason="eos")
    assert sched.dense_alloc.num_used == 0       # every page returned
    assert sched.stats["admitted"] == 3 and sched.stats["retired"] == 3


def test_scheduler_rejects_oversized_request():
    serving = ServingCfg(num_slots=1, page_size=4, num_pages=9,
                         max_blocks_per_slot=2)  # max_len = 8
    sched = Scheduler(serving)
    with pytest.raises(SchedulerConfigError):
        sched.submit(Request(rid=0, prompt=np.arange(6, dtype=np.int32),
                             max_new_tokens=4))


def test_scheduler_growth_and_ceiling():
    serving = ServingCfg(num_slots=1, page_size=2, num_pages=9,
                         max_blocks_per_slot=3)
    sched = Scheduler(serving)
    r = Request(rid=0, prompt=np.arange(3, dtype=np.int32), max_new_tokens=3)
    sched.submit(r)
    sched.admit_next(now=0, step=0)
    sched.finish_prefill(r)
    assert len(r.pages) == 2                      # ceil(3/2)
    assert sched.ensure_writable(r)               # position 3: page already mapped
    r.length = 4
    assert sched.ensure_writable(r)               # position 4: grows a 3rd page
    assert len(r.pages) == 3
    r.length = 6
    assert not sched.ensure_writable(r)           # context ceiling (3 blocks)


def test_admission_at_exact_pool_exhaustion():
    """A prompt whose page demand EQUALS the free-page count admits (no
    off-by-one slack required); the next request waits until a retirement
    frees pages, then takes the vacated capacity."""
    serving = ServingCfg(num_slots=2, page_size=4, num_pages=5,  # 4 usable
                         max_blocks_per_slot=4)
    sched = Scheduler(serving)
    a = Request(rid=0, prompt=np.arange(16, dtype=np.int32), max_new_tokens=0)
    b = Request(rid=1, prompt=np.arange(4, dtype=np.int32), max_new_tokens=0)
    sched.submit(a)
    sched.submit(b)
    got = sched.admit_next(now=0, step=0)
    assert got is a and sched.dense_alloc.num_free == 0   # exact fit admitted
    assert sched.admit_next(now=0, step=0) is None        # b must wait
    assert b.state == "queued"
    sched.retire(a, step=1, reason="eos")
    got = sched.admit_next(now=0, step=1)
    assert got is b and len(b.pages) == 1
    sched.retire(b, step=2, reason="eos")
    assert sched.dense_alloc.num_used == 0


def test_preemption_picks_newest_same_arena_row():
    """The preemption victim is the YOUNGEST running request (latest
    admitted), never the grower itself — LIFO recompute keeps the oldest
    request's progress."""
    serving = ServingCfg(num_slots=3, page_size=2, num_pages=9,
                         max_blocks_per_slot=4)
    sched = Scheduler(serving)
    reqs = [Request(rid=i, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=2) for i in range(3)]
    for r in reqs:
        sched.submit(r)
    for step, r in enumerate(reqs):
        assert sched.admit_next(now=step, step=step) is r  # staggered ages
    victim = sched.preemption_victim(exclude=reqs[0])
    assert victim is reqs[2]                               # newest row
    victim = sched.preemption_victim(exclude=reqs[2])      # newest excluded
    assert victim is reqs[1]
    sched.preempt(reqs[2])
    assert reqs[2].state == "queued" and reqs[2].pages == []
    assert sched.queue[0] is reqs[2]                       # requeued at front
    # engine-level: under page starvation the OLDER request keeps its slot
    cfg = smoke_config(ARCHS["qwen1.5-0.5b"])
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ContinuousServeEngine(cfg, params, serving=ServingCfg(
        num_slots=2, page_size=4, num_pages=7, max_blocks_per_slot=8,
        prefill_bucket=4))
    rng = np.random.default_rng(11)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=10) for i in range(2)]
    res, stats = eng.serve(reqs, GenerationConfig(max_new_tokens=10))
    assert stats["preemptions"] >= 1
    assert res[1]["preemptions"] >= 1 and res[0]["preemptions"] == 0
    assert all(len(res[i]["tokens"]) == 10 for i in res)
    assert stats["dense_pages_leaked"] == 0


def test_escalation_then_continued_decode_is_correct(model):
    """Watermark escalation mid-request must not corrupt the survivor: the
    escalated request keeps decoding AFTER the dense -> T2 migration (its
    done_step postdates escalation), finishes its full budget with in-vocab
    tokens, and both arenas end leak-free. A re-run of the same workload is
    bit-identical (escalation is deterministic, no hidden state)."""
    cfg, params = model
    serving = ServingCfg(num_slots=2, page_size=4, num_pages=9,
                         escalated_pages=33, max_blocks_per_slot=8,
                         prefill_bucket=4, low_watermark=0.75,
                         critical_watermark=0.5, enable_escalation=True)
    eng = ContinuousServeEngine(cfg, params, serving=serving)

    def fresh():
        rng = np.random.default_rng(13)
        return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 7
                                                   ).astype(np.int32),
                        max_new_tokens=12) for i in range(2)]

    res, stats = eng.serve(fresh(), GenerationConfig(max_new_tokens=12))
    assert stats["escalations"] >= 1
    esc = [i for i in res if res[i]["escalated"]]
    assert esc
    for i in esc:
        t = res[i]["tokens"]
        assert len(t) == 12 and res[i]["finish_reason"] == "max_tokens"
        assert (t >= 0).all() and (t < cfg.vocab_size).all()
        # decode continued after the escalation step (which can only happen
        # once decoding is underway, i.e. after admission)
        assert res[i]["done_step"] > res[i]["admitted_step"] + 1
    assert stats["dense_pages_leaked"] == 0 and stats["cpq_pages_leaked"] == 0
    res2, stats2 = eng.serve(fresh(), GenerationConfig(max_new_tokens=12))
    for i in res:
        np.testing.assert_array_equal(res[i]["tokens"], res2[i]["tokens"])
    assert stats2["escalations"] == stats["escalations"]


# ------------------------------------------------------------- engine runs


def test_continuous_no_leak_and_all_finish(model):
    cfg, params = model
    serving = ServingCfg(num_slots=3, page_size=4, num_pages=33,
                         max_blocks_per_slot=8, prefill_bucket=4)
    eng = ContinuousServeEngine(cfg, params, serving=serving)
    reqs = _reqs(cfg, sizes=(5, 11, 7, 3, 9, 6), max_new=7)
    res, stats = eng.serve(reqs, GenerationConfig(max_new_tokens=7))
    assert set(res) == set(range(6))
    assert all(r["finish_reason"] == "max_tokens" for r in res.values())
    assert all(len(r["tokens"]) == 7 for r in res.values())
    assert stats["dense_pages_leaked"] == 0 and stats["cpq_pages_leaked"] == 0
    assert stats["admitted"] >= 6 and stats["retired"] == 6


def test_admitted_request_resumes_at_correct_position(model):
    """A request admitted into a vacated slot must decode exactly as if it had
    the machine to itself (same greedy tokens, position continuity)."""
    cfg, params = model
    gen = GenerationConfig(max_new_tokens=6)
    sizes = (5, 9, 12, 3, 8, 6)
    reqs = _reqs(cfg, sizes, max_new=6, arrivals=[0, 0, 1, 2, 3, 8])
    static = ServeEngine(cfg, params, max_len=64)
    refs = []
    for r in reqs:
        out, _ = static.generate({"tokens": jnp.asarray(r.prompt[None])}, gen)
        refs.append(out[0])
    serving = ServingCfg(num_slots=2, page_size=4, num_pages=33,
                         max_blocks_per_slot=8, prefill_bucket=4,
                         prefill_chunk=0)  # one-shot oracle: shares static ops
    # gather path == static ops
    eng = ContinuousServeEngine(
        cfg, params, serving=serving,
        rt=dataclasses.replace(cfg.attention, paged_kernels=False))
    res, stats = eng.serve(reqs, gen)
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(res[i]["tokens"], ref)
    # later arrivals really were admitted later (slot reuse, not parallel)
    admits = sorted(res[i]["admitted_step"] for i in res)
    assert admits[-1] > admits[0]
    assert stats["dense_pages_leaked"] == 0


def test_preemption_recompute_is_exact(model):
    """Out-of-pages preemption requeues and re-prefills prompt+generated; the
    final greedy tokens must equal an unconstrained run's."""
    cfg, params = model
    gen = GenerationConfig(max_new_tokens=12)
    reqs_small = _reqs(cfg, sizes=(8, 8, 8), max_new=12, seed=3)
    refs = {}
    static = ServeEngine(cfg, params, max_len=64)
    for r in reqs_small:
        refs[r.rid] = static.generate({"tokens": jnp.asarray(r.prompt[None])}, gen)[0][0]
    serving = ServingCfg(num_slots=3, page_size=4, num_pages=10,  # too small
                         max_blocks_per_slot=8, prefill_bucket=4,
                         prefill_chunk=0)  # one-shot oracle: shares static ops
    # gather path == static ops
    eng = ContinuousServeEngine(
        cfg, params, serving=serving,
        rt=dataclasses.replace(cfg.attention, paged_kernels=False))
    res, stats = eng.serve(reqs_small, gen)
    assert stats["preemptions"] >= 1
    for rid, ref in refs.items():
        np.testing.assert_array_equal(res[rid]["tokens"], ref)
    assert stats["dense_pages_leaked"] == 0


def test_tier_escalation_under_pressure(model):
    """Watermark policy: under critical memory pressure a running dense
    request is escalated to the T2 CPQ arena and still produces valid output;
    both arenas end leak-free."""
    cfg, params = model
    serving = ServingCfg(num_slots=3, page_size=4, num_pages=13,
                         escalated_pages=33, max_blocks_per_slot=8,
                         prefill_bucket=4, low_watermark=0.5,
                         critical_watermark=0.25, enable_escalation=True)
    eng = ContinuousServeEngine(cfg, params, serving=serving)
    assert eng.tiered
    reqs = _reqs(cfg, sizes=(8, 10, 6, 7, 9), max_new=10, seed=2)
    res, stats = eng.serve(reqs, GenerationConfig(max_new_tokens=10))
    assert stats["escalations"] >= 1
    assert any(res[i]["escalated"] for i in res)
    for i in res:
        t = res[i]["tokens"]
        assert res[i]["finish_reason"] in ("max_tokens", "eos")
        assert len(t) == 10
        assert (t >= 0).all() and (t < cfg.vocab_size).all()
    assert stats["dense_pages_leaked"] == 0 and stats["cpq_pages_leaked"] == 0


def test_eos_retirement_vacates_and_admits(model):
    """Per-row EOS retirement frees the slot for the queue (the continuous
    engine's reason to exist); stats count only live tokens."""
    cfg, params = model
    serving = ServingCfg(num_slots=2, page_size=4, num_pages=65,
                         max_blocks_per_slot=32, prefill_bucket=4)
    eng = ContinuousServeEngine(cfg, params, serving=serving)
    reqs = _reqs(cfg, sizes=(6, 9, 5, 11, 7, 8), max_new=24, seed=5)

    # probe greedily for a token the model actually emits mid-stream, then
    # replay with that token as EOS — deterministic early retirement
    probe, _ = eng.serve(reqs, GenerationConfig(max_new_tokens=24))
    eos = -1
    for i in probe:
        mid = probe[i]["tokens"][1:-1]
        if len(mid):
            eos = int(mid[0])
            break
    assert eos >= 0
    for r in reqs:  # reset scheduler-owned request state for the replay
        r.generated, r.state, r.length = [], "queued", 0
        r.admitted_step = r.first_token_step = r.done_step = -1
    res, stats = eng.serve(reqs, GenerationConfig(max_new_tokens=24, eos_id=eos))
    assert set(res) == set(range(6))
    eos_finishers = [i for i in res if res[i]["finish_reason"] == "eos"]
    assert eos_finishers, "probe token never re-emitted; premise broken"
    for i in eos_finishers:
        t = res[i]["tokens"]
        assert t[-1] == eos and (t[:-1] != eos).all()  # stops AT the first EOS
        assert len(t) < 24                             # retired early
    assert stats["generated_tokens"] == sum(len(res[i]["tokens"]) for i in res)
    assert stats["dense_pages_leaked"] == 0


def test_static_engine_eos_masking(model):
    """Satellite: static engine masks post-EOS samples to eos_id and reports
    only live tokens."""
    cfg, params = model
    eng = ServeEngine(cfg, params, max_len=64)
    rng = np.random.default_rng(7)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 8)))}
    out, stats = eng.generate(batch, GenerationConfig(max_new_tokens=32, eos_id=0))
    for row in out:
        hits = np.flatnonzero(row == 0)
        if hits.size and hits[0] < len(row) - 1:
            assert (row[hits[0]:] == 0).all()  # everything after EOS is eos_id
    live = sum((np.flatnonzero(r == 0)[0] + 1) if (r == 0).any() else len(r)
               for r in out)
    assert stats["generated_tokens"] == live


def test_nonfinite_logit_rows_are_counted(model):
    """The engine counts sampled rows whose logits hold NaN/Inf (first
    tokens and decode rows alike): zero on a healthy model, every emitted
    token once the final norm is poisoned."""
    cfg, params = model
    serving = ServingCfg(num_slots=2, page_size=4, num_pages=17,
                         max_blocks_per_slot=8, prefill_chunk=4)
    reqs = lambda: _reqs(cfg, [5, 7], max_new=3)  # noqa: E731
    eng = ContinuousServeEngine(cfg, params, serving=serving)
    _, stats = eng.serve(reqs(), GenerationConfig(max_new_tokens=3))
    assert stats["nonfinite_logit_rows"] == 0
    bad = dict(params, final_norm=jax.tree.map(
        lambda a: jnp.full_like(a, jnp.nan), params["final_norm"]))
    eng = ContinuousServeEngine(cfg, bad, serving=serving)
    _, stats = eng.serve(reqs(), GenerationConfig(max_new_tokens=3))
    assert stats["nonfinite_logit_rows"] == stats["generated_tokens"] == 6


# ------------------------------------------------------- ServingCfg.validate

# One case per rule of ``ServingCfg.validate``: (fields, strict, match).
# ``strict`` rules pass construction and fail only ``validate()``.
_INVALID = [
    pytest.param(dict(num_pages=1), False, "num_pages", id="pages"),
    pytest.param(dict(num_slots=0), False, "num_slots", id="slot_minimums"),
    pytest.param(dict(critical_watermark=0.5, low_watermark=0.25), False,
                 "critical_watermark", id="critical_le_low"),
    pytest.param(dict(low_watermark=0.6, high_watermark=0.4), False,
                 "high_watermark", id="low_le_high"),
    pytest.param(dict(policy="lifo"), False, "policy", id="policy"),
    pytest.param(dict(prefill_bucket=0), False, "prefill_bucket",
                 id="prefill_bucket"),
    pytest.param(dict(prefill_chunk=-16), False, "prefill_chunk=-16",
                 id="negative_prefill_chunk"),
    pytest.param(dict(defrag_every=-1), False, "defrag_every",
                 id="defrag_every"),
    pytest.param(dict(probe_interval=-1), False, "probe_interval",
                 id="probe_interval"),
    pytest.param(dict(probe_backoff=0), False, "probe_backoff",
                 id="probe_failures_backoff"),
    pytest.param(dict(probe_exhaust_frac=1.5), False, "probe_exhaust_frac",
                 id="probe_exhaust_frac"),
    pytest.param(dict(deadline_scale=-1.0), False, "deadline_scale",
                 id="deadline_scale"),
    pytest.param(dict(max_backlog=-1), False, "max_backlog",
                 id="max_backlog"),
    pytest.param(dict(spec_len=-1), False, "spec_len", id="spec_len"),
    pytest.param(dict(spec_ngram=0), False, "spec_ngram", id="spec_ngram"),
    pytest.param(dict(page_size=8, prefill_chunk=12), False, "page-aligned",
                 id="page_alignment"),
    pytest.param(dict(spec_len=2, prefill_chunk=0), True, "chunked admission",
                 id="strict_spec_needs_chunk"),
    pytest.param(dict(page_size=1, max_blocks_per_slot=1), True, "max_len",
                 id="strict_max_len"),
]


@pytest.mark.parametrize("fields,strict,match", _INVALID + [
    pytest.param({}, True, None, id="default")])
def test_validate_clear_errors(fields, strict, match):
    if match is None:
        ok = ServingCfg(**fields)
        assert ok.validate() is ok               # chainable: returns self
        return
    if not strict:
        with pytest.raises(ValueError, match=match):
            ServingCfg(**fields)
        return
    cfg = ServingCfg(**fields)                   # constructs fine
    with pytest.raises(ValueError, match=match):
        cfg.validate()


def test_engine_construction_raises_scheduler_config_error():
    cfg = smoke_config(ARCHS["qwen1.5-0.5b"])
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    bad = ServingCfg(spec_len=2, prefill_chunk=0)
    with pytest.raises(SchedulerConfigError, match="spec_len"):
        ContinuousServeEngine(cfg, params, serving=bad)
