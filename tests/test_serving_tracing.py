"""The serving engine's own trace: host spans per tick phase, read back from
a real profiler trace, stable names for its jitted steps, and token streams
that no span changes."""
import contextlib
import dataclasses
import glob
import logging
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, ServingCfg, get_config, smoke_config
from repro.models import model as M
from repro.serving.engine import (ContinuousServeEngine, GenerationConfig,
                                  ServeEngine)
from repro.serving.request import SamplingParams, ServeRequest

PHASES = ("serve.admit", "serve.prefill_chunk", "serve.grow", "serve.speculate",
          "serve.decode", "serve.sample", "serve.emit")
# the benchmark's own host spans (chipbench/bench.py): the program never
# emits these, or the benchmark would count its spans as its own
BENCH_SPANS = ("engine.step", "gen.add", "wait", "bench.trace")


@pytest.fixture(scope="module")
def engine():
    cfg = smoke_config(ARCHS["qwen1.5-0.5b"])
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    serving = ServingCfg(num_slots=2, page_size=8, num_pages=17,
                         max_blocks_per_slot=8, prefill_chunk=16)
    return ContinuousServeEngine(cfg, params, serving=serving)


def _requests(vocab):
    rng = np.random.default_rng(0)
    return [ServeRequest(prompt=rng.integers(0, vocab, size=n).astype(np.int32),
                         sampling=SamplingParams(max_tokens=4))
            for n in (40, 12, 20)]


def _serve(eng, reqs) -> int:
    """Serve ``reqs`` to completion; returns how many steps had work."""
    eng.reset()
    for r in reqs:
        eng.add_request(r)
    working = 0
    while eng.has_unfinished():
        working += 1
        eng.step()
    return working


def _options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for pl in ProfileData.from_file(path).planes
            if pl.name.startswith("/host:")
            for ln in pl.lines for e in ln.events]


def test_tick_phases_are_spans_in_the_trace(engine, tmp_path):
    reqs = _requests(engine.cfg.vocab_size)
    _serve(engine, reqs)                      # compile outside the trace
    with jax.profiler.trace(str(tmp_path), profiler_options=_options()):
        working = _serve(engine, reqs)
    spans = _host_spans(tmp_path)
    ticks = [s for s in spans if s[0] == "serve.tick"]
    assert len(ticks) == working > 0
    kids = [s for s in spans if s[0] in PHASES]
    for name, a, b, _ in kids:
        assert sum(t[1] <= a and b <= t[2] for t in ticks) == 1, name
    assert {s[0] for s in kids} >= {"serve.admit", "serve.prefill_chunk",
                                    "serve.grow", "serve.decode",
                                    "serve.sample", "serve.emit"}
    assert "serve.speculate" not in {s[0] for s in kids}   # speculation off
    for _, a, b, _ in ticks:                  # in order, one after another
        inner = sorted((s[1], s[2], s[0]) for s in kids if a <= s[1] and s[2] <= b)
        order = [PHASES.index(n) for _, _, n in inner]
        assert order == sorted(set(order)), [n for _, _, n in inner]
        assert all(e1 <= s2 for (_, e1, _), (s2, _, _) in zip(inner, inner[1:]))

    rids = list(range(len(reqs)))
    added = [s[3]["rid"] for s in spans if s[0] == "serve.add_request"]
    assert added == rids
    admitted = [int(r) for s in spans if s[0] == "serve.admit" and "rid" in s[3]
                for r in str(s[3]["rid"]).split()]
    assert sorted(admitted) == rids
    chunks = [s[3] for s in spans if s[0] == "serve.prefill_chunk"]
    assert {c["rid"] for c in chunks} == set(rids)
    for rid, r in zip(rids, reqs):            # chunk tokens add up per request
        assert sum(c["tokens"] for c in chunks if c["rid"] == rid) == len(r.prompt)
    assert all(s[3]["rows"] >= 1 for s in spans if s[0] == "serve.decode")
    assert not [s for s in spans if s[0] in BENCH_SPANS]


def test_idle_engine_step_opens_no_tick(engine, tmp_path):
    engine.reset()
    with jax.profiler.trace(str(tmp_path), profiler_options=_options()):
        assert engine.step() == []
    assert not [s for s in _host_spans(tmp_path) if s[0].startswith("serve.")]


def test_jitted_steps_lower_to_named_modules(engine):
    engine.reset()
    st = engine._st
    sv = engine.serving
    dec = engine._decode.lower(engine.params, jnp.asarray(st.last_tok[:, None]),
                               engine._row_state(st.sched), st.caches)
    assert dec.as_text().startswith("module @jit_decode_step_rows ")
    i32 = lambda v: jnp.asarray(v, jnp.int32)      # noqa: E731
    chunk = engine._chunk_fn(0, True).lower(
        engine.params, jnp.zeros((1, sv.prefill_chunk), jnp.int32), i32(0),
        jnp.zeros((sv.max_blocks_per_slot,), jnp.int32), i32(0), i32(1),
        st.caches)
    assert chunk.as_text().startswith("module @jit_prefill_chunk_rows ")
    assert engine._verify_fn(0).__name__ == "verify_chunk_rows"


@pytest.mark.parametrize("attr,name", [
    ("_decode", "decode_step_rows"), ("_pack", "pack_prefill_caches"),
    ("_escalate", "escalate_slot"), ("_defrag", "defrag_caches"),
    ("_copy_page", "copy_page_caches")])
def test_jitted_steps_keep_their_function_names(engine, attr, name):
    assert getattr(engine, attr).__name__ == name


def test_session_spans_carry_device_memory(engine, tmp_path):
    """``serve.reset`` and ``serve.release`` open once a session each and
    carry device 0's memory where the backend keeps memory stats."""
    reqs = _requests(engine.cfg.vocab_size)
    _serve(engine, reqs)
    engine.release()
    with jax.profiler.trace(str(tmp_path), profiler_options=_options()):
        _serve(engine, reqs)                  # its reset opens the session
        engine.release()
    spans = _host_spans(tmp_path)
    session = [s for s in spans if s[0] in ("serve.reset", "serve.release")]
    assert [s[0] for s in session] == ["serve.reset", "serve.release"]
    reset, release = session
    ticks = [s for s in spans if s[0] == "serve.tick"]
    assert reset[2] <= min(t[1] for t in ticks)
    assert release[1] >= max(t[2] for t in ticks)
    keys = {"bytes_in_use", "peak_bytes_in_use"}
    if jax.local_devices()[0].memory_stats() is None:      # the CPU
        assert not keys & (set(reset[3]) | set(release[3]))
    else:
        for s in session:
            assert keys <= set(s[3])
            assert int(s[3]["peak_bytes_in_use"]) >= int(s[3]["bytes_in_use"])


def test_no_unnamed_program_is_compiled_for_a_tick(engine, caplog):
    """Every program a tick runs is compiled under its function's name
    (``jit(<unknown>)`` is a ``functools.partial`` jitted bare, which
    reaches the trace as ``jit__unknown``). A fresh engine compiles each
    of its model steps anew on its first ticks; module-level functions,
    such as the samplers, may already be compiled."""
    fresh = ContinuousServeEngine(engine.cfg, engine.params,
                                  serving=engine.serving)
    with caplog.at_level(logging.WARNING), jax.log_compiles(True):
        _serve(fresh, _requests(engine.cfg.vocab_size))
    names = set(re.findall(r"Compiling (\S+) with", caplog.text))
    assert {"jit(decode_step_rows)", "jit(prefill_chunk_rows)"} <= names
    assert not [n for n in names if "unknown" in n], names


@pytest.fixture(scope="module")
def oracle(engine):
    """A one-shot, gather-path engine, which shares the static engine's
    ops, and the static engine's greedy tokens for the same prompts."""
    cfg, params = engine.cfg, engine.params
    static = ServeEngine(cfg, params, max_len=64)
    gen = GenerationConfig(max_new_tokens=4)
    refs = [np.asarray(static.generate({"tokens": jnp.asarray(r.prompt[None])},
                                       gen)[0][0])
            for r in _requests(cfg.vocab_size)]
    serving = ServingCfg(num_slots=2, page_size=8, num_pages=17,
                         max_blocks_per_slot=8, prefill_bucket=8,
                         prefill_chunk=0)
    rt = dataclasses.replace(cfg.attention, paged_kernels=False)
    return ContinuousServeEngine(cfg, params, rt=rt, serving=serving), refs


@pytest.mark.parametrize("traced", [False, True])
def test_token_streams_unchanged_by_the_spans(oracle, traced, tmp_path):
    """The engine's tokens match the static engine's, which opens no span,
    with no profiler open and with one open."""
    eng, refs = oracle
    trace = (jax.profiler.trace(str(tmp_path), profiler_options=_options())
             if traced else contextlib.nullcontext())
    with trace:
        _serve(eng, _requests(eng.cfg.vocab_size))
    res = eng.results()
    assert sorted(res) == list(range(len(refs)))
    for rid, ref in enumerate(refs):
        np.testing.assert_array_equal(res[rid]["tokens"], ref)


def test_static_engine_steps_keep_their_function_names(engine):
    static = ServeEngine(engine.cfg, engine.params, max_len=64)
    assert (static._prefill.__name__, static._decode.__name__) == (
        "prefill", "decode_step")


@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_mla_and_expert_scopes_reach_the_lowered_steps(step):
    """The smoke DeepSeek-V2-Lite engine's decode and chunk programs carry
    the attention scopes around MLA and the expert layer's own scopes (the
    names the trace's ``tf_op`` paths are built from)."""
    cfg = smoke_config(get_config("deepseek-v2-lite-16b"))
    serving = ServingCfg(num_slots=2, page_size=8, num_pages=17,
                         max_blocks_per_slot=8, prefill_chunk=8)
    eng = ContinuousServeEngine(cfg, M.init_params(cfg, jax.random.PRNGKey(0)),
                                serving=serving)
    assert eng.chunked and eng.donates
    eng.reset()
    st = eng._st
    if step == "decode":
        low = eng._decode.lower(eng.params, jnp.asarray(st.last_tok[:, None]),
                                eng._row_state(st.sched), st.caches)
    else:
        i32 = lambda v: jnp.asarray(v, jnp.int32)      # noqa: E731
        low = eng._chunk_fn(0, True).lower(
            eng.params, jnp.zeros((1, 8), jnp.int32), i32(0),
            jnp.zeros((8,), jnp.int32), i32(0), i32(1), st.caches)
    text = low.as_text(debug_info=True)
    for scope in ("attn.qkv", "kv_write", "attn.kernel", "attn.out",
                  "moe.router", "moe.dispatch", "moe.experts", "moe.shared"):
        assert re.search(rf'["/]{re.escape(scope)}[/"]', text), scope
    eng.release()
