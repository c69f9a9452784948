"""The engine's jitted steps donate the dense arena and update it in place.

A dense arena rides in the layer scan's carry (``models/model.py``), so the
engine donates ``caches`` to every step that takes them: decode, prompt
chunks, speculative verify, one-shot pack, copy-on-write page copies and
defrag. The CPU backend honours donation and raises on any read of a deleted
buffer, so a whole session that runs through shows that no caller reads
caches it donated; its tokens must match the gather path
(``paged_kernels=False``), which indexes the same stacked arena.
"""
import dataclasses

import numpy as np
import pytest

import jax

from repro.configs import ARCHS, ServingCfg, smoke_config
from repro.models import model as M
from repro.serving.engine import ContinuousServeEngine, GenerationConfig
from repro.serving.scheduler import Request

MAX_NEW = (8, 8, 16)            # tokens per request of ``_prompts``


@pytest.fixture(scope="module")
def model_f32():
    cfg = dataclasses.replace(smoke_config(ARCHS["qwen1.5-0.5b"]),
                              dtype="float32")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _prompts(cfg):
    """A long prompt, a late one that shares its first 22 tokens and
    diverges mid-page (a copy-on-write split when sharing is on), and a
    short unrelated one that outlives it (its pages then sit above freed
    ones, so a defrag moves them)."""
    rng = np.random.default_rng(3)
    draw = lambda n: rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)  # noqa: E731
    sys_p = draw(24)
    return [np.concatenate([sys_p, draw(8)]),
            np.concatenate([sys_p[:22], draw(6)]),
            draw(5)]


ADMISSIONS = {
    # chunked admission, prefix sharing with a copy-on-write split, defrag
    "chunked": dict(prefill_chunk=4, share_prefix=True, defrag_every=1),
    # one-shot prefill packed into the arena, defrag
    "one_shot": dict(prefill_chunk=0, defrag_every=1),
    # chunked admission with speculative verify chunks
    "speculative": dict(prefill_chunk=4, spec_len=2),
}


def _session(cfg, params, admission: str, fused: bool):
    sv = ServingCfg(num_slots=2, page_size=4, num_pages=33,
                    max_blocks_per_slot=12, prefill_bucket=4,
                    **ADMISSIONS[admission])
    rt = dataclasses.replace(cfg.attention, paged_kernels=fused)
    eng = ContinuousServeEngine(cfg, params, rt=rt, serving=sv)
    assert eng.donates
    eng.reset(GenerationConfig(max_new_tokens=max(MAX_NEW)))
    first = eng._st.caches["blocks"][0].k
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(_prompts(cfg), MAX_NEW))]
    eng.add_request(reqs[0])
    for _ in range(12):          # the long prompt's pages land and register
        eng.step()
    for r in reqs[1:]:
        eng.add_request(r)
    while eng.has_unfinished():
        eng.step()
    assert first.is_deleted()    # the arena the session began with was donated
    toks = {r: np.asarray(v["tokens"]) for r, v in eng._st.results.items()}
    return toks, eng.stats()


@pytest.mark.parametrize("admission", list(ADMISSIONS))
def test_donated_session_matches_gather_path(model_f32, admission):
    cfg, params = model_f32
    fused, stats = _session(cfg, params, admission, fused=True)
    gather, _ = _session(cfg, params, admission, fused=False)
    assert sorted(fused) == [0, 1, 2]
    for rid in gather:
        assert len(fused[rid]) == MAX_NEW[rid]
        np.testing.assert_array_equal(fused[rid], gather[rid])
    if admission == "chunked":
        assert stats["cow_copies"] >= 1 and stats["defrags"] >= 1
    if admission == "one_shot":
        assert stats["defrags"] >= 1
    if admission == "speculative":
        assert stats["spec_on"] and stats["spec_accept_rate"] > 0.0
