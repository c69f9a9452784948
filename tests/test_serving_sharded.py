"""Mesh-native paged serving: sharded-vs-single-device greedy parity (every
tier, gather AND fused kernels), spec-tree structure, the sharded-arena
allocation/defrag logical-contents property, mesh validation guards, and the
public allocator-stats / defrag engine surface.

Multi-device tests run in subprocesses with 8 emulated host devices
(conftest.run_with_devices) so the in-process suite keeps the single real
CPU device; ``mesh=None`` bit-identity is what every OTHER serving suite
already pins (they run unmodified on the unsharded path)."""
import dataclasses

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, ServingCfg, get_config, smoke_config
from repro.models import model as M
from repro.serving.engine import ContinuousServeEngine, GenerationConfig
from repro.serving.scheduler import Request

from conftest import run_with_devices

# ------------------------------------------------------------ spec structure


@pytest.mark.parametrize("arch,mode,tiered", [
    ("qwen1.5-0.5b", "dense", False),
    ("qwen1.5-0.5b", "decomposed", False),
    ("qwen1.5-0.5b", "cpq", False),
    ("qwen1.5-0.5b", "retrieval", False),
    ("qwen1.5-0.5b", "decomposed_cpq", False),
    ("qwen1.5-0.5b", "dense", True),
    ("deepseek-v2-lite-16b", "decomposed", False),
    ("jamba-1.5-large-398b", "dense", False),
])
def test_paged_spec_tree_matches_cache_structure(arch, mode, tiered):
    """paged_cache_pspecs mirrors init_paged_caches exactly (same pytree),
    so device placement and shard_map specs can never misalign."""
    from functools import partial

    from repro.distributed.cache_specs import paged_cache_pspecs

    cfg = smoke_config(get_config(arch)).with_attention(mode)
    serving = ServingCfg(num_slots=2, page_size=4, num_pages=9,
                         max_blocks_per_slot=4)
    caches = jax.eval_shape(
        partial(M.init_paged_caches, cfg, cfg.attention, serving, tiered))
    specs = paged_cache_pspecs(cfg, cfg.attention, serving, tiered)
    assert jax.tree.structure(caches) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, P))


def test_serve_paged_rules_shard_head_and_latent_axes():
    from repro.distributed.cache_specs import paged_layer_cache_specs

    cfg = smoke_config(ARCHS["qwen1.5-0.5b"])
    serving = ServingCfg(num_slots=2, page_size=4, num_pages=9,
                         max_blocks_per_slot=4)
    dense = paged_layer_cache_specs(cfg, cfg.attention, ("attn", "dense"),
                                    serving)
    assert dense.k == P(None, None, "model", None)
    x = paged_layer_cache_specs(cfg, cfg.with_attention("decomposed").attention,
                                ("attn", "dense"), serving)
    assert x.x == P(None, None, "model")          # latent feature axis
    assert x.k_rope == P(None, None, "model", None)
    mamba = paged_layer_cache_specs(
        smoke_config(get_config("jamba-1.5-large-398b")), cfg.attention,
        ("mamba", "dense"), serving)
    assert all(sp == P() for sp in jax.tree.leaves(
        mamba, is_leaf=lambda s: isinstance(s, P)))


# --------------------------------------------------- engine stats / defrag


@pytest.fixture(scope="module")
def model_f32():
    cfg = dataclasses.replace(smoke_config(ARCHS["qwen1.5-0.5b"]),
                              dtype="float32")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _reqs(cfg, sizes, max_new=6, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=s).astype(np.int32),
                    max_new_tokens=max_new)
            for i, s in enumerate(sizes)]


def test_engine_surfaces_allocator_stats(model_f32):
    """The small-fix satellite: utilization + defrag counts are public serve
    stats (bench_serving / the sharded watermark read these, not private
    allocator state)."""
    cfg, params = model_f32
    serving = ServingCfg(num_slots=2, page_size=4, num_pages=17,
                         max_blocks_per_slot=4, prefill_bucket=4,
                         prefill_chunk=4)
    eng = ContinuousServeEngine(cfg, params, serving=serving)
    _, stats = eng.serve(_reqs(cfg, [5, 3, 6, 4]), GenerationConfig(max_new_tokens=5))
    for key in ("dense_arena_utilization", "dense_pages_used",
                "dense_pages_free", "defrags", "model_shards",
                "arena_bytes_total", "arena_bytes_per_device",
                "interconnect_bytes_per_token"):
        assert key in stats, key
    assert stats["model_shards"] == 1
    assert stats["arena_bytes_per_device"] == stats["arena_bytes_total"]
    assert stats["interconnect_bytes"] == 0.0   # no mesh, no concat traffic
    assert stats["dense_arena_utilization"] == 0.0  # all pages freed at end


def test_defrag_policy_preserves_outputs_and_counts(model_f32):
    """defrag_every compacts the base arena mid-serve: greedy outputs are
    unchanged and the compaction count surfaces in stats."""
    cfg, params = model_f32
    serving = ServingCfg(num_slots=2, page_size=4, num_pages=17,
                         max_blocks_per_slot=4, prefill_bucket=4,
                         prefill_chunk=4)
    gen = GenerationConfig(max_new_tokens=6)
    base_eng = ContinuousServeEngine(cfg, params, serving=serving)
    base, bstats = base_eng.serve(_reqs(cfg, [5, 3, 7, 4, 6]), gen)
    frag_eng = ContinuousServeEngine(
        cfg, params, serving=dataclasses.replace(serving, defrag_every=1))
    frag, fstats = frag_eng.serve(_reqs(cfg, [5, 3, 7, 4, 6]), gen)
    assert bstats["defrags"] == 0 and fstats["defrags"] > 0
    for rid in base:
        np.testing.assert_array_equal(base[rid]["tokens"], frag[rid]["tokens"])


def test_scheduler_plan_defrag_remaps_pages_and_free_list():
    from repro.serving.paged_cache import NULL_PAGE
    from repro.serving.scheduler import Scheduler

    serving = ServingCfg(num_slots=2, page_size=4, num_pages=9,
                         max_blocks_per_slot=4)
    sched = Scheduler(serving)
    reqs = _reqs(smoke_config(ARCHS["qwen1.5-0.5b"]), [8, 8])
    for r in reqs:
        sched.submit(r)
    a = sched.admit_next(now=0, step=0)
    b = sched.admit_next(now=0, step=0)
    sched.finish_prefill(a), sched.finish_prefill(b)
    sched.retire(a, 1, "eos")      # leaves b's pages fragmented (high ids)
    perm = sched.plan_defrag()
    assert perm is not None and sched.stats["defrags"] == 1
    assert sorted(b.pages) == [1, 2]       # compacted onto the lowest ids
    assert set(sched.block_tables[b.slot]) - {NULL_PAGE} == set(b.pages)
    free = sched.dense_alloc
    assert free.num_free == serving.num_pages - 1 - len(b.pages)
    assert sched.plan_defrag() is None     # already compact


# ------------------------------------------------------------ mesh validation


def test_mesh_validation_rejects_nondividing_heads():
    run_with_devices("""
import jax
from repro.configs import ARCHS, ServingCfg, smoke_config
from repro.models import model as M
from repro.serving.engine import ContinuousServeEngine
from repro.serving.scheduler import SchedulerConfigError
from repro.launch.mesh import make_serve_mesh

cfg = smoke_config(ARCHS["qwen1.5-0.5b"])  # 4 query / 4 kv heads
params = M.init_params(cfg, jax.random.PRNGKey(0))
try:
    ContinuousServeEngine(cfg, params, serving=ServingCfg(),
                          mesh=make_serve_mesh(1, 8))
except SchedulerConfigError as e:
    assert "num_heads" in str(e) or "num_kv_heads" in str(e)
    print("REJECTED-OK")
else:
    raise AssertionError("8-way model sharding of 4 heads was accepted")
""")


# ------------------------------------- sharded-vs-single-device greedy parity

_PARITY_CODE = """
import dataclasses
import numpy as np
import jax
from repro.configs import ARCHS, ServingCfg, get_config, smoke_config
from repro.models import model as M
from repro.serving.engine import ContinuousServeEngine, GenerationConfig
from repro.serving.scheduler import Request
from repro.launch.mesh import make_serve_mesh

arch, mode, tiered = {arch!r}, {mode!r}, {tiered}
cfg = smoke_config(get_config(arch))
cfg = dataclasses.replace(cfg, dtype="float32")
if mode is not None:
    cfg = cfg.with_attention(mode)
params = M.init_params(cfg, jax.random.PRNGKey(0))
serving = ServingCfg(num_slots=2, page_size=4, num_pages=33,
                     max_blocks_per_slot=8, prefill_bucket=4, prefill_chunk=4,
                     enable_escalation=tiered,
                     low_watermark=0.6 if tiered else 0.25,
                     critical_watermark=0.3 if tiered else 0.10)
gen = GenerationConfig(max_new_tokens=6)

def serve(mesh, fused):
    rt = dataclasses.replace(cfg.attention, paged_kernels=fused)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=s)
                    .astype(np.int32), max_new_tokens=6)
            for i, s in enumerate([5, 9, 3, 7])]
    eng = ContinuousServeEngine(cfg, params, rt=rt, serving=serving, mesh=mesh)
    return eng.serve(reqs, gen)

mesh = make_serve_mesh(1, 2)
for fused in (True, False):
    r0, s0 = serve(None, fused)
    r1, s1 = serve(mesh, fused)
    for rid in r0:
        assert np.array_equal(r0[rid]["tokens"], r1[rid]["tokens"]), (
            mode, fused, rid, r0[rid]["tokens"], r1[rid]["tokens"])
        assert r0[rid]["finish_reason"] == r1[rid]["finish_reason"]
    assert s1["model_shards"] == 2
    assert s1["dense_pages_leaked"] == 0
    assert s1["arena_bytes_per_device"] < s1["arena_bytes_total"]
    assert s1["interconnect_bytes"] > 0
    if tiered:
        assert s0["escalations"] == s1["escalations"]
print("PARITY-OK", s1["arena_bytes_per_device"], "/", s1["arena_bytes_total"])
"""


@pytest.mark.parametrize("arch,mode,tiered", [
    ("qwen1.5-0.5b", "dense", False),
    ("qwen1.5-0.5b", "cpq", False),
    ("qwen1.5-0.5b", "decomposed", False),
    ("deepseek-v2-lite-16b", None, False),   # MLA latent (one-shot: MoE)
    ("qwen1.5-0.5b", "dense", True),         # tiered dense+CPQ watermark
], ids=["dense", "cpq", "decomposed", "mla", "tiered"])
def test_sharded_engine_greedy_parity(arch, mode, tiered):
    """mesh=(dp=1, model=2): token-exact greedy parity vs the single-device
    engine at f32, fused AND gather kernel paths; per-device arena bytes
    shrink and only per-head partials cross the interconnect."""
    out = run_with_devices(_PARITY_CODE.format(arch=arch, mode=mode,
                                               tiered=tiered))
    assert "PARITY-OK" in out


# --------------------------- sharded arena alloc/defrag logical invariance

_ARENA_PROPERTY_CODE = """
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.serving import paged_cache as pgc

def scenario(seed, num_pages, n_slots, mp):
    \"\"\"Replay one random alloc/write/retire/defrag history against a
    replicated arena and a model-sharded one: logical contents (the
    gathered per-slot views) must match exactly for any mesh shape.\"\"\"
    page, kv, dh, max_blocks = 2, 8, 4, 4
    mesh = make_mesh((1, mp), ("data", "model"))
    sh = NamedSharding(mesh, P(None, None, "model", None))
    rng = np.random.default_rng(seed)
    ref = jnp.zeros((num_pages, page, kv, dh), jnp.float32)
    shd = jax.device_put(ref, sh)
    alloc = pgc.PageAllocator(num_pages)
    tables = np.zeros((n_slots, max_blocks), np.int32)
    owned = {}
    for step in range(20):
        op = rng.integers(0, 3)
        if op == 0:  # admit a prompt into a free slot
            slot = next((s for s in range(n_slots) if s not in owned), None)
            n_tok = int(rng.integers(1, page * max_blocks + 1))
            need = pgc.pages_needed(n_tok, page)
            if slot is None or not alloc.can_alloc(need):
                continue
            pages = alloc.alloc(need)
            owned[slot] = pages
            tables[slot, :] = pgc.NULL_PAGE
            tables[slot, :need] = pages
            val = jnp.asarray(rng.normal(size=(n_tok, kv, dh)), jnp.float32)
            row = jnp.asarray(tables[slot])
            ref = pgc.write_prompt_pages(ref, row, val)
            shd = pgc.write_prompt_pages(shd, row, val)
        elif op == 1:  # retire a slot
            if not owned:
                continue
            slot = int(rng.choice(list(owned)))
            alloc.free(owned.pop(slot))
            tables[slot, :] = pgc.NULL_PAGE
        else:  # defrag: relabel mapped pages onto the lowest ids
            perm, new_bt, free = pgc.defrag_plan(tables, num_pages)
            remap = {int(o): n for n, o in enumerate(perm)}
            tables[:] = new_bt
            owned = {s: [remap[p] for p in ps] for s, ps in owned.items()}
            alloc.reset_free(free)
            pj = jnp.asarray(perm)
            ref = jnp.take(ref, pj, axis=0)
            shd = jnp.take(shd, pj, axis=0)
    bt = jnp.asarray(tables)
    np.testing.assert_array_equal(
        np.asarray(pgc.gather_pages(ref, bt)),
        np.asarray(pgc.gather_pages(shd, bt)))

try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None, derandomize=True)
    @given(seed=st.integers(0, 2**31 - 1), num_pages=st.integers(4, 24),
           n_slots=st.integers(1, 4), mp=st.sampled_from([2, 4, 8]))
    def prop(seed, num_pages, n_slots, mp):
        scenario(seed, num_pages, n_slots, mp)

    prop()
    print("PROPERTY-OK hypothesis")
except ImportError:
    for seed in range(8):           # deterministic fallback sweep
        for mp in (2, 4, 8):
            scenario(seed, 4 + 3 * seed, 1 + seed % 4, mp)
    print("PROPERTY-OK deterministic")
"""


def test_sharded_arena_alloc_defrag_logical_invariance():
    """Any alloc/write/retire/defrag history leaves a model-sharded arena
    with logical contents identical to the replicated arena, for any mesh
    shape (hypothesis when installed; seed-pinned ci profile in CI)."""
    out = run_with_devices(_ARENA_PROPERTY_CODE)
    assert "PROPERTY-OK" in out
