"""Distributed machinery: spec resolution, cache spec trees, HLO analysis,
flash-decoding combine, ring overlap, GPipe (multi-device parts run in
subprocesses so in-process tests keep the single real CPU device)."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.common.param import ParamDef, spec_tree
from repro.configs import ARCHS, SHAPES, get_config
from repro.distributed import hlo_analysis
from repro.distributed.rules import act_rules, batch_axes, param_rules
from repro.distributed.sharding import resolve


def test_spec_tree_divisibility_filter():
    defs = {
        "ok": ParamDef((64, 32), jnp.float32, ("embed", "heads")),
        "bad_heads": ParamDef((4, 4, 8, 8), jnp.float32, (None, "heads", None, None)),
    }
    specs = spec_tree(defs, param_rules(False), {"data": 16, "model": 16})
    assert specs["ok"] == P("data", "model")
    assert specs["bad_heads"] == P(None, None, None, None)


def test_rules_resolve_dedup():
    rules = act_rules(True)
    spec = resolve(rules, ("act_batch", None, "act_heads"))
    assert spec == P(("pod", "data"), None, "model")


def test_batch_axes_divisibility():
    ms = {"pod": 2, "data": 16, "model": 16}
    assert batch_axes(True, 256, ms) == ("pod", "data")
    assert batch_axes(False, 1, {"data": 16, "model": 16}) == ()
    assert batch_axes(True, 2, ms) == ("pod",)


def test_cache_spec_trees_match_cache_structure():
    """Spec tree structure == eval_shape(init_caches) structure, all modes."""
    from functools import partial

    from repro.distributed.cache_specs import cache_pspecs
    from repro.models import model as M

    for arch in ("qwen3-4b", "deepseek-v2-lite-16b", "jamba-1.5-large-398b",
                 "xlstm-125m", "llama-3.2-vision-11b"):
        cfg = get_config(arch)
        for mode in ("dense", "decomposed", "cpq", "retrieval"):
            c = cfg.with_attention(mode)
            caches = jax.eval_shape(partial(M.init_caches, c, c.attention, 4, 64))
            specs = cache_pspecs(c, c.attention, "data", None)
            s1 = jax.tree.structure(caches)
            s2 = jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, P))
            assert s1 == s2, (arch, mode)


def test_hlo_analysis_matmul_and_scan():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=5)
        return y

    c = jax.jit(f).lower(jax.ShapeDtypeStruct((128, 64), jnp.float32),
                         jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    a = hlo_analysis.analyze(c.as_text())
    expect = 5 * 2 * 128 * 64 * 64
    np.testing.assert_allclose(a.flops, expect, rtol=0.01)
    assert 5 in hlo_analysis.while_trip_counts(c.as_text())


def test_hlo_analysis_collectives(run8):
    out = run8("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.distributed.hlo_analysis import analyze
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('d',))
def h(x, w):
    def body(c, _): return c @ w, None
    y, _ = jax.lax.scan(body, x, None, length=3)
    return jnp.sum(y)
fn = jax.jit(h, in_shardings=(NamedSharding(mesh, P(None, 'd')),
                              NamedSharding(mesh, P('d', None))))
c = fn.lower(jax.ShapeDtypeStruct((64, 64), jnp.float32),
             jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
a = analyze(c.as_text())
assert a.collective_total > 0, a.collectives
assert abs(a.flops - 3 * 2 * 64 * 64 * 64 / 8) / a.flops < 0.05
print('collectives ok', a.collectives)
""")
    assert "collectives ok" in out


def test_flash_decoding_and_ring(run8):
    out = run8("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.collectives import flash_decoding_attention, ring_decomposed_scores
from repro.core.attention import dense_attention
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('s',))
key = jax.random.PRNGKey(0)
B,H,KV,Dh,N = 2,8,4,32,128
ks = jax.random.split(key,4)
q = jax.random.normal(ks[0],(B,1,H,Dh)); k = jax.random.normal(ks[1],(B,N,KV,Dh)); v = jax.random.normal(ks[2],(B,N,KV,Dh))
ln = jnp.asarray(100, jnp.int32)
out = flash_decoding_attention(mesh, 's')(q, k, v, ln, 0.125)
ref = dense_attention(q, k, v, 0.125, causal=False, kv_length=ln)
assert np.abs(np.asarray(out-ref)).max() < 1e-5
r = jax.random.normal(ks[3],(B,16,64)); x = jax.random.normal(ks[0],(B,N,64))
s1 = ring_decomposed_scores(mesh, 's')(r, x)
s2 = jnp.einsum('bhm,bnm->bhn', r, x)
assert np.abs(np.asarray(s1-s2)).max() < 2e-4
print('dist ok')
""")
    assert "dist ok" in out


def test_gpipe(run8):
    out = run8("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import gpipe_forward
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ('pod',))
key = jax.random.PRNGKey(0)
w = jax.random.normal(key, (8, 16, 16)) / 4.0
x = jax.random.normal(key, (6, 2, 16))
blk = lambda p, h: jnp.tanh(h @ p)
out = gpipe_forward(mesh, 'pod', blk)(w, x)
ref = x
for i in range(8): ref = blk(w[i], ref)
assert np.abs(np.asarray(out-ref)).max() < 1e-6
print('gpipe ok')
""")
    assert "gpipe ok" in out


def test_bubble_fraction():
    from repro.distributed.pipeline import bubble_fraction

    assert bubble_fraction(1, 4) == 0.75
    assert bubble_fraction(32, 2) < 0.04


def test_dryrun_records_complete():
    """The 40-cell x 2-mesh dry-run artifacts exist and are green
    (deliverable e) — regenerate with launch/dryrun.py --all --both-meshes."""
    import json
    from pathlib import Path

    d = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"
    if not d.exists():
        import pytest
        pytest.skip("dry-run artifacts not generated yet")
    recs = [json.loads(p.read_text()) for p in d.glob("*.json")]
    ok = [r for r in recs if not r.get("skipped")]
    meshes = {r["mesh"] for r in ok}
    assert {"16x16", "pod2x16x16"} <= meshes
    archs = {r["arch"] for r in ok}
    assert len(archs) >= 10
    for r in ok:
        assert r["flops_per_device"] and r["flops_per_device"] > 0, r["arch"]
