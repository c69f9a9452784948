"""DeepSeek-V2-Lite's published equations on the serving path, at smoke
widths on the CPU: YaRN rope and the softmax scale, the dropless expert
layer that holds a share of the experts, and chunked prefill then decode
through the continuous engine against the plain float32 reference of the
benchmark (``chipbench/configs/mla_moe_ref.py``, which imports nothing of
the program)."""
import dataclasses
import importlib.util
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.common.param import init_tree
from repro.configs import get_config
from repro.configs.base import MLACfg, MoECfg, ServingCfg
from repro.models import mla as mla_lib
from repro.models import model as M
from repro.models import moe as moe_lib
from repro.models.layers import rope_inv_freq, yarn_correction_range, yarn_mscale
from repro.serving.engine import ContinuousServeEngine, GenerationConfig
from repro.serving.scheduler import Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V2 = get_config("deepseek-v2-lite-16b")

# the published router (64 experts, top 6, no renormalisation) and YaRN at
# small widths; this chip holds 8 of the 64 experts
SMALL = dataclasses.replace(
    V2, d_model=64, num_heads=4, num_kv_heads=4, head_dim=16, d_ff=96,
    vocab_size=256, num_blocks=2, experts_held=8, dtype="float32",
    moe=dataclasses.replace(V2.moe, d_ff_expert=32),
    mla=MLACfg(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
               v_head_dim=16))


def _ref():
    path = os.path.join(ROOT, "chipbench", "configs", "mla_moe_ref.py")
    spec = importlib.util.spec_from_file_location("mla_moe_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(os.path.join(ROOT, "chipbench", "configs", "deepseek-v2-lite.json")) as f:
        conf = json.load(f)
    return mod, mod.switches(dict(conf, num_attention_heads=SMALL.num_heads))


# ------------------------------------------------------------------ YaRN


def test_yarn_matches_the_published_formula():
    y = V2.rope_scaling
    assert yarn_correction_range(64, 10000.0, y) == (10, 23)
    i = np.arange(32)
    extra = 10000.0 ** (-2 * i / 64)
    m = 1 - np.clip((i - 10) / (23 - 10), 0, 1)
    want = extra * m + extra / 40 * (1 - m)
    np.testing.assert_allclose(np.asarray(rope_inv_freq(64, 10000.0, y)), want,
                               rtol=1e-6)
    ms = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_mscale(40, 0.707) == pytest.approx(ms)
    assert ms ** 2 == pytest.approx(1.5896, abs=1e-4)
    assert mla_lib._scale(V2) == pytest.approx(192 ** -0.5 * ms ** 2)


# ------------------------------------------------------- the expert layer


def _moe_params(cfg, key=0):
    return init_tree(moe_lib.moe_defs(cfg), jax.random.PRNGKey(key))


def _share(p, lo, n):
    """The chip that holds experts lo .. lo + n - 1: its router's rows
    rolled so they come first, as that chip numbers them."""
    return dict(p, router=jnp.roll(p["router"], -lo, axis=0),
                **{k: p[k][lo:lo + n] for k in ("w_gate", "w_up", "w_down")})


@pytest.mark.parametrize("n_held", [8, 16])
def test_expert_shares_sum_to_the_whole_layer(n_held):
    """Every chip's part, with the shared experts every chip computes alike
    counted once, adds up to the layer that holds all 64 experts."""
    whole = dataclasses.replace(SMALL, experts_held=0)
    p = _moe_params(whole)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, whole.d_model))
    full = moe_lib.serve_moe(whole, p, x)
    shared = moe_lib._glu(x, *(p["shared"][k] for k in ("w_gate", "w_up", "w_down")))
    shares = [moe_lib.serve_moe(whole, _share(p, lo, n_held), x)
              for lo in range(0, 64, n_held)]
    total = sum(shares) - (len(shares) - 1) * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(full), atol=1e-5)


def test_dropless_layer_equals_the_capacity_dispatch_when_nothing_drops():
    """The sorted grouped-matmul dispatch computes what the training
    dispatch computes when its capacity drops no token."""
    whole = dataclasses.replace(SMALL, experts_held=0, moe=dataclasses.replace(
        SMALL.moe, capacity_factor=64.0))
    p = _moe_params(whole, 2)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 6, whole.d_model))
    want, _ = moe_lib.apply_moe(whole, p, x)
    np.testing.assert_allclose(np.asarray(moe_lib.serve_moe(whole, p, x)),
                               np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("split", ["alone", "batched", "chunked"])
def test_a_tokens_expert_output_does_not_depend_on_its_call(split):
    """No token is dropped or re-routed by the others in its call."""
    p = _moe_params(SMALL, 4)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 12, SMALL.d_model))
    whole = np.asarray(moe_lib.serve_moe(SMALL, p, x))
    if split == "alone":
        parts = [moe_lib.serve_moe(SMALL, p, x[:, t:t + 1]) for t in range(12)]
    elif split == "batched":
        other = jax.random.normal(jax.random.PRNGKey(6), (3, 12, SMALL.d_model))
        parts = [moe_lib.serve_moe(SMALL, p, jnp.concatenate([x, other]))[:1]]
    else:
        parts = [moe_lib.serve_moe(SMALL, p, x[:, a:a + 5]) for a in (0, 5, 10)]
    np.testing.assert_array_equal(np.concatenate([np.asarray(a) for a in parts],
                                                 axis=1), whole)


# ------------------------------------------- the engine against the reference


def _served_logits(cfg, params, prompt, fused: bool, max_new: int):
    """Greedy logits the engine produced for one request, in order: the
    last chunk's, then each decode step's."""
    serving = ServingCfg(num_slots=1, page_size=16, num_pages=9,
                         max_blocks_per_slot=8, prefill_chunk=16)
    rt = dataclasses.replace(cfg.attention, paged_kernels=fused)
    eng = ContinuousServeEngine(cfg, params, rt=rt, serving=serving)
    assert eng.chunked and eng.donates
    seen, orig = [], eng._greedy_rows

    def record(logits):
        seen.append(np.asarray(logits)[0])
        return orig(logits)

    eng._greedy_rows = record
    res, stats = eng.serve([Request(rid=0, prompt=prompt, max_new_tokens=max_new)],
                           GenerationConfig(max_new_tokens=max_new))
    assert stats["prefill_chunks"] >= 2
    return np.stack(seen), res[0]["tokens"]


@pytest.mark.parametrize("fused", [False, True], ids=["gather", "kernels"])
def test_chunked_prefill_then_decode_matches_the_reference(fused):
    """Logits at every served position, f32 weights, against the reference
    over the whole sequence. Tolerance 1e-4 absolute on logits of size ~1:
    both sides compute in float32 (the reference at ``highest``), and they
    differ only in association, the absorbed against the naive attention
    and an online against a one-pass softmax, which moves float32 sums by a
    few units in 1e-6."""
    ref, arch = _ref()
    params = M.init_params(SMALL, jax.random.PRNGKey(7))
    prompt = np.random.default_rng(8).integers(0, SMALL.vocab_size, 37).astype(np.int32)
    got, tokens = _served_logits(SMALL, params, prompt, fused, max_new=9)
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    read = len(prompt) - 1 + np.arange(len(tokens))
    want = np.asarray(ref.logits_at(params, jnp.asarray(seq), jnp.asarray(read),
                                    arch=arch, precision="f32"))
    assert got.shape == want.shape
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
