"""The ``dsv2lite-longdoc`` cell on the CPU at a size a test can hold (4
layers at the published d 2048, the published router: 64 experts, top 6, 8
held): a sound run is correct against the MLA/MoE reference; the fp8 control
and planted faults of the routed and shared experts are not; and the
operation counts of ``flops_mla_moe.py`` against hand counts."""
import time

import pytest

import bench
import flops_mla_moe as FM
from conftest import tiny_cell

SEED = 2**31 + 77
# readings at this size (seeds 1, 2 and SEED): program 0.021-0.040, fp8
# control 0.64-0.74; held routed experts zeroed 0.48-1.37, misrouted
# 1.36-2.41, shared experts zeroed 2.66-3.62. At d 2048 the router's logits
# spread as in the cell (std ~5.7), so the routed experts carry a share of
# the layer's output that the comparison sees
LIMIT = 0.3


def _size():
    from repro.configs.base import MLACfg, MoECfg

    return dict(d_model=2048, num_heads=4, num_kv_heads=4, head_dim=64,
                d_ff=512, vocab_size=4096, num_blocks=3,
                mla=MLACfg(kv_lora_rank=64, qk_nope_head_dim=32,
                           qk_rope_head_dim=16, v_head_dim=32),
                moe=MoECfg(num_experts=64, num_shared=2, top_k=6, d_ff_expert=64,
                           norm_topk=False))


def _cell(out=(16, 32), check=60):
    cell = tiny_cell("dsv2lite-longdoc")
    cell.setup = dict(cell.setup, limits={"max_logit_gap": LIMIT})
    cell.mix.update(prompt={"dist": "uniform", "min": 8, "max": 40},
                    output={"dist": "uniform", "min": out[0], "max": out[1]},
                    check_tokens=check, check_max_requests=8)
    return cell


def _run(cell, fault=None, precision="f32", seed=SEED):
    return bench.run(cell, seed, 3.0, False, time.perf_counter(),
                     require_tpu=False, smoke=_size(), fault=fault,
                     precision=precision)


def _names(path):
    return [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]


def _edit(eng, pick, change):
    """A fault in the weights the engine serves with; the reference keeps
    the sound ones."""
    import jax

    eng.params = jax.tree_util.tree_map_with_path(
        lambda path, a: change(a) if pick(_names(path)) else a, eng.params)


def _held_experts_zeroed(eng):
    """Every routed expert this chip holds adds nothing (down projections
    zeroed in every MoE layer)."""
    _edit(eng, lambda n: n[0] == "blocks" and n[-2:] == ["mlp", "w_down"],
          lambda a: a * 0)


def _misrouted(eng):
    """Each (token, expert) pair reaches the neighbouring expert: the
    router's rows rolled by one."""
    import jax.numpy as jnp

    _edit(eng, lambda n: n[-1] == "router", lambda a: jnp.roll(a, 1, axis=-2))


def _shared_experts_zeroed(eng):
    """The shared experts, which every token reaches, add nothing."""
    _edit(eng, lambda n: n[-2:] == ["shared", "w_down"], lambda a: a * 0)


def test_sound_run_is_correct():
    r = _run(_cell())
    assert r["correct"] is True, r["compared"]
    c = r["compared"]
    assert c["checked_tokens"]["value"] >= c["checked_tokens"]["limit"]


@pytest.mark.parametrize("fault", [_held_experts_zeroed, _misrouted,
                                   _shared_experts_zeroed],
                         ids=lambda f: f.__name__.strip("_"))
def test_fault_fails(fault):
    r = _run(_cell(), fault)
    assert r["correct"] is False
    assert r["compared"]["max_logit_gap"]["value"] > LIMIT


def test_control_reads_wider_than_program():
    for seed in (1, 2):
        r = _run(_cell((24, 32), 120), precision="fp8", seed=seed)
        gap = r["compared"]["max_logit_gap"]
        assert gap["value"] > 3 * r["program_gap"], (gap, r["program_gap"])
        assert r["correct"] is False


# ------------------------------------------------------------- the counts


def test_dims_from_published_keys():
    d = FM.load()
    assert (d.d, d.heads, d.latent, d.nope, d.rope, d.v, d.layers) == \
        (2048, 16, 512, 128, 64, 128, 27)
    assert (d.experts, d.held, d.top_k, d.shared, d.expert_ff, d.ff) == \
        (64, 8, 6, 2, 1408, 10944)
    assert d.bytes_per_key == 1152


def test_token_flops_hand_count():
    d = FM.load()
    attn = (2048 * 16 * 192 + 2048 * 576 + 16 * 128 * 512 + 16 * 512 * 128
            + 16 * 128 * 2048)
    moe = 2048 * 64 + 3 * 2048 * 1408 * 2 + 3 * 2048 * 1408 * 0.75
    assert d.token_flops == pytest.approx(
        2 * (27 * attn + 3 * 2048 * 10944 + 26 * moe))


def test_decode_attention_hand_count():
    d = FM.load()
    fl, by = FM.decode_attn(d, [100, 300])
    assert fl == 27 * (2 * 16 * 576 + 2 * 16 * 512) * 400
    assert by == 27 * (1152 * 400 + 2 * 16 * (2 * 512 + 64) * 2)


def test_chunk_attention_hand_count():
    d = FM.load()
    fl, by = FM.chunk_attn(d, 32, 4)
    keys = 4 * 32 + (1 + 2 + 3 + 4)
    assert fl == 27 * d.flops_per_key * keys
    assert by == 27 * (1152 * 36 + d.qo_bytes * 4)


def test_step_flops_adds_its_parts():
    d = FM.load()
    got = FM.step_flops(d, [10, 20], (64, 16, True))
    want = (d.token_flops * 18 + 2 * 2048 * 102400 * 3
            + FM.decode_attn(d, [10, 20])[0] + FM.chunk_attn(d, 64, 16)[0])
    assert got == pytest.approx(want)
