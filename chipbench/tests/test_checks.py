"""The comparison that decides ``correct``, on the CPU at a size a test can
hold (4 layers, d 256, vocab 4096): a sound run passes; each fault a serving
cell can have, planted under the timed path, fails it; and the fp8 control
reads gaps many times wider than the program's."""
import time

import pytest

import bench
from conftest import tiny_cell

SEED = 2**31 + 99
# readings at this size: program 0.005-0.011, fp8 control 0.13-0.23, faults 1.4-1.9
LIMIT = 0.03
SIZE = dict(d_model=256, num_heads=4, num_kv_heads=4, head_dim=64, d_ff=512,
            vocab_size=4096, num_blocks=4)


def _cell(name, out=(16, 32), check=60):
    """Short prompts and answers long enough that a lost K/V write of the
    decode step reaches later tokens, held to the test size's own limit
    (``LIMIT``): the chip size's limit is set from the chip's readings."""
    cell = tiny_cell(name)
    cell.setup = dict(cell.setup, limits={"max_logit_gap": LIMIT})
    cell.mix.update(prompt={"dist": "uniform", "min": 8, "max": 24},
                    output={"dist": "uniform", "min": out[0], "max": out[1]},
                    check_tokens=check, check_max_requests=8)
    return cell


def _run(cell, fault=None, precision="f32", seed=SEED):
    return bench.run(cell, seed, 3.0, False, time.perf_counter(),
                     require_tpu=False, smoke=SIZE, fault=fault,
                     precision=precision)


def _token_altered(eng):
    """Every greedy token is replaced by its neighbour id where it is drawn."""
    orig = eng._greedy_rows
    V = eng.cfg.vocab_size

    def altered(logits):
        tok, finite = orig(logits)
        return (tok + 1) % V, finite
    eng._greedy_rows = altered


def _state_unchanged(eng):
    """The decode step returns the arenas it was given: its K/V writes are lost."""
    orig = eng._decode
    eng._decode = lambda p, t, rows, caches: (orig(p, t, rows, caches)[0], caches)


@pytest.mark.parametrize("name", ["qwen05b-longdoc"])
def test_sound_run_is_correct(name):
    r = _run(_cell(name))
    assert r["correct"] is True
    c = r["compared"]
    assert c["max_logit_gap"]["value"] <= c["max_logit_gap"]["limit"]
    assert c["checked_tokens"]["value"] >= c["checked_tokens"]["limit"]


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged])
@pytest.mark.parametrize("name", ["qwen05b-longdoc"])
def test_fault_fails(name, fault):
    r = _run(_cell(name), fault)
    assert r["correct"] is False
    assert r["compared"]["max_logit_gap"]["value"] > r["compared"]["max_logit_gap"]["limit"]


@pytest.mark.parametrize("name", ["qwen05b-longdoc"])
def test_control_reads_wider_than_program(name):
    """The fp8 control, on the same prompts and served tokens: on each
    seed its widest gap is over three times the bfloat16 program's, and at
    the test size's limit the run that judges it is not correct."""
    for seed in (1, 2, 3):
        r = _run(_cell(name, (48, 64), 240), precision="fp8", seed=seed)
        gap = r["compared"]["max_logit_gap"]
        assert gap["value"] > 3 * r["program_gap"]
        assert gap["value"] > gap["limit"]
        assert r["correct"] is False
