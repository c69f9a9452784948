"""Compile the serving path's paged Pallas kernels for a TPU v5e that is
described, not attached, at Qwen1.5-0.5B widths (H = KV = 16, Dh = 64,
d_model 1024, page 16, 8 slots, prompt chunks of 128).

Interpret-mode tests check semantics only; Mosaic's block-shape and VMEM
rules show up only when a kernel is compiled for the chip, and a compile
takes a second or two here. The topology is described inside a fixture
(never at import time) because only one process may hold the TPU library:
every xdist worker collects the same tests, and only the worker that runs
this file loads it. A compile that passes is not a chip run.
"""
import os

import pytest

import jax
import jax.numpy as jnp

B, H, KV, DH, PAGE, NB, C, DM, R, L = 8, 16, 16, 64, 16, 34, 128, 1024, 32, 4
P = B * NB + 1  # one slot's worth of pages per row, plus the null page


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a deviceless compile can be written to the persistent cache but never
    # read back without a chip: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _cases(sharding):
    from repro.kernels.cpq_dequant_attn import kernel as ck
    from repro.kernels.decomposed_attn import kernel as dk
    from repro.kernels.flash_attn import kernel as fk

    bf, i8, i32, f32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    kv_pool = S((P, PAGE, KV, DH), bf)
    codes, levels = S((P, PAGE, KV, DH), i8), S((P, PAGE, KV), i32)
    table, lens = S((B, NB), i32), S((B,), i32)
    row, scalar = S((NB,), i32), S((), i32)
    return {
        "flash_decode": (fk.paged_flash_decode_fwd,
                         [S((B, 1, H, DH), bf), kv_pool, kv_pool, table, lens]),
        "flash_prefill": (fk.paged_flash_prefill_fwd,
                          [S((1, C, H, DH), bf), kv_pool, kv_pool, row, scalar,
                           scalar]),
        "cpq_decode": (ck.paged_cpq_decode_fwd,
                       [S((B, KV, H // KV, DH), bf), codes, codes]
                       + [S((B, L, KV, DH), f32)] * 4
                       + [levels, levels, table, lens]),
        "cpq_prefill": (ck.paged_cpq_prefill_fwd,
                        [S((1, KV, C * H // KV, DH), bf), codes, codes]
                        + [S((1, L, KV, DH), f32)] * 4
                        + [levels, levels, S((C, KV, DH), bf),
                           S((C, KV, DH), bf), row, scalar, scalar]),
        "decomposed_decode": (dk.paged_decomposed_decode_fwd,
                              [S((B, H, DM), bf), S((B, H, R), bf),
                               S((P, PAGE, DM), bf), S((P, PAGE, KV, R), bf),
                               table, lens]),
        "decomposed_prefill": (dk.paged_decomposed_prefill_fwd,
                               [S((C, H, DM), bf), S((C, H, R), bf),
                                S((P, PAGE, DM), bf), S((P, PAGE, KV, R), bf),
                                row, scalar, scalar]),
    }


@pytest.mark.parametrize("name", ["flash_decode", "flash_prefill",
                                  "cpq_decode", "cpq_prefill",
                                  "decomposed_decode", "decomposed_prefill"])
def test_paged_kernel_compiles_for_v5e(one_chip, name):
    fwd, args = _cases(one_chip)[name]
    compiled = jax.jit(
        lambda *a: fwd(*a, scale=DH ** -0.5, interpret=False)).lower(
            *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


KERNEL_NAMES = {"flash_decode": "paged_flash_decode",
                "flash_prefill": "paged_flash_prefill",
                "cpq_decode": "paged_cpq_decode",
                "cpq_prefill": "paged_cpq_prefill",
                "decomposed_decode": "paged_t1_decode",
                "decomposed_prefill": "paged_t1_prefill"}


@pytest.mark.parametrize("name", list(KERNEL_NAMES))
def test_paged_kernel_carries_its_name(one_chip, name):
    """Each compiled kernel is named by its ``pallas_call``'s ``name``, so
    a profiler trace tells the kernels apart (the op is named after it and
    its op_name path ends ``<name>/pallas_call``)."""
    fwd, args = _cases(one_chip)[name]
    hlo = jax.jit(lambda *a: fwd(*a, scale=DH ** -0.5, interpret=False)).lower(
        *args).compile().as_text()
    (kernel,) = [ln for ln in hlo.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in ln]
    want = KERNEL_NAMES[name]
    assert kernel.split(" = ")[0].split()[-1].startswith(f"%{want}")
    assert f'/{want}/pallas_call"' in kernel
    assert len(set(KERNEL_NAMES.values())) == len(KERNEL_NAMES)


def test_engine_kernel_entry_compiles_mosaic_on_tpu(one_chip):
    """The engine-facing wrapper, left to pick its own mode, lowers the
    compiled Mosaic kernel for a TPU (the CPU side is covered by
    test_kernels_paged.test_interpret_mode_follows_the_platform)."""
    from repro.kernels.flash_attn.ops import paged_flash_decode_tpu

    _, args = _cases(one_chip)["flash_decode"]
    hlo = paged_flash_decode_tpu.lower(*args, scale=DH ** -0.5).compile(
        ).as_text()
    assert "tpu_custom_call" in hlo
