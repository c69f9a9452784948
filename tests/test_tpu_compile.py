"""Compile the serving path's paged Pallas kernels, and the engine's
donated decode and chunk steps, for a TPU v5e that is described, not
attached, at Qwen1.5-0.5B widths (H = KV = 16, Dh = 64, d_model 1024, page
16, 8 slots, prompt chunks of 128; the flash kernels also at the Qwen
cells' block tables, 8 x 256 and 16 x 64 pages, and 512-token chunks, and
the chunk prefill at OPT-6.7B widths), and the MLA latent kernels and
steps at DeepSeek-V2-Lite widths (latent 512, rope 64, 16 heads) with the
``dsv2lite-longdoc`` cell's arena (32 slots x 4096 positions).

Interpret-mode tests check semantics only; Mosaic's block-shape and VMEM
rules show up only when a kernel is compiled for the chip, and a compile
takes a second or two here. The topology is described inside a fixture
(never at import time) because only one process may hold the TPU library:
every xdist worker collects the same tests, and only the worker that runs
this file loads it. A compile that passes is not a chip run.
"""
import os
import re
from functools import partial

import pytest

import jax
import jax.numpy as jnp

B, H, KV, DH, PAGE, NB, C, DM, R, L = 8, 16, 16, 64, 16, 34, 128, 1024, 32, 4
P = B * NB + 1  # one slot's worth of pages per row, plus the null page
LAYERS = 4      # layers of the stacked dense arena (the model's depth here)
DC, DR, MLA_H = 512, 64, 16   # MLA latent, shared rope key, heads


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


# the Qwen cells' arenas, (rows, block-table width): qwen05b-longdoc's 8 x
# 256 pages and qwen05b-chat's 16 x 64, with their 512-token chunks; the
# flash kernels take 16 pages a grid step there (NB = 34 above takes 2)
CELL_TABLES = ((8, 256), (16, 64))
CELL_CHUNK = 512
# and the chunk prefill at OPT-6.7B widths (32 heads of 128) with 256-token
# chunks, 16 pages a step, inside its scoped VMEM (512-token chunks are not)
OPT_H, OPT_DH, OPT_CHUNK, OPT_NB = 32, 128, 256, 128
SHAPE_CASES = {f"flash_{kind}_nb{nb}": f"flash_{kind}"
               for _, nb in CELL_TABLES for kind in ("decode", "prefill")}
SHAPE_CASES["flash_prefill_opt"] = "flash_prefill"


def _cases(sharding):
    from repro.kernels.cpq_dequant_attn import kernel as ck
    from repro.kernels.decomposed_attn import kernel as dk
    from repro.kernels.flash_attn import kernel as fk

    bf, i8, i32, f32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    kv_pool = S((LAYERS, P, PAGE, KV * DH), bf)
    latent = S((LAYERS, P, PAGE, DC), bf)
    rope = S((LAYERS, P, PAGE // 2, 2 * DR), bf)   # two tokens per 128 lanes
    codes, levels = S((P, PAGE, KV, DH), i8), S((P, PAGE, KV), i32)
    table, lens = S((B, NB), i32), S((B,), i32)
    row, scalar = S((NB,), i32), S((), i32)
    cases = {
        "flash_decode": (fk.paged_flash_decode_fwd,
                         [S((B, 1, H, DH), bf), kv_pool, kv_pool, table, lens,
                          scalar]),
        "flash_prefill": (fk.paged_flash_prefill_fwd,
                          [S((1, C, H, DH), bf), kv_pool, kv_pool, row, scalar,
                           scalar, scalar]),
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
        "latent_decode": (dk.paged_latent_decode_fwd,
                          [S((B, MLA_H, DC), bf), S((B, MLA_H, DR), bf),
                           latent, rope, table, lens, scalar]),
        "latent_prefill": (dk.paged_latent_prefill_fwd,
                           [S((C, MLA_H, DC), bf), S((C, MLA_H, DR), bf),
                            latent, rope, row, scalar, scalar, scalar]),
    }
    for rows, nb in CELL_TABLES:
        pool = S((LAYERS, rows * nb + 1, PAGE, KV * DH), bf)
        cases[f"flash_decode_nb{nb}"] = (
            fk.paged_flash_decode_fwd,
            [S((rows, 1, H, DH), bf), pool, pool, S((rows, nb), i32),
             S((rows,), i32), scalar])
        cases[f"flash_prefill_nb{nb}"] = (
            fk.paged_flash_prefill_fwd,
            [S((1, CELL_CHUNK, H, DH), bf), pool, pool, S((nb,), i32), scalar,
             scalar, scalar])
    wide = S((LAYERS, OPT_NB + 1, PAGE, OPT_H * OPT_DH), bf)
    cases["flash_prefill_opt"] = (
        fk.paged_flash_prefill_fwd,
        [S((1, OPT_CHUNK, OPT_H, OPT_DH), bf), wide, wide, S((OPT_NB,), i32),
         scalar, scalar, scalar])
    return cases


@pytest.mark.parametrize("name", ["flash_decode", "flash_prefill",
                                  "cpq_decode", "cpq_prefill",
                                  "decomposed_decode", "decomposed_prefill",
                                  "latent_decode", "latent_prefill"]
                         + list(SHAPE_CASES))
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
                "decomposed_prefill": "paged_t1_prefill",
                "latent_decode": "paged_mla_decode",
                "latent_prefill": "paged_mla_prefill"}


@pytest.mark.parametrize("name", list(KERNEL_NAMES) + list(SHAPE_CASES))
def test_paged_kernel_carries_its_name(one_chip, name):
    """Each compiled kernel is named by its ``pallas_call``'s ``name``, so
    a profiler trace tells the kernels apart (the op is named after it and
    its op_name path ends ``<name>/pallas_call``)."""
    fwd, args = _cases(one_chip)[name]
    hlo = jax.jit(lambda *a: fwd(*a, scale=DH ** -0.5, interpret=False)).lower(
        *args).compile().as_text()
    (kernel,) = [ln for ln in hlo.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in ln]
    want = KERNEL_NAMES[SHAPE_CASES.get(name, name)]
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


# ------------------------------------------- the engine's donated steps

# the engine's arena holds 4096 positions a slot, as a long-document cell's
# does: an arena small enough for VMEM (128 MiB on a v5e) may be staged
# through it whole by the compiler, which a served arena never is
ARENA_NB = 256
ARENA_P = B * ARENA_NB + 1


def _engine(mode: str):
    """A continuous engine at Qwen1.5-0.5B widths, LAYERS layers deep, whose
    jitted steps are lowered here and never run (it holds no weights)."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import AttentionRuntime, CPQCfg, ServingCfg
    from repro.serving.engine import ContinuousServeEngine

    cfg = dataclasses.replace(get_config("qwen1.5-0.5b"), num_blocks=LAYERS)
    rt = AttentionRuntime(mode=mode, cpq=CPQCfg() if mode == "cpq" else None)
    serving = ServingCfg(num_slots=B, page_size=PAGE, num_pages=ARENA_P,
                         max_blocks_per_slot=ARENA_NB, prefill_chunk=C)
    return ContinuousServeEngine(cfg, None, rt, serving)


def _compiled_step(eng, sharding, step: str):
    """(compiled program, abstract caches) of the engine's jitted ``step``:
    "decode" or a chunk, "chunk_first" / "chunk_next"."""
    from repro.models import model as M
    from repro.serving import paged_cache as pgc

    def S(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)

    params = jax.tree.map(S, jax.eval_shape(
        lambda: M.init_params(eng.cfg, jax.random.key(0))))
    caches = jax.tree.map(S, jax.eval_shape(partial(
        M.init_paged_caches, eng.cfg, eng.rt, eng.serving, False)))
    b, nb = eng.serving.num_slots, eng.serving.max_blocks_per_slot
    if step == "decode":
        rows = pgc.RowState(lengths=i32(b), block_table=i32(b, nb),
                            active=jax.ShapeDtypeStruct((b,), jnp.bool_,
                                                        sharding=sharding),
                            tier=i32(b))
        fn, args = eng._decode, (params, i32(b, 1), rows, caches)
    else:
        fn = eng._chunk_fn(0, step == "chunk_first")
        args = (params, i32(1, eng.serving.prefill_chunk), i32(), i32(nb),
                i32(), i32(), caches)
    return fn.lower(*args).compile(), caches


_MOVES = ("copy", "copy-start", "copy-done", "dynamic-slice",
          "dynamic-update-slice")


def _arena_moves(hlo: str, pages: int) -> list[str]:
    """Instructions of a compiled program that copy, slice or allocate a
    buffer whose shape holds the arena's page count."""
    found = []
    for ln in hlo.splitlines():
        if " = " not in ln:
            continue
        rhs = ln.split(" = ", 1)[1]
        if rhs.startswith("("):                    # tuple-shaped result
            depth = 0
            for i, ch in enumerate(rhs):
                depth += {"(": 1, ")": -1}.get(ch, 0)
                if depth == 0:
                    break
            shape, rest = rhs[:i + 1], rhs[i + 1:].lstrip()
        else:
            shape, _, rest = rhs.partition(" ")
        op = rest.split("(", 1)[0]
        moves = op in _MOVES or (op == "custom-call"
                                 and 'custom_call_target="AllocateBuffer"' in ln)
        if moves and re.search(rf"[\[,]{pages}[,\]]", shape):
            found.append(ln.strip()[:160])
    return found


@pytest.mark.parametrize("step", ["decode", "chunk_first", "chunk_next"])
def test_donated_steps_update_the_arena_in_place(one_chip, step):
    """The dense arena rides in the layer scan's carry and the engine
    donates it: the compiled step aliases the whole arena to its output,
    needs less temporary memory than one layer's pool, and no op copies,
    slices or allocates a buffer of the arena's page count — the two K/V
    scatters and the kernels read and write it where it lies."""
    eng = _engine("dense")
    assert eng.donates
    compiled, caches = _compiled_step(eng, one_chip, step)
    arena = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(caches))
    layer_pool = ARENA_P * PAGE * KV * DH * jnp.dtype(jnp.bfloat16).itemsize
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == arena
    assert mem.temp_size_in_bytes < layer_pool, mem.temp_size_in_bytes
    assert _arena_moves(compiled.as_text(), ARENA_P) == []


def test_cpq_engine_steps_stay_undonated(one_chip):
    """A CPQ arena is sliced per layer through the scan (it is not carried),
    where donation would add whole-arena copies: its engine keeps undonated
    steps, and the compiled decode step aliases nothing."""
    eng = _engine("cpq")
    assert not eng.donates
    compiled, _ = _compiled_step(eng, one_chip, "decode")
    assert compiled.memory_analysis().alias_size_in_bytes == 0


# ------------------------------------------- the MLA latent arena, donated

MLA_SLOTS, MLA_NB = 32, 256            # the dsv2lite-longdoc cell's arena
MLA_P = MLA_SLOTS * MLA_NB + 1


def _mla_engine():
    """A continuous engine at DeepSeek-V2-Lite widths, 4 layers deep (the
    dense first layer and 3 expert layers, 8 of 64 experts held), with the
    cell's arena; its steps are lowered here and never run."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import AttentionRuntime, ServingCfg
    from repro.serving.engine import ContinuousServeEngine

    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b"),
                              num_blocks=LAYERS - 1, experts_held=8)
    serving = ServingCfg(num_slots=MLA_SLOTS, page_size=PAGE, num_pages=MLA_P,
                         max_blocks_per_slot=MLA_NB, prefill_chunk=C)
    return ContinuousServeEngine(cfg, None, AttentionRuntime(mode="decomposed"),
                                 serving)


@pytest.mark.parametrize("step", ["decode", "chunk_first", "chunk_next"])
def test_mla_donated_steps_update_the_latent_arena_in_place(one_chip, step):
    """The stacked latent arena rides in the layer scan's carry (and the
    first layer's pools beside it) and the engine donates it: the compiled
    step aliases the whole arena, needs far less temporary memory than the
    arena, and no op copies, slices or allocates a buffer of its page
    count."""
    eng = _mla_engine()
    assert eng.donates and eng.chunked
    compiled, caches = _compiled_step(eng, one_chip, step)
    arena = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(caches))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == arena
    assert mem.temp_size_in_bytes < arena // 2, mem.temp_size_in_bytes
    assert _arena_moves(compiled.as_text(), MLA_P) == []


def test_latent_arena_holds_31104_bytes_a_token(one_chip):
    """Lane-dense on the chip: the arena's buffers, as the v5e compile lays
    them out in its tiles, hold (512 + 64) x 2 B per token and layer, 31 104
    B over V2-Lite's 27 layers, within one page's worth of padding."""
    from repro.models import model as M

    eng = _mla_engine()
    caches = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(partial(M.init_paged_caches, eng.cfg, eng.rt,
                               eng.serving, False)))
    compiled = jax.jit(lambda c: c, donate_argnums=0).lower(caches).compile()
    tokens = MLA_P * PAGE
    per_token = 27 * compiled.memory_analysis().argument_size_in_bytes / LAYERS / tokens
    assert 31104 <= per_token <= 31104 * (tokens + PAGE) / tokens
