"""Compile a cell's decode step and prefill chunks for a described TPU v5e,
without the chip, and print each program's memory analysis.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 chipbench/tools/compile_v5e.py <cell>...
"""
import dataclasses
import os
import sys
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import bench  # noqa: E402

MiB = 2 ** 20


def main(names):
    jax.config.update("jax_enable_compilation_cache", False)
    from repro.configs.base import ServingCfg
    from repro.models import model as M
    from repro.serving import paged_cache as pgc

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    put = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)
    for name in names:
        cell = bench.load_cell(name)
        cfg = bench.program_config(cell.conf)
        cfg = dataclasses.replace(cfg, attention=dataclasses.replace(
            cfg.attention, mode=cell.setup["mode"]))
        sv = ServingCfg(**cell.setup["serving"])
        rt = cfg.attention
        params = put(M.abstract_params(cfg))
        caches = put(jax.eval_shape(partial(M.init_paged_caches, cfg, rt, sv, False)))
        B, C, nb = sv.num_slots, sv.prefill_chunk, sv.max_blocks_per_slot
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
        rows = pgc.RowState(lengths=i32(B), block_table=i32(B, nb),
                            active=jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one),
                            tier=i32(B), alt_block_table=None)
        pbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
        abytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(caches))
        print(f"{name}: weights {pbytes / MiB:.1f} MiB, arena {abytes / MiB:.1f} MiB")
        progs = {
            "decode_step_rows": (jax.jit(partial(M.decode_step_rows, cfg, rt)),
                                 (params, i32(B, 1), rows, caches)),
        }
        for first in (True, False):
            progs[f"prefill_chunk_rows(first={first})"] = (
                jax.jit(partial(M.prefill_chunk_rows, cfg, rt, 0, first)),
                (params, i32(1, C), i32(), i32(nb), i32(), i32(), caches))
        for label, (fn, args) in progs.items():
            ma = fn.lower(*args).compile().memory_analysis()
            print(f"  {label}: args {ma.argument_size_in_bytes / MiB:.1f} MiB, "
                  f"out {ma.output_size_in_bytes / MiB:.1f} MiB, "
                  f"temp {ma.temp_size_in_bytes / MiB:.1f} MiB, "
                  f"alias {ma.alias_size_in_bytes / MiB:.1f} MiB", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
