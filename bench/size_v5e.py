#!/usr/bin/env python3
"""Size a cell's pool before its first chip run: compile the cell's two
step programs (fused decode+sample and the prefill chunk) at its real
shapes for a described TPU v5e, without a chip, and print
``memory_analysis()`` of each against the chip's HBM.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 bench/size_v5e.py \\
        --workload qwen3-4b.chat [--num-pages 2048]
    JAX_PLATFORMS=cpu PYTHONPATH=src python3 bench/size_v5e.py \\
        --config qwen3-4b --engine '{"max_batch": 8, "max_seq": 4096,
        "page_size": 16, "num_pages": 2052, "prefill_chunk": 512,
        "mesh": "4"}'           # a cell not in BENCHMARK.json yet

The engine is built on the CPU with a pool of a few pages and the
configuration cut to one layer (its architecture module's ``one_layer``,
``bench/arch/<model_type>.py``); the programs are lowered with the
parameters and the pool as shapes placed on the described chip (or, with
``"mesh"``, on a described 2x2 mesh, the pool sharded as the engine
shards it), the pool at ``--num-pages`` (default: the cell's).  Memory is
reported per chip.  Like ``tools/compile_v5e.py``, nothing runs: a
compile that passes is not a chip run.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--config", help="a configuration file's name, with "
                    "--engine, for a cell not in BENCHMARK.json")
    ap.add_argument("--engine", help="engine settings as JSON")
    ap.add_argument("--num-pages", type=int, default=None)
    a = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import json

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    import flops
    import run
    import spec
    import weights
    from repro.kernels import ops
    from repro.launch import serve
    from repro.models import LM

    if a.workload:
        cell = spec.load_cell(a.workload)
        config, engine, name = cell.config, dict(cell.engine), a.workload
    else:
        config = spec.load_json(spec.BENCH / "configs" / f"{a.config}.json")
        engine, name = json.loads(a.engine), a.config
    pages = a.num_pages or int(engine["num_pages"])
    ops._interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    devices = topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2").devices
    hbm = flops.peaks("TPU v5 lite")["hbm_bytes"]
    args = run.serve_args(dict(engine, num_pages=8 * len(devices)))
    mesh, dp_axis = serve.build_mesh(args, devices)
    place = (SingleDeviceSharding(devices[0]) if mesh is None
             else NamedSharding(mesh, PartitionSpec()))

    def sds(shape, dtype, sharding=place):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    real_put = jax.device_put

    def put(x, sharding=None, **kw):
        # the pool goes onto the described mesh: keep its shape only
        if isinstance(sharding, NamedSharding) and sharding.mesh is mesh:
            return sds(x.shape, x.dtype, sharding)
        return real_put(x, sharding, **kw)

    jax.device_put = put
    arch = spec.arch(config["model_type"])
    lm = LM(spec.program_config(config))
    one = arch.one_layer(config)
    small = jax.jit(lambda k: weights.to_program(
        weights.canonical(k, one), one, lm.cfg.padded_vocab))(
        weights.seed_key(0))
    eng = serve.build_engine(LM(spec.program_config(one)), small, args,
                             mesh=mesh, dp_axis=dp_axis)
    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          lm.abstract_params())
    layers = jax.tree.map(
        lambda x: sds((lm.cfg.num_layers, pages) + x.shape[2:], x.dtype,
                      x.sharding if isinstance(x, jax.ShapeDtypeStruct)
                      else place),
        eng.kv.state["layers"])
    B, C = eng.B, eng.chunk
    M = eng.kv.page_table.shape[1]
    i32, f32 = jnp.int32, jnp.float32
    eng.lm = lm
    fused = jax.jit(eng._make_fused(), static_argnums=(12,),
                    donate_argnums=(2,)).lower(
        params, sds((B, 1), i32), layers, sds((B, M), i32), sds((B,), i32),
        sds((B,), bool), sds((B,), f32), sds((B,), i32), sds((B,), f32),
        sds((B,), i32), sds((B,), i32), sds((B,), bool), True)
    chunk = jax.jit(eng._make_chunk(), donate_argnums=(2,)).lower(
        params, sds((1, C), i32), layers, sds((1, M), i32), sds((1, C), i32),
        sds((1,), i32), sds((1,), i32), sds((1,), f32), sds((1,), i32),
        sds((1,), f32), sds((1,), i32), sds((1,), i32))
    pool = arch.cache_bytes(config, pages * int(engine["page_size"]),
                            int(engine["max_batch"]))
    chips = 1 if mesh is None else mesh.size
    print(f"{name}: {pages} pages = {pool} B of cache over {chips} "
          f"chip(s)")
    worst = 0
    for name, low in (("decode", fused), ("chunk", chunk)):
        mem = low.compile().memory_analysis()
        need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        worst = max(worst, need)
        print(f"{name}: arguments {mem.argument_size_in_bytes} B, "
              f"temporaries {mem.temp_size_in_bytes} B, outputs "
              f"{mem.output_size_in_bytes} B, aliased "
              f"{mem.alias_size_in_bytes} B; arguments + temporaries "
              f"{need} B of {hbm} ({100 * need / hbm:.1f}%)")
    if worst > hbm:
        raise SystemExit(f"{worst} B exceeds the chip's {hbm} B")


if __name__ == "__main__":
    main()
