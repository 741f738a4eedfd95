#!/usr/bin/env python
"""Compile the serving engine's fused decode+sample step at published widths
for a described TPU v5e, without a chip, and print what the compiler says.

    JAX_PLATFORMS=cpu PYTHONPATH=src python tools/compile_v5e.py
    JAX_PLATFORMS=cpu PYTHONPATH=src python tools/compile_v5e.py \\
        --decode-impl pallas --mesh 4

Takes ``launch/serve.py``'s flags (``--max-seq`` defaults to 1024 here).
Parameters are shapes from ``jax.eval_shape``; the KV pool is allocated on
the host at its real size (0.6 GB for qwen3-4b at 4 x 1024 positions).  It
prints the compile time, whether the paged kernel is in the program
(``tpu_custom_call``), the collectives of a sharded step, and
``memory_analysis()``, and exits non-zero when one chip's share of
arguments plus temporaries exceeds its 16 GB.  Nothing runs: a compile
that passes is not a chip run.
"""
from __future__ import annotations

import os
import sys
import time

import jax
from jax.experimental import topologies
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.kernels import ops
from repro.launch import serve

HBM_BYTES = 16 * 1024 ** 3          # one v5e chip
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def main(argv=None) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    args = serve.build_parser().parse_args(
        ["--max-seq", "1024"] + list(sys.argv[1:] if argv is None else argv))
    # compiled for the TPU, so no interpret mode and no cache entry that
    # could not be read back without a chip
    ops._interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    mesh, dp_axis = serve.build_mesh(args, devices)
    place = (SingleDeviceSharding(devices[0]) if mesh is None
             else NamedSharding(mesh, PartitionSpec()))

    def shape_of(x):
        if x is None or isinstance(x, jax.ShapeDtypeStruct):
            return x
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=place)

    real_put = jax.device_put

    def put(x, sharding=None, **kw):
        # the pool is placed on the described mesh: keep its shape only
        if isinstance(sharding, NamedSharding) and sharding.mesh is mesh:
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        return real_put(x, sharding, **kw)

    jax.device_put = put
    lm = serve.build_lm(args)
    params = jax.tree.map(shape_of,
                          jax.eval_shape(lm.init, jax.random.key(0)))
    eng = serve.build_engine(lm, params, args, mesh=mesh, dp_axis=dp_axis)
    t0 = time.perf_counter()
    compiled = eng.lower_decode(abstract=shape_of).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    found = {c: text.count(c + "(") for c in COLLECTIVES}
    print(f"{args.arch} decode [{args.decode_impl}, mesh {args.mesh}]: "
          f"compiled in {time.perf_counter() - t0:.1f} s, "
          f"tpu_custom_call={'tpu_custom_call' in text}, "
          f"collectives {found}")
    print(mem)
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    if need > HBM_BYTES:
        raise SystemExit(f"{need} B per chip exceeds {HBM_BYTES} B of HBM")


if __name__ == "__main__":
    main()
