"""Gradient compression for data-parallel synchronization: int8 quantization
with error feedback (residual carried to the next step), exchanged by
all-gather so the wire format stays int8 end-to-end.

Wire accounting per device per step (N-way DP, G gradient floats):
  f32 ring all-reduce:            2 · 4B · G      = 8G bytes
  int8 AG-based compressed sync:  1B · G + 4B·G/N ≈ 1G bytes   (~8x less)

Validated against the dry-run HLO byte parser in tests; convergence impact
bounded by the error-feedback property (tested: quantization residual decays,
fixed-batch training still converges).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.quant import dequantize, quantize_int8  # noqa: F401  (re-export: the int8 wire format lives in repro.kernels.quant, shared with the quantized KV page path)


def compressed_allreduce_mean(x, err, *, axis: str):
    """Inside shard_map: int8 all-gather + local dequant-mean.
    Returns (mean_f32, new_err)."""
    q, scale, new_err = quantize_int8(x, err)
    qs = jax.lax.all_gather(q, axis)                 # (N, ...) int8 on wire
    scales = jax.lax.all_gather(scale, axis)         # (N,) f32
    mean = jnp.mean(qs.astype(jnp.float32)
                    * scales.reshape((-1,) + (1,) * x.ndim), axis=0)
    return mean, new_err


def make_compressed_grad_sync(mesh: Mesh, axis: str):
    """Returns sync(grads, err_state) -> (mean_grads, new_err_state) where
    grads are replicated pytrees whose leading batch-grad content is per-
    device partial gradients (pure-DP layout)."""

    def one(g, e):
        fn = jax.shard_map(
            partial(compressed_allreduce_mean, axis=axis),
            mesh=mesh, in_specs=(P(axis), P(axis)),
            out_specs=(P(), P(axis)), check_vma=False)
        return fn(g, e)

    def sync(grads, err_state):
        flat_g, td = jax.tree.flatten(grads)
        flat_e = td.flatten_up_to(err_state)
        outs = [one(g, e) for g, e in zip(flat_g, flat_e)]
        return (td.unflatten([o[0] for o in outs]),
                td.unflatten([o[1] for o in outs]))

    return sync


def init_error_state(grads):
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def wire_bytes_f32_allreduce(n_floats: int) -> int:
    return 8 * n_floats


def wire_bytes_int8_sync(n_floats: int, n_dp: int) -> int:
    return n_floats + (4 * n_floats) // max(n_dp, 1)
