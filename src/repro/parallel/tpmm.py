"""Explicit tensor-parallel contractions via shard_map (§Perf).

Under pure GSPMD the TP psum after the attention output-projection and the
MLP down-projection reduces the *f32* dot output before converting to bf16
(observed: 8.6 GB all-reduces per layer on llama3-405b — 2× the necessary
wire bytes).  These wrappers make the collective explicit: local matmul →
cast partials to bf16 → psum in bf16, which is exactly what NCCL/ICI
reductions do in production (tensor-dtype reduction).

FSDP composition: the weight's embed dim stays data-sharded at rest and is
all-gathered over ``data`` inside (the same gather GSPMD inserted, now
explicit).  Falls back to a plain einsum outside a sharding context or when
the contraction dim doesn't divide the model axis.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax.ad_checkpoint import checkpoint_name

from repro.parallel.sharding import current_context

TP_SAVE_NAME = "tp_psum_out"   # remat policy saves these (§Perf llama it6):
# jax.checkpoint can't see inside shard_map, so without the name the psum'd
# projection outputs get recomputed (collectives replayed!) in the backward.


def _ctx_ok(k_dim: int, axis: str):
    ctx = current_context()
    if ctx is None:
        return None
    mesh, rules = ctx
    if axis not in mesh.shape or k_dim % mesh.shape[axis] != 0:
        return None
    return mesh, rules


def o_proj_tp(y, kernel, bias=None, axis: str = "model"):
    """y: (B,S,H,D) head-sharded over ``axis``; kernel: (H,D,dm) with H over
    ``axis`` and dm FSDP-sharded over ``data``.  Returns (B,S,dm) psum'd in
    bf16."""
    dtype = y.dtype
    got = _ctx_ok(y.shape[2], axis)
    if got is None:
        out = jnp.einsum("bshe,hed->bsd", y, kernel.astype(dtype))
        return out if bias is None else out + bias.astype(dtype)
    mesh, rules = got
    dp = rules.get("batch")
    dm = kernel.shape[-1]
    data_ok = "data" in mesh.shape and dm % mesh.shape["data"] == 0

    def body(y_loc, w_loc):
        if data_ok:
            w_loc = jax.lax.all_gather(w_loc, "data", axis=2, tiled=True)
        part = jnp.einsum("bshe,hed->bsd", y_loc, w_loc.astype(dtype))
        return jax.lax.psum(part.astype(dtype), axis)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, axis, None),
                  P(axis, None, "data" if data_ok else None)),
        out_specs=P(dp, None, None), check_vma=False)
    out = checkpoint_name(fn(y, kernel), TP_SAVE_NAME)
    return out if bias is None else out + bias.astype(dtype)


def col_proj_tp(x, kernel, bias=None, axis: str = "model"):
    """Column-parallel projection: x (B,S,d) -> (B,S,*out) with the first
    output dim of kernel sharded over ``axis`` (no fwd collective; the
    *backward* dx psum runs in bf16 through the shard_map instead of GSPMD's
    f32).  kernel: (d, F) or (d, H, D) with F/H sharded; d FSDP over data."""
    dtype = x.dtype
    got = _ctx_ok(kernel.shape[1], axis)
    if got is None:
        return _plain_col(x, kernel, bias, dtype)
    mesh, rules = got
    dp = rules.get("batch")
    d = kernel.shape[0]
    data_ok = "data" in mesh.shape and d % mesh.shape["data"] == 0
    rank3 = kernel.ndim == 3
    eq = "bsd,dhe->bshe" if rank3 else "bsd,df->bsf"

    def body(x_loc, w_loc):
        if data_ok:
            w_loc = jax.lax.all_gather(w_loc, "data", axis=0, tiled=True)
        return jnp.einsum(eq, x_loc, w_loc.astype(dtype))

    w_spec = P("data" if data_ok else None, axis, None) if rank3 else \
        P("data" if data_ok else None, axis)
    out_spec = P(dp, None, axis, None) if rank3 else P(dp, None, axis)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(dp, None, None), w_spec),
                       out_specs=out_spec, check_vma=False)
    out = checkpoint_name(fn(x, kernel), TP_SAVE_NAME)
    if bias is not None:
        out = out + bias.astype(dtype)
    return out


def _plain_col(x, kernel, bias, dtype):
    eq = "bsd,dhe->bshe" if kernel.ndim == 3 else "bsd,df->bsf"
    out = jnp.einsum(eq, x, kernel.astype(dtype))
    if bias is not None:
        out = out + bias.astype(dtype)
    return out


def down_proj_tp(h, kernel, bias=None, axis: str = "model"):
    """h: (B,S,F) F-sharded over ``axis``; kernel: (F,dm), F over ``axis``,
    dm FSDP-sharded.  Returns (B,S,dm) psum'd in bf16."""
    dtype = h.dtype
    got = _ctx_ok(h.shape[-1], axis)
    if got is None:
        out = jnp.einsum("bsf,fd->bsd", h, kernel.astype(dtype))
        return out if bias is None else out + bias.astype(dtype)
    mesh, rules = got
    dp = rules.get("batch")
    dm = kernel.shape[-1]
    data_ok = "data" in mesh.shape and dm % mesh.shape["data"] == 0

    def body(h_loc, w_loc):
        if data_ok:
            w_loc = jax.lax.all_gather(w_loc, "data", axis=1, tiled=True)
        part = jnp.einsum("bsf,fd->bsd", h_loc, w_loc.astype(dtype))
        return jax.lax.psum(part.astype(dtype), axis)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, axis),
                  P(axis, "data" if data_ok else None)),
        out_specs=P(dp, None, None), check_vma=False)
    out = checkpoint_name(fn(h, kernel), TP_SAVE_NAME)
    return out if bias is None else out + bias.astype(dtype)
