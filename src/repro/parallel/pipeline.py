"""Pipeline parallelism (GPipe) over a mesh axis via shard_map + ppermute.

The paper's Granite-20B recipe is 4TP × 4PP × 48DP with point-to-point PP
traffic on GDR; on TPU the analogue is a pipeline over the slow axis (the
``pod`` axis of the multi-pod mesh) with ``collective-permute`` hops, keeping
high-volume TP traffic on intra-pod ICI.

Implementation: stages hold a contiguous slice of layers (params sharded over
the stage axis); microbatches stream through with a rotating buffer.  The
backward pass is obtained by differentiating through the shard_map (GPipe
schedule: all forwards live, then backwards — paired with remat on the stage
body this is the classic memory/compute trade).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_forward(stage_fn: Callable, n_stages: int, axis: str):
    """Returns fn(stage_params, x_microbatches) for use INSIDE shard_map.

    stage_fn(stage_params, x) -> y applies this stage's layer slice.
    x_microbatches: (M, mb, ...) — all microbatches, present on every stage
    (stage 0 consumes them; other stages ignore and read their ppermute
    buffer).  Output: (M, mb, ...) results on the LAST stage (zeros
    elsewhere).
    """

    def run(stage_params, x_mb):
        s = jax.lax.axis_index(axis)
        m_total = x_mb.shape[0]
        t_total = m_total + n_stages - 1
        buf = jnp.zeros_like(x_mb[0])
        out = jnp.zeros_like(x_mb)
        fwd = [(i, i + 1) for i in range(n_stages - 1)]

        def body(carry, t):
            buf, out = carry
            m = t - s                       # microbatch index at this stage
            src = jnp.where(s == 0,
                            x_mb[jnp.clip(t, 0, m_total - 1)], buf)
            y = stage_fn(stage_params, src)
            active = jnp.logical_and(m >= 0, m < m_total)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # deliver to next stage
            nxt = jax.lax.ppermute(y, axis, fwd)
            # last stage records its finished microbatch
            write_idx = jnp.clip(m, 0, m_total - 1)
            is_last = s == n_stages - 1
            upd = jnp.where(jnp.logical_and(active, is_last), y,
                            out[write_idx])
            out = jax.lax.dynamic_update_index_in_dim(out, upd, write_idx, 0)
            return (nxt, out), None

        (buf, out), _ = jax.lax.scan(body, (buf, out),
                                     jnp.arange(t_total))
        return out

    return run


def make_pipelined_apply(layer_fn: Callable, mesh: Mesh, axis: str,
                         n_microbatches: int,
                         remat: bool = True):
    """Builds apply(stacked_params, x) where stacked_params leaves have a
    leading layer dim (n_stages * layers_per_stage, ...) that gets sharded
    over ``axis`` (each stage's local block is its contiguous layer slice)
    and x: (batch, ...) is split into microbatches.

    The result lives on the last stage and is psum-broadcast so every stage
    returns it (convenient for loss computation).
    """
    n_stages = mesh.shape[axis]

    def stage_fn(stage_params, x):
        def one_layer(h, lp):
            return layer_fn(lp, h), None
        body = jax.checkpoint(one_layer) if remat else one_layer
        h, _ = jax.lax.scan(body, x, stage_params)
        return h

    pipe = pipeline_forward(stage_fn, n_stages, axis)

    def apply(params, x):
        b = x.shape[0]
        assert b % n_microbatches == 0
        x_mb = x.reshape(n_microbatches, b // n_microbatches, *x.shape[1:])

        def inner(params_local, x_loc):
            out = pipe(params_local, x_loc)
            # broadcast final result from the last stage to all stages
            s = jax.lax.axis_index(axis)
            out = jnp.where(s == n_stages - 1, out, jnp.zeros_like(out))
            return jax.lax.psum(out, axis)

        spec_params = jax.tree.map(lambda _: P(axis), params)
        fn = jax.shard_map(inner, mesh=mesh,
                           in_specs=(spec_params, P()),
                           out_specs=P(),
                           check_vma=False)
        out = fn(params, x_mb)
        return out.reshape(b, *out.shape[2:])

    return apply
