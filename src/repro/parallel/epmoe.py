"""Expert-parallel MoE via shard_map + all_to_all (§Perf arctic it2).

The pure-GSPMD capacity dispatch gathers expert outputs from model-axis
shards with (B,S,d)-sized f32 all-reduces per routing slot per direction
(~790 GB/step/device on arctic).  The production EP pattern exchanges only
the *dispatched token slots*:

  per device: route local tokens -> scatter into an (E, C, d) send buffer
  (expert-major) -> all_to_all over ``model`` (each shard keeps its E/16
  experts' slots) -> local expert GEMMs -> all_to_all back -> local combine.

Wire bytes/device/step ≈ 2 · T_loc · k · cf · d · 2B  (bf16, both hops) —
for arctic train_4k ≈ 2·65536·2·1.25·7168·2 ≈ 4.7 GB/layer vs ~22 GB of f32
AR in the GSPMD form.  The arctic dense-residual FFN rides in the same
shard_map with a bf16-psum TP down-projection.

Capacity grouping is per-device (G = data shards), the standard GShard
choice at scale; gates/keep masks stay local so combine needs no collective.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from repro.models.common import activation
from repro.parallel.sharding import current_context
from repro.parallel.tpmm import TP_SAVE_NAME


def moe_ffn_ep(p, cfg, x, axis: str = "model"):
    """Drop-in for models.moe.moe_ffn under a sharding context.
    x: (B, S, d).  Returns (y, aux_loss)."""
    ctx = current_context()
    n_exp = cfg.num_experts
    if ctx is None:
        from repro.models.moe import moe_ffn
        return moe_ffn(p, cfg, x)
    mesh, rules = ctx
    if axis not in mesh.shape or n_exp % mesh.shape[axis] != 0:
        from repro.models.moe import moe_ffn
        return moe_ffn(p, cfg, x)
    n_sh = mesh.shape[axis]
    dp = rules.get("batch")
    dp_axes = tuple(a for a in (dp if isinstance(dp, tuple) else (dp,))
                    if a in mesh.shape) if dp else ()
    k = cfg.experts_per_token
    e_loc = n_exp // n_sh
    d = cfg.d_model
    f = cfg.moe_d_ff
    act = activation(cfg.act)
    dtype = jnp.dtype(cfg.dtype)
    data_ok = "data" in mesh.shape and d % mesh.shape["data"] == 0
    wspec = P(axis, "data" if data_ok else None, None)

    has_dense = "dense" in p
    dense_ok = has_dense and cfg.d_ff % n_sh == 0

    def body(x_loc, router_w, wi, wg, wo, dwi, dwg, dwo):
        b_loc, s, _ = x_loc.shape
        t_all = b_loc * s
        # x is replicated over the model axis inside this shard_map; each
        # model column routes only its token slice (otherwise all 16 peers
        # send identical buffers -> 16x redundant expert work; observed as
        # 5x flops + 6x a2a bytes in §Perf arctic it2, fixed in it3)
        assert t_all % n_sh == 0
        t = t_all // n_sh
        me = jax.lax.axis_index(axis)
        xf = jax.lax.dynamic_slice_in_dim(x_loc.reshape(t_all, d),
                                          me * t, t, axis=0)
        cap = max(math.ceil(t * k / n_exp * cfg.capacity_factor), k)

        # ------- routing (local tokens, full router) -------------------------
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                            router_w.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gates, ids = jax.lax.top_k(probs, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        density = jnp.mean(jax.nn.one_hot(ids[..., 0], n_exp,
                                          dtype=jnp.float32), axis=0)
        aux = jnp.mean(density * jnp.mean(probs, axis=0)) * (n_exp * n_exp)
        aux = jax.lax.pmean(aux, dp_axes + (axis,) if dp_axes else (axis,))

        # ------- capacity positions (slot-major priority) --------------------
        ids_sm = ids.T.reshape(k * t)
        onehot = jax.nn.one_hot(ids_sm, n_exp, dtype=jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(onehot, 0) - onehot,
                                  ids_sm[:, None], axis=-1)[:, 0]
        pos = pos.reshape(k, t).T                       # (t, k)
        keep = (pos < cap).astype(dtype) * (gates > 0).astype(dtype)
        flat_idx = ids * cap + jnp.minimum(pos, cap - 1)

        # ------- dispatch scatter + all_to_all to expert owners --------------
        buf = jnp.zeros((n_exp * cap, d), dtype)
        for j in range(k):
            buf = buf.at[flat_idx[:, j]].add(xf * keep[:, j, None])
        send = buf.reshape(n_sh, e_loc * cap, d)
        # tiled a2a: (n_sh, e_loc*cap, d) -> (1, n_sh*e_loc*cap, d) with the
        # received axis ordered [src][e][c]; regroup expert-major
        recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=1,
                                  tiled=True)
        recv = recv.reshape(n_sh, e_loc, cap, d).swapaxes(0, 1) \
                   .reshape(e_loc, n_sh * cap, d)

        # ------- local expert GEMMs ------------------------------------------
        if data_ok:
            wi_l = jax.lax.all_gather(wi, "data", axis=1, tiled=True)
            wg_l = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wo_l = jax.lax.all_gather(wo, "data", axis=2, tiled=True)
        else:
            wi_l, wg_l, wo_l = wi, wg, wo
        hi = jnp.einsum("ecd,edf->ecf", recv, wi_l.astype(dtype))
        hg = jnp.einsum("ecd,edf->ecf", recv, wg_l.astype(dtype))
        out = jnp.einsum("ecf,efd->ecd", act(hg) * hi, wo_l.astype(dtype))

        # ------- return slots to sources + local combine ----------------------
        back = out.reshape(e_loc, n_sh, cap, d).swapaxes(0, 1).reshape(
            n_sh, e_loc * cap, d)
        ret = jax.lax.all_to_all(back, axis, split_axis=0, concat_axis=1,
                                 tiled=True)
        # received [owner][e][c] == global-expert-major == flat_idx layout
        ret = ret.reshape(n_exp * cap, d)
        y = jnp.zeros_like(xf)
        for j in range(k):
            y = y + ret[flat_idx[:, j]] * (gates[:, j, None].astype(dtype)
                                           * keep[:, j, None])
        # reassemble the full sequence from the model columns' slices
        y = jax.lax.all_gather(y, axis, axis=0, tiled=True)
        y = y.reshape(b_loc, s, d)

        # ------- arctic dense residual (TP over model, bf16 psum) ------------
        if dense_ok:
            if data_ok:
                dwi_l = jax.lax.all_gather(dwi, "data", axis=0, tiled=True)
                dwg_l = jax.lax.all_gather(dwg, "data", axis=0, tiled=True)
                dwo_l = jax.lax.all_gather(dwo, "data", axis=1, tiled=True)
            else:
                dwi_l, dwg_l, dwo_l = dwi, dwg, dwo
            hh = jnp.einsum("bsd,df->bsf", x_loc, dwi_l.astype(dtype))
            gg = jnp.einsum("bsd,df->bsf", x_loc, dwg_l.astype(dtype))
            dn = jnp.einsum("bsf,fd->bsd", act(gg) * hh, dwo_l.astype(dtype))
            y = y + jax.lax.psum(dn.astype(dtype), axis)
        return y, aux

    zeros = jnp.zeros((), dtype)
    dense_args = (p["dense"]["wi"]["kernel"], p["dense"]["wg"]["kernel"],
                  p["dense"]["wo"]["kernel"]) if dense_ok else \
        (zeros, zeros, zeros)
    dense_specs = (P("data" if data_ok else None, axis),
                   P("data" if data_ok else None, axis),
                   P(axis, "data" if data_ok else None)) if dense_ok else \
        (P(), P(), P())

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None), wspec, wspec,
                  P(axis, None, "data" if data_ok else None)) + dense_specs,
        out_specs=(P(dp, None, None), P()),
        check_vma=False)
    y, aux = fn(x, p["router"]["kernel"], p["wi"]["kernel"],
                p["wg"]["kernel"], p["wo"]["kernel"], *dense_args)
    y = checkpoint_name(y, TP_SAVE_NAME)
    if has_dense and not dense_ok:
        from repro.models.mlp import mlp
        y = y + mlp(p["dense"], cfg, x)
    return y, aux
