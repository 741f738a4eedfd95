import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Pipeline-parallel multi-pod dry-run (the paper's PP dimension, §2.4):
layers pipelined over the ``pod`` axis (point-to-point collective-permute
hops between pods — the slow-fabric-friendly traffic pattern) with data
parallelism inside each pod.

The mesh here is (pod=2, data=256), fully shard_map-manual: the
partial-manual composition (Manual pod + GSPMD-auto TP inside) trips an XLA
CPU backend crash ("Invalid binary instruction opcode copy") — recorded as a
backend limitation in DESIGN.md; on TPU the `auto=` composition is the
intended deployment.

    python -m repro.launch.pp_dryrun --arch granite-20b-code
"""
import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.models import LM, ForwardOpts
from repro.models import transformer as tfm
from repro.models.common import apply_norm
from repro.parallel.mesh import make_mesh, make_production_mesh
from repro.parallel.pipeline import pipeline_forward
from repro.parallel.sharding import default_rules, logical_to_sharding, \
    sharding_context
from repro.roofline.hlo import parse_collectives

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def build_pp_forward(lm: LM, cfg, mesh, rules, opts, n_microbatches: int):
    """Forward pass with layers pipelined over 'pod'; embed/unembed replicated
    across pods; data/model axes stay GSPMD-automatic inside the stages."""
    n_stages = mesh.shape["pod"]
    assert cfg.num_layers % n_stages == 0

    def layer_fn(lp, h):
        h, _, _ = tfm._attn_layer(lp, cfg, h, opts, collect=False)
        return h

    def stage_fn(stage_params, x):
        def one(h, lp):
            return layer_fn(lp, h), None
        body = jax.checkpoint(one, prevent_cse=False)
        h, _ = jax.lax.scan(body, x, stage_params)
        return h

    pipe = pipeline_forward(stage_fn, n_stages, "pod")

    def fwd(params, tokens):
        h = tfm.embed_inputs(params, cfg, {"tokens": tokens})
        b = h.shape[0]
        mb = b // n_microbatches
        x_mb = h.reshape(n_microbatches, mb, *h.shape[1:])

        def inner(stage_params, x_loc):
            out = pipe(stage_params, x_loc)
            s = jax.lax.axis_index("pod")
            out = jnp.where(s == n_stages - 1, out, jnp.zeros_like(out))
            return jax.lax.psum(out, "pod")

        spec_params = jax.tree.map(lambda _: P("pod"), params["layers"])
        # fully manual: pipeline over pod, batch over data (microbatch dim
        # replicated; the per-microbatch batch dim is data-sharded)
        fn = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(spec_params, P(None, "data", None, None)),
            out_specs=P(None, "data", None, None),
            check_vma=False)
        h = fn(params["layers"], x_mb)
        h = h.reshape(b, *h.shape[2:])
        logits = tfm.unembed(params, cfg, h)
        return logits

    return fwd


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-20b-code")
    ap.add_argument("--microbatches", type=int, default=4)
    args = ap.parse_args()

    cfg = dataclasses.replace(get_config(args.arch), param_dtype="bfloat16")
    lm = LM(cfg)
    mesh = make_mesh((2, 256), ("pod", "data"))   # PP across pods, DP inside
    rules = default_rules(mesh.axis_names)
    rules["batch"] = ("data",)       # pod axis is the pipeline, not DP
    opts = ForwardOpts(attn_impl="blockwise", q_chunk=1024, kv_chunk=1024,
                       remat="none", scan_layers=True)

    params_abs = lm.abstract_params()
    params_sh = logical_to_sharding(lm.param_logical_axes(), params_abs,
                                    mesh, rules)
    # layer stack: leading dim over pods (stage-contiguous slices)
    params_sh["layers"] = jax.tree.map(
        lambda s: NamedSharding(mesh, P(*(("pod",) + tuple(s.spec)[1:]))),
        params_sh["layers"])
    b, s = 1024, 1024   # mb=256 divides data=256
    tokens = jax.ShapeDtypeStruct((b, s), jnp.int32)
    tok_sh = NamedSharding(mesh, P("data", None))

    fwd = build_pp_forward(lm, cfg, mesh, rules, opts, args.microbatches)
    t0 = time.time()
    with mesh:
        lowered = jax.jit(fwd, in_shardings=(params_sh, tok_sh)).lower(
            params_abs, tokens)
        compiled = lowered.compile()
    dt = time.time() - t0
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    mem = compiled.memory_analysis()
    # The paper's pods flip between training and inferencing: project how
    # much serve-side KV capacity the leftover HBM buys on this arch, dense
    # rows vs paged (the serve engine's default backend).  Pure byte math —
    # nothing here is compiled or allocated.  Attention-cache families
    # only: recurrent state has no (L, B, S, KV, D) cache to page.
    kv_proj = None
    if cfg.family in ("dense", "vlm", "moe"):
        from repro.serve.kvcache import (contiguous_kv_bytes,
                                         decode_transient_bytes,
                                         page_kv_bytes,
                                         prefill_transient_bytes)
        kv_b, kv_s, kv_page = 64, 8192, 16
        kv_m = kv_s // kv_page
        kv_proj = {
            "batch": kv_b, "max_seq": kv_s, "page_size": kv_page,
            "contiguous_bytes": contiguous_kv_bytes(cfg, kv_b, kv_s,
                                                    jnp.bfloat16),
            "bytes_per_page": page_kv_bytes(cfg, kv_page, jnp.bfloat16),
            "pages_in_dense_equiv": kv_b * kv_m,
            # per-decode-step transient of the paged KV *read* path (one
            # layer): the XLA gather materializes dense-equivalent views
            # (scales with batch x pages), the page-table-walking kernel
            # streams one page block per (slot, kv-head) program
            "decode_transient_gather_bytes": decode_transient_bytes(
                cfg, kv_b, kv_m, kv_page, jnp.bfloat16, "gather"),
            "decode_transient_kernel_bytes": decode_transient_bytes(
                cfg, kv_b, kv_m, kv_page, jnp.bfloat16, "pallas"),
            # per-chip transient of the sharded prefill *write* path (a
            # group of 4 chunk-length-512 staged blocks): the shard_map
            # local scatter stages only the O(group x block) K/V block —
            # vs the O(P) pool a replicated GSPMD transient would cost
            "prefill_transient_sharded_bytes": prefill_transient_bytes(
                cfg, 4, 512, jnp.bfloat16),
            "prefill_transient_replicated_pool_bytes":
                kv_b * kv_m * page_kv_bytes(cfg, kv_page, jnp.bfloat16),
        }
    rec = {
        "arch": args.arch, "shape": f"pp_fwd_b{b}_s{s}",
        "mesh": "pod2x16x16_PP", "tag": "pp", "chips": 512, "ok": True,
        "compile_s": round(dt, 1),
        "collectives": coll,
        "cost_analysis": {k: float(v) for k, v in
                          (compiled.cost_analysis() or {}).items()
                          if isinstance(v, (int, float))},
        "memory_analysis": {k: int(getattr(mem, k)) for k in
                            ("argument_size_in_bytes", "temp_size_in_bytes")
                            if hasattr(mem, k)},
        "serve_kv_projection": kv_proj,
    }
    out = OUT_DIR / "pod2x16x16" / f"{args.arch}__pp_fwd.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=1, default=str))
    cp = coll["per_kind"].get("collective-permute", {"bytes": 0, "count": 0})
    print(f"[OK] PP dry-run {args.arch}: compile={dt:.1f}s "
          f"collective-permute hops={cp['count']} "
          f"({cp['bytes']/1e9:.2f} GB/dev) "
          f"total coll={coll['total_bytes']/1e9:.2f} GB/dev")
    if kv_proj is not None:
        print(f"     serve KV projection (b{kv_proj['batch']} "
              f"s{kv_proj['max_seq']}): dense "
              f"{kv_proj['contiguous_bytes']/1e9:.2f} GB = "
              f"{kv_proj['pages_in_dense_equiv']} pages of "
              f"{kv_proj['page_size']} "
              f"({kv_proj['bytes_per_page']/1e6:.2f} MB/page)")
        print(f"     paged decode transient/step/layer: gather "
              f"{kv_proj['decode_transient_gather_bytes']/1e6:.1f} MB vs "
              f"kernel {kv_proj['decode_transient_kernel_bytes']/1e3:.1f} kB "
              f"(x{kv_proj['decode_transient_gather_bytes'] / max(kv_proj['decode_transient_kernel_bytes'], 1):.0f})")


if __name__ == "__main__":
    main()
