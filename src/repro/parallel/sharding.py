"""Logical-axis sharding: MaxText-style rules mapping logical names to mesh axes.

The key robustness property: a rule is *dropped per-tensor* when the dimension
size is not divisible by the mapped mesh-axis extent.  This is what lets one
rule set serve every architecture — e.g. ``heads -> model`` gives clean tensor
parallelism for llama3-405b (128H/16) and silently degrades to FSDP-sharded
weights with replicated head compute for arctic (56H ∤ 16).  The roofline
analysis then *shows* the replication cost, and the §Perf hillclimb addresses
it explicitly (see EXPERIMENTS.md).
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AxisVal = Union[None, str, Tuple[str, ...]]
Rules = Dict[str, AxisVal]

_ctx = threading.local()


def _axes_tuple(v: AxisVal) -> Tuple[str, ...]:
    if v is None:
        return ()
    if isinstance(v, str):
        return (v,)
    return tuple(v)


# ----------------------------------------------------------------- rule sets --

def default_rules(mesh_axes: Sequence[str], *, fsdp: bool = True,
                  shape_kind: str = "train", seq_sharded_cache: bool = False,
                  fsdp_over_pod: bool = False) -> Rules:
    """Baseline rules for the production mesh.

    - TP over ``model``: heads / mlp / experts / vocab.
    - FSDP (ZeRO-3) over ``data`` (optionally + ``pod``): the ``embed`` dim of
      every weight, and optimizer state.
    - DP over ``pod``+``data``: the batch dim.
    - decode: the KV-cache sequence dim is sharded over ``model``
      (flash-decoding: sharded-softmax partials combined by psum), and for
      long-context (``seq_sharded_cache``, batch=1) additionally over the DP
      axes with batch replicated.
    """
    has_pod = "pod" in mesh_axes
    dp: Tuple[str, ...] = ("pod", "data") if has_pod else ("data",)
    fsdp_ax: AxisVal = (dp if fsdp_over_pod else ("data",)) if fsdp else None
    rules: Rules = {
        # activations
        "batch": None if seq_sharded_cache else dp,
        "seq": None,
        "seq_sp": None,   # -> "model" enables sequence-parallel residual (§Perf)
        "kv_seq": (dp + ("model",)) if seq_sharded_cache else ("model",),
        # paged serving: the page pool's leading (P) dim shards P/n per chip
        # over the model axis (repro.parallel.pagedkv) — pinned pool bytes
        # scale down with the mesh, reads merge by partial softmax
        "kv_pages": "model",
        "enc_seq": None,
        # weights
        "vocab": "model",
        "embed": fsdp_ax,
        "heads": "model",
        "kv_heads": "model",
        "q_group": None,
        "head_dim": None,
        "mlp": "model",
        "expert": "model",
        "moe_group": None if seq_sharded_cache else dp,
        "mamba_inner": "model",
        "mamba_heads": "model",
        "mamba_conv": "model",
        "rwkv_heads": "model",
        "state": None,
        "conv": None,
        "lora": None,
        "norm": None,
        "layers": None,
        "stage": None,
        "img": None,
    }
    return rules


# --------------------------------------------------------------- resolution --

def spec_for(logical: Sequence[Optional[str]], shape: Sequence[int],
             rules: Rules, mesh: Mesh) -> PartitionSpec:
    """Resolve logical axes to a PartitionSpec, dropping non-divisible rules and
    never using the same mesh axis twice in one spec."""
    used: set = set()
    out: List[AxisVal] = []
    for dim, name in zip(shape, logical):
        val = rules.get(name) if name is not None else None
        axes = _axes_tuple(val)
        # keep only mesh axes that exist, are unused, and divide the dim
        kept: List[str] = []
        extent = 1
        for a in axes:
            if a in mesh.shape and a not in used:
                if dim % (extent * mesh.shape[a]) == 0:
                    kept.append(a)
                    extent *= mesh.shape[a]
        for a in kept:
            used.add(a)
        if not kept:
            out.append(None)
        elif len(kept) == 1:
            out.append(kept[0])
        else:
            out.append(tuple(kept))
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def logical_to_sharding(axes_tree, abstract_tree, mesh: Mesh, rules: Rules):
    """Map a tree of logical-axes tuples (+ matching ShapeDtypeStructs) to
    NamedShardings."""
    def one(axes, aval):
        return NamedSharding(mesh, spec_for(axes, aval.shape, rules, mesh))
    return jax.tree.map(one, axes_tree, abstract_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(e, (str, type(None))) for e in x))


# ----------------------------------------------------------------- context ---

@contextmanager
def sharding_context(mesh: Optional[Mesh], rules: Optional[Rules]):
    """Activates ``constrain()`` inside jitted model code.  With no context (CPU
    smoke tests) ``constrain`` is a no-op."""
    prev = getattr(_ctx, "val", None)
    _ctx.val = (mesh, rules) if mesh is not None else None
    try:
        yield
    finally:
        _ctx.val = prev


def current_context():
    return getattr(_ctx, "val", None)


def constrain(x, logical: Sequence[Optional[str]]):
    """with_sharding_constraint by logical axes; no-op outside a context."""
    ctx = current_context()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = spec_for(logical, x.shape, rules, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
