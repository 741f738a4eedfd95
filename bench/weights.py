"""Weights drawn from the run's seed, in one jitted call on the device.

The benchmark, not the program, defines the weights: a canonical tree of
a dense GQA decoder (the names and orientation of the published
checkpoints), drawn from ``--seed`` in the dtype the model is served in.
``to_program`` lays that tree out as the program's parameter tree, and the
plain reference (``reference.py``) draws the same canonical tree again
from the seed, so it takes nothing the program made.

Every leaf is ``std * N(0, 1)``; norm scales are ``1 + 0.05 * N(0, 1)`` so
that a norm whose scale is dropped or misplaced shows in the logits, and
biases (where ``attention_bias`` / ``mlp_bias`` are on) are
``BIAS_STD * N(0, 1)``, small beside the activations they are added to.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02
BIAS_STD = 0.05


def sizes(config: Dict[str, Any]) -> Dict[str, int]:
    c = config
    return dict(L=c["num_hidden_layers"], d=c["hidden_size"],
                H=c["num_attention_heads"], KV=c["num_key_value_heads"],
                hd=c.get("head_dim") or c["hidden_size"]
                // c["num_attention_heads"],
                F=c["intermediate_size"], V=c["vocab_size"])


def shapes(config: Dict[str, Any]) -> Dict[str, tuple]:
    """Canonical leaf -> (shape, std); std None marks a norm scale."""
    s = sizes(config)
    L, d, H, KV, hd, F, V = (s[k] for k in ("L", "d", "H", "KV", "hd", "F",
                                            "V"))
    out_std = STD / math.sqrt(2 * L)
    leaves = {
        "embed": ((V, d), STD),
        "final_norm": ((d,), None),
        "ln1": ((L, d), None),
        "ln2": ((L, d), None),
        "wq": ((L, d, H * hd), STD),
        "wk": ((L, d, KV * hd), STD),
        "wv": ((L, d, KV * hd), STD),
        "wo": ((L, H * hd, d), out_std),
        "w_gate": ((L, d, F), STD),
        "w_up": ((L, d, F), STD),
        "w_down": ((L, F, d), out_std),
    }
    if config.get("attention_bias"):
        for name, width in (("bq", H * hd), ("bk", KV * hd), ("bv", KV * hd),
                            ("bo", d)):
            leaves[name] = ((L, width), BIAS_STD)
    if config.get("mlp_bias"):
        for name, width in (("b_gate", F), ("b_up", F), ("b_down", d)):
            leaves[name] = ((L, width), BIAS_STD)
    if config["model_type"] == "qwen3":
        leaves["q_norm"] = ((L, hd), None)
        leaves["k_norm"] = ((L, hd), None)
    if not config["tie_word_embeddings"]:
        leaves["lm_head"] = ((d, V), STD)
    return leaves


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, also one past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def canonical(key, config: Dict[str, Any]) -> Dict[str, jax.Array]:
    """Trace-time body: the canonical tree for ``key`` (call under jit)."""
    dtype = jnp.dtype(config["torch_dtype"])
    tree = {}
    for i, (name, (shape, std)) in enumerate(sorted(shapes(config).items())):
        # drawn in the served dtype: no float32 copy of a 3 GB leaf
        x = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
        tree[name] = (1.0 + 0.05 * x if std is None else std * x).astype(
            dtype)
    return tree


def make_canonical(seed: int, config: Dict[str, Any]):
    return jax.jit(lambda key: canonical(key, config))(seed_key(seed))


def to_program(w: Dict[str, jax.Array], config: Dict[str, Any],
               padded_vocab: int) -> Dict[str, Any]:
    """The program's parameter tree (``repro.models.transformer``'s spec)
    holding the canonical weights ``w``."""
    s = sizes(config)
    L, d, H, KV, hd = s["L"], s["d"], s["H"], s["KV"], s["hd"]
    pad = padded_vocab - s["V"]
    attn = {"wq": {"kernel": w["wq"].reshape(L, d, H, hd)},
            "wk": {"kernel": w["wk"].reshape(L, d, KV, hd)},
            "wv": {"kernel": w["wv"].reshape(L, d, KV, hd)},
            "wo": {"kernel": w["wo"].reshape(L, H, hd, d)}}
    if "bq" in w:
        attn["wq"]["bias"] = w["bq"].reshape(L, H, hd)
        attn["wk"]["bias"] = w["bk"].reshape(L, KV, hd)
        attn["wv"]["bias"] = w["bv"].reshape(L, KV, hd)
        attn["wo"]["bias"] = w["bo"]
    mlp = {"wg": {"kernel": w["w_gate"]}, "wi": {"kernel": w["w_up"]},
           "wo": {"kernel": w["w_down"]}}
    if "b_gate" in w:
        mlp["wg"]["bias"], mlp["wi"]["bias"], mlp["wo"]["bias"] = \
            w["b_gate"], w["b_up"], w["b_down"]
    if "q_norm" in w:
        attn["q_norm"] = {"scale": w["q_norm"]}
        attn["k_norm"] = {"scale": w["k_norm"]}
    tree = {
        "embed": {"table": jnp.pad(w["embed"], ((0, pad), (0, 0)))},
        "final_norm": {"scale": w["final_norm"]},
        "layers": {"ln1": {"scale": w["ln1"]}, "attn": attn,
                   "ln2": {"scale": w["ln2"]},
                   # SwiGLU as the program writes it: act(wg) * wi, then wo
                   "mlp": mlp},
    }
    if "lm_head" in w:
        tree["lm_head"] = {"kernel": jnp.pad(w["lm_head"], ((0, 0), (0, pad)))}
    return tree


def make_program_params(seed: int, config: Dict[str, Any], lm, sharding=None):
    """The program's parameters for this seed, made on the device in one
    jitted call.  Fails when the program's parameter tree (its shapes or
    dtypes) is not the one this module lays out."""
    pv = lm.cfg.padded_vocab
    want = jax.tree.map(lambda x: (tuple(x.shape), np.dtype(x.dtype).name),
                        lm.abstract_params())
    got = jax.tree.map(
        lambda x: (tuple(x.shape), np.dtype(x.dtype).name),
        jax.eval_shape(lambda k: to_program(canonical(k, config), config, pv),
                       seed_key(0)))
    if want != got:
        raise SystemExit(f"the program's parameter tree is not the one "
                         f"bench/weights.py lays out:\n  program {want}\n"
                         f"  bench   {got}")
    fn = jax.jit(lambda key: to_program(canonical(key, config), config, pv),
                 out_shardings=sharding)
    return fn(seed_key(seed))
