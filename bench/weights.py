"""Weights drawn from the run's seed, in one jitted call on the device.

The benchmark, not the program, defines the weights: a canonical tree
(the names and orientation of the published checkpoints), whose leaves the
configuration's architecture module (``bench/arch/<model_type>.py``)
lists, drawn from ``--seed`` in the dtype the model is served in.
``to_program`` lays that tree out as the program's parameter tree, and the
plain reference (``reference.py``) draws the same canonical tree again
from the seed, so it takes nothing the program made.

Every leaf is ``std * N(0, 1)``; norm scales are ``1 + 0.05 * N(0, 1)`` so
that a norm whose scale is dropped or misplaced shows in the logits.  The
modules draw matrices at ``STD`` (read when called, so a test can change
it) and biases at ``BIAS_STD``, small beside the activations they are
added to.  Leaf ``i`` of the sorted names is drawn with ``fold_in(key,
i)``: renaming a leaf changes every weight.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

import spec

STD = 0.02
BIAS_STD = 0.05


def shapes(config: Dict[str, Any]) -> Dict[str, tuple]:
    """Canonical leaf -> (shape, std); std None marks a norm scale.  The
    leaves are the architecture module's (``bench/arch/<model_type>.py``)."""
    return spec.arch(config["model_type"]).leaves(config)


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
    """The program's parameter tree holding the canonical weights ``w``,
    as the architecture module lays it out."""
    return spec.arch(config["model_type"]).to_program(w, config,
                                                      padded_vocab)


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
                         f"bench/arch/{config['model_type']}.py lays out:"
                         f"\n  program {want}\n  bench   {got}")
    fn = jax.jit(lambda key: to_program(canonical(key, config), config, pv),
                 out_shardings=sharding)
    return fn(seed_key(seed))
