"""Qwen3's dense decoder (``model_type`` "qwen3"): the Llama-family
decoder of ``bench/arch/llama.py`` with an RMSNorm over each head of q
and k before the rotary embedding."""
from __future__ import annotations

import spec

_dense = spec.arch("llama")

to_program = _dense.to_program
logits = _dense.logits              # applies the q/k norms it is given
layer_params = _dense.layer_params
attention_flops = _dense.attention_flops
cache_bytes = _dense.cache_bytes
one_layer = _dense.one_layer


def model_fields(config):
    return _dense.model_fields(config, qk_norm=True)


def leaves(config):
    return _dense.leaves(config, qk_norm=True)


def decode_step(c, tokens, ctx_sum):
    return _dense.decode_step(c, tokens, ctx_sum, qk_norm=True)


def chunk(c, start, n):
    return _dense.chunk(c, start, n, qk_norm=True)
