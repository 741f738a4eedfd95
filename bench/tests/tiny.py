"""A cell at CPU size: the harness's data files, shrunk."""
import spec

CONFIG = {
    "architectures": ["Qwen3ForCausalLM"], "model_type": "qwen3",
    "attention_bias": False, "head_dim": 16, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128,
    "max_position_embeddings": 256, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2,
    "rms_norm_eps": 1e-06, "rope_theta": 1000000,
    "tie_word_embeddings": True, "torch_dtype": "float32",
    "vocab_size": 512, "arch": "qwen3-4b",
}
TRAFFIC = {
    "arrivals": {"kind": "poisson", "rate_per_s": 6.0},
    "prompt": {"median": 12, "sigma": 0.6, "min": 4, "max": 40},
    "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 16},
    "block": 4, "drain_s": 60,
}
SETTINGS = {
    "engine": {"max_batch": 4, "max_seq": 64, "page_size": 8,
               "prefill_chunk": 16, "decode_impl": "gather"},
    "limits": {"logit_gap": 1e-3, "tokens_checked": 10},
}


def cell(traffic=None, config=None) -> spec.Cell:
    return spec.Cell(name="tiny.chat", chips=1, config=config or CONFIG,
                     traffic=traffic or TRAFFIC, settings=SETTINGS,
                     end_to_end=[{"name": n, "unit": "ms"} for n in
                                 ("itl_p50_ms", "itl_p95_ms")],
                     per_layer=[])


PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
