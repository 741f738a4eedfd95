"""Operation and byte counts, against independent counts: the canonical
weight tree, the program's own parameter tree and hand arithmetic."""
import math

import pytest

import flops
import spec
import weights

CONFIGS = {p.stem: spec.load_json(p)
           for p in (spec.BENCH / "configs").glob("*.json")}


def param_count(c):
    return sum(math.prod(s) for s, _ in weights.shapes(c).values())


def test_qwen3_4b_weights_are_the_published_size():
    c = CONFIGS["qwen3-4b"]
    assert param_count(c) == 4_022_468_096
    # tied: the embedding table is the output head, read every step
    assert flops.weight_bytes(c) == 8_044_936_192
    assert flops.kv_bytes_per_position(c) == 147_456


def test_granite_8b_share_size():
    c = CONFIGS["granite-8b-pp2"]
    # 18 layers of 218,103,808 matrix and 51,200 norm and bias parameters,
    # the final norm and the tied 49,152 x 4,096 table
    assert param_count(c) == 18 * (218_103_808 + 51_200) + 4096 \
        + 49152 * 4096 == 4_128_120_832
    # tied: the table is the output head, read every step
    assert flops.weight_bytes(c) == 2 * 4_128_120_832
    assert flops.kv_bytes_per_position(c) == 73_728


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_counts_match_the_program_parameter_tree(name):
    from repro.models import LM
    c = CONFIGS[name]
    lm = LM(spec.program_config(c))
    tree = lm.abstract_params()
    import jax
    n = sum(math.prod(x.shape) for x in jax.tree.leaves(tree))
    pad = lm.cfg.padded_vocab - c["vocab_size"]
    heads = 1 if c["tie_word_embeddings"] else 2
    assert n == param_count(c) + heads * pad * c["hidden_size"]


def test_decode_step_by_hand():
    c = CONFIGS["qwen3-4b"]
    L, d, H, KV, hd, F, V = 36, 2560, 32, 8, 128, 9728, 151936
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F
    f, b = flops.decode_step(c, tokens=3, ctx_sum=3000)
    assert f == 2 * 3 * (L * per_layer + d * V) + 4 * L * H * hd * 3000
    assert b == flops.weight_bytes(c) + 147_456 * (3000 + 3)


def test_chunk_attention_is_the_sum_over_its_rows():
    c = CONFIGS["granite-8b-pp2"]
    start, n = 700, 37
    f, b = flops.chunk(c, start, n)
    att = sum(flops.attention_flops(c, p + 1) for p in range(start,
                                                             start + n))
    L, d, V = 18, 4096, 49152
    assert f == 2 * L * flops.layer_params(c) * n + 2 * d * V + att
    assert b == flops.weight_bytes(c) + 73_728 * (start + n)


def test_peaks_table():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "cloud" in p["source"].lower()
    with pytest.raises(SystemExit):
        flops.peaks("TPU v9 imaginary")


def test_canonical_bytes_equal_weight_bytes_plus_unread_embedding():
    for c in CONFIGS.values():
        total = sum(math.prod(s) for s, _ in weights.shapes(c).values()) * 2
        unread = 0 if c["tie_word_embeddings"] else \
            c["vocab_size"] * c["hidden_size"] * 2
        assert total == flops.weight_bytes(c) + unread
