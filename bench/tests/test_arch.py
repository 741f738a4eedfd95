"""The architecture modules, ``bench/arch/<model_type>.py``: numbers
pinned from the harness as it was before they existed (bitwise), a model
type without a module, no architecture named outside ``bench/arch``, and
a new module found with no edit anywhere else.

The pinned reference logits are sha256 digests of their float32 bytes on
the CPU with XLA's default flags (``--xla_cpu_multi_thread_eigen=false``
changes their last bits; the number of cores does not)."""
import hashlib
import math
import re

import numpy as np
import pytest

import flops
import reference
import spec
import tiny
import weights
from test_reference import GRANITE, LLAMA

CONFIGS = {name: spec.load_json(spec.BENCH / "configs" / f"{name}.json")
           for name in ("qwen3-4b", "granite-8b-pp2")}

LEAVES = {
    "qwen3-4b": {
        "embed": (151936, 2560), "final_norm": (2560,), "k_norm": (36, 128),
        "ln1": (36, 2560), "ln2": (36, 2560), "q_norm": (36, 128),
        "w_down": (36, 9728, 2560), "w_gate": (36, 2560, 9728),
        "w_up": (36, 2560, 9728), "wk": (36, 2560, 1024),
        "wo": (36, 4096, 2560), "wq": (36, 2560, 4096),
        "wv": (36, 2560, 1024)},
    "granite-8b-pp2": {
        "b_down": (18, 4096), "b_gate": (18, 14336), "b_up": (18, 14336),
        "bk": (18, 1024), "bo": (18, 4096), "bq": (18, 4096),
        "bv": (18, 1024), "embed": (49152, 4096), "final_norm": (4096,),
        "ln1": (18, 4096), "ln2": (18, 4096), "w_down": (18, 14336, 4096),
        "w_gate": (18, 4096, 14336), "w_up": (18, 4096, 14336),
        "wk": (18, 4096, 1024), "wo": (18, 4096, 4096),
        "wq": (18, 4096, 4096), "wv": (18, 4096, 1024)},
}
# (tokens, ctx_sum) -> (FLOPs, bytes); (start, n) -> (FLOPs, bytes)
DECODE = {
    "qwen3-4b": {(1, 1): (8045133824, 8045231104),
                 (8, 8 * 7601): (100222369792, 17012620288),
                 (32, 32 * 900): (274412339200, 12296387584)},
    "granite-8b-pp2": {(1, 1): (8254685184, 8256389120),
                       (8, 8 * 7601): (83968131072, 12740083712),
                       (32, 32 * 900): (272633954304, 10381967360)},
}
CHUNK = {
    "qwen3-4b": {(0, 512): (3798753738752, 8120433664),
                 (512, 513): (3961243762688, 8196078592),
                 (9728, 512): (6736511369216, 9554885632)},
    "granite-8b-pp2": {(0, 512): (4059222245376, 8293990400),
                       (512, 513): (4144685678592, 8331812864),
                       (9728, 512): (5528101060608, 9011216384)},
}
# teacher_forced(make_canonical(5, config), config, toks[:20], toks[20:41],
# 64, 32) with toks = default_rng(0).integers(0, vocab, 41), weights at the
# tiny width's STD: digests of the logits, f32 and fp8
LOGITS = {
    "qwen3": ("0028566eedd1e784cac4de2ec09131f0bb89d5bc851e2c9e146332cc1d38af7a",
              "366bcd10c1ffe159de1e60ae5ea7a113c58e6a357e305915063d230f077810ed"),
    "llama": ("c35ce36bc9050cc4da121c1889f76f3b5ec9c189b3a1e7ec442b66029ca9fff0",
              "acd705556ce5a9c1f08a9c4876062273cb240fe4e78e85e5e655d88de473f2f4"),
    "granite": ("19968fcc1921420cc6909a3144fa837dbaa93747a2ab7fe03470d5fff518a19a",
                "cec39a7761e9606401e88662d599d32d5b407a4eb77211ade272673d614d9cb3"),
}
TINY = {"qwen3": tiny.CONFIG, "llama": LLAMA, "granite": GRANITE}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_pinned_leaves_and_work_of_the_bench_configs(name):
    c = CONFIGS[name]
    got = {k: tuple(s) for k, (s, _) in sorted(weights.shapes(c).items())}
    assert list(got) == sorted(LEAVES[name]) and got == LEAVES[name]
    for (tokens, ctx), want in DECODE[name].items():
        assert flops.decode_step(c, tokens, ctx) == want
    for (start, n), want in CHUNK[name].items():
        assert flops.chunk(c, start, n) == want


@pytest.mark.parametrize("label", sorted(TINY))
def test_pinned_reference_logits_bitwise(label):
    c = TINY[label]
    w = weights.make_canonical(5, c)
    toks = np.random.default_rng(0).integers(
        0, c["vocab_size"], 41).astype(np.int32)
    for mode, want in zip(("f32", "fp8"), LOGITS[label]):
        _, _, logits = reference.teacher_forced(
            w, c, toks[:20], toks[20:41], 64, 32, mode=mode)
        got = hashlib.sha256(np.asarray(logits, np.float32).tobytes())
        assert got.hexdigest() == want, mode


def test_model_type_without_module_names_the_file():
    c = dict(tiny.CONFIG, model_type="granitemoehybrid")
    for call in (lambda: spec.model_fields(c), lambda: weights.shapes(c),
                 lambda: flops.decode_step(c, 1, 1)):
        with pytest.raises(SystemExit, match=re.escape(
                "bench/arch/granitemoehybrid.py")):
            call()


def test_no_architecture_named_outside_arch():
    """The harness's code dispatches on ``model_type``: no model type
    and no key of one architecture appears in it outside ``bench/arch``
    (tests and data files name configurations, and are not searched)."""
    words = ('"qwen3"', "'qwen3'", '"llama"', "'llama'",
             "intermediate_size", "attention_bias")
    files = [p for p in spec.BENCH.rglob("*.py")
             if not p.relative_to(spec.BENCH).parts[0] in ("arch", "tests")]
    assert spec.BENCH / "run.py" in files and len(files) > 20
    found = [(p.name, w) for p in files for w in words if w in p.read_text()]
    assert not found


TOY = '''"""A bag-of-words model: the logits at a row are the running sum of
the embeddings up to it, through an output head."""
import jax.numpy as jnp

import weights


def model_fields(config):
    return {"d_model": config["hidden_size"],
            "vocab_size": config["vocab_size"]}


def leaves(config):
    d, v = config["hidden_size"], config["vocab_size"]
    return {"embed": ((v, d), weights.STD), "head": ((d, v), weights.STD)}


def to_program(w, config, padded_vocab):
    return {"table": w["embed"], "head": w["head"]}


def logits(w, config, tokens, rows, mm):
    h = jnp.cumsum(w["embed"][tokens].astype(jnp.float32), axis=0)
    return mm("rd,dv->rv", h[rows], w["head"], -1, 0)


def decode_step(config, tokens, ctx_sum):
    d, v = config["hidden_size"], config["vocab_size"]
    return 2 * d * v * tokens, 4 * 2 * d * v + 4 * d * tokens


def chunk(config, start, n):
    d, v = config["hidden_size"], config["vocab_size"]
    return 2 * d * v, 4 * 2 * d * v + 4 * d


def cache_bytes(config, positions, slots):
    return 4 * config["hidden_size"] * slots


def one_layer(config):
    return dict(config)
'''


def test_new_module_is_found_without_an_edit(tmp_path, monkeypatch):
    (tmp_path / "toybag.py").write_text(TOY)
    monkeypatch.setattr(spec, "ARCH", tmp_path)
    c = {"model_type": "toybag", "hidden_size": 8, "vocab_size": 32,
         "torch_dtype": "float32"}
    assert spec.model_fields(c) == {"d_model": 8, "vocab_size": 32,
                                    "dtype": "float32",
                                    "param_dtype": "float32"}
    assert set(weights.shapes(c)) == {"embed", "head"}
    w = weights.make_canonical(3, c)
    assert w["head"].shape == (8, 32)
    assert set(weights.to_program(w, c, 32)) == {"table", "head"}
    prompt, served = np.array([3, 5, 7], np.int32), [9, 11, 2, 30]
    gap, top, logits = reference.teacher_forced(w, c, prompt, served, 16, 8)
    seq = np.concatenate([prompt, served[:-1]])
    emb, head = np.asarray(w["embed"], np.float64), np.asarray(w["head"])
    want = np.cumsum(emb[seq], axis=0)[2:] @ head
    np.testing.assert_allclose(np.asarray(logits), want, rtol=1e-5,
                               atol=1e-6)
    assert np.array_equal(top, want.argmax(-1))
    np.testing.assert_allclose(
        gap, want.max(-1) - want[np.arange(4), served], atol=1e-5)
    assert flops.decode_step(c, 3, 100) == (2 * 8 * 32 * 3,
                                            4 * 2 * 8 * 32 + 4 * 8 * 3)
    assert flops.chunk(c, 4, 16)[0] == 2 * 8 * 32
    assert flops.weight_bytes(c) == 4 * 2 * 8 * 32
    assert flops.kv_bytes_per_position(c) == 0
    assert math.isclose(spec.arch("toybag").cache_bytes(c, 10, 2), 64)
