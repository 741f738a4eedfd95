"""The plain reference against the program's own full forward at CPU size
(both float32), and its float8 control against the limit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import spec
import tiny
import weights

LLAMA = dict(tiny.CONFIG, model_type="llama", tie_word_embeddings=False,
             architectures=["LlamaForCausalLM"], arch="granite-8b",
             rms_norm_eps=1e-05, rope_theta=10000)
# Granite Code's published settings: biases on every projection, tied head
GRANITE = dict(LLAMA, attention_bias=True, mlp_bias=True,
               tie_word_embeddings=True, rope_theta=10000000.0)


@pytest.mark.parametrize("config", [tiny.CONFIG, LLAMA, GRANITE],
                         ids=["qwen3", "llama", "granite"])
def test_reference_matches_program_forward(config):
    from repro.models import ForwardOpts, LM
    lm = LM(spec.program_config(config))
    params = weights.make_program_params(5, config, lm)
    w = weights.make_canonical(5, config)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, config["vocab_size"], 41).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        prog = lm.forward(params, {"tokens": jnp.asarray(toks[:40])[None]},
                          ForwardOpts(attn_impl="dense", remat="none"))[0]
    prog = np.asarray(prog[0, 19:40, :config["vocab_size"]])
    # prompt toks[:20], "served" toks[20:41]: rows 19..39 of toks[:40]
    gap, top, logits = reference.teacher_forced(
        w, config, toks[:20], toks[20:41], 64, 32)
    np.testing.assert_allclose(np.asarray(logits), prog, atol=2e-5)
    assert np.array_equal(top, prog.argmax(-1))
    np.testing.assert_allclose(
        gap, prog.max(-1) - prog[np.arange(21), toks[20:41]], atol=4e-5)


def test_float8_control_fails_the_limit():
    w = weights.make_canonical(9, tiny.CONFIG)
    rng = np.random.default_rng(1)
    toks = rng.integers(0, tiny.CONFIG["vocab_size"], 60).astype(np.int32)
    _, top, logits = reference.teacher_forced(
        w, tiny.CONFIG, toks[:20], toks[20:60], 64, 40)
    _, top8, _ = reference.teacher_forced(
        w, tiny.CONFIG, toks[:20], toks[20:60], 64, 40, mode="fp8")
    logits = np.asarray(logits)
    ctrl = (logits.max(1) - logits[np.arange(40), top8]).max()
    assert ctrl > tiny.SETTINGS["limits"]["logit_gap"]


def test_biases_move_the_reference():
    """A reference that dropped the biases would read a gap: they are
    large enough to show."""
    w = weights.make_canonical(5, GRANITE)
    toks = np.arange(30, dtype=np.int32) * 7 % GRANITE["vocab_size"]
    _, _, with_b = reference.teacher_forced(w, GRANITE, toks[:10],
                                            toks[10:], 64, 32)
    w0 = {k: v for k, v in w.items() if not k.startswith("b")}
    _, _, without = reference.teacher_forced(w0, GRANITE, toks[:10],
                                             toks[10:], 64, 32)
    assert np.abs(np.asarray(with_b) - np.asarray(without)).max() > 0.05
