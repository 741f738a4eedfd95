"""``chip_smoke.py``'s control flow at reduced() size on the CPU (Pallas in
interpret mode): the phases serve, the checks pass on a healthy engine, and
the script refuses to run without a TPU."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402

SMALL = dict(reduced=True, max_seq=128, max_prompt=64, new_tokens=8)


def test_one_chip_phases_pass_at_reduced_size():
    lines, failures, phases = chip_smoke.one_chip(chunk=16, **SMALL)
    assert failures == [], failures
    assert [p.name[0] for p in phases] == ["a", "b", "c"]
    for ph in phases:
        assert len(ph.streams) == chip_smoke.N_REQUESTS
        assert all(len(s) == 8 for s in ph.streams.values())
        assert ph.pool_bytes_per_chip == ph.pool_bytes > 0
    # interpret mode lowers the kernel to plain XLA, never a custom call
    assert phases[1].custom_call is False
    assert any(ln.startswith("reference:") for ln in lines)


def test_check_streams_fails_a_token_off_the_reference():
    """A stream that samples a token other than the reference argmax, away
    from any tie, fails the check; the divergence step is reported."""
    from repro.launch import serve
    args = chip_smoke.serve_args(reduced=True, max_seq=128, new_tokens=8)
    cfg, lm, params = serve.build_model(args)
    prompts = chip_smoke.make_prompts(cfg.vocab_size, 64, n=2)
    with chip_smoke.CompileClock() as clock:
        good = chip_smoke.run_phase("a", lm, params, args, prompts, clock)
    bad = chip_smoke.Phase(**{**vars(good), "name": "bad", "streams": {
        i: list(s) for i, s in good.streams.items()}})
    bad.streams[1][3] = (bad.streams[1][3] + 1) % cfg.vocab_size
    lines, failures = chip_smoke.check_streams(lm, params, prompts,
                                               [good, bad])
    # later steps were sampled after the original token, so they may fail
    # too; nothing before the corrupted step does
    assert failures[0].startswith("phase bad: request 1 step 3 sampled")
    assert all(f.startswith("phase bad: request 1 step ") for f in failures)
    assert any("bad vs a: diverge request 1 at step 3" in ln for ln in lines)


def test_four_chip_phases_pass_on_virtual_devices(subproc):
    out = subproc(
        f"import sys; sys.path.insert(0, {str(ROOT)!r})\n"
        "import chip_smoke\n"
        "lines, failures, phases = chip_smoke.four_chips("
        "reduced=True, max_seq=128, max_prompt=64, new_tokens=8)\n"
        "assert failures == [], failures\n"
        "print([(p.pool_bytes, p.pool_bytes_per_chip) for p in phases])\n",
        devices=4)
    sizes = eval(out.strip().splitlines()[-1])
    (total, one), (t1, c1), (t2, c2) = sizes
    assert one == total and c1 * 4 == t1 and c2 * 2 == t2


def test_prompts_fill_two_buckets():
    prompts = chip_smoke.make_prompts(151936)
    lens = [len(p) for p in prompts]
    assert len(prompts) == chip_smoke.N_REQUESTS
    assert all(257 <= n <= 512 for n in lens[:4])
    assert all(129 <= n <= 256 for n in lens[4:])


def test_main_refuses_without_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert "no TPU" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out
