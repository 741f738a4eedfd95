"""A run of a sessions cell over a four-chip mesh (four virtual CPU
devices, in a process of its own), the chip check skipped: sound, it is
judged correct and its float8 control, put in the program's place, not
correct; with its timed path broken underneath, not correct, once
for each fault the cell can have: the exchange between chips left out of
the decode step's softmax merge, a decode step that returns its cache
unchanged, and a token altered where it is produced."""
import json
import os
import subprocess
import sys
from pathlib import Path

TESTS = Path(__file__).resolve().parent

SCRIPT = r'''
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2], sys.argv[3]]
import jax
import jax.numpy as jnp
import run, spec, tiny, weights
from repro.models import attention
from test_run import altered_token, state_unchanged

weights.STD = tiny.CONFIG["hidden_size"] ** -0.5
assert len(jax.devices()) == 4
TRAFFIC = {"arrivals": {"kind": "sessions", "sessions": 4},
           "prompt": {"median": 40, "sigma": 0.3, "min": 24, "max": 60},
           "output": {"fill": 96, "min": 36, "max": 72, "checked": 24},
           "block": 4, "drain_s": 0}
ENGINE = {"max_batch": 4, "max_seq": 96, "page_size": 8, "num_pages": 52,
          "prefill_chunk": 16, "decode_impl": "gather", "mesh": "4"}
cell = spec.Cell(name="tiny.longctx", chips=4, config=tiny.CONFIG,
                 traffic=TRAFFIC,
                 settings={"engine": ENGINE, "limits": {
                     "logit_gap": 1e-3, "tokens_checked": 30}},
                 end_to_end=[{"name": "itl_p50_ms", "unit": "ms"}],
                 per_layer=[])


def local_merge(acc, l, m, axis_name):
    """The decode merge with no exchange: each chip's own pages alone."""
    return acc / jnp.maximum(l, 1e-30)[:, None, :, :, None]


real_build, real_merge = run.build, attention.merge_paged_partials


def fault_build(fault):
    def build(c, s):
        lm, params, eng = real_build(c, s)
        fault(eng)
        return lm, params, eng
    return build


out = {}
for name in ("sound", "no_exchange", "state_unchanged", "altered_token"):
    run.build = real_build
    attention.merge_paged_partials = real_merge
    if name == "no_exchange":
        attention.merge_paged_partials = local_merge
    elif name != "sound":
        run.build = fault_build({"state_unchanged": state_unchanged,
                                 "altered_token": altered_token}[name])
    res = run.run_cell(cell, 2**31 + 41, 1.0, False, peaks=tiny.PEAKS,
                       control=name == "sound")
    out[name] = [res["correct"], res["checks"]["logit_gap"]["value"],
                 res["checks"]["tokens_checked"]["value"]]
    if name == "sound":
        ctrl = res["control"]
        out["control"] = [ctrl["correct"],
                          ctrl["checks"]["logit_gap"]["value"],
                          ctrl["checks"]["tokens_checked"]["value"]]
print(json.dumps(out))
'''


def test_mesh_faults_are_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    src = TESTS.parents[1] / "src"
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(TESTS.parent),
                        str(TESTS), str(src)], env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    correct, gap, tokens = out.pop("sound")
    assert correct and gap <= 1e-3 and tokens >= 30
    for name, (correct, gap, tokens) in out.items():
        assert not correct and gap > 1e-3, name
