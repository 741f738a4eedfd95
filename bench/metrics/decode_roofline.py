"""Share of the roofline the fused decode step reaches: for each decode
dispatch in the traced stretch, the least time the chip could take for the
work the step needs (``flops.decode_step``: the larger of its operations
over peak bf16 FLOP/s and its bytes over HBM bandwidth), summed, over the
summed device time of those dispatches."""
import flops
from devtrace import MissingEvent


def read(run):
    if run.trace is None:
        return None
    dur = run.trace.modules("fused")
    steps = [s for s in run.traced_steps() if s.decode_tokens]
    if len(dur) != len(steps):
        raise MissingEvent(f"{len(dur)} jit_fused executions traced for "
                           f"{len(steps)} decode steps")
    need = 0.0
    for s in steps:
        f, b = flops.decode_step(run.config, s.decode_tokens, s.decode_ctx)
        need += max(f / run.peaks["bf16_flops_per_s"],
                    b / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need / (sum(dur) * 1e-9)
