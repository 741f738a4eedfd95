"""Whole-step model FLOP/s utilization: the operations the window's steps
needed (the decode tokens and the prefill chunks they processed,
``flops.decode_step`` and ``flops.chunk``) over the summed wall time of
those ``step()`` calls times the chip's bf16 peak."""
import flops


def read(run):
    steps = run.steps_in_window()
    if not steps:
        return None
    work = 0
    for s in steps:
        if s.decode_tokens:
            work += flops.decode_step(run.config, s.decode_tokens,
                                      s.decode_ctx)[0]
        for start, n in s.chunks:
            work += flops.chunk(run.config, start, n)[0]
    wall = sum(s.end - s.start for s in steps)
    return 100.0 * work / (wall * run.peaks["bf16_flops_per_s"])
