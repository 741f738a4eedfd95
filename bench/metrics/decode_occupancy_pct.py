"""Mean slots decoding per fused decode dispatch in the window, as a share
of the engine's slots."""


def read(run):
    n = [s.decode_tokens for s in run.steps_in_window() if s.decode_tokens]
    return 100.0 * sum(n) / len(n) / run.slots if n else None
