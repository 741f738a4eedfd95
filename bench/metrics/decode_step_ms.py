"""Mean device time of the fused decode+sample dispatch (the engine's
``jit_fused`` module) in the traced stretch."""


def read(run):
    if run.trace is None:
        return None
    d = run.trace.modules("fused")
    return sum(d) / len(d) * 1e-6
