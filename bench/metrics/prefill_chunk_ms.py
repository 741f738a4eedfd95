"""Mean device time of the chunked-prefill dispatch (the engine's chunk
step, ``jit_run``) in the traced stretch."""


def read(run):
    if run.trace is None:
        return None
    d = run.trace.modules("run")
    return sum(d) / len(d) * 1e-6
