"""Seconds set-up spent obtaining executables: backend compiles plus loads
from the persistent compilation cache (JAX's monitoring events)."""


def read(run):
    return run.compile_s
