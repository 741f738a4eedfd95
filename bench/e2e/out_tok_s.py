"""Output tokens emitted inside the window over the window's seconds."""


def read(run):
    n = sum(sum(1 for t in s.token_times if 0 <= t <= run.seconds)
            for s in run.window.served)
    return n / run.seconds
