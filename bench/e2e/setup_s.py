"""Set-up seconds: process start to the window's opening (loading the
program, drawing the weights, building the engine, obtaining and warming
its step programs, and prefilling a mix's sessions)."""


def read(run):
    return run.setup_s
