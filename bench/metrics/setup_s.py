"""Seconds from the start of the process to the start of the window:
imports, weights, engine, compiling or loading programs, warm-up, ramp."""


def read(run):
    return run.setup_s
