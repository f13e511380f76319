"""Seconds from the start of the process to the opening of the window:
imports, weights, compilation or loading of every program, warm-up."""


def read(run):
    return run.setup_s
