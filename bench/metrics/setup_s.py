"""Seconds from the start of the process to the start of the window:
imports, configuration, the warm-up call and every compilation."""


def read(run):
    return run.setup_s
