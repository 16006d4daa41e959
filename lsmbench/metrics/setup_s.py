"""Seconds from the process's start to the window's: JAX start-up,
building the store, warming up and any compilation."""


def read(run):
    return run.setup_s
