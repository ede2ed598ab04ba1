"""Seconds from the start of the process to the start of the window: JAX's
start, the cell's data, the program's set-up and the warm-up that
compiles, or loads from the cache, every shape the window uses."""


def read(run):
    return run.setup_s
