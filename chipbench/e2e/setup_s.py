"""Seconds from the process's start to the first timed call: imports,
the engine's init from the seed, and the warm rounds, which compile or
read from the cache every program the window runs."""


def read(run):
    return run.setup_s
