"""Live SEs times steps completed in the window, over the wall time from
the window's start to the end of its last completed round (each round
ends with the window's counters read back to the host)."""


def read(run):
    return run.n_live * run.steps / (run.t_end - run.t0)
