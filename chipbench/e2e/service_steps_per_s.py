"""Simulated steps completed per second over the whole window, churn
and queries included."""


def read(run):
    return run.steps / (run.t_end - run.t0)
