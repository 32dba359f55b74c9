"""Device self time of the ops under the `step.heuristic` scope (window
update, evaluation, balancer, selection), per step, on device 0."""


def read(run):
    if run.trace is None:
        return None
    ns = run.trace.self_by_phase(0).get("heuristic")
    return ns / 1e6 / run.steps if ns else None
