"""Device self time of the ops under the `step.proximity` scope, per
step, on device 0 (on four chips, device 0 holds LP 0)."""


def read(run):
    if run.trace is None:
        return None
    ns = run.trace.self_by_phase(0).get("proximity")
    return ns / 1e6 / run.steps if ns else None
