"""Device time during which a collective op (all-gather, all-reduce,
all-to-all, collective-permute, reduce-scatter; sync or async) ran on
device 0, per step."""


def read(run):
    if run.trace is None or len(run.trace.devices) < 2:
        return None
    ns = run.trace.collective_ns(0)
    return ns / 1e6 / run.steps if ns else None
