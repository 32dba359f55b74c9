"""Device idle time between consecutive windows of steps, per window, on
device 0: from the end of one window program's execution to the start
of the next, less any device work in between (the counter read's
reductions). A window program is the longest-running module executed
inside each `step` call."""


def read(run):
    if run.trace is None:
        return None
    tr = run.trace
    dev = tr.devices[0]
    windows = []
    for s, e, name in tr.spans:
        if name != "step":
            continue
        mods = [m for m in dev.modules if m[0] >= s and m[1] <= e]
        if mods:
            windows.append(max(mods, key=lambda m: m[1] - m[0]))
    if len(windows) < 2:
        return None
    idle = sum((b[0] - a[1]) - dev.busy_ns(a[1], b[0])
               for a, b in zip(windows, windows[1:]))
    return idle / 1e6 / (len(windows) - 1)
