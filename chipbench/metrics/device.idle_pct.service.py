"""Share of the traced window, in %, in which no op ran on the device,
averaged over the chips the cell uses: 1 - (union of leaf-op intervals)
over the window."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.busy_s / run.window_s)
