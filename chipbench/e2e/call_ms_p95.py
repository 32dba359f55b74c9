"""95th percentile, in ms, of every service call in the window (all but
`step`), each timed from the client's side until the call returned its
answer to the host."""
from chipbench.stats import percentile


def read(run):
    ms = [1e3 * (t1 - t0) for name, t0, t1 in run.spans if name != "step"]
    return percentile(ms, 95) if ms else None
