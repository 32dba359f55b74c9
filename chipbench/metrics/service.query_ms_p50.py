"""Median of the window's query calls (`query_neighbors`, `query_lcr`),
in ms, timed by the client."""
from chipbench.stats import percentile


def read(run):
    ms = [1e3 * (t1 - t0) for name, t0, t1 in run.spans
          if name.startswith("query_")]
    return percentile(ms, 50) if ms else None
