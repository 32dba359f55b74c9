"""The proximity phase's share of its roofline, in %: the least time the
chip could take for the phase's required work (chipbench/roofline.py,
from the configuration alone), over the phase's measured device time
per step."""
from chipbench import roofline


def read(run):
    if run.trace is None:
        return None
    ns = run.trace.self_by_phase(0).get("proximity")
    if not ns:
        return None
    t_min, _ = roofline.min_seconds(run.cfg["engine"], run.peaks)
    return 100.0 * t_min / (ns / 1e9 / run.steps)
