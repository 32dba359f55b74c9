"""The program's exact count of bytes sent between chips (halo rows and
migrated rows), summed over the window's steps, per step."""


def read(run):
    vals = [c["bytes_on_wire"] for c in run.counters if "bytes_on_wire" in c]
    return sum(vals) / run.steps if vals else None
