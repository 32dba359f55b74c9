"""Record the small chip trace that test_trace.py reduces.

On a machine with a TPU, from the root of the checkout:

    python chipbench/tests/record_trace.py

runs two 5-step windows of the paper model cut to 400 SEs (the
paper's density) under the profiler, with the harness's host spans,
and writes chipbench/tests/data/small.xplane.pb.gz and the scope map of
its programs, small.scopes.json.
"""
import gzip
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main():
    import jax
    from chipbench import trace as tr
    from chipbench.sut import ProgramSUT
    from chipbench.tests.tiny import TINY_ABM
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace.py needs a TPU")
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "gaia-paper-10k.json")) as f:
        cfg = json.load(f)
    cfg["engine"]["abm"].update(TINY_ABM)
    hlo = tr.HloCapture().install()
    sut = ProgramSUT(cfg, {}, 7)
    sut.step(5)
    sut.step(5)
    with tr.profiling() as prof:
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.step"):
                sut.step(5)
    hlo.uninstall()
    out = os.path.join(HERE, "data")
    os.makedirs(out, exist_ok=True)
    with open(prof["path"], "rb") as src, \
            gzip.open(os.path.join(out, "small.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    # the scopes of the instructions the trace holds
    full = hlo.scopes()
    trc = tr.Trace.from_file(prof["path"], full)
    used = {tr._INSTR.match(op[3]).group(1)
            for d in trc.devices.values() for op in d.ops}
    scopes = {m: {k: v for k, v in ins.items() if k in used}
              for m, ins in full.items()}
    scopes = {m: v for m, v in scopes.items() if v}
    with open(os.path.join(out, "small.scopes.json"), "w") as f:
        json.dump(scopes, f, sort_keys=True)
    tr.cleanup(prof)


if __name__ == "__main__":
    main()
