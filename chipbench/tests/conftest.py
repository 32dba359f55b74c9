"""CPU rehearsal of the chip benchmark: tiny sizes, four forced host
devices, Pallas in interpret mode.

XLA's CPU backend contracts a multiply and an add into one fused
multiply-add in some programs and not in others, so two programs that
compute the same positions can differ in the last bit on the CPU; the
v5e shows no such drift (PERF.md). The instruction set is capped at AVX,
which has no FMA, so that the program and the reference round alike
here too. Both variables must be set before JAX is imported.

    python -m pytest chipbench/tests
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4"
                           " --xla_cpu_max_isa=AVX").strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
