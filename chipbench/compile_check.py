#!/usr/bin/env python3
"""Compile each cell's window program for a described TPU v5e (a 2x2
host), with no chip attached: what the chip's compiler would refuse is
found here at no chip time, and `memory_analysis` says what one window
needs on each chip.

    JAX_PLATFORMS=cpu python chipbench/compile_check.py

Prints one line per cell: the programs compiled, their temp and argument
bytes per device, and whether the HLO holds collectives. A compile that
passes is not a chip run.
"""
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    import json
    import re

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
        SingleDeviceSharding

    from chipbench import traffic
    from chipbench.sut import engine_config
    from repro.core import engine
    from repro.parallel import lp_shard

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    confs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        with open(os.path.join(ROOT, confs[cell["config"]]["file"])) as f:
            cfg = json.load(f)
        mix = traffic.load(ROOT, cell["traffic"])
        ecfg = engine_config(cfg, mix.get("world", {}))
        steps = sorted({op["n"] for op in mix["round"]
                        if op["op"] == "step"})
        mf = jax.ShapeDtypeStruct((), np.float32)
        if ecfg.sharding == "lp_device":
            # the state as init builds it (on the CPU's forced devices),
            # placed on the described chips' mesh
            spec = lp_shard.make_shard_spec(ecfg)
            st = lp_shard.init_sharded(jax.random.key(0), ecfg, spec)
            mesh = Mesh(np.array(topo.devices[:spec.n_dev]), ("lp",))
            specs = lp_shard._field_specs(spec)
            lp_shard.make_mesh = lambda s: mesh

            def shape(k, v):
                return jax.ShapeDtypeStruct(
                    v.shape, v.dtype, sharding=NamedSharding(
                        mesh, specs.get(k, PartitionSpec())))
            args = {k: shape(k, v) for k, v in st.items()}
            fns = [lp_shard._compiled_window_sharded(
                engine.window_key_cfg(ecfg), n) for n in steps]
        else:
            one = SingleDeviceSharding(topo.devices[0])
            st = jax.eval_shape(lambda k: engine._init_engine(k, ecfg),
                                jax.random.key(0))
            args = jax.tree.map(lambda v: jax.ShapeDtypeStruct(
                v.shape, v.dtype, sharding=one), st)
            mf = jax.ShapeDtypeStruct((), np.float32, sharding=one)
            fns = [engine._compiled_window(ecfg, n) for n in steps]
        for n, fn in zip(steps, fns):
            c = fn.lower(args, mf).compile()
            m = c.memory_analysis()
            coll = sorted(set(re.findall(
                r"\s(all-gather|all-reduce|all-to-all|collective-permute)"
                r"(?:-start)?\(", c.as_text())))
            print(f"{cell['name']}: {n}-step window compiled for "
                  f"{cell['chips']} described v5e chip(s); temp "
                  f"{m.temp_size_in_bytes / 2**20:.1f} MiB, arguments "
                  f"{m.argument_size_in_bytes / 2**20:.1f} MiB per device; "
                  f"collectives {coll or 'none'}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
