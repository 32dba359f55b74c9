"""Multi-host launcher for the sharded engine (jax.distributed).

Within one process, `sharding="lp_device"` runs the "lp" mesh over
`--xla_force_host_platform_device_count` host threads — exact for
equivalence testing, but every "device" shares the process's cores, so
D>1 wall-clock measures orchestration overhead rather than speedup
(see benchmarks/exp5_sharded.py's honest-measurement note). This module
boots the *same* engine across P processes (one per host, or one per
core): `jax.distributed.initialize` wires them into a single JAX
runtime whose global device list concatenates every process's local
devices, the "lp" mesh spans all of them, and lp_shard's collectives
(psum / all_to_all / all_gather) move real bytes between processes —
the sparse halo's `bytes_on_wire` becomes physical traffic and D>1
measures real parallelism.

Launch P processes with identical arguments except --process-id:

    PYTHONPATH=src python -m repro.parallel.multihost \\
        --coordinator 10.0.0.1:9911 --processes 2 --process-id 0 ...
    PYTHONPATH=src python -m repro.parallel.multihost \\
        --coordinator 10.0.0.1:9911 --processes 2 --process-id 1 ...

or use --spawn to fork all P ranks locally from one command (smoke
testing). Process 0 prints aggregate counters as a ``RESULT {json}``
line — the exp5 harness idiom.

Capability gate: the CPU backend in current jaxlib cannot *execute*
cross-process computations ("Multiprocess computations aren't
implemented on the CPU backend") even though distributed init and the
global device list work. Rather than hang or crash mid-scan, the
launcher probes a 1-element psum right after mesh construction and
exits with code 3 and a clear message when the backend refuses —
multi-process runs need a GPU/TPU backend (or a jaxlib with CPU
cross-process collectives); single-process runs (--processes 1) work
everywhere and still exercise this exact code path.

Bit-identity note: every process builds the identical initial state
from the shared seed (init_sharded is deterministic), keeps only its
own slot rows, and assembles the global sharded arrays from them —
so a P-process run computes exactly what the single-process mesh of
the same total device count computes, which is bit-identical to the
single-device oracle (tests/test_sharding.py).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_UNSUPPORTED_EXIT = 3  # backend cannot run cross-process computations


def _build_config(args):
    from repro.core.abm import ABMConfig
    from repro.core.engine import EngineConfig
    from repro.core.heuristics import HeuristicConfig
    return EngineConfig(
        abm=ABMConfig(n_se=args.n_se, n_lp=args.n_lp, area=10_000.0,
                      speed=11.0, interaction_range=250.0, p_interact=0.2,
                      mobility=args.mobility),
        heuristic=HeuristicConfig(mf=1.2, mt=10),
        gaia_on=not args.gaia_off, timesteps=args.steps,
        sharding="lp_device", n_devices=0,  # 0 = all global devices
        mig_capacity=max(512, args.n_se // 4))


def _globalize(state, spec, mesh):
    """Turn the (identical-on-every-process) host state into global
    sharded arrays: each process keeps the slot rows its local devices
    own and `host_local_array_to_global_array` stitches the shards.
    Device order in the mesh is process-major (jax.devices()), so a
    process's share is one contiguous slot range."""
    import jax
    from jax.experimental import multihost_utils
    from repro.parallel import lp_shard

    fspecs = lp_shard._field_specs(spec)
    pid, nproc = jax.process_index(), jax.process_count()

    def to_global(v, pspec):
        sharded_axis = next(
            (i for i, ax in enumerate(pspec) if ax == "lp"), None)
        if sharded_axis is not None:
            share = v.shape[sharded_axis] // nproc
            v = jax.lax.slice_in_dim(v, pid * share, (pid + 1) * share,
                                     axis=sharded_axis)
        return multihost_utils.host_local_array_to_global_array(
            jax.device_get(v), mesh, pspec)

    from jax.sharding import PartitionSpec as P
    out = {k: to_global(v, fspecs.get(k, P())) for k, v in state.items()}
    return out


def _fetch(v):
    """The counter summary comes back replicated, but a global array
    spanning non-addressable devices refuses np.asarray; pull the local
    replica instead."""
    from jax.experimental import multihost_utils
    import numpy as np

    if getattr(v, "is_fully_addressable", True):
        return np.asarray(v)
    return np.asarray(multihost_utils.process_allgather(v))


def _probe_collectives(mesh) -> bool:
    """One tiny psum over the global mesh: returns False when the
    backend cannot execute cross-process computations (current CPU
    jaxlib), instead of letting the first real scan die mid-flight."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    try:
        fn = jax.jit(jax.shard_map(
            lambda x: jax.lax.psum(x, "lp"), mesh=mesh,
            in_specs=P(), out_specs=P(), check_vma=False))
        jax.block_until_ready(fn(jnp.float32(1.0)))
        return True
    except Exception as e:  # jaxlib raises XlaRuntimeError
        print(f"[multihost] collective probe failed: {e}", file=sys.stderr)
        return False


def run_distributed(args) -> int:
    import jax

    if args.processes > 1:
        jax.distributed.initialize(coordinator_address=args.coordinator,
                                   num_processes=args.processes,
                                   process_id=args.process_id)
    import jax.numpy as jnp
    from repro.core.engine import series_shapes, unpack, window_key_cfg
    from repro.parallel import lp_shard

    cfg = _build_config(args)
    spec = lp_shard.make_shard_spec(cfg)
    mesh = lp_shard.make_mesh(spec)
    pid = jax.process_index()
    if pid == 0:
        print(f"[multihost] {jax.process_count()} process(es), "
              f"{jax.device_count()} global devices, mesh lp={spec.n_dev}, "
              f"{spec.cap} slots/device, backend={jax.default_backend()}")
    if args.processes > 1 and not _probe_collectives(mesh):
        print(f"[multihost] backend {jax.default_backend()!r} cannot run "
              "cross-process computations; rerun with --processes 1 or on "
              "a GPU/TPU cluster", file=sys.stderr)
        return _UNSUPPORTED_EXIT

    state = lp_shard.init_sharded(jax.random.key(args.seed), cfg, spec)
    if args.processes > 1:
        state = _globalize(state, spec, mesh)
    scan = lp_shard._compiled_window_sharded(window_key_cfg(cfg), args.steps)
    mf = jnp.float32(cfg.heuristic.mf)
    state, series, summary = jax.block_until_ready(scan(state, mf))
    t0 = time.time()  # the first call compiled and ran
    state, series, summary = jax.block_until_ready(scan(state, mf))
    dt = (time.time() - t0) / args.steps
    counters = unpack(_fetch(summary), series_shapes(series))
    if pid == 0:
        out = dict(processes=args.processes, devices=jax.device_count(),
                   n_se=args.n_se, n_lp=args.n_lp, steps=args.steps,
                   per_step_s=round(dt, 4),
                   bytes_on_wire=counters["bytes_on_wire"],
                   mean_halo_frac=round(counters["mean_halo_frac"], 4),
                   mean_lcr=round(counters["mean_lcr"], 4),
                   migrations=counters["migrations"],
                   shard_overflow=counters["shard_overflow"])
        print("RESULT " + json.dumps(out), flush=True)
    return 0


def _spawn_ranks(args) -> int:
    """Fork all P ranks of this launcher locally (smoke testing): rank 0
    runs in children too so the parent can aggregate exit codes."""
    import subprocess
    procs = []
    env = dict(os.environ)
    if args.local_devices > 0:
        env["XLA_FLAGS"] = (
            f"{env.get('XLA_FLAGS', '')} "
            f"--xla_force_host_platform_device_count="
            f"{args.local_devices}").strip()
    base = [sys.executable, "-m", "repro.parallel.multihost",
            "--coordinator", args.coordinator,
            "--processes", str(args.processes),
            "--n-se", str(args.n_se), "--n-lp", str(args.n_lp),
            "--steps", str(args.steps), "--seed", str(args.seed),
            "--mobility", args.mobility]
    if args.gaia_off:
        base.append("--gaia-off")
    for rank in range(args.processes):
        procs.append(subprocess.Popen(base + ["--process-id", str(rank)],
                                      env=env))
    codes = [p.wait() for p in procs]
    if any(c == _UNSUPPORTED_EXIT for c in codes):
        return _UNSUPPORTED_EXIT
    return max(codes)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="run the sharded GAIA engine across jax.distributed "
                    "processes")
    ap.add_argument("--coordinator", default="127.0.0.1:9911",
                    help="process-0 address:port for jax.distributed")
    ap.add_argument("--processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--spawn", action="store_true",
                    help="fork all --processes ranks locally (smoke test)")
    ap.add_argument("--local-devices", type=int, default=0,
                    help="force this many host-platform devices per "
                         "process (XLA pins the count at first jax init, "
                         "so the launcher re-execs itself with XLA_FLAGS "
                         "set when needed)")
    ap.add_argument("--n-se", type=int, default=10_000)
    ap.add_argument("--n-lp", type=int, default=8)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mobility", default="rwp")
    ap.add_argument("--gaia-off", action="store_true")
    args = ap.parse_args(argv)

    if args.spawn:
        return _spawn_ranks(args)
    if (args.local_devices > 0 and argv is None
            and os.environ.get("_MULTIHOST_REEXEC") != "1"):
        # `python -m` imports the repro.parallel package (and with it
        # jax) before main() runs, and XLA pins the host device count at
        # first init — so apply the flag by re-exec'ing this launcher
        env = dict(os.environ, _MULTIHOST_REEXEC="1")
        env["XLA_FLAGS"] = (
            f"{env.get('XLA_FLAGS', '')} "
            f"--xla_force_host_platform_device_count="
            f"{args.local_devices}").strip()
        os.execve(sys.executable,
                  [sys.executable, "-m", "repro.parallel.multihost"]
                  + sys.argv[1:], env)
    return run_distributed(args)


if __name__ == "__main__":
    sys.exit(main())
