"""Beyond-paper benchmark: GAIA self-clustering as MoE expert placement.

Simulates drifting, group-skewed routing traffic (the MoE analogue of
the ABM's mobility) and measures the all-to-all payload with a static
placement vs. GAIA's adaptive placement, charging every expert move at
its real MigComm price (Eq. 6).  The paper's trade — pay MigC to convert
remote traffic into local traffic — reproduced at the expert level.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import write_csv
from repro.core import gaia_moe as gm


def drifting_traffic(key, cfg, step, drift_every=200, tokens=4096):
    """(G, E) token counts: each expert has a 'home' group that rotates
    every `drift_every` steps (locality that moves, like RWP)."""
    E, G = cfg.num_experts, cfg.num_groups
    phase = step // drift_every
    home = (jnp.arange(E) + phase) % G
    base = jax.random.uniform(jax.random.fold_in(key, step), (G, E))
    w = base + 10.0 * (jnp.arange(G)[:, None] == home[None, :])
    w = w / w.sum()
    return w * tokens




def main(scale: str = "quick", steps=600, drift_every=200):
    cfg = gm.GaiaMoEConfig(num_experts=64, num_groups=8, mf=1.2, mt=50,
                           window=8, interval=25)
    d_model, d_expert, token_bytes = 2048, 768, 2 * 2048
    key = jax.random.key(0)

    st = gm.init_state(cfg)
    static_pl = st["placement"]
    upd = jax.jit(lambda s, tr: gm.maybe_update(cfg, s, tr))
    a2a = jax.jit(lambda pl, tr: gm.a2a_bytes(pl, tr, token_bytes))
    traffic = jax.jit(lambda t: drifting_traffic(key, cfg, t, drift_every))
    rows = []
    a2a_static = a2a_gaia = mig_bytes = moves = 0.0
    for t in range(steps):
        tr = traffic(jnp.int32(t))
        a2a_static += float(a2a(static_pl, tr))
        a2a_gaia += float(a2a(st["placement"], tr))
        st, n = upd(st, tr)
        n = int(n)
        moves += n
        mig_bytes += float(gm.migration_bytes(n, d_model, d_expert))
        if (t + 1) % 100 == 0:
            rows.append((t + 1, a2a_static, a2a_gaia, mig_bytes, moves))
            print(f"[gaia-moe] step {t+1}: a2a static={a2a_static/1e9:.2f}GB "
                  f"gaia={a2a_gaia/1e9:.2f}GB migs={int(moves)} "
                  f"migbytes={mig_bytes/1e9:.3f}GB")
    path = write_csv("gaia_moe.csv",
                     "step,a2a_static_bytes,a2a_gaia_bytes,mig_bytes,moves",
                     rows)
    total_static = a2a_static
    total_gaia = a2a_gaia + mig_bytes  # charge migrations at full price
    gain = 100 * (total_static - total_gaia) / total_static
    print(f"[gaia-moe] total comms: static {total_static/1e9:.2f}GB vs "
          f"gaia {total_gaia/1e9:.2f}GB  (gain {gain:+.1f}%)")
    assert moves > 0, "no expert migrations happened"
    assert gain > 10.0, f"GAIA-MoE should cut a2a traffic: {gain}%"
    print(f"[gaia-moe] OK -> {path}")
    return gain


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    print(f"[gaiamoe] compile cache: {use_compile_cache()}")
    main()
