"""Pallas TPU flash-decode: one query token vs a long KV cache.

Decode is memory-bound: the whole job is streaming the KV cache through
VMEM once at full HBM bandwidth. Grid (B*Hkv, nk): for each KV head the
G = Hq/Hkv grouped query rows ride along as a (G, D) tile, so GQA
expansion happens in-register instead of materializing repeated KV in
HBM (the decisive difference from the GPU kernel, which shuffles within
a warp; see DESIGN.md §Adaptations). Positions beyond `pos` are masked,
which also makes the kernel safe for ring-buffer caches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            bk: int, nk: int, scale: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[0]
    # skip blocks entirely beyond the valid prefix
    @pl.when(j * bk <= pos)
    def _body():
        q = q_ref[0].astype(jnp.float32)  # (G, D)
        k = k_ref[0].astype(jnp.float32)  # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def flash_decode(q, k_cache, v_cache, pos, bk: int = 512,
                 *, interpret: bool):
    """q: (B, Hq, D); caches: (B, S, Hkv, D); pos: scalar int32.

    Returns (B, Hq, D)."""
    B, H, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    bk = min(bk, S)
    assert S % bk == 0, (S, bk)
    nk = S // bk
    qg = q.reshape(B, Hkv, G, D).reshape(B * Hkv, G, D)
    kc = k_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    vc = v_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (1,))

    out = pl.pallas_call(
        functools.partial(_kernel, bk=bk, nk=nk, scale=D ** -0.5),
        grid=(B * Hkv, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, G, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(pos_arr, qg, kc, vc)
    return out.reshape(B, H, D)
