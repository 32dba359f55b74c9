"""Pallas TPU flash-attention forward (causal / bidirectional).

Grid (BH, nq, nk), nk innermost: the fp32 accumulator and the running
max/denominator tiles live in VMEM scratch across the whole KV sweep of
one query block (HBM->VMEM traffic is O(S) per query block, the flash
invariant). Causal scheduling skips fully-masked KV blocks with pl.when —
on TPU the skipped grid step costs only the (tiny) control iteration, so
causal attention does ~half the MXU work of the masked dense loop (this
is the kernel counterpart of the jnp path's `causal_skip`).

Block sizes default to (256 q x 512 kv) x d_head<=128: working set
~(256+512)*128*2B for q/k/v tiles + 256*128*4B acc ~= 0.5 MiB, far under
the ~16 MiB VMEM budget, leaving room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            causal: bool, bq: int, bk: int, nk: int, scale: float):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = (not causal) or (j * bk <= i * bq + bq - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
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


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, causal: bool = True, bq: int = 256,
                    bk: int = 512, *, interpret: bool):
    """q,k,v: (BH, S, D) with KV already group-expanded. Returns (BH,S,D)."""
    import math
    BH, S, D = q.shape
    Skv = k.shape[1]
    bq = math.gcd(S, min(bq, S))  # largest block <= bq that divides S
    bk = math.gcd(Skv, min(bk, Skv))
    assert S % bq == 0 and Skv % bk == 0, (S, bq, Skv, bk)
    nq, nk = S // bq, Skv // bk
    kern = functools.partial(_kernel, causal=causal, bq=bq, bk=bk, nk=nk,
                             scale=D ** -0.5)
    return pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
