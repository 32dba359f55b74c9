"""Pallas TPU fused MoE gate: softmax + (biased) top-k + expert histogram.

One pass over a (bt, E) logit tile in VMEM produces the top-k weights/
ids (k sequential argmax sweeps on the VPU — k <= 8, E <= 512, so the
sweep is cheap relative to the HBM read of the logits) and accumulates
the per-expert token histogram with a mask matmul on the MXU. Fusing the
histogram in-kernel is what feeds GAIA-MoE its traffic matrix without a
second pass over the routing tensors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(logits_ref, bias_ref, top_p_ref, top_e_ref, counts_ref, *,
            k: int, norm_topk: bool, nt: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    x = logits_ref[...].astype(jnp.float32)  # (bt, E)
    E = x.shape[-1]
    mx = x.max(axis=-1, keepdims=True)
    ex = jnp.exp(x - mx)
    probs = ex / ex.sum(axis=-1, keepdims=True)
    sel = probs + bias_ref[...]

    remaining = sel
    hist = jnp.zeros_like(probs)
    ps, es = [], []
    for _ in range(k):
        idx = remaining.argmax(axis=-1)  # (bt,)
        onehot = (jax.lax.broadcasted_iota(jnp.int32, remaining.shape, 1)
                  == idx[:, None])
        ps.append(jnp.sum(jnp.where(onehot, probs, 0.0), axis=-1))
        es.append(idx.astype(jnp.int32))
        hist = hist + onehot.astype(jnp.float32)
        remaining = jnp.where(onehot, -jnp.inf, remaining)
    top_p = jnp.stack(ps, axis=-1)  # (bt, k)
    if norm_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    top_p_ref[...] = top_p
    top_e_ref[...] = jnp.stack(es, axis=-1)
    counts_ref[...] += hist.sum(axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("k", "norm_topk", "bt",
                                             "interpret"))
def moe_gate(logits, k: int, bias=None, norm_topk: bool = True,
             bt: int = 512, *, interpret: bool):
    """logits: (T, E) f32 -> (top_p (T,k) f32, top_e (T,k) i32,
    counts (E,) i32)."""
    T, E = logits.shape
    bt = min(bt, T)
    assert T % bt == 0, (T, bt)
    nt = T // bt
    if bias is None:
        bias = jnp.zeros((E,), jnp.float32)
    top_p, top_e, counts = pl.pallas_call(
        functools.partial(_kernel, k=k, norm_topk=norm_topk, nt=nt),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((bt, E), lambda t: (t, 0)),
            pl.BlockSpec((1, E), lambda t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bt, k), lambda t: (t, 0)),
            pl.BlockSpec((bt, k), lambda t: (t, 0)),
            pl.BlockSpec((1, E), lambda t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, k), jnp.float32),
            jax.ShapeDtypeStruct((T, k), jnp.int32),
            jax.ShapeDtypeStruct((1, E), jnp.float32),
        ],
        interpret=interpret,
    )(logits, bias[None, :])
    return top_p, top_e, counts[0].astype(jnp.int32)
