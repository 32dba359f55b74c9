"""Public wrapper: model-facing layout adapters for the flash kernel."""
from __future__ import annotations


from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref  # noqa: F401


def flash_attention_bhsd(q, k, v, causal: bool = True, *, interpret: bool):
    """(B,H,S,D) layout wrapper (KV pre-expanded to H heads)."""
    B, H, S, D = q.shape
    out = flash_attention(q.reshape(B * H, S, D),
                          k.reshape(B * H, k.shape[2], D),
                          v.reshape(B * H, v.shape[2], D),
                          causal=causal, interpret=interpret)
    return out.reshape(B, H, S, D)
