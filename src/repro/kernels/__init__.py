"""Pallas TPU kernels for the reproduction's compute hot spots.

Each kernel lives in its own subpackage with three files:

  <name>.py  the Pallas kernel (and its tiling/fusion rationale)
  ops.py     the jit'd public entry points
  ref.py     the pure-jnp oracle the kernel must match bit-for-bit or
             within dtype tolerance (tests/test_kernels.py)

Subpackages:

- proximity: the paper's §5.1 hot spot — fused toroidal-distance +
  range-test + per-sender LP histogram. Two variants: a dense O(N^2)
  sweep (MXU histogram) and a cell-list candidate version (O(N*C),
  fed by repro.core.neighbors). See DESIGN.md §Adaptations.
- flash_attention: tiled online-softmax attention (beyond-paper stack)
- flash_decode: single-token decode attention with GQA
- moe_gate: fused top-k gating for the MoE layer

Interpret mode is the CPU lowering only. The proximity kernels pick it
in proximity/ops.py: the Pallas interpreter when lowering for the CPU
(correct but slow — never benchmark it), the compiled Mosaic kernel
when lowering for a TPU (DESIGN.md §Interpret-mode caveat). The
beyond-paper kernels (flash_attention, flash_decode, moe_gate) are off
the engine's path; they take `interpret` as a required keyword, so no
caller gets the interpreter without asking for it.
"""
