"""Jit'd public wrappers around the Pallas kernels.

The kernels compile for the MXU on a TPU and run in interpret mode
elsewhere (:func:`repro.kernels.interpret_mode`).  Model graphs normally
reach the kernels through :mod:`repro.kernels.dispatch` (backend selection
+ shape policy); these wrappers are the direct, QTensor-typed entry points
for tests and benchmarks.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.quant import QTensor
from repro.core.integerize import QLinearParams
from repro.core.softmax2 import LOG2E
from repro.kernels.int_attention import int_attention, int_attention_fused
from repro.kernels.pq_layernorm import pq_layernorm
from repro.kernels.qmatmul import qmatmul


def qlinear_op(x: QTensor, p: QLinearParams, **kw):
    """Kernel-backed version of core.integerize.int_linear (2D inputs)."""
    scale = (p.w_scale * x.scale).astype(jnp.float32)
    bias = None if p.bias is None else p.bias.astype(jnp.float32)
    return qmatmul(x.q, p.w_q, scale, bias, **kw)


def int_attention_op(q: QTensor, k: QTensor, v: QTensor, *, softmax_scale,
                     attn_bits=7, causal=True, window=None, fused=True,
                     **kw):
    """Kernel-backed integer attention on (H, S, D) QTensors.

    ``fused=True`` (default) runs the single-pass kernel; ``fused=False``
    the two-pass baseline.  Identical outputs, 2/3 the MXU MACs.
    """
    sc = softmax_scale * q.scale * k.scale * LOG2E
    kern = int_attention_fused if fused else int_attention
    return kern(q.q, k.q, v.q, sc, v.scale, attn_bits=attn_bits,
                causal=causal, window=window, **kw)


def pq_layernorm_op(x, gamma, beta, delta, **kw):
    return pq_layernorm(x, gamma, beta, delta, **kw)
