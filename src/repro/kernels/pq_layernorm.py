"""Pallas TPU kernel: fused LayerNorm -> low-bit quantizer (paper §IV-C).

One VMEM-resident pass per row tile: moments, normalization, affine, and the
quantizer all happen before anything returns to HBM, so the normalized
activations are never materialized in float — the TPU analogue of the
paper's systolic mu/sigma^2 rows feeding a comparator array.  The producer's
per-tensor scale dx_bar cancels inside the normalization (the paper's
absorption trick): callers simply skip applying it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _pqln_kernel(x_ref, g_ref, b_ref, d_ref, o_ref, *, eps, qmin, qmax,
                 rms_only):
    x = x_ref[...].astype(jnp.float32)
    if rms_only:
        nrm = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    else:
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        nrm = (x - mu) * jax.lax.rsqrt(var + eps)
    y = nrm * g_ref[0, :][None, :] + b_ref[0, :][None, :]
    q = jnp.clip(jnp.round(y / d_ref[0, 0]), qmin, qmax)
    o_ref[...] = q.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("bits", "eps", "rms_only", "br",
                                             "interpret"))
def pq_layernorm(x, gamma, beta, delta, *, bits=8, eps=1e-6, rms_only=False,
                 br=256, interpret=None):
    """(rows, d) float -> (rows, d) int8 codes on the signed b-bit grid.

    ``interpret``: see :func:`repro.kernels.interpret_mode`.
    """
    rows, d = x.shape
    qmin, qmax = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    pr = (-rows) % br
    if pr:
        x = jnp.pad(x, ((0, pr), (0, 0)))
    g2 = gamma.reshape(1, d).astype(jnp.float32)
    b2 = (jnp.zeros((1, d), jnp.float32) if beta is None
          else beta.reshape(1, d).astype(jnp.float32))
    d2 = jnp.asarray(delta, jnp.float32).reshape(1, 1)

    out = pl.pallas_call(
        functools.partial(_pqln_kernel, eps=eps, qmin=qmin, qmax=qmax,
                          rms_only=rms_only),
        grid=((rows + pr) // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pr, d), jnp.int8),
        interpret=interpret_mode(interpret),
    )(x, g2, b2, d2)
    return out[:rows]
