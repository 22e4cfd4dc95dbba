"""Base-2 shift softmax (paper Eq. 3-4).

    exp(s * qk) = 2^(s * log2(e) * qk)
               ~= (1 + r) * 2^floor(x),   x = s*log2(e)*qk, r = x - floor(x)

i.e. a piecewise-linear-in-mantissa approximation of 2^x realized in hardware
as "(r+1) << floor(x)".  On TPU the shift is built from exponent bits on the
VPU (:func:`pow2`), so XLA and the Pallas kernels emit the same bits: both
compilers lower a float ``2^k`` differently (``exp(k ln 2)`` against a
native pow2), and only the bit construction is exact in both.
Maximum relative error of (1+r)*2^floor(x) vs 2^x is max_r (1+r)/2^r - 1
~= 6.15% at r = 1/ln2 - 1; mean error ~2.6%.

The row sum (the paper's scan-chain-accumulated Sigma) is the same quantity
as the online-softmax denominator; :func:`softmax2` exposes a numerically
safe variant that subtracts floor(row-max) — an integer shift, so the
approximation algebra is unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LOG2E = 1.4426950408889634


def pow2(k: jax.Array) -> jax.Array:
    """Exact f32 ``2^k`` for integer-valued ``k``, built from exponent bits.

    Below the normal range (k < -126) the result is 0, above it (k > 127)
    it is inf.
    """
    e = jnp.clip(k, -127.0, 128.0).astype(jnp.int32) + 127
    return jax.lax.bitcast_convert_type(e << 23, jnp.float32)


def exp2_shift(x: jax.Array) -> jax.Array:
    """(1 + r) * 2^floor(x): the paper's shift-exp approximation of 2^x."""
    f = jnp.floor(x)
    r = x - f
    return (1.0 + r) * pow2(f).astype(r.dtype)


def tree_sum(x: jax.Array) -> jax.Array:
    """Sum over the last axis in one fixed pairwise order (keeps the axis).

    Each step adds lanes ``i`` and ``i + h`` (``h`` the largest power of two
    below the width); lanes without a partner pass through.  That is the
    order of a zero-padded power-of-two tree, so any zero padding of ``x``
    gives the same bits.  The integer attention paths accumulate Sigma
    with it: ``jnp.sum`` leaves the order to the compiler, which differs
    between XLA and a Pallas kernel (and between two XLA fusions), and a
    one-ulp Sigma difference moves every dequantized output.
    """
    w = x.shape[-1]
    while w > 1:
        h = 1 << ((w - 1).bit_length() - 1)
        lo = x[..., :w - h] + x[..., h:w]
        x = lo if 2 * h == w else jnp.concatenate([lo, x[..., w - h:h]], -1)
        w = h
    return x


def exp_shift(x: jax.Array) -> jax.Array:
    """Approximate e^x via exp2_shift(x * log2 e) (Eq. 4)."""
    return exp2_shift(x * LOG2E)


def softmax2(logits: jax.Array, *, axis: int = -1, scale=1.0,
             stable: bool = True) -> jax.Array:
    """softmax(scale * logits) with the base-2 shift-exp (Eq. 3-4).

    ``stable=True`` subtracts floor(max) along ``axis`` first.  Because the
    subtrahend is an integer, it commutes exactly with the floor/residual
    decomposition: (1+r)*2^(f-m) for every element, so the approximate
    softmax is *identical* to the unstable form in exact arithmetic while
    keeping 2^x in fp32 range for long rows.
    """
    x = logits * (scale * LOG2E)
    if stable:
        m = jnp.floor(jnp.max(x, axis=axis, keepdims=True))
        x = x - m
    e = exp2_shift(x)
    return e / jnp.sum(e, axis=axis, keepdims=True)


def softmax_ref(logits: jax.Array, *, axis: int = -1, scale=1.0) -> jax.Array:
    """Exact softmax oracle."""
    return jax.nn.softmax(logits * scale, axis=axis)


def quantize_probs(e: jax.Array, sigma: jax.Array, bits: int,
                   delta_attn: jax.Array) -> jax.Array:
    """Paper §IV-B quantizer with Sigma-scaled thresholds.

    Instead of dividing every exponential by the row sum Sigma, the
    comparator references are multiplied by Sigma:

        p_q = clip(round(e / (Sigma * delta)), 0, 2^b - 1)
            = sum_k [ e > (k - 1/2) * delta * Sigma ]

    Both forms are implemented; this function uses the division form (exact
    same integer output, and the division is one rsqrt-class VPU op per row).
    """
    qmax = (1 << bits) - 1
    q = jnp.clip(jnp.round(e / (sigma * delta_attn)), 0, qmax)
    return q.astype(jnp.uint8)


def quantize_probs_comparator(e: jax.Array, sigma: jax.Array, bits: int,
                              delta_attn: jax.Array) -> jax.Array:
    """Threshold-comparator formulation (faithful hardware model).

    O(2^bits) comparisons per element — exactly what the parallel comparator
    array in the paper's Fig. 4 computes.  Property-tested equal to
    :func:`quantize_probs`.
    """
    qmax = (1 << bits) - 1
    ks = jnp.arange(1, qmax + 1, dtype=e.dtype)          # thresholds (k-1/2)*d
    thr = (ks - 0.5) * delta_attn * sigma[..., None, None]   # (..., 1, K)
    q = jnp.sum(e[..., None] > thr, axis=-1)
    return q.astype(jnp.uint8)
