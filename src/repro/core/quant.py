"""Uniform low-bit quantization primitives (paper §III).

Symmetric uniform grid with step ``delta``::

    q = clip(round(x / delta), qmin, qmax),   qmin = -2^(b-1), qmax = 2^(b-1)-1

matching the paper's 3-bit example whose quantizer thresholds are
``(k - 1/2) * delta`` for k in [-4, 3].  Attention probabilities use the
unsigned grid ``[0, 2^b - 1]``.

All quantized values are physically stored in int8 (TPU MXU operand dtype);
4-bit additionally packs two nibbles per byte for HBM storage
(:func:`pack_int4` / :func:`unpack_int4`).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

STORAGE_DTYPE = jnp.int8
ACC_DTYPE = jnp.int32


def qrange(bits: int, *, unsigned: bool = False) -> tuple[int, int]:
    """(qmin, qmax) of the b-bit grid."""
    if unsigned:
        return 0, (1 << bits) - 1
    return -(1 << (bits - 1)), (1 << (bits - 1)) - 1


def absmax_scale(x: jax.Array, bits: int, *, axis=None, unsigned: bool = False,
                 eps: float = 1e-8) -> jax.Array:
    """Calibrate step size from the abs-max of ``x`` (keepdims over ``axis``).

    The step is f32 whatever ``x``'s dtype: scale arithmetic in bf16 would
    leave each rounding to the compiler (XLA may keep a bf16 product in f32
    inside one fusion and round it at another's boundary), so the codes and
    epilogues of two backends could differ by an ulp of the step.
    """
    _, qmax = qrange(bits, unsigned=unsigned)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    return pin(jnp.maximum(amax.astype(jnp.float32), eps) / qmax)


def pin(step: jax.Array) -> jax.Array:
    """A step size as a value XLA may not re-derive.

    XLA's algebraic simplifier folds constant factors across a product
    chain: ``(amax * (1/qmax)) * (softmax_scale * log2e)`` becomes ``amax *
    c``, rounded once instead of twice.  Whether it folds depends on the ops
    between the two constants (a gather in one program, none in another),
    so the Pallas and XLA programs would dequantize on steps one ulp apart
    (seen on a v5e).  It also reorders a chain to scale the smaller operand
    first: ``(c * q_step) * k_step`` became ``(c * k_step) * q_step`` in
    one program only.  Behind an optimization barrier the step is rounded
    where it is computed, in every program.
    """
    return jax.lax.optimization_barrier(step)


def quantize(x: jax.Array, delta: jax.Array, bits: int, *,
             unsigned: bool = False) -> jax.Array:
    """Float -> int8-stored b-bit code (uint8 for unsigned grids)."""
    qmin, qmax = qrange(bits, unsigned=unsigned)
    q = jnp.clip(jnp.round(x / delta), qmin, qmax)
    return q.astype(jnp.uint8 if unsigned else STORAGE_DTYPE)


def dequantize(q: jax.Array, delta: jax.Array) -> jax.Array:
    return q.astype(delta.dtype) * delta


# ---------------------------------------------------------------------------
# Fake quantization for QAT (straight-through estimator, LSQ-style step grad)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fake_quant(x: jax.Array, delta: jax.Array, bits: int, unsigned: bool = False):
    """Quantize-dequantize with STE wrt ``x`` and LSQ gradient wrt ``delta``.

    Preserves ``x``'s dtype: the f32 step size must not upcast bf16 weights/
    activations (it silently doubled matmul + FSDP-gather bytes in training
    graphs before this cast).
    """
    qmin, qmax = qrange(bits, unsigned=unsigned)
    q = jnp.clip(jnp.round(x / delta), qmin, qmax)
    return (q * delta).astype(x.dtype)


def _fq_fwd(x, delta, bits, unsigned):
    qmin, qmax = qrange(bits, unsigned=unsigned)
    scaled = x / delta
    q = jnp.clip(jnp.round(scaled), qmin, qmax)
    return (q * delta).astype(x.dtype), (scaled, q, delta)


def _fq_bwd(bits, unsigned, res, g):
    qmin, qmax = qrange(bits, unsigned=unsigned)
    scaled, q, delta = res
    inside = (scaled >= qmin) & (scaled <= qmax)
    gx = jnp.where(inside, g, 0.0)
    # LSQ: d(q*delta)/d(delta) = (q - x/delta) inside, clip boundary outside.
    gdelta_elem = jnp.where(inside, q - scaled, q) * g
    # Reduce onto delta's (broadcast) shape.
    gdelta = _reduce_to_shape(gdelta_elem, jnp.shape(delta))
    return gx, gdelta.astype(delta.dtype)


def _reduce_to_shape(x, shape):
    if shape == ():
        return jnp.sum(x)
    axes = []
    x_shape = jnp.shape(x)
    ndiff = len(x_shape) - len(shape)
    axes.extend(range(ndiff))
    for i, s in enumerate(shape):
        if s == 1 and x_shape[ndiff + i] != 1:
            axes.append(ndiff + i)
    out = jnp.sum(x, axis=tuple(axes), keepdims=False)
    return jnp.reshape(out, shape)


fake_quant.defvjp(_fq_fwd, _fq_bwd)


# ---------------------------------------------------------------------------
# QTensor: a quantized activation flowing between integerized modules
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QTensor:
    """int8-coded tensor plus its (per-tensor) dequantization step size."""
    q: jax.Array            # int8 codes
    scale: jax.Array        # scalar f32 step size
    bits: int = 8           # logical bit width (static)
    unsigned: bool = False  # static

    def dequant(self) -> jax.Array:
        return dequantize(self.unpacked().q, self.scale)

    @property
    def is_packed(self) -> bool:
        """Signed uint8 storage marks 2x4-bit nibble packing (the KV-cache/
        weight convention); unsigned QTensors legitimately store uint8
        codes.  The single source of truth for the packed-storage test."""
        return self.q.dtype == jnp.uint8 and not self.unsigned

    def unpacked(self) -> "QTensor":
        """int8-coded view of a nibble-packed QTensor (no-op otherwise)."""
        if self.is_packed:
            return QTensor(unpack_int4(self.q), self.scale, self.bits,
                           self.unsigned)
        return self

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype

    def astype_acc(self):
        return self.q.astype(ACC_DTYPE)

    def tree_flatten(self):
        return (self.q, self.scale), (self.bits, self.unsigned)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scale = children
        bits, unsigned = aux
        return cls(q=q, scale=scale, bits=bits, unsigned=unsigned)


def quantize_tensor(x: jax.Array, bits: int, *, scale: Optional[jax.Array] = None,
                    unsigned: bool = False) -> QTensor:
    """Quantize activation to a per-tensor QTensor (calibrates if no scale)."""
    if scale is None:
        scale = absmax_scale(x, bits, unsigned=unsigned)
    return QTensor(quantize(x, scale, bits, unsigned=unsigned),
                   jnp.asarray(scale, jnp.float32), bits, unsigned)


# ---------------------------------------------------------------------------
# Low-bit physical packing (HBM storage format; unpacked in-kernel)
# ---------------------------------------------------------------------------

def pack_int4(q: jax.Array) -> jax.Array:
    """Pack int8-stored 4-bit codes pairwise along the last axis (2x smaller).

    Last dim must be even. q values must lie in [-8, 7].
    """
    if q.shape[-1] % 2:
        raise ValueError("pack_int4 needs an even trailing dim")
    lo = q[..., 0::2].astype(jnp.uint8) & 0xF
    hi = (q[..., 1::2].astype(jnp.uint8) & 0xF) << 4
    return (lo | hi).astype(jnp.uint8)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4` (sign-extends nibbles back to int8)."""
    lo = (packed & 0xF).astype(jnp.int8)
    hi = ((packed >> 4) & 0xF).astype(jnp.int8)
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(*packed.shape[:-1], packed.shape[-1] * 2)


def storage_bits(bits: int) -> int:
    """Physical bits per value as stored (4-bit packs; 2/3-bit live in int8).

    2/3-bit could pack 4x/2x as well; we model the paper's logical grid with
    int8 containers and take the real packing win only where the unpack is
    cheap on the VPU (nibbles).  Size accounting in benchmarks uses the
    *logical* width, matching the paper's Table II.
    """
    return 4 if bits == 4 else 8
