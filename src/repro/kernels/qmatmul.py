"""Pallas TPU kernel: operand-reordered integer matmul (paper Eq. 2).

    out[m, n] = (sum_k Xq[m, k] * Wq[n, k]) * scale[n] + bias[n]

The contraction runs on int8 operands (MXU int8 path: 2x bf16 peak on v5e);
the dequantization is a per-output-channel epilogue applied to the int32
accumulator tile while it is still in VMEM — the kernel-level realization of
"delay dequantization until after the matrix operation".

A packed variant stores W as 2x4-bit nibbles per byte in HBM, halving
weight bandwidth (the TPU analogue of the paper's low-bit storage benefit).
Byte j of a packed row holds logical columns 2j (low nibble) and 2j+1 (high
nibble).  The kernel never re-interleaves them: it splits each weight tile
into its low and high nibble planes (:func:`nibble_planes`) and contracts
them against the even and odd columns of X, which the wrapper
de-interleaves once.  Integer partial sums are exact, so the result equals
the unpacked contraction bit for bit.

Block sizes default to (128, 128, 512): MXU-aligned (multiples of 128 in
lane dims) and VMEM-light (x: 64KB, w: 64KB int8, acc: 64KB int32).  The
serving graph overrides them per shape via
:func:`repro.kernels.dispatch.qmatmul_blocks` (VMEM-budgeted heuristics);
model graphs reach this kernel through ``dispatch.maybe_qlinear``.

The weight is read where it is stored and never padded or copied in HBM
when N and K are multiples of 128 (every serving width): the grid is
``cdiv(N, bn) x cdiv(K, bk)`` and the last N and K blocks are partial blocks
of the stored weight.  The K edge is exact because X is padded with zero
columns, so the weight tile's out-of-range columns add 0 to the int32
accumulator whatever they hold; the N edge is exact because the output
columns past N are partial-block writes, which are dropped.  Shapes off the
128 grid pad the weight to the tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

_LANE = 128                       # lane width: the grid the weight is read on


def nibble_planes(packed):
    """uint8 nibble pairs -> (low, high) planes of sign-extended int8 codes.

    The low plane holds the even logical columns, the high plane the odd
    ones; both keep ``packed``'s shape.
    """
    w = packed.astype(jnp.int32)
    lo = w & 0xF
    hi = (w >> 4) & 0xF
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    return lo.astype(jnp.int8), hi.astype(jnp.int8)


def _qmatmul_kernel(x_ref, w_ref, scale_ref, bias_ref, rs_ref, o_ref,
                    acc_ref, *, nk: int, out_dtype, packed: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if packed:
        # x_ref holds the (even, odd) column planes of this X tile.
        lo, hi = nibble_planes(w_ref[...])
        acc_ref[...] += (
            jnp.dot(x_ref[0], lo.T, preferred_element_type=jnp.int32)
            + jnp.dot(x_ref[1], hi.T, preferred_element_type=jnp.int32))
    else:
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...].T,
                                preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        out = acc * (scale_ref[0, :][None, :] * rs_ref[:, 0][:, None]) \
            + bias_ref[0, :][None, :]
        o_ref[...] = out.astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype",
                                             "interpret", "packed"))
def qmatmul(x_q, w_q, scale, bias=None, row_scale=None, *, bm=128, bn=128,
            bk=512, out_dtype=jnp.float32, interpret=None, packed=False):
    """x_q (M, K) int8 @ w_q (N, K) int8 -> (M, N) float, fused epilogue.

    ``scale`` (N,) f32 folds the per-tensor input step and per-channel weight
    step (dx_bar * dw).  ``row_scale`` (M,) optionally refines ``dx_bar`` to
    a per-input-row step (decode batches quantize each sequence on its own
    grid); the epilogue then applies ``scale[n] * row_scale[m]``.
    ``packed=True`` takes w_q as (N, K//2) uint8 nibbles; ``bk`` must then be
    a multiple of 256 so each nibble plane tile stays lane-aligned.
    ``interpret``: see :func:`repro.kernels.interpret_mode`.
    """
    m, kdim = x_q.shape
    n = w_q.shape[0]
    k_logical = w_q.shape[1] * (2 if packed else 1)
    assert kdim == k_logical, (x_q.shape, w_q.shape, packed)
    if bias is None:
        bias = jnp.zeros((n,), jnp.float32)
    if row_scale is None:
        row_scale = jnp.ones((m,), jnp.float32)

    # X is zero-padded to the tile.  The weight is padded only off the 128
    # grid; on it a ragged edge is a partial block (module docstring).
    pm, pk = (-m) % bm, (-kdim) % bk
    pn = (-n) % bn if n % _LANE else 0
    pwk = pk if kdim % _LANE else 0
    if pm or pk:
        x_q = jnp.pad(x_q, ((0, pm), (0, pk)))
    if pn or pwk:
        w_q = jnp.pad(w_q, ((0, pn), (0, pwk // (2 if packed else 1))))
    if pn:
        scale = jnp.pad(scale, (0, pn))
        bias = jnp.pad(bias, (0, pn))
    if pm:
        row_scale = jnp.pad(row_scale, (0, pm))
    mm, nn, kk = m + pm, n + pn, kdim + pk
    nm, nn_blocks, nk = mm // bm, pl.cdiv(nn, bn), kk // bk

    scale2 = scale.reshape(1, nn).astype(jnp.float32)
    bias2 = bias.reshape(1, nn).astype(jnp.float32)
    rs2 = row_scale.reshape(mm, 1).astype(jnp.float32)
    if packed:
        # (2, M, K/2): the even and odd columns of X, matching the weight's
        # low and high nibble planes.
        x_q = jnp.stack([x_q[:, 0::2], x_q[:, 1::2]])
        xspec = pl.BlockSpec((2, bm, bk // 2), lambda i, j, k: (0, i, k))
        wspec = pl.BlockSpec((bn, bk // 2), lambda i, j, k: (j, k))
    else:
        xspec = pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))
        wspec = pl.BlockSpec((bn, bk), lambda i, j, k: (j, k))

    out = pl.pallas_call(
        functools.partial(_qmatmul_kernel, nk=nk, out_dtype=out_dtype,
                          packed=packed),
        grid=(nm, nn_blocks, nk),
        in_specs=[
            xspec,
            wspec,
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret_mode(interpret),
    )(x_q, w_q, scale2, bias2, rs2)
    return out[:m, :n]
