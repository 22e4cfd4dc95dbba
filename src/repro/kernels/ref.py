"""Pure-jnp oracles for every kernel (exact intended semantics, no tiling).

Probability quantizer (v2, this PR): codes are quantized on the
**power-of-two Sigma-scaled grid**

    e    = (1+r) * 2^(x - m),  m = floor(row max)  =>  e in [0, 2)
    p_q  = clip(round(e * qmax / 2), 0, qmax)            (code grid: 2/qmax)
    out  = (sum_j p_q[j] v[j]) * dattn * dv,   dattn = (2/qmax) / Sigma

Unlike the v1 grid (step ``emax/(Sigma*qmax)``), the code grid does not
depend on the row maximum of ``e`` — only on the *integer* ``m``.  Two
consequences:

- hardware: the comparator thresholds are fixed power-of-two multiples of
  Sigma (pure shifts), no per-row ``emax`` divider in front of the
  quantizer;
- kernels: an online pass can emit final codes as keys stream by, because
  a change of the running ``m`` rescales previously accumulated integer
  contributions by an exact power of two.  This is what enables the fused
  single-pass ``int_attention_fused`` kernel.

The cost is up to one bit of code range (max code lands in [qmax/2, qmax]
instead of pinning qmax exactly).

Two oracles are provided for attention:

- :func:`int_attention_ref` — full-row semantics: ``m`` is the final row
  max.  This is what the XLA serving path computes, and what the kernels
  compute whenever one key block covers the row (``bk >= Sk``).
- :func:`int_attention_ref_streamed` — block-streamed semantics: keys are
  consumed in ``bk``-sized blocks and every block's codes are quantized
  against the *running* ``m``.  Bit-matches the Pallas kernels for any
  ``bk``.
- :func:`int_decode_attention_ref` — the decode oracle: one query position
  against a KV *ring cache* whose slot->position map is ``k_positions``
  (negative = unwritten).  ``bk=None`` gives full-row semantics (the XLA
  decode path); an integer ``bk`` streams ring blocks in slot order on the
  running grid, bit-matching ``kernels.int_decode_attention`` for any
  ``bk`` (the kernel's live-block skipping is bit-exact: a fully-masked
  block contributes e = 0 and cannot raise the running ``m``).
- :func:`int_paged_decode_attention_ref` — the PAGED decode oracle: each
  batch row gathers its own pages (via :func:`gather_pages`) into a
  position-contiguous key row and runs the ring oracle with per-sequence
  position and scales.  ``bk=None`` is the full-gather grid (the XLA
  serving fallback); ``bk = page_size`` streams pages in logical order,
  bit-matching ``kernels.int_paged_decode_attention``.

Attention logit scales (``sc``) accept per-row forms everywhere: a scalar
(per-tensor), a (sq,) per-query-row vector, or (h, sq) — the reference
semantics of the kernels' per-query-block activation scales (each bq-tile
of the fused kernel dequantizes on its own grid; rows of one tile share a
scale).  ``v_scale`` accepts a scalar or (h,) per-head-fold vector.
:func:`ragged_write_ref` is the loop oracle for the ragged paged-prefill
pool scatter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.softmax2 import exp2_shift, pow2, tree_sum


def qmatmul_ref(x_q, w_q, scale, bias=None):
    """int8 (M,K) @ int8 (N,K)^T * scale[n] + bias[n] -> f32 (M,N)."""
    acc = jnp.matmul(x_q.astype(jnp.int32), w_q.astype(jnp.int32).T)
    out = acc.astype(jnp.float32) * scale[None, :]
    if bias is not None:
        out = out + bias[None, :]
    return out


def _row_sc(sc, h, sq):
    """Broadcast an attention logit scale to (h, sq, 1).

    Accepts a scalar (per-tensor, the pre-PR-4 contract), a (sq,) per-query-
    row vector (per-block activation scales expanded to rows), or a full
    (h, sq) matrix (per-head-fold x per-row — what the dispatch layer builds
    when batch rows fold into the head axis).
    """
    sc = jnp.asarray(sc, jnp.float32)
    if sc.ndim == 0:
        return sc
    if sc.ndim == 1:
        return jnp.broadcast_to(sc[None, :, None], (h, sq, 1))
    return jnp.broadcast_to(sc[:, :, None], (h, sq, 1))


def _head_sc(s, h):
    """Broadcast a per-head-fold scale (scalar or (h,)) to (h, 1, 1)."""
    s = jnp.asarray(s, jnp.float32)
    if s.ndim == 0:
        return s
    return s.reshape(h, 1, 1)


def _attn_mask(sq, sk, sq_mod, causal, window):
    q_pos = (jnp.arange(sq) % sq_mod)[:, None]
    k_pos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    return mask


def int_attention_ref(q_q, k_q, v_q, sc, v_scale, *, attn_bits=7,
                      causal=True, window=None, sq_mod=None):
    """Full-row integer attention with base-2 softmax (paper semantics).

    Same shapes/contract as kernels.int_attention; ``sq_mod`` is the true
    query length when G GQA groups are stacked along Sq (q row r has
    position ``r % sq_mod``; defaults to Sq).  ``sc`` may be a scalar, a
    (sq,) per-query-row vector, or (h, sq) (per-block activation scales —
    each query row carries its own quantization grid); ``v_scale`` a scalar
    or (h,) per-head-fold vector.
    """
    h, sq, d = q_q.shape
    sk = k_q.shape[1]
    qmax = (1 << attn_bits) - 1
    acc = jnp.einsum("hqd,hkd->hqk", q_q.astype(jnp.int32),
                     k_q.astype(jnp.int32))
    x = acc.astype(jnp.float32) * _row_sc(sc, h, sq)
    mask = _attn_mask(sq, sk, sq_mod or sq, causal, window)
    x = jnp.maximum(jnp.where(mask, x, -1e30), -120.0)
    m = jnp.floor(jnp.max(x, axis=-1, keepdims=True))
    e = jnp.where(x <= -120.0, 0.0, exp2_shift(x - m))
    s = jnp.maximum(tree_sum(e), 1e-30)
    dattn = (2.0 / qmax) / s                      # power-of-two Sigma grid
    p_q = jnp.clip(jnp.round(e * (qmax / 2.0)), 0, qmax)
    pv = jnp.einsum("hqk,hkd->hqd", p_q.astype(jnp.int32),
                    v_q.astype(jnp.int32))
    return pv.astype(jnp.float32) * (dattn * _head_sc(v_scale, h))


def int_attention_ref_streamed(q_q, k_q, v_q, sc, v_scale, *, bk,
                               attn_bits=7, causal=True, window=None,
                               sq_mod=None):
    """Block-streamed oracle: quantize each key block at the running grid.

    Mirrors the Pallas kernels' online accumulation exactly: per key block
    the running ``m`` is updated first, the block's codes are emitted on the
    grid referenced to the *current* ``2^m``, and the integer PV partials
    are carried in f32 with an exact ``2^(m_old - m_new)`` rescale.
    ``sc``/``v_scale`` accept the same per-row / per-head-fold forms as
    :func:`int_attention_ref`.
    """
    h, sq, d = q_q.shape
    sk = k_q.shape[1]
    qmax = (1 << attn_bits) - 1
    pad = (-sk) % bk
    if pad:
        k_q = jnp.pad(k_q, ((0, 0), (0, pad), (0, 0)))
        v_q = jnp.pad(v_q, ((0, 0), (0, pad), (0, 0)))
    mask = _attn_mask(sq, sk, sq_mod or sq, causal, window)
    if pad:
        mask = jnp.pad(mask, ((0, 0), (0, pad)))  # padded keys invalid
    nk = (sk + pad) // bk

    acc_all = jnp.einsum("hqd,hkd->hqk", q_q.astype(jnp.int32),
                         k_q.astype(jnp.int32))
    x_all = acc_all.astype(jnp.float32) * _row_sc(sc, h, sq)
    x_all = jnp.maximum(jnp.where(mask[None], x_all, -1e30), -120.0)

    def block(carry, t):
        m_old, s_run, pv = carry
        x = jax.lax.dynamic_slice_in_dim(x_all, t * bk, bk, axis=2)
        v = jax.lax.dynamic_slice_in_dim(v_q, t * bk, bk, axis=1)
        m_new = jnp.maximum(m_old, jnp.floor(jnp.max(x, -1, keepdims=True)))
        e = jnp.where(x <= -120.0, 0.0, exp2_shift(x - m_new))
        p_q = jnp.clip(jnp.round(e * (qmax / 2.0)), 0, qmax)
        r = pow2(m_old - m_new)                   # exact: both integers
        blk = jnp.einsum("hqk,hkd->hqd", p_q.astype(jnp.int32),
                         v.astype(jnp.int32))
        return (m_new, s_run * r + tree_sum(e),
                pv * r + blk.astype(jnp.float32)), None

    init = (jnp.full((h, sq, 1), -1e30), jnp.zeros((h, sq, 1)),
            jnp.zeros((h, sq, d)))
    (m, s, pv), _ = jax.lax.scan(block, init, jnp.arange(nk))
    dattn = (2.0 / qmax) / jnp.maximum(s, 1e-30)
    return pv * (dattn * _head_sc(v_scale, h))


def int_decode_attention_ref(q_q, k_q, v_q, sc, v_scale, k_positions, pos, *,
                             attn_bits=7, causal=True, window=None, bk=None,
                             k_factor=None, v_factor=None):
    """Decode-step oracle: (H, G, D) query row vs an (H, span, D) ring cache.

    ``k_positions`` (span,) maps ring slot -> absolute position (negative =
    unwritten, masked); all G GQA rows share query position ``pos``.
    ``bk=None``: full-row grid (== the XLA serving path).  Integer ``bk``:
    ring blocks stream in slot order, each quantized at the running grid —
    bit-matches the Pallas decode kernel.  ``sc``/``v_scale`` may be scalars
    or (h,) per-head-fold vectors (batch rows folded into the head axis
    quantize their queries per sequence).

    ``k_factor`` / ``v_factor`` — (span,) per-key dequantization factors,
    uniform inside each bk-block — are the reference semantics of the paged
    kernel's per-PHYSICAL-page scale resolution (prefix-sharing): the logit
    scale of key j becomes ``sc * k_factor[j]`` and each streamed block's
    integer PV contribution is scaled by ``v_scale * v_factor[block]``
    before accumulation (the epilogue then applies only ``dattn``),
    mirroring the kernel op for op.  In full-row mode (``bk=None``) the
    per-key v factor is applied to the prob codes before the (then float)
    PV contraction — reference semantics only, used self-consistently.
    """
    h, g, d = q_q.shape
    span = k_q.shape[1]
    qmax = (1 << attn_bits) - 1
    mask = k_positions >= 0
    if causal:
        mask &= k_positions <= pos
    if window is not None:
        mask &= k_positions > pos - window
    acc = jnp.einsum("hgd,hkd->hgk", q_q.astype(jnp.int32),
                     k_q.astype(jnp.int32))
    if k_factor is not None:
        x = acc.astype(jnp.float32) * (_head_sc(sc, h)
                                       * k_factor[None, None, :])
    else:
        x = acc.astype(jnp.float32) * _head_sc(sc, h)
    x = jnp.maximum(jnp.where(mask[None, None, :], x, -1e30), -120.0)

    if bk is None:                                # full-row grid
        m = jnp.floor(jnp.max(x, axis=-1, keepdims=True))
        e = jnp.where(x <= -120.0, 0.0, exp2_shift(x - m))
        s = jnp.maximum(tree_sum(e), 1e-30)
        p_q = jnp.clip(jnp.round(e * (qmax / 2.0)), 0, qmax)
        if v_factor is not None:
            pv = jnp.einsum("hgk,hkd->hgd", p_q * v_factor[None, None, :],
                            v_q.astype(jnp.float32))
            return pv * ((2.0 / qmax) / s * _head_sc(v_scale, h))
        pv = jnp.einsum("hgk,hkd->hgd", p_q.astype(jnp.int32),
                        v_q.astype(jnp.int32))
        return pv.astype(jnp.float32) * ((2.0 / qmax) / s
                                         * _head_sc(v_scale, h))

    pad = (-span) % bk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)), constant_values=-120.0)
        v_q = jnp.pad(v_q, ((0, 0), (0, pad), (0, 0)))
    nk = (span + pad) // bk
    if v_factor is not None:
        vf_blk = jnp.pad(v_factor, (0, pad),
                         constant_values=1.0).reshape(nk, bk)[:, 0]

    def block(carry, t):
        m_old, s_run, pv = carry
        xb = jax.lax.dynamic_slice_in_dim(x, t * bk, bk, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(v_q, t * bk, bk, axis=1)
        m_new = jnp.maximum(m_old, jnp.floor(jnp.max(xb, -1, keepdims=True)))
        e = jnp.where(xb <= -120.0, 0.0, exp2_shift(xb - m_new))
        p_q = jnp.clip(jnp.round(e * (qmax / 2.0)), 0, qmax)
        r = pow2(m_old - m_new)                   # exact: both integers
        blk = jnp.einsum("hgk,hkd->hgd", p_q.astype(jnp.int32),
                         vb.astype(jnp.int32)).astype(jnp.float32)
        if v_factor is not None:                  # per-block dv, kernel-wise
            blk = blk * (_head_sc(v_scale, h) * vf_blk[t])
        return (m_new, s_run * r + tree_sum(e),
                pv * r + blk), None

    init = (jnp.full((h, g, 1), -1e30), jnp.zeros((h, g, 1)),
            jnp.zeros((h, g, d)))
    (_, s, pv), _ = jax.lax.scan(block, init, jnp.arange(nk))
    if v_factor is not None:
        return pv * ((2.0 / qmax) / jnp.maximum(s, 1e-30))
    return pv * ((2.0 / qmax) / jnp.maximum(s, 1e-30) * _head_sc(v_scale, h))


def gather_pages(pages, page_table):
    """Gather one row per sequence from a paged pool, in position order.

    pages: (num_pages, H, page_size, d) as stored (int8 codes, uint8
    nibbles, or floats — the dtype passes through untouched);
    page_table: (B, max_pages) int32, negative = unallocated (clamped —
    callers mask those slots via positions).  Returns
    (B, H, max_pages * page_size, d): logical position p of row b lands at
    key index p, so ``k_positions`` for the gathered row is just arange.
    """
    num_phys = pages.shape[0]
    g = pages[jnp.clip(page_table, 0, num_phys - 1)]   # (B, P, H, ps, d)
    b, p, h, ps, d = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(b, h, p * ps, d)


def int_paged_decode_attention_ref(q_q, k_pages, v_pages, sc, v_scale,
                                   page_table, pos, *, attn_bits=7,
                                   window=None, bk=None, k_page_scale=None,
                                   v_page_scale=None):
    """Paged decode oracle: (B, Hkv, G, D) queries vs shared page pools.

    Shapes/contract as ``kernels.int_paged_decode_attention``; uint8 pools
    are treated as nibble-packed and unpacked to int8 codes (never float).
    Each row's pages gather into a position-contiguous key row — slots of
    unallocated pages are marked unwritten — then the ring oracle runs per
    row with that row's ``pos``/``sc``/``v_scale``.  ``bk=None``: full-row
    grid (the XLA fallback).  ``bk``: streamed grid; ``bk = page_size``
    bit-matches the Pallas paged kernel (leading out-of-window pages are
    fully masked, so streaming from logical page 0 is exact).

    ``k_page_scale`` / ``v_page_scale``: (num_pages,) per-PHYSICAL-page
    dequantization steps (the prefix-sharing resolution — shared pages stay
    on the grid their owner prefilled them with).  They expand to per-key
    factors through each row's page table and flow into the ring oracle's
    ``k_factor``/``v_factor``, bit-matching the kernel at ``bk=page_size``.
    """
    b = q_q.shape[0]
    num_phys = k_pages.shape[0]
    k = gather_pages(k_pages, page_table)
    v = gather_pages(v_pages, page_table)
    if k.dtype == jnp.uint8:                 # nibble-packed pools
        from repro.core.quant import unpack_int4
        k, v = unpack_int4(k), unpack_int4(v)
    ps = k_pages.shape[2]
    total = page_table.shape[1] * ps
    alloc = jnp.repeat(page_table >= 0, ps, axis=1)          # (B, total)
    kpos = jnp.where(alloc, jnp.arange(total)[None, :], -1)
    sc = jnp.broadcast_to(jnp.asarray(sc, jnp.float32).reshape(-1), (b,))
    vs = jnp.broadcast_to(jnp.asarray(v_scale, jnp.float32).reshape(-1),
                          (b,))
    pos = jnp.asarray(pos, jnp.int32).reshape(b)

    if k_page_scale is not None:
        phys = jnp.clip(page_table, 0, num_phys - 1)
        kfac = jnp.repeat(
            jnp.asarray(k_page_scale, jnp.float32)[phys], ps, axis=1)
        vfac = jnp.repeat(
            jnp.asarray(v_page_scale, jnp.float32)[phys], ps, axis=1)

        def one_ps(qb, kb, vb, scb, vsb, kpb, pb, kfb, vfb):
            return int_decode_attention_ref(
                qb, kb, vb, scb, vsb, kpb, pb, attn_bits=attn_bits,
                causal=True, window=window, bk=bk, k_factor=kfb,
                v_factor=vfb)

        return jax.vmap(one_ps)(q_q, k, v, sc, vs, kpos, pos, kfac, vfac)

    def one(qb, kb, vb, scb, vsb, kpb, pb):
        return int_decode_attention_ref(qb, kb, vb, scb, vsb, kpb, pb,
                                        attn_bits=attn_bits, causal=True,
                                        window=window, bk=bk)

    return jax.vmap(one)(q_q, k, v, sc, vs, kpos, pos)


def ragged_write_ref(pages, codes, lengths, page_table):
    """Loop oracle for the ragged paged-prefill scatter (models.lm).

    pages: (num_pages + 1, H, page_size, d) pool as stored (last page =
    TRASH); codes: (B, H, S, d) already-quantized rows; lengths (B,);
    page_table (B, max_pages) physical ids (negative = unallocated).  Row
    b's position p < lengths[b] lands at
    ``pages[page_table[b, p // ps], :, p % ps]``; pad and unallocated
    positions land in the trash page.  Only non-trash pages are specified
    (concurrent trash writes race, and the trash page is never read); the
    oracle is exact when live page tables are disjoint — the allocator
    invariant.
    """
    import numpy as np
    out = np.array(pages)
    b, _, s, _ = codes.shape
    ps = out.shape[2]
    trash = out.shape[0] - 1
    pt = np.asarray(page_table)
    codes_np = np.asarray(codes)
    lens = np.asarray(lengths)
    for i in range(b):
        for p in range(s):
            phys = pt[i, min(p // ps, pt.shape[1] - 1)]
            if p >= lens[i] or phys < 0:
                phys = trash
            out[phys, :, p % ps] = codes_np[i, :, p]
    return out


def pq_layernorm_ref(x, gamma, beta, delta, *, bits=8, eps=1e-6,
                     rms_only=False):
    xf = x.astype(jnp.float32)
    if rms_only:
        nrm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    else:
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
        nrm = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = nrm * gamma[None, :]
    if beta is not None:
        y = y + beta[None, :]
    qmin, qmax = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    return jnp.clip(jnp.round(y / delta), qmin, qmax).astype(jnp.int8)
