"""Attention core: GQA / local-window / cross / decode, in three modes.

The paper's integerized attention computes, per query row (Fig. 2-4):

    int QK^T  ->  x = s*dq*dk*log2(e)*acc  ->  e = (1+r)*2^floor(x - m)
    Sigma = sum_j e  ->  p_q = quantize(e / Sigma)  ->  int PV  ->  dequant

The systolic array holds the *full key row* while Sigma propagates to the
row end; we mirror that with a full-row formulation chunked over queries
(scan), which is also what the serving KV-cache path wants.  Numerical
stability uses ``m = floor(row max)`` — an integer, so the base-2 shift
approximation commutes with it *exactly* (2^(x-m) = 2^x >> m).

Modes:
  float — exact softmax, fp matmuls (baseline / Q-ViT-style path)
  fake  — QAT: fake-quantized q/k/v and probs, fp matmuls (training graph)
  int   — integer matmuls + base-2 softmax + quantized probs (serving graph)

The int path runs as XLA einsums by default; with the "pallas" kernel
backend active (see :mod:`repro.kernels.dispatch`) supported shapes route
to the fused single-pass Pallas kernel instead.  Activation grids are
PER SEQUENCE on both backends — k/v per batch row, q per (batch row,
query chunk) — and the kernel path matches the XLA chunked recalibration
in ONE kernel call: dispatch threads a per-query-block scale matrix
through the kernel's scalar-prefetch stream, so there is no chunked outer
loop on the kernel path and no granularity gap at Sq > q_chunk.  One
carve-out: the NARROW-window chunked path (window set, Sk > 2*window)
slices keys per chunk below and quantizes each SLICE, while the kernel
quantizes the full key row per sequence — backends there agree only to
~one prob code (see test_windowed_dispatch_straddling_blocks_close), not
bitwise.  Per-row grids are also what makes a batched ragged prefill
bit-identical per row to running each prompt alone (the admission-prefill
contract of :mod:`repro.launch.engine`).

Serving KV-cache contract (in-place ring reads): decode callers hand k/v
over as the cache stores them — int8-coded ``QTensor``s, or int4
nibble-packed ``QTensor``s (uint8 codes, ``bits == 4``) — together with
``k_positions``, the (span,) ring slot->absolute-position map (negative =
unwritten slot).  The Pallas decode kernel consumes that storage format
directly; only the XLA fallback unpacks nibbles (to int8 codes — never to
float) before its einsums.

Paged KV caches (continuous batching) use :func:`paged_attention` instead:
shared ``(num_pages, Hkv, page_size, D[/2])`` pools, a per-sequence
``(B, max_pages)`` page table, per-sequence positions and per-sequence
KV scales — or, with ``k_page_scale``/``v_page_scale`` pools (the
prefix-sharing layout), per-PHYSICAL-page scales so shared pages keep
their owner's grid.  The Pallas paged kernel reads the pools in place;
the XLA fallback gathers each sequence's pages as *codes* and runs the
full-row oracle grid per row (int mode), or gathers stored floats (float
mode).  :func:`prefix_prefill_attention` is the tail-chunk prefill over a
cached prefix (chunked prefill / prefix sharing): fresh tail queries
attend already-cached prefix codes plus the fresh tail, XLA-only so both
backends serve identical tokens.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.core.api import QuantConfig
from repro.core.quant import ACC_DTYPE
from repro.core.softmax2 import LOG2E, exp2_shift, tree_sum
from repro.models.scan_util import scan as _scan

NEG_BIG = -1e9


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    causal: bool = True
    window: Optional[int] = None      # local attention: keys in (i-window, i]
    softmax_scale: Optional[float] = None
    q_chunk: int = 128


def _split_heads(x, n_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, head_dim).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _mask(q_pos, k_pos, spec: AttnSpec):
    """(bq, Sk) boolean validity mask. Negative k_pos = unwritten ring slot."""
    m = (k_pos >= 0)[None, :]
    if spec.causal:
        m = m & (k_pos[None, :] <= q_pos[:, None])
    if spec.window is not None:
        m = m & (k_pos[None, :] > (q_pos[:, None] - spec.window))
    return m


def _as_q(x, bits):
    """View a float array or QTensor as (codes, scale) for the int path."""
    if isinstance(x, quant.QTensor):
        return x
    return quant.quantize_tensor(x, bits)


def _as_q_rows(x, bits):
    """Per-batch-row quantization (axis 0 keeps its own grid).

    Serving isolation: every sequence of a batch calibrates its own
    activation scale, so batched (ragged) prefill is bit-identical per row
    to the solo run and one hot tenant cannot coarsen another's codes.
    QTensors (KV-cache codes) pass through on their stored grid.
    """
    if isinstance(x, quant.QTensor):
        return x
    scale = quant.absmax_scale(x, bits, axis=tuple(range(1, x.ndim)))
    return quant.quantize_tensor(x, bits, scale=scale)


def _sc5(s):
    """Broadcast a per-row (or scalar) scale over (B, Hkv, G, q, k) axes."""
    s = jnp.asarray(s)
    return s if s.ndim == 0 else s.reshape(s.shape[0], 1, 1, 1, 1)


def _as_f(x, dtype):
    return x.dequant().astype(dtype) if isinstance(x, quant.QTensor) else x


def _row_attention(q, k, v, q_pos, k_pos, spec: AttnSpec,
                   cfg: Optional[QuantConfig]):
    """Full-key-row attention for one query chunk.

    q: (B, Hkv, G, bq, D); k, v: (B, Hkv, Sk, D).  Returns (B, Hkv, G, bq, D).
    """
    scale = spec.softmax_scale or (1.0 / q.shape[-1] ** 0.5)
    mode = cfg.mode if cfg is not None else "float"
    mask = _mask(q_pos, k_pos, spec)                       # (bq, Sk)

    if mode == "int":
        # Fresh float operands calibrate per batch row; cache-fed calls
        # (QTensor k — the ring-decode XLA fallback) keep their per-tensor
        # query grid, matching the Pallas ring-decode kernel bit for bit
        # (the whole batch shares one ring cache and scale there).
        fresh = not isinstance(k, quant.QTensor)
        qq = _as_q_rows(q, cfg.a_bits) if fresh else _as_q(q, cfg.a_bits)
        kq = _as_q_rows(k, cfg.a_bits)
        vq = _as_q_rows(v, cfg.a_bits)
        acc = jnp.einsum("bhgqd,bhkd->bhgqk", qq.q, kq.q,
                         preferred_element_type=ACC_DTYPE)
        # The query factor is pinned: the kernel path forms the same
        # product, and XLA would otherwise reorder the chain per program.
        x = acc.astype(jnp.float32) * (
            quant.pin(scale * LOG2E * _sc5(qq.scale)) * _sc5(kq.scale))
        x = jnp.where(mask, x, NEG_BIG)
        x = jnp.maximum(x, -120.0)                          # keep 2^x in range
        m = jnp.floor(jnp.max(x, axis=-1, keepdims=True))   # integer shift
        e = exp2_shift(x - m) if cfg.softmax == "base2" \
            else jnp.exp2(x - m)
        e = jnp.where(mask & (x > -120.0), e, 0.0)
        sigma = jnp.maximum(tree_sum(e), 1e-30)
        # Sigma-scaled quantizer (paper §IV-B) on the power-of-two grid:
        # code step 2/qmax relative to 2^m (m integer), so thresholds are
        # pure shifts of Sigma and the same codes can be emitted online by
        # the streaming Pallas kernel (see kernels/ref.py).
        qmax = (1 << cfg.attn_bits) - 1
        dattn = (2.0 / qmax) / sigma                        # prob-domain step
        # Unsigned codes; int32 container in the XLA path (the Pallas
        # kernels carry them in int8 for the MXU — 8-bit grids biased by
        # -128 with an exact un-bias in the PV epilogue).
        p_q = jnp.clip(jnp.round(e * (qmax / 2.0)), 0, qmax).astype(
            ACC_DTYPE)
        pv = jnp.einsum("bhgqk,bhkd->bhgqd", p_q, vq.q,
                        preferred_element_type=ACC_DTYPE)
        out = pv.astype(jnp.float32) * (dattn * _sc5(vq.scale))
        return out.astype(q.dtype)

    k = _as_f(k, q.dtype)
    v = _as_f(v, q.dtype)
    if mode == "fake":
        q = quant.fake_quant(q, quant.absmax_scale(q, cfg.a_bits), cfg.a_bits)
        k = quant.fake_quant(k, quant.absmax_scale(k, cfg.a_bits), cfg.a_bits)
        v = quant.fake_quant(v, quant.absmax_scale(v, cfg.a_bits), cfg.a_bits)

    x = jnp.einsum("bhgqd,bhkd->bhgqk", q, k).astype(jnp.float32) * scale
    x = jnp.where(mask, x, NEG_BIG)
    if mode == "fake":
        # QAT trains through the same pipeline the int path serves: shift
        # exp (or exact 2^x for the ablation), floor-max shift, and the
        # power-of-two Sigma-scaled prob grid — so the fake-quantized probs
        # land on exactly the codes mode="int" will emit.
        xl = jnp.maximum(x * LOG2E, -120.0)
        m = jnp.floor(jnp.max(xl, axis=-1, keepdims=True))
        e = exp2_shift(xl - m) if cfg.softmax == "base2" \
            else jnp.exp2(xl - m)
        e = jnp.where(mask & (xl > -120.0), e, 0.0)
        sigma = jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
        p = e / sigma
        qmaxp = (1 << cfg.attn_bits) - 1
        dp = (2.0 / qmaxp) / sigma                  # serving-grid step
        p = quant.fake_quant(p, dp, cfg.attn_bits, True)
    else:
        p = jax.nn.softmax(x, axis=-1)
    p = p.astype(q.dtype)
    return jnp.einsum("bhgqk,bhkd->bhgqd", p, v)


def paged_attention(q, k_pages, v_pages, k_scale, v_scale, page_table, pos,
                    spec: AttnSpec, cfg: Optional[QuantConfig] = None, *,
                    k_page_scale=None, v_page_scale=None):
    """One decode step of multi-head attention over a PAGED KV cache.

    q: (B, Hq, 1, D) float; k_pages, v_pages: shared page pools as stored —
    (num_pages, Hkv, page_size, D) int8 codes / floats, or (..., D//2)
    uint8 nibbles (int4).  ``page_table``: (B, max_pages) int32, negative =
    unallocated; ``pos``: (B,) int32 per-sequence positions (negative =
    inactive row, output unspecified); ``k_scale``/``v_scale``: (B,)
    per-sequence dequantization steps (ignored for float pools).  Returns
    (B, Hq, 1, D).

    ``k_page_scale``/``v_page_scale`` — (num_pages,) per-PHYSICAL-page
    dequantization steps, the prefix-sharing cache layout — switch both
    backends to per-page scale resolution: every page dequantizes on the
    grid it was PREFILLED with (a shared prefix page on its owner's scale),
    and ``k_scale``/``v_scale`` are ignored.

    int mode dispatches to the Pallas paged kernel when supported; the XLA
    fallback gathers pages per sequence as codes (nibbles unpack to int8 —
    never to float) and evaluates the same page-streamed running-m grid
    (``bk = page_size``), each row on its own quantization scales — so the
    two backends emit bit-identical codes and toggling the backend never
    changes served outputs.
    """
    b, hq, _, d = q.shape
    hkv = k_pages.shape[1]
    g = hq // hkv
    mode = cfg.mode if cfg is not None else "float"
    if mode == "int":
        from repro.kernels import ref as kref
        from repro.kernels.dispatch import (maybe_paged_attention,
                                            paged_read_grid)
        out = maybe_paged_attention(q, k_pages, v_pages, k_scale, v_scale,
                                    spec, cfg, page_table=page_table,
                                    pos=pos, k_page_scale=k_page_scale,
                                    v_page_scale=v_page_scale)
        if out is not None:                    # Pallas kernel path
            return out
        # Same grid derivation as the kernel path (paged_read_grid), so
        # the backends stay bit-identical by construction.
        qq, sc, vs = paged_read_grid(q, spec, cfg, k_scale, v_scale,
                                     k_page_scale is not None)
        out = kref.int_paged_decode_attention_ref(
            qq.reshape(b, hkv, g, d), k_pages, v_pages, sc, vs,
            page_table, pos, attn_bits=cfg.attn_bits, window=spec.window,
            bk=k_pages.shape[2], k_page_scale=k_page_scale,
            v_page_scale=v_page_scale)
        return out.reshape(b, hq, 1, d).astype(q.dtype)

    # float pools: gather (stored floats ARE the storage format) + softmax.
    from repro.kernels.ref import gather_pages
    k = gather_pages(k_pages, page_table)              # (B, Hkv, total, D)
    v = gather_pages(v_pages, page_table)
    ps = k_pages.shape[2]
    total = page_table.shape[1] * ps
    kpos = jnp.where(jnp.repeat(page_table >= 0, ps, axis=1),
                     jnp.arange(total)[None, :], -1)   # (B, total)
    valid = (kpos >= 0) & (kpos <= pos[:, None])
    if spec.window is not None:
        valid &= kpos > (pos[:, None] - spec.window)
    scale = spec.softmax_scale or (1.0 / d ** 0.5)
    x = jnp.einsum("bhgd,bhkd->bhgk", q.reshape(b, hkv, g, d),
                   k.astype(q.dtype)).astype(jnp.float32) * scale
    x = jnp.where(valid[:, None, None, :], x, NEG_BIG)
    p = jax.nn.softmax(x, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v.astype(q.dtype))
    return out.reshape(b, hq, 1, d)


def prefix_prefill_attention(q, k, v, k_pre, v_pre, pre_k_scale, pre_v_scale,
                             prefix_len: int, lengths, spec: AttnSpec,
                             cfg: Optional[QuantConfig] = None):
    """Tail-chunk prefill attention over a cached (possibly shared) prefix.

    The serving path of chunked prefill: a request admitted onto shared
    prefix pages prefills only its divergent tail, and the tail attends the
    prefix THROUGH ITS CACHED CODES — exactly as decode will later — so the
    computation is a pure function of (prefix cache, tail tokens).  Because
    a prefix chunk's own prefill is in turn a pure function of the prefix
    tokens, a sharer's tail here is bit-identical to the same request
    prefilling a private prefix first (the engine's sharing parity
    contract).  Deliberately XLA-only: both kernel backends run this same
    graph, so toggling the backend cannot change served tokens.

    q: (B, Hq, St, D) fresh tail queries at absolute positions
    ``prefix_len + i``; k, v: (B, Hkv, St, D) fresh tail keys/values
    (right-padded, ``lengths`` (B,) true tail lengths).  k_pre, v_pre:
    (B, Hkv, Kp, D) the prefix KV gathered from the page pools — int8
    codes in int mode (int4 nibbles unpacked by the caller, never to
    float), stored floats otherwise; Kp covers whole pages and positions
    ``>= prefix_len`` (a partially filled CoW boundary page) are masked.
    pre_k_scale / pre_v_scale: (B, Kp // page_size) per-page dequant steps
    (int mode) — the PREFIX OWNER's grids.  Returns (B, Hq, St, D).
    """
    b, hq, st, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    kp = k_pre.shape[2]
    mode = cfg.mode if cfg is not None else "float"
    scale = spec.softmax_scale or (1.0 / d ** 0.5)
    lens = jnp.full((b,), st, jnp.int32) if lengths is None \
        else jnp.asarray(lengths, jnp.int32)
    pre_pos = jnp.arange(kp)
    tail_pos = prefix_len + jnp.arange(st)
    qg = q.reshape(b, hkv, g, st, d)

    def masks(q_pos, bq):
        m_pre = (pre_pos[None, :] < prefix_len) & \
                (pre_pos[None, :] <= q_pos[:, None])
        m_tail = (tail_pos[None, None, :] <= q_pos[None, :, None]) & \
                 (tail_pos[None, None, :] <
                  (prefix_len + lens)[:, None, None])
        if spec.window is not None:
            m_pre = m_pre & (pre_pos[None, :] > q_pos[:, None] - spec.window)
            m_tail = m_tail & (tail_pos[None, None, :] >
                               q_pos[None, :, None] - spec.window)
        m_pre = jnp.broadcast_to(m_pre[None, None, None],
                                 (b, hkv, g, bq, kp))
        m_tail = jnp.broadcast_to(m_tail[:, None, None],
                                  (b, hkv, g, bq, st))
        return jnp.concatenate([m_pre, m_tail], axis=-1)

    if mode == "int":
        npg = pre_k_scale.shape[1]
        ps = kp // npg
        kq = _as_q_rows(k, cfg.a_bits)
        vq = _as_q_rows(v, cfg.a_bits)
        qmaxp = (1 << cfg.attn_bits) - 1
        kfac = jnp.repeat(pre_k_scale.astype(jnp.float32), ps, axis=1)

        def one_chunk(ci, qc):
            bq = qc.shape[3]
            q_pos = prefix_len + ci * bq + jnp.arange(bq)
            mask = masks(q_pos, bq)
            qq = _as_q_rows(qc, cfg.a_bits)
            base = scale * LOG2E * _sc5(qq.scale)
            acc_pre = jnp.einsum("bhgqd,bhkd->bhgqk", qq.q, k_pre,
                                 preferred_element_type=ACC_DTYPE)
            x_pre = acc_pre.astype(jnp.float32) * \
                (base * kfac[:, None, None, None, :])
            acc_t = jnp.einsum("bhgqd,bhkd->bhgqk", qq.q, kq.q,
                               preferred_element_type=ACC_DTYPE)
            x_t = acc_t.astype(jnp.float32) * (base * _sc5(kq.scale))
            x = jnp.concatenate([x_pre, x_t], axis=-1)
            x = jnp.maximum(jnp.where(mask, x, NEG_BIG), -120.0)
            m = jnp.floor(jnp.max(x, axis=-1, keepdims=True))
            e = exp2_shift(x - m) if cfg.softmax == "base2" \
                else jnp.exp2(x - m)
            e = jnp.where(mask & (x > -120.0), e, 0.0)
            sigma = jnp.maximum(tree_sum(e), 1e-30)
            dattn = (2.0 / qmaxp) / sigma
            p_q = jnp.clip(jnp.round(e * (qmaxp / 2.0)), 0,
                           qmaxp).astype(ACC_DTYPE)
            # Prefix PV: integer contraction PER PAGE, each page's int32
            # partial scaled by ITS OWN stored dv before the f32 sum —
            # the same per-page resolution the paged decode kernel applies.
            pp = p_q[..., :kp].reshape(b, hkv, g, bq, npg, ps)
            vpre = v_pre.astype(ACC_DTYPE).reshape(b, hkv, npg, ps, d)
            pv_pre = jnp.einsum("bhgqnk,bhnkd->bhgqnd", pp, vpre,
                                preferred_element_type=ACC_DTYPE)
            pv_pre = jnp.sum(
                pv_pre.astype(jnp.float32)
                * pre_v_scale[:, None, None, None, :, None], axis=4)
            pv_t = jnp.einsum("bhgqk,bhkd->bhgqd", p_q[..., kp:], vq.q,
                              preferred_element_type=ACC_DTYPE)
            pv = pv_pre + pv_t.astype(jnp.float32) * _sc5(vq.scale)
            return (pv * dattn).astype(q.dtype)
    else:
        kpre_f = k_pre.astype(q.dtype)
        vpre_f = v_pre.astype(q.dtype)

        def one_chunk(ci, qc):
            bq = qc.shape[3]
            q_pos = prefix_len + ci * bq + jnp.arange(bq)
            mask = masks(q_pos, bq)
            x = jnp.concatenate(
                [jnp.einsum("bhgqd,bhkd->bhgqk", qc, kpre_f),
                 jnp.einsum("bhgqd,bhkd->bhgqk", qc, k.astype(q.dtype))],
                axis=-1).astype(jnp.float32) * scale
            x = jnp.where(mask, x, NEG_BIG)
            p = jax.nn.softmax(x, axis=-1).astype(q.dtype)
            vcat = jnp.concatenate([vpre_f, v.astype(q.dtype)], axis=2)
            return jnp.einsum("bhgqk,bhkd->bhgqd", p, vcat)

    from repro.kernels.dispatch import chunk_len
    bq = chunk_len(st, spec.q_chunk)
    n_chunks = st // bq
    if n_chunks == 1:
        out = one_chunk(0, qg)
        return out.reshape(b, hq, st, d)
    qs = jnp.moveaxis(qg.reshape(b, hkv, g, n_chunks, bq, d), 3, 0)

    def body(_, args):
        ci, qc = args
        return None, one_chunk(ci, qc)

    _, outs = _scan(body, None, (jnp.arange(n_chunks), qs))
    out = jnp.moveaxis(outs, 0, 3)
    return out.reshape(b, hq, st, d)


def attention(q, k, v, spec: AttnSpec, cfg: Optional[QuantConfig] = None, *,
              q_offset=0, k_offset=0, k_positions=None):
    """Multi-head attention with GQA, chunked over queries.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D) float arrays or QTensors
    (int8 — or int4 nibble-packed — KV cache flows in as stored, without a
    dequantized copy); Hq % Hkv == 0.  ``q_offset`` gives absolute query
    positions (decode: cache length); ``k_positions`` (Sk,) overrides key
    positions for ring caches (negative entries mark unwritten slots and
    are masked).  Returns (B, Hq, Sq, D).
    """
    if cfg is not None and cfg.mode == "int":
        from repro.kernels.dispatch import maybe_attention
        out = maybe_attention(q, k, v, spec, cfg, q_offset=q_offset,
                              k_offset=k_offset, k_positions=k_positions)
        if out is not None:                    # Pallas kernel path
            return out
    # XLA fallback: nibble-packed cache QTensors unpack to int8 codes here
    # (the Pallas decode kernel above reads the packed bytes in place).
    if isinstance(k, quant.QTensor):
        k = k.unpacked()
    if isinstance(v, quant.QTensor):
        v = v.unpacked()
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    sk = k.shape[2]
    qg = q.reshape(b, hkv, g, sq, d)
    k_pos = k_positions if k_positions is not None \
        else k_offset + jnp.arange(sk)

    window = spec.window
    if sq <= spec.q_chunk:
        q_pos = q_offset + jnp.arange(sq)
        out = _row_attention(qg, k, v, q_pos, k_pos, spec, cfg)
        return out.reshape(b, hq, sq, d)

    # Largest chunk <= q_chunk that divides sq (shapes are static).  The
    # ONE definition of this policy lives in dispatch.chunk_len: the
    # kernel path's per-block q grids must match this chunking exactly.
    from repro.kernels.dispatch import chunk_len
    bq = chunk_len(sq, spec.q_chunk)
    spec = dataclasses.replace(spec, q_chunk=bq)
    n_chunks = sq // spec.q_chunk
    qs = qg.reshape(b, hkv, g, n_chunks, spec.q_chunk, d)
    qs = jnp.moveaxis(qs, 3, 0)                             # (n, B, Hkv, G, bq, D)

    if window is not None and sk > 2 * window:
        # Local attention: slice just the (bq + window) keys that can matter.
        span = spec.q_chunk + window

        def chunk_fn(ci, qc):
            start = jnp.maximum(ci * spec.q_chunk + spec.q_chunk - span, 0)
            start = jnp.minimum(start, sk - span)
            ks = jax.lax.dynamic_slice_in_dim(k, start, span, axis=2)
            vs = jax.lax.dynamic_slice_in_dim(v, start, span, axis=2)
            q_pos = q_offset + ci * spec.q_chunk + jnp.arange(spec.q_chunk)
            kp = k_offset + start + jnp.arange(span)
            return _row_attention(qc, ks, vs, q_pos, kp, spec, cfg)
    else:
        def chunk_fn(ci, qc):
            q_pos = q_offset + ci * spec.q_chunk + jnp.arange(spec.q_chunk)
            return _row_attention(qc, k, v, q_pos, k_pos, spec, cfg)

    def body(_, args):
        ci, qc = args
        return None, chunk_fn(ci, qc)

    _, outs = _scan(body, None, (jnp.arange(n_chunks), qs))
    out = jnp.moveaxis(outs, 0, 3)                          # (B,Hkv,G,n,bq,D)
    return out.reshape(b, hq, sq, d)
