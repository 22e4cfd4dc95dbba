"""Pallas TPU kernels: integer attention with embedded base-2 softmax.

Paper mapping (Fig. 3-4): the systolic array computes a full integer QK^T
row while the scan chain accumulates Sigma = sum_j exp(...); the quantizer
(thresholds scaled by Sigma) then emits low-bit probabilities that feed the
integer PV matmul.

Probability grid (v2, see kernels/ref.py): codes are quantized on the
power-of-two Sigma-scaled grid — ``p_q = round(e * qmax / 2)`` with
``e = (1+r) * 2^(x - m)`` and ``m = floor(running max)``.  Because the grid
references ``2^m`` (an integer power of two) rather than the row's ``emax``,
the codes for a key block depend only on the *running* statistics at the
time the block streams by: when a later block raises ``m`` by ``dm``, every
previously accumulated integer contribution rescales by exactly ``2^-dm``.
The cross-block PV carry lives in an f32 scratch accumulator (f32 represents
ints < 2^24 exactly and power-of-two rescales only touch the exponent), so
the rescale chain is exact; each block's PV contraction itself runs on the
MXU in int8 x int8 -> int32.

Three kernels share that quantizer:

- :func:`int_attention` — the original TWO-PASS design: a stats pass
  computes Sigma (one QK^T sweep), then a PV pass recomputes QK^T per tile,
  quantizes, and accumulates integer PV.  3*H*Sq*Sk*D MXU MACs, K read
  twice per query block.  Kept as the measured baseline.
- :func:`int_attention_fused` — SINGLE-PASS online kernel (the prefill
  serving path): batch*head and query blocks span the grid, K/V tiles
  stream through VMEM once while running (m, Sigma) and the PV carry
  advance together.  Key tiles are visited through a STATIC live-block map
  (scalar-prefetch index map): causal upper-triangle, out-of-window and
  padded key tiles are never DMA'd at all, so local attention streams only
  the ~(bq + window) live keys per query block instead of all Sk.  The
  logit scale ``sc`` may be a (H, nq) PER-QUERY-BLOCK matrix riding the
  same scalar-prefetch stream: each bq-tile dequantizes with its own
  activation grid (per-sequence, per-XLA-chunk — see kernels/dispatch.py),
  which is what makes batched ragged prefill bit-identical per row to solo
  runs.
- :func:`int_decode_attention` — SINGLE-QUERY decode kernel (the per-token
  serving path): reads the int8 / int4-nibble-packed KV *ring cache in
  place*.  ``k_positions[j]`` gives ring slot ``j``'s absolute position
  (negative = unwritten); a RUNTIME live-block map (scalar-prefetched, so
  the index map sees it before the body runs) DMAs only ring blocks that
  hold a key inside the causal/window span of the current position.  GQA
  query groups ride along as the G query rows of a single MXU tile.
- :func:`int_paged_decode_attention` — the ring kernel generalized to a
  PAGED KV cache for continuous batching: keys/values live in shared
  ``(num_pages, Hkv, page_size, D[/2])`` page pools and each sequence owns
  a ``(max_pages,)`` page-table row plus its own position.  The runtime
  block map becomes per-sequence (:func:`_paged_meta`): grid step ``t`` of
  row ``b`` DMAs physical page ``page_table[b, lo_b + t]``, so a decode
  step reads exactly the pages holding that sequence's live keys — never
  the batch-max span, never another tenant's pages.  Key positions need no
  stored map: logical page ``l`` holds positions ``l*page_size + r``.
  Scales are per-sequence ``(B,)`` vectors (multi-tenant isolation) —
  optionally joined by per-PHYSICAL-page ``k_page_scale``/``v_page_scale``
  pools riding the same phys-id stream, so pages shared across sequences
  (prefix sharing / CoW) dequantize on the grid they were prefilled with.

Skipping a fully-masked key block is bit-exact: it contributes ``e = 0``
to every carry and cannot raise the running ``m`` — which is why both block
maps (static for prefill, runtime for decode) drop dead tiles without
changing the emitted code sequence.

Prob codes are carried in int8 for the MXU.  ``attn_bits <= 7`` codes are
stored as-is; ``attn_bits == 8`` codes (the paper's unsigned uint8 grid)
are stored biased by -128 and the PV contraction adds the exact
``128 * colsum(v)`` correction per tile (``sum_j p_j v_j ==
sum_j (p_j - 128) v_j + 128 * sum_j v_j``, all in int32), closing the
8-bit paper-parity gap without leaving the integer path.  int32 per-block
PV accumulation is safe while ``attn_bits + 7 + log2(bk) + 1 <= 32``.

TPU layout rules shape the operands.  Per-sequence, per-head and per-page
scales are scalars read from SMEM (whole-array specs or the scalar-prefetch
stream, all flattened to 1-D), never (1, 1) VMEM blocks.  Running carries
are (rows, 1) columns.  Each kernel returns its unscaled PV accumulator and
its Sigma column; the wrapper applies ``dattn = (2/qmax) / Sigma`` (and the
v step) in XLA, so both backends divide with the same instruction
sequence.  Nibble-packed KV tiles are split into low/high planes
(:func:`~repro.kernels.qmatmul.nibble_planes`) and contracted against the
even/odd query and output columns, which the wrapper de-interleaves and
re-interleaves: the integer sums are exact, so this equals the unpacked
contraction bit for bit.

Kernels compile for the MXU on a TPU and run in interpret mode elsewhere
(:func:`repro.kernels.interpret_mode`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.softmax2 import exp2_shift, pow2, tree_sum
from repro.kernels import interpret_mode
from repro.kernels.qmatmul import nibble_planes

NEG = -1e30
MAX_PROB_BITS = 8


def _smem():
    """Whole-array operand in scalar memory (per-row / per-page scales)."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _mask(i, kblk, bq, bk, sq_mod, sk, causal, window):
    """Validity of (q row, key) pairs in one (bq, bk) tile.

    Query rows wrap modulo ``sq_mod`` (GQA groups stacked along Sq); keys at
    or beyond ``sk`` are padding and always invalid.
    """
    q_pos = (i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)) \
        % sq_mod
    k_pos = kblk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    m = k_pos < sk
    if causal:
        m &= k_pos <= q_pos
    if window is not None:
        m &= k_pos > q_pos - window
    return m


def _tile_logits(q_ref, k_ref, sc, valid):
    """Masked, clamped base-2 logits of one tile (int8 MXU contraction).

    ``sc`` is this tile's scalar logit scale — per (head-fold, q-block),
    so every bq-tile dequantizes on its own activation grid.
    """
    acc = jnp.dot(q_ref[0], k_ref[0].T, preferred_element_type=jnp.int32)
    x = acc.astype(jnp.float32) * sc
    return jnp.maximum(jnp.where(valid, x, NEG), -120.0)


def _online_update(x, m_ref, qmax):
    """Advance running m, emit this tile's codes + rescale factor + e-sum.

    ``m_ref`` is the (rows, 1) running-max column; ``r`` comes back in the
    same shape.  8-bit grids (qmax = 255) store codes biased by -128 so they
    fit the MXU's int8 operands; :func:`_pv_dot` adds the exact un-bias term.
    """
    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, jnp.floor(jnp.max(x, axis=-1, keepdims=True)))
    e = jnp.where(x <= -120.0, 0.0, exp2_shift(x - m_new))
    p = jnp.clip(jnp.round(e * (qmax / 2.0)), 0, qmax)
    if qmax > 127:                   # biased uint8-as-int8 storage
        p = p - 128.0
    p_q = p.astype(jnp.int8)
    r = pow2(m_old - m_new)          # exact: both integers (NEG init -> 0)
    m_ref[...] = m_new
    return e, p_q, r


def _pv_dot(p_q, v, qmax):
    """Integer PV contraction; exact un-bias for 8-bit biased codes.

    ``sum_j p_j v_j == sum_j (p_j - 128) v_j + 128 * sum_j v_j`` holds per
    row in int32 because masked keys carry real code 0 (stored -128), so
    their two terms cancel exactly.
    """
    pv = jnp.dot(p_q, v, preferred_element_type=jnp.int32)
    if qmax > 127:
        pv = pv + 128 * jnp.sum(v.astype(jnp.int32), axis=0)[None, :]
    return pv


def _kv_planes(tile, packed):
    """A KV tile as stored -> its int8 column planes (two when packed)."""
    return nibble_planes(tile) if packed else (tile,)


def _planes_dot(q_planes, k_planes):
    """sum_j q_j @ k_j^T over column planes (exact int32 partial sums)."""
    acc = None
    for q, k in zip(q_planes, k_planes):
        part = jnp.dot(q, k.T, preferred_element_type=jnp.int32)
        acc = part if acc is None else acc + part
    return acc


def _to_planes(x, npl):
    """(..., rows, d) -> (..., npl, rows, d // npl): plane j = columns j::npl."""
    *lead, rows, d = x.shape
    return jnp.moveaxis(x.reshape(*lead, rows, d // npl, npl), -1, -3)


def _from_planes(x):
    """Inverse of :func:`_to_planes`."""
    *lead, npl, rows, dp = x.shape
    return jnp.moveaxis(x, -3, -1).reshape(*lead, rows, dp * npl)


def _finish(acc, s, qmax, v_scale=None):
    """Kernel epilogue, applied in XLA: ``acc * (dattn [* dv])``.

    ``s`` is the Sigma column the kernel returned; ``v_scale`` broadcasts
    against ``acc`` (None when the kernel already folded dv per block).
    """
    dattn = (2.0 / qmax) / jnp.maximum(s, 1e-30)
    return acc * dattn if v_scale is None else acc * (dattn * v_scale)


# ---------------------------------------------------------------------------
# Live-block maps (bounded-key streaming)
# ---------------------------------------------------------------------------

def _live_kblock_meta(nq, nk, bq, bk, sq_mod, sk, causal, window):
    """STATIC per-query-block key-tile map for the fused prefill kernel.

    Row i is ``[n_live, kblk ids of live tiles ascending, last id
    repeated]``.  A tile is live iff any (q row, key) pair in it passes
    :func:`_mask`; repeating the last id means dead grid steps re-map the
    previous block, so Pallas issues no DMA for them.  Returns
    ``(meta (nq * (1 + nt),) int32, nt)`` — rows flattened for SMEM — with
    ``nt = max live tiles per row``.
    """
    q_pos = np.arange(nq * bq) % sq_mod
    lo = (np.maximum(q_pos - (window - 1), 0) if window is not None
          else np.zeros_like(q_pos))
    hi = (np.minimum(q_pos, sk - 1) if causal
          else np.full_like(q_pos, sk - 1))
    kb = np.arange(nk)
    live = ((lo[:, None] <= kb[None, :] * bk + bk - 1)
            & (hi[:, None] >= kb[None, :] * bk)
            & (lo <= hi)[:, None]).reshape(nq, bq, nk).any(axis=1)
    nt = max(int(live.sum(axis=1).max()), 1)
    meta = np.zeros((nq, 1 + nt), np.int32)
    for i in range(nq):
        ids = np.nonzero(live[i])[0]
        meta[i, 0] = len(ids)
        if len(ids) == 0:
            ids = np.array([0])
        meta[i, 1:1 + len(ids)] = ids
        meta[i, 1 + len(ids):] = ids[-1]
    return jnp.asarray(meta.reshape(-1)), nt


def _decode_meta(k_positions, pos, nk, bk, causal, window):
    """RUNTIME ring-block map for the decode kernel.

    ``[pos, n_live, live block ids ascending (dead steps repeat the last
    live id -> no DMA)]``.  A ring block is live iff any of its slots holds
    a written key (position >= 0) inside the causal/window span of ``pos``.
    """
    valid = k_positions >= 0
    if causal:
        valid &= k_positions <= pos
    if window is not None:
        valid &= k_positions > pos - window
    blk = valid.reshape(nk, bk).any(axis=1)
    order = jnp.argsort(~blk).astype(jnp.int32)     # stable: live ids first
    n_live = jnp.sum(blk).astype(jnp.int32)
    last = order[jnp.clip(n_live - 1, 0, nk - 1)]
    kmap = jnp.where(jnp.arange(nk) < n_live, order, last)
    return jnp.concatenate(
        [jnp.stack([pos, n_live]).astype(jnp.int32), kmap])


def _paged_meta(page_table, pos, num_phys, page_size, window):
    """RUNTIME per-sequence page map for the paged decode kernel.

    Row ``b`` is ``[pos_b, n_live, physical page ids (P entries), logical
    page ids (P entries)]``, rows flattened to one (B * (2 + 2P),) vector
    for SMEM.  Live logical pages are the window-clipped span ``[lo_b,
    pos_b // page_size]`` (logical page ``l`` holds positions
    ``l*page_size .. l*page_size + page_size - 1``); dead grid steps repeat
    the last live entry so Pallas issues no DMA for them.  ``pos_b < 0``
    marks an inactive row: zero live pages, every step dead.  An
    UNALLOCATED entry (< 0) inside the live span DMAs physical page 0 but
    its logical id is emitted as -1, which the body's liveness test
    rejects — the hole contributes e = 0 (bit-exact skip), matching the
    oracle's ``kpos = -1`` for unallocated slots.
    """
    b, p = page_table.shape
    hi = pos // page_size
    lo = jnp.zeros_like(pos) if window is None \
        else jnp.maximum((pos - window + 1) // page_size, 0)
    n_live = jnp.where(pos >= 0, jnp.clip(hi - lo + 1, 0, p), 0)
    logical = jnp.clip(jnp.minimum(lo[:, None] + jnp.arange(p)[None, :],
                                   hi[:, None]), 0, p - 1)
    raw = jnp.take_along_axis(page_table, logical, axis=1)
    phys = jnp.clip(raw, 0, num_phys - 1)
    logical = jnp.where(raw >= 0, logical, -1)
    return jnp.concatenate([pos[:, None], n_live[:, None], phys, logical],
                           axis=1).astype(jnp.int32).reshape(-1)


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------

def _stats_kernel(sc_ref, q_ref, k_ref, s_ref, mb_ref, sb_ref, *,
                  nq, nk, bq, bk, sq_mod, sk, causal, window, qmax):
    h, i, kblk = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kblk == 0)
    def _init():
        mb_ref[...] = jnp.full_like(mb_ref, NEG)
        sb_ref[...] = jnp.zeros_like(sb_ref)

    valid = _mask(i, kblk, bq, bk, sq_mod, sk, causal, window)

    # Fully-masked tiles (causal upper triangle, out-of-window, key padding)
    # contribute e = 0 to every carry: skipping them is bit-exact and saves
    # the MXU contraction.
    @pl.when(jnp.any(valid))
    def _compute():
        x = _tile_logits(q_ref, k_ref, sc_ref[h * nq + i], valid)
        e, _, r = _online_update(x, mb_ref, qmax)
        sb_ref[...] = sb_ref[...] * r + tree_sum(e)

    @pl.when(kblk == nk - 1)
    def _out():
        s_ref[0] = sb_ref[...]


def _pv_kernel(sc_ref, q_ref, k_ref, v_ref, o_ref, mb_ref, acc_ref, *,
               nq, nk, bq, bk, sq_mod, sk, causal, window, qmax):
    h, i, kblk = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kblk == 0)
    def _init():
        mb_ref[...] = jnp.full_like(mb_ref, NEG)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = _mask(i, kblk, bq, bk, sq_mod, sk, causal, window)

    @pl.when(jnp.any(valid))
    def _compute():
        x = _tile_logits(q_ref, k_ref, sc_ref[h * nq + i], valid)
        _, p_q, r = _online_update(x, mb_ref, qmax)
        pv = _pv_dot(p_q, v_ref[0], qmax)
        acc_ref[...] = acc_ref[...] * r + pv.astype(jnp.float32)

    @pl.when(kblk == nk - 1)
    def _out():
        o_ref[0] = acc_ref[...]


def _fused_kernel(meta_ref, sc_ref, q_ref, k_ref, v_ref, o_ref, s_ref,
                  mb_ref, sb_ref, acc_ref, *, nq, nt, bq, bk, sq_mod, sk,
                  causal, window, qmax):
    h, i, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        mb_ref[...] = jnp.full_like(mb_ref, NEG)
        sb_ref[...] = jnp.zeros_like(sb_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The key tile in VMEM is meta[i, 1 + t], not t: dead tiles were never
    # DMA'd.  Guard on liveness so the repeated tail entries do not double
    # count their block.
    row = i * (1 + nt)
    kblk = meta_ref[row + 1 + t]
    live = t < meta_ref[row]
    valid = _mask(i, kblk, bq, bk, sq_mod, sk, causal, window)

    @pl.when(live & jnp.any(valid))
    def _compute():
        # Per-q-block activation scale, read straight from the prefetched
        # (h, nq) vector: every bq-tile dequantizes on its own grid.
        x = _tile_logits(q_ref, k_ref, sc_ref[h * nq + i], valid)
        e, p_q, r = _online_update(x, mb_ref, qmax)
        pv = _pv_dot(p_q, v_ref[0], qmax)
        sb_ref[...] = sb_ref[...] * r + tree_sum(e)
        acc_ref[...] = acc_ref[...] * r + pv.astype(jnp.float32)

    @pl.when(t == nt - 1)
    def _out():
        o_ref[0] = acc_ref[...]
        s_ref[0] = sb_ref[...]


def _decode_kernel(meta_ref, sc_ref, q_ref, k_ref, v_ref, kp_ref, o_ref,
                   s_ref, mb_ref, sb_ref, acc_ref, *, nt, causal, window,
                   qmax, packed):
    h, t = pl.program_id(0), pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        mb_ref[...] = jnp.full_like(mb_ref, NEG)
        sb_ref[...] = jnp.zeros_like(sb_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = meta_ref[0]
    live = t < meta_ref[1]
    kp = kp_ref[...]                     # (1, bk) ring positions of the tile
    valid = kp >= 0                      # negative = unwritten slot
    if causal:
        valid &= kp <= pos
    if window is not None:
        valid &= kp > pos - window

    @pl.when(live & jnp.any(valid))
    def _compute():
        vpl = _kv_planes(v_ref[0], packed)
        acc = _planes_dot([q_ref[0, j] for j in range(len(vpl))],
                          _kv_planes(k_ref[0], packed))
        x = acc.astype(jnp.float32) * sc_ref[h]
        x = jnp.maximum(jnp.where(valid, x, NEG), -120.0)
        e, p_q, r = _online_update(x, mb_ref, qmax)
        sb_ref[...] = sb_ref[...] * r + tree_sum(e)
        for j, v in enumerate(vpl):
            acc_ref[j] = acc_ref[j] * r + \
                _pv_dot(p_q, v, qmax).astype(jnp.float32)

    @pl.when(t == nt - 1)
    def _out():
        o_ref[0] = acc_ref[...]
        s_ref[0] = sb_ref[...]


def _paged_decode_kernel(meta_ref, q_ref, k_ref, v_ref, sc_ref, *refs, nt,
                         page_size, window, qmax, packed, page_scaled):
    if page_scaled:
        vs_ref, kps_ref, vps_ref, *refs = refs
    o_ref, s_ref, mb_ref, sb_ref, acc_ref = refs
    b, t = pl.program_id(0), pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        mb_ref[...] = jnp.full_like(mb_ref, NEG)
        sb_ref[...] = jnp.zeros_like(sb_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    row = b * (2 + 2 * nt)
    pos = meta_ref[row]
    live = t < meta_ref[row + 1]
    phys = meta_ref[row + 2 + t]
    # Key positions are implied by the logical page id: no per-slot position
    # map is stored (unlike the ring kernel) — page r of logical page l is
    # absolute position l*page_size + r.  logical = -1 marks an unallocated
    # page inside the span.
    first = meta_ref[row + 2 + nt + t] * page_size
    kp = first + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
    valid = (kp >= 0) & (kp <= pos)
    page_live = (first >= 0) & (first <= pos)
    if window is not None:
        valid &= kp > pos - window
        page_live &= first + page_size - 1 > pos - window

    @pl.when(live & page_live)
    def _compute():
        vpl = _kv_planes(v_ref[0, 0], packed)
        acc = _planes_dot([q_ref[0, 0, j] for j in range(len(vpl))],
                          _kv_planes(k_ref[0, 0], packed))
        # page_scaled: this page's codes dequantize on the grid they were
        # PREFILLED with (prefix-sharing: the prefix owner's scale, read
        # per physical page through the meta's phys-id stream), so shared
        # pages never re-scale to the reading tenant's grid.
        if page_scaled:
            x = acc.astype(jnp.float32) * (sc_ref[b] * kps_ref[phys])
        else:
            x = acc.astype(jnp.float32) * sc_ref[b]
        x = jnp.maximum(jnp.where(valid, x, NEG), -120.0)
        e, p_q, r = _online_update(x, mb_ref, qmax)
        sb_ref[...] = sb_ref[...] * r + tree_sum(e)
        for j, v in enumerate(vpl):
            pv = _pv_dot(p_q, v, qmax).astype(jnp.float32)
            if page_scaled:
                pv = pv * (vs_ref[b] * vps_ref[phys])
            acc_ref[j] = acc_ref[j] * r + pv

    @pl.when(t == nt - 1)
    def _out():
        o_ref[0, 0] = acc_ref[...]
        s_ref[0, 0] = sb_ref[...]


# ---------------------------------------------------------------------------
# Wrappers
# ---------------------------------------------------------------------------

def _prep(q_q, k_q, v_q, bq, bk):
    h, sq, d = q_q.shape
    sk = k_q.shape[1]
    pq_, pk_ = (-sq) % bq, (-sk) % bk
    if pq_:
        q_q = jnp.pad(q_q, ((0, 0), (0, pq_), (0, 0)))
    if pk_:
        k_q = jnp.pad(k_q, ((0, 0), (0, pk_), (0, 0)))
        v_q = jnp.pad(v_q, ((0, 0), (0, pk_), (0, 0)))
    return q_q, k_q, v_q, (sq + pq_) // bq, (sk + pk_) // bk


def _grid_scales(sc, v_scale, h, nq):
    """Broadcast the epilogue scales to their grid shapes.

    ``sc``: scalar (one grid for the whole call), (nq,) per-q-block vector,
    or (h, nq) per (head-fold, q-block) — the finest granularity: dispatch
    folds batch into the head axis and XLA-chunk-sized row groups into q
    blocks, so per-sequence-per-chunk activation grids land here.
    ``v_scale``: scalar or (h,) per-head-fold.  Returns (sc flattened to
    (h * nq,) f32 for SMEM, v_scale as (h, 1, 1) f32).
    """
    sc = jnp.asarray(sc, jnp.float32)
    if sc.ndim == 1:
        sc = sc[None, :]
    sc = jnp.broadcast_to(sc, (h, nq)).reshape(-1)
    vs = jnp.broadcast_to(jnp.asarray(v_scale, jnp.float32).reshape(-1),
                          (h,)).reshape(h, 1, 1)
    return sc, vs


@functools.partial(jax.jit, static_argnames=(
    "attn_bits", "causal", "window", "bq", "bk", "sq_mod", "interpret"))
def int_attention(q_q, k_q, v_q, sc, v_scale, *, attn_bits=7, causal=True,
                  window=None, bq=128, bk=128, sq_mod=None, interpret=None):
    """TWO-PASS integer attention over int8 operands (baseline design).

    q_q: (H, Sq, D) int8 (GQA pre-folded: G query groups stacked along Sq,
    row r has position ``r % sq_mod``; ``sq_mod`` defaults to Sq); k_q, v_q:
    (H, Sk, D) int8.  ``sc`` = softmax_scale * dq * dk * log2(e) — scalar,
    (nq,) per-q-block, or (H, nq) per (head-fold, q-block) f32 (per-block
    activation grids); ``v_scale`` = dv (scalar or (H,)).  Returns
    (H, Sq, D) f32.  ``interpret``: see :func:`repro.kernels.interpret_mode`.

    Pass 1 sweeps K once for Sigma; pass 2 re-sweeps K, recomputing QK^T
    and the running-m code sequence (identical to the fused kernel's), and
    accumulates integer PV.  Kept as the measured baseline the single-pass
    kernel improves on: 3 MXU sweeps and 2x K-tile HBM reads.
    """
    assert attn_bits <= MAX_PROB_BITS, \
        f"prob codes are <= {MAX_PROB_BITS}-bit (int8 carried, 8-bit biased)"
    h, sq, d = q_q.shape
    sk = k_q.shape[1]
    qmax = float((1 << attn_bits) - 1)
    q_q, k_q, v_q, nq, nk = _prep(q_q, k_q, v_q, bq, bk)
    sc2, vs = _grid_scales(sc, v_scale, h, nq)
    kw = dict(nq=nq, nk=nk, bq=bq, bk=bk, sq_mod=sq_mod or sq, sk=sk,
              causal=causal, window=window, qmax=qmax)
    qspec = pl.BlockSpec((1, bq, d), lambda h, i, k: (h, i, 0))
    kspec = pl.BlockSpec((1, bk, d), lambda h, i, k: (h, k, 0))
    interpret = interpret_mode(interpret)

    s = pl.pallas_call(
        functools.partial(_stats_kernel, **kw),
        grid=(h, nq, nk),
        in_specs=[_smem(), qspec, kspec],
        out_specs=pl.BlockSpec((1, bq, 1), lambda h, i, k: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((h, nq * bq, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32)] * 2,
        interpret=interpret,
    )(sc2, q_q, k_q)

    acc = pl.pallas_call(
        functools.partial(_pv_kernel, **kw),
        grid=(h, nq, nk),
        in_specs=[_smem(), qspec, kspec, kspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((h, nq * bq, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(sc2, q_q, k_q, v_q)
    return _finish(acc, s, qmax, vs)[:, :sq]


@functools.partial(jax.jit, static_argnames=(
    "attn_bits", "causal", "window", "bq", "bk", "sq_mod", "interpret"))
def int_attention_fused(q_q, k_q, v_q, sc, v_scale, *, attn_bits=7,
                        causal=True, window=None, bq=128, bk=128,
                        sq_mod=None, interpret=None):
    """SINGLE-PASS fused integer attention (the prefill serving kernel).

    Same contract as :func:`int_attention`.  One sweep over K/V per query
    block: each tile's QK^T feeds the running (m, Sigma) update AND the
    quantized PV accumulation, so every K/V tile is read from HBM and
    pushed through the MXU exactly once — 2*H*Sq*Sk*D MACs vs the
    two-pass design's 3*H*Sq*Sk*D.

    Key tiles stream through a static live-block map (scalar-prefetch
    index map, :func:`_live_kblock_meta`): dead tiles — causal upper
    triangle, beyond the local window, key padding — are neither DMA'd nor
    visited, so windowed rows stream only their bounded live span.

    Per-query-block activation scales: ``sc`` broadcast to (H, nq) rides
    the scalar-prefetch stream next to the block map, so each bq-tile's
    logits dequantize with their own scale — dispatch threads per-sequence
    per-XLA-chunk q grids through here, closing the granularity gap with
    the chunked XLA path at Sq > q_chunk.
    """
    assert attn_bits <= MAX_PROB_BITS, \
        f"prob codes are <= {MAX_PROB_BITS}-bit (int8 carried, 8-bit biased)"
    h, sq, d = q_q.shape
    sk = k_q.shape[1]
    qmax = float((1 << attn_bits) - 1)
    q_q, k_q, v_q, nq, nk = _prep(q_q, k_q, v_q, bq, bk)
    sc2, vs = _grid_scales(sc, v_scale, h, nq)
    meta, nt = _live_kblock_meta(nq, nk, bq, bk, sq_mod or sq, sk, causal,
                                 window)
    w = 1 + nt                                # meta row width
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(h, nq, nt),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, t, m, s: (h, i, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda h, i, t, m, s: (h, m[i * w + 1 + t], 0)),
            pl.BlockSpec((1, bk, d),
                         lambda h, i, t, m, s: (h, m[i * w + 1 + t], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, t, m, s: (h, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, i, t, m, s: (h, i, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
    )
    acc, s = pl.pallas_call(
        functools.partial(_fused_kernel, nq=nq, nt=nt, bq=bq, bk=bk,
                          sq_mod=sq_mod or sq, sk=sk, causal=causal,
                          window=window, qmax=qmax),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((h, nq * bq, d), jnp.float32),
                   jax.ShapeDtypeStruct((h, nq * bq, 1), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(meta, sc2, q_q, k_q, v_q)
    return _finish(acc, s, qmax, vs)[:, :sq]


@functools.partial(jax.jit, static_argnames=(
    "attn_bits", "causal", "window", "bk", "packed", "interpret"))
def int_decode_attention(q_q, k_q, v_q, sc, v_scale, k_positions, pos, *,
                         attn_bits=7, causal=True, window=None, bk=128,
                         packed=False, interpret=None):
    """Single-query integer decode attention over a KV ring cache, in place.

    q_q: (H, G, D) int8 — the G GQA query groups of one decode step as MXU
    rows (all share query position ``pos``).  k_q, v_q: the ring cache as
    stored — (H, span, D) int8, or (H, span, D//2) uint8 nibbles with
    ``packed=True`` (split into nibble planes on the VPU per tile; HBM
    reads stay halved).  ``k_positions``: (span,) int32, ring slot j's
    absolute position (negative = unwritten slot, masked).  ``pos``: scalar
    int32 query position (may be traced).  ``sc`` = softmax_scale * dq * dk
    * log2(e), scalar or (H,) per head-fold row (batch rows folded into H
    quantize their single query per sequence); ``v_scale`` = dv (scalar or
    (H,)).  Returns (H, G, D) f32.

    Bounded-key streaming: a runtime block map (:func:`_decode_meta`,
    scalar-prefetched so index maps see it) DMAs only ring blocks holding a
    live key — early decode over a long ring reads ~(pos/span) of the
    cache, windowed decode only the window span.  Blocks stream in slot
    order on the running-m grid; with one block covering the ring
    (``bk >= span``, what dispatch prefers) the grid coincides with the
    full-row XLA path bit-for-bit.
    """
    assert attn_bits <= MAX_PROB_BITS, \
        f"prob codes are <= {MAX_PROB_BITS}-bit (int8 carried, 8-bit biased)"
    h, g, d = q_q.shape
    span = k_q.shape[1]
    if packed:
        assert d % 2 == 0 and k_q.shape[-1] * 2 == d, (q_q.shape, k_q.shape)
    qmax = float((1 << attn_bits) - 1)
    nk = -(-span // bk)
    pad = nk * bk - span
    if pad:
        k_q = jnp.pad(k_q, ((0, 0), (0, pad), (0, 0)))
        v_q = jnp.pad(v_q, ((0, 0), (0, pad), (0, 0)))
        k_positions = jnp.pad(k_positions, (0, pad), constant_values=-1)
    pg = (-g) % 8                       # f32 sublane alignment for scratch
    if pg:
        q_q = jnp.pad(q_q, ((0, 0), (0, pg), (0, 0)))
    gq = g + pg
    npl = 2 if packed else 1            # column planes per KV tile
    dp = d // npl
    k_positions = k_positions.astype(jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    meta = _decode_meta(k_positions, pos, nk, bk, causal, window)
    kp2 = k_positions.reshape(1, nk * bk)
    sc2 = jnp.broadcast_to(jnp.asarray(sc, jnp.float32).reshape(-1), (h,))
    vs = jnp.broadcast_to(jnp.asarray(v_scale, jnp.float32).reshape(-1),
                          (h,)).reshape(h, 1, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(h, nk),
        in_specs=[
            pl.BlockSpec((1, npl, gq, dp), lambda h, t, m, s: (h, 0, 0, 0)),
            pl.BlockSpec((1, bk, dp), lambda h, t, m, s: (h, m[2 + t], 0)),
            pl.BlockSpec((1, bk, dp), lambda h, t, m, s: (h, m[2 + t], 0)),
            pl.BlockSpec((1, bk), lambda h, t, m, s: (0, m[2 + t])),
        ],
        out_specs=[
            pl.BlockSpec((1, npl, gq, dp), lambda h, t, m, s: (h, 0, 0, 0)),
            pl.BlockSpec((1, gq, 1), lambda h, t, m, s: (h, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((gq, 1), jnp.float32),
                        pltpu.VMEM((gq, 1), jnp.float32),
                        pltpu.VMEM((npl, gq, dp), jnp.float32)],
    )
    acc, s = pl.pallas_call(
        functools.partial(_decode_kernel, nt=nk, causal=causal,
                          window=window, qmax=qmax, packed=packed),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((h, npl, gq, dp), jnp.float32),
                   jax.ShapeDtypeStruct((h, gq, 1), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(meta, sc2, _to_planes(q_q, npl), k_q, v_q, kp2)
    return _finish(_from_planes(acc), s, qmax, vs)[:, :g]


@functools.partial(jax.jit, static_argnames=(
    "attn_bits", "window", "packed", "interpret"))
def int_paged_decode_attention(q_q, k_pages, v_pages, sc, v_scale,
                               page_table, pos, *, k_page_scale=None,
                               v_page_scale=None, attn_bits=7, window=None,
                               packed=False, interpret=None):
    """Single-query integer decode attention over a PAGED KV cache, in place.

    q_q: (B, Hkv, G, D) int8 — one decode step per sequence, the G GQA
    query groups as MXU rows.  k_pages, v_pages: the shared page pools as
    stored — (num_pages, Hkv, page_size, D) int8, or (..., D//2) uint8
    nibbles with ``packed=True``.  ``page_table``: (B, max_pages) int32,
    sequence b's logical page l lives in physical page ``page_table[b, l]``
    (negative = unallocated).  ``pos``: (B,) int32 per-sequence query
    positions (negative = inactive row -> zero output).  ``sc`` / ``v_scale``
    are per-sequence (B,) vectors (or scalars, broadcast): multi-tenant
    isolation means every sequence carries its own quantization grid.
    Returns (B, Hkv, G, D) f32.

    Per-PAGE scale resolution (prefix sharing / CoW): with
    ``k_page_scale`` / ``v_page_scale`` — (num_pages,) f32 vectors indexed
    by PHYSICAL page id, first axis aligned with ``k_pages`` — grid step t
    of row b dequantizes page ``page_table[b, lo_b + t]`` on THAT page's
    stored grid: logit scale ``sc[b] * k_page_scale[phys]`` and PV
    contribution ``pv * (v_scale[b] * v_page_scale[phys])`` accumulated
    per block (the epilogue then applies only ``dattn``).  Pages shared
    from a prefix owner therefore keep the scales they were prefilled
    with, and a tenant's own activation grid never re-scales another's
    codes.  Both vectors must be given together; ``None`` keeps the
    per-sequence contract above bit-for-bit.  All scales live in SMEM
    whole, so the pool size bounds them (one f32 per physical page each).

    This is :func:`int_decode_attention` with the runtime live-block map
    made per-sequence: grid step t of row b DMAs physical page
    ``page_table[b, lo_b + t]`` (window-clipped span), so per-step HBM
    traffic is proportional to THAT sequence's live pages — not the batch
    max, and never another sequence's pages.  Pages stream in logical
    (= position) order on the running-m grid, bit-matching the streamed
    oracle in kernels/ref.py with ``bk = page_size``; dead pages (outside
    the window, before lo, unwritten) are never DMA'd, which is bit-exact
    because a fully-masked page contributes e = 0 and cannot raise the
    running m.
    """
    assert attn_bits <= MAX_PROB_BITS, \
        f"prob codes are <= {MAX_PROB_BITS}-bit (int8 carried, 8-bit biased)"
    b, hkv, g, d = q_q.shape
    num_phys, _, page_size, dk = k_pages.shape
    if packed:
        assert d % 2 == 0 and dk * 2 == d, (q_q.shape, k_pages.shape)
    else:
        assert dk == d, (q_q.shape, k_pages.shape)
    qmax = float((1 << attn_bits) - 1)
    nt = page_table.shape[1]            # grid steps = max logical pages
    w = 2 + 2 * nt                      # meta row width
    pg = (-g) % 8                       # f32 sublane alignment for scratch
    if pg:
        q_q = jnp.pad(q_q, ((0, 0), (0, 0), (0, pg), (0, 0)))
    gq = g + pg
    npl = 2 if packed else 1            # column planes per KV tile
    pos = jnp.asarray(pos, jnp.int32).reshape(b)
    meta = _paged_meta(jnp.asarray(page_table, jnp.int32), pos, num_phys,
                       page_size, window)
    sc2 = jnp.broadcast_to(jnp.asarray(sc, jnp.float32).reshape(-1), (b,))
    vs = jnp.broadcast_to(jnp.asarray(v_scale, jnp.float32).reshape(-1),
                          (b,))
    page_scaled = k_page_scale is not None
    assert page_scaled == (v_page_scale is not None), \
        "k_page_scale and v_page_scale must be given together"
    scales = [sc2]
    if page_scaled:
        # per-PHYSICAL-page k/v dequant steps, indexed in-kernel through
        # the same phys-id stream as the page pools themselves
        scales += [vs,
                   jnp.asarray(k_page_scale, jnp.float32).reshape(num_phys),
                   jnp.asarray(v_page_scale, jnp.float32).reshape(num_phys)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, nt),
        in_specs=[
            pl.BlockSpec((1, 1, npl, gq, dk),
                         lambda b, h, t, m: (b, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, page_size, dk),
                         lambda b, h, t, m: (m[b * w + 2 + t], h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, dk),
                         lambda b, h, t, m: (m[b * w + 2 + t], h, 0, 0)),
        ] + [_smem()] * len(scales),
        out_specs=[
            pl.BlockSpec((1, 1, npl, gq, dk),
                         lambda b, h, t, m: (b, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, gq, 1), lambda b, h, t, m: (b, h, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((gq, 1), jnp.float32),
                        pltpu.VMEM((gq, 1), jnp.float32),
                        pltpu.VMEM((npl, gq, dk), jnp.float32)],
    )
    acc, s = pl.pallas_call(
        functools.partial(_paged_decode_kernel, nt=nt, page_size=page_size,
                          window=window, qmax=qmax, packed=packed,
                          page_scaled=page_scaled),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hkv, npl, gq, dk), jnp.float32),
                   jax.ShapeDtypeStruct((b, hkv, gq, 1), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(meta, _to_planes(q_q, npl), k_pages, v_pages, *scales)
    out = _finish(_from_planes(acc), s, qmax,
                  None if page_scaled else vs.reshape(b, 1, 1, 1))
    return out[:, :, :g]


def attention_macs(h, sq, sk, d, *, design="single"):
    """Analytic MXU MAC count per kernel call (both int8 contractions).

    ``design="decode"`` counts one decode step over ``sk`` *live* keys
    (single sweep, same as the fused kernel's 2 contractions per key).
    """
    qk = h * sq * sk * d
    return {"single": 2 * qk, "decode": 2 * qk, "two_pass": 3 * qk}[design]
