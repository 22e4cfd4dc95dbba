"""Kernel backend dispatch: route the ``mode="int"`` serving graph onto
the Pallas kernels.

The paper's reordered integer contraction exists twice in this repo: as XLA
einsums inside the model graph (``core.api.dense`` / ``layers.attention``)
and as Pallas TPU kernels (``kernels.qmatmul`` / ``kernels.int_attention``).
This module is the seam between them: the model graph calls
:func:`maybe_qlinear` / :func:`maybe_attention`, which either lower onto the
Pallas kernels (ND->2D flattening, packed-int4 weights, GQA/batch folding,
block-size heuristics) or return ``None`` to signal "use the XLA path".

Backend selection (checked at trace time, so switching requires a re-trace):

1. ``QuantConfig.backend`` — per-model override ("xla" | "pallas" | None);
2. ``REPRO_KERNEL_BACKEND`` env var / :func:`set_backend` /
   :func:`use_backend` — explicit process-wide override;
3. the platform — "pallas" when JAX's default backend is a TPU, "xla"
   elsewhere (where the kernels could only run interpreted);
4. shape policy — even under "pallas", ops the kernels cannot express
   (3D weight stacks, >8-bit prob grids, multi-query ring reads) fall
   back to XLA per call site.

Attention routes onto TWO kernels:

- prefill / full-sequence calls (``q_offset == 0``, contiguous keys) fold
  GQA/batch and run :func:`~repro.kernels.int_attention.int_attention_fused`
  — including narrow local windows over long keys, which stream only their
  bounded live span via the kernel's static block map
  (``REPRO_PALLAS_WINDOW_VETO=1`` restores the old XLA fallback as an
  escape hatch);
- decode steps (Sq == 1 with ring-cache ``k_positions``) run
  :func:`~repro.kernels.int_attention.int_decode_attention` over the int8/int4
  ring cache *in place* — no dequantized or unpacked copy, and only ring
  blocks holding live keys are DMA'd per step.

Paged decode (continuous batching) routes through
:func:`maybe_paged_attention` onto
:func:`~repro.kernels.int_attention.int_paged_decode_attention`: shared
page pools + per-sequence page tables/positions/scales, with per-step DMA
bounded by each sequence's own live pages (``attention_paged_pallas``
STATS).  With per-PHYSICAL-page scale pools (prefix sharing), the kernel
call carries them as extra operands riding the ``_paged_meta`` phys-id
stream, so a page shared from a prefix owner dequantizes on the OWNER's
grid.  The XLA fallback (``attention_paged_xla``) gathers pages as
*codes* — int8, or nibbles unpacked to int8 — never as floats.

The kernels compile for the MXU on a TPU and run in interpret mode
elsewhere (:func:`repro.kernels.interpret_mode`): correct everywhere, fast
only on the chip — which is why "xla" is the default off-TPU.

Parity with the XLA int path is exact (<= 1e-5) whenever one key block
covers the row — ``attention_blocks`` / ``decode_blocks`` prefer that and
achieve it for Sk <= 4096 at default budget.  Beyond that the kernels
stream codes on the running-m grid (see kernels/int_attention.py): outputs
then differ from the full-row XLA grid by at most ~one prob code on early
keys — the same order as the quantization error itself, and bit-identical
to the streamed oracles in kernels/ref.py.

:data:`STATS` counts pallas dispatches and XLA fallbacks per op at trace
time; tests assert on it to prove the serving graph really runs the
kernels (``attention_decode_pallas`` proves decode_step serves from the
ring-cache kernel).
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core.softmax2 import LOG2E
from repro.kernels.int_attention import (MAX_PROB_BITS, int_attention_fused,
                                         int_decode_attention,
                                         int_paged_decode_attention)
from repro.kernels.qmatmul import qmatmul

_VALID = ("xla", "pallas")


def _checked(name: str, source: str) -> str:
    if name not in _VALID:
        raise ValueError(f"unknown kernel backend {name!r} from {source}; "
                         f"expected one of {_VALID}")
    return name


# None: follow the platform (see get_backend).
_env_backend = os.environ.get("REPRO_KERNEL_BACKEND")
_backend = [None if _env_backend is None
            else _checked(_env_backend, "REPRO_KERNEL_BACKEND")]

STATS = {"qlinear_pallas": 0, "qlinear_xla": 0,
         # qlinear_pallas calls whose N or K is not a multiple of its block
         # (a partial weight block, where the kernel once padded the weight)
         "qlinear_ragged": 0,
         "attention_pallas": 0, "attention_decode_pallas": 0,
         "attention_paged_pallas": 0, "attention_paged_xla": 0,
         "attention_xla": 0,
         # prefix-sharing copy-on-write page copies (bumped by the engine's
         # allocator on the first divergent write into a shared partial
         # page — one copy per sharer, ever)
         "cow_page_copies": 0,
         # failure-handling counters, bumped by the serving engine
         # (launch/engine.py) and surfaced by the serve CLI report:
         # victim preemptions (incl. NaN quarantines), bit-exact resume
         # readmissions, cancelled / expired-while-queued requests,
         # EMA-watchdog straggler fires, engine-audit failures, steps
         # served through the forced pallas->XLA fallback twin, and rows
         # quarantined for non-finite logits.
         "preemptions": 0, "resumes": 0, "cancelled": 0, "expired": 0,
         "watchdog_fires": 0, "audit_failures": 0, "forced_xla_steps": 0,
         "quarantined": 0,
         # admission-prefill accounting, bumped by launch/engine.py:
         # logical admission prefills (one per prompt/prefix cut plan, the
         # PR-4 burst-of-N==one-call quantity), ragged chunk launches
         # (>= calls once chunked prefill engages), and real unpadded
         # prompt tokens prefilled.
         "prefill_calls": 0, "prefill_chunks": 0, "prefill_tokens": 0,
         # chosen tile sizes per (op, shape) — the baseline the future
         # measured autotuner (ROADMAP) diffs against; serialized by
         # kernel_bench --json and the serve CLI report.
         "blocks": {}}


def reset_stats():
    for k in STATS:
        STATS[k] = {} if k == "blocks" else 0


def snapshot() -> dict:
    """JSON-serializable copy of STATS (the blocks dict deep-copied)."""
    return {k: (dict(v) if isinstance(v, dict) else v)
            for k, v in STATS.items()}


def _record_blocks(op: str, key: str, *choice: int):
    STATS["blocks"][f"{op}:{key}"] = list(choice)


def get_backend() -> str:
    """The process-wide backend: an explicit override, else the platform's
    ("pallas" on a TPU, "xla" elsewhere)."""
    if _backend[-1] is not None:
        return _backend[-1]
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def set_backend(name: str):
    _backend[-1] = _checked(name, "set_backend")


@contextlib.contextmanager
def use_backend(name: str):
    _backend.append(_checked(name, "use_backend"))
    try:
        yield
    finally:
        _backend.pop()


def resolve_backend(cfg) -> str:
    b = getattr(cfg, "backend", None)
    if b is None:
        return get_backend()
    return _checked(b, "QuantConfig.backend")


def window_veto() -> bool:
    """Escape hatch: REPRO_PALLAS_WINDOW_VETO=1 restores the pre-streaming
    behaviour of sending narrow windows over long keys to the XLA path."""
    return os.environ.get("REPRO_PALLAS_WINDOW_VETO", "0") == "1"


# ---------------------------------------------------------------------------
# Block-size heuristics (shape + VMEM budget instead of hard-coded tiles)
# ---------------------------------------------------------------------------

# Usable VMEM per core after double buffering; ~16MB physical on v5e.
VMEM_BUDGET = 6 * 2 ** 20
_LANE = 128                       # MXU lane width; block dims align to it


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _halve(x: int) -> int:
    """Stay 128-aligned while shrinking."""
    return max(_LANE, _round_up(x // 2, _LANE))


def qmatmul_blocks(m: int, n: int, k: int, *, packed: bool = False,
                   budget: int = VMEM_BUDGET) -> tuple[int, int, int]:
    """(bm, bn, bk) for an (M,K) x (N,K)^T int8 matmul.

    Tile VMEM ~ bm*bk + bn*bk (int8 operands) + 8*bm*bn (int32 acc + f32
    out).  Prefer covering K in one step (single-shot accumulator, no
    revisits of the output tile), then grow bm/bn toward the MXU sweet spot.
    ``packed`` (nibble weights) rounds bk to 256 so the kernel's bk/2-wide
    nibble-plane tiles stay lane-aligned.

    bn and bk need not divide N and K: on the 128 grid the kernel never pads
    the weight, and reads the ragged edge as a partial block (exact: the K
    edge meets zero activation columns, the N edge's extra output columns
    are dropped).  :func:`maybe_qlinear` counts those calls in
    ``STATS["qlinear_ragged"]``.
    """
    bk = min(_round_up(k, _LANE), 2048)
    bm = min(_round_up(m, _LANE), 256)
    bn = min(_round_up(n, _LANE), 256)
    while bm * bk + bn * bk + 8 * bm * bn > budget and bk > _LANE:
        bk = _halve(bk)
    while bm * bk + bn * bk + 8 * bm * bn > budget and max(bm, bn) > _LANE:
        if bm >= bn:
            bm = _halve(bm)
        else:
            bn = _halve(bn)
    if packed:
        bk = _round_up(bk, 2 * _LANE)
    return bm, bn, bk


def attention_blocks(sq: int, sk: int, d: int, *, window: Optional[int] = None,
                     chunk: Optional[int] = None,
                     budget: int = VMEM_BUDGET) -> tuple[int, int]:
    """(bq, bk) for the fused attention kernel.

    ``chunk`` is the XLA path's query-recalibration chunk length (see
    :func:`chunk_len`): when given, bq is capped to its largest divisor so
    a q tile never straddles two activation grids — the per-block scale
    vector then maps one scale per tile.

    Tile VMEM ~ (bq + 2*bk)*d int8 operands + 9*bq*d f32 (out + carry) +
    5*bq*bk (f32 logits + int8 codes).  A single key block covering the
    whole row (bk >= Sk) additionally makes the online grid coincide with
    the full-row reference, so prefer it while it fits.  Only for NARROW
    local windows over long keys (Sk > 2*window — shapes that used to veto
    pallas entirely) is bk instead capped near the ~(bq + window) live
    span per query block: the static live-block map then DMAs 1-2 key
    tiles per query block instead of the whole row.  Wider windows keep
    the full-row-parity preference unchanged.
    """
    bq = min(_round_up(sq, _LANE), 256)
    narrow = window is not None and sk > 2 * window
    cap = 4096
    if narrow:
        cap = min(cap, _round_up(bq + window, _LANE))
    bk = min(_round_up(sk, _LANE), cap)

    def vmem(bq, bk):
        return (bq + 2 * bk) * d + 9 * bq * d + 5 * bq * bk

    while vmem(bq, bk) > budget and bk > 512:
        bk = _halve(bk)
    while vmem(bq, bk) > budget and bq > _LANE:
        bq = _halve(bq)
    while vmem(bq, bk) > budget and bk > _LANE:
        bk = _halve(bk)
    if chunk is not None:
        bq = next(x for x in range(min(bq, chunk), 0, -1) if chunk % x == 0)
    if narrow and bk < sk:
        # The shrink loops may have halved bq below the cap's assumption;
        # re-cap bk to the final live span (smaller bk is always VMEM-safe).
        bk = min(bk, _round_up(bq + window, _LANE))
    _record_blocks("attention", f"sq{sq}_sk{sk}_d{d}_w{window}_c{chunk}",
                   bq, bk)
    return bq, bk


def decode_blocks(span: int, d: int, *, budget: int = VMEM_BUDGET) -> int:
    """bk for the decode kernel over a ``span``-slot ring cache.

    Tile VMEM ~ 2*bk*d int8 K/V + 4*bk positions + ~17*8*d f32 q/out/carry.
    Prefer one block over the whole ring (running grid == full-row grid,
    bit-parity with the XLA path) up to the 4096 sweet spot; longer rings
    stream in 4096-key blocks, of which only the live ones are DMA'd.
    """
    bk = min(_round_up(span, _LANE), 4096)
    while 2 * bk * d + 4 * bk + 17 * 8 * d > budget and bk > _LANE:
        bk = _halve(bk)
    _record_blocks("decode", f"span{span}_d{d}", bk)
    return bk


def paged_decode_blocks(page_size: int, d: int, *,
                        budget: int = VMEM_BUDGET) -> int:
    """Key-block size for the paged decode kernel: page-granularity blocks.

    Pages are the DMA unit — physically scattered, so a kernel block can
    never span two of them; the block size IS the page size.  Tile VMEM ~
    2*page_size*d int8 K/V + ~17*8*d f32 q/out/carry.  Returns 0 when one
    page per block cannot fit the budget (dispatch veto -> XLA fallback);
    any realistic page size (<= 4096 keys at d <= 256) fits easily.
    """
    if 2 * page_size * d + 17 * 8 * d > budget:
        _record_blocks("paged_decode", f"ps{page_size}_d{d}", 0)
        return 0
    _record_blocks("paged_decode", f"ps{page_size}_d{d}", page_size)
    return page_size


# ---------------------------------------------------------------------------
# Linear: ND activation x integerized weight -> Pallas qmatmul
# ---------------------------------------------------------------------------

def qlinear_supported(x, p) -> bool:
    """Shape policy: can this dense() call lower onto kernels.qmatmul?"""
    w_q = p.get("w_q")
    if w_q is None or w_q.ndim != 2:          # float or expert/scan-stacked
        return False
    if x.ndim < 1 or x.shape[-1] == 0 or x.size == 0:
        return False
    if w_q.dtype == jnp.uint8 and x.shape[-1] % 2:
        return False                          # packed nibbles need even K
    return True


def maybe_qlinear(x, p: dict, cfg):
    """Pallas-backed dense() body; ``None`` -> caller uses the XLA path.

    Flattens leading dims to 2D, quantizes the activation on the same grid
    as the XLA path, keeps nibble-packed weights packed in HBM, and folds
    ``dx_bar * dw`` plus bias into the kernel epilogue.  ALL (B, S, K)
    activations — decode steps and (ragged batched) prefill alike —
    quantize per sequence via the kernel's per-row epilogue scale, so
    continuous-batching tenants never share an activation grid and a
    batched admission prefill is bit-identical per row to the solo run
    (matches the XLA path in core.api).
    """
    if resolve_backend(cfg) != "pallas" or not qlinear_supported(x, p):
        STATS["qlinear_xla"] += 1
        return None
    STATS["qlinear_pallas"] += 1
    w_q = p["w_q"]
    packed = w_q.dtype == jnp.uint8
    kdim = x.shape[-1]
    n = w_q.shape[0]
    per_row = x.ndim == 3
    if per_row:
        codes, row_scale = quantize_rows(x, cfg.a_bits)
        x2 = codes.reshape(-1, kdim)
        scale = p["w_scale"].astype(jnp.float32)
        row_scale = jnp.repeat(row_scale.astype(jnp.float32), x.shape[1])
    else:
        xq = quant.quantize_tensor(x, cfg.a_bits)
        x2 = xq.q.reshape(-1, kdim)
        scale = (p["w_scale"] * xq.scale).astype(jnp.float32)
        row_scale = None
    bias = p.get("b")
    bm, bn, bk = qmatmul_blocks(x2.shape[0], n, kdim, packed=packed)
    if n % bn or kdim % bk:
        STATS["qlinear_ragged"] += 1
    out = qmatmul(x2, w_q, scale,
                  None if bias is None else bias.astype(jnp.float32),
                  row_scale, bm=bm, bn=bn, bk=bk, packed=packed)
    return out.reshape(*x.shape[:-1], n).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention: (B, H, S, D) GQA -> folded (B*Hkv, ...) kernels
# ---------------------------------------------------------------------------

def _is_packed(x) -> bool:
    """Nibble-packed QTensor (int4 KV cache / weights convention)."""
    return isinstance(x, quant.QTensor) and x.is_packed


def attention_supported(q, k, spec, cfg, q_offset, k_offset,
                        k_positions) -> bool:
    """Shape policy for the fused (prefill) attention kernel.

    The kernel indexes keys 0..Sk-1 from position 0: ring caches
    (``k_positions``) and decode offsets go to :func:`decode_supported`
    or fall back to XLA, as do prob grids wider than 8 bits.
    """
    if cfg.attn_bits > MAX_PROB_BITS:
        return False
    if getattr(cfg, "softmax", "base2") != "base2":
        return False              # kernels hardcode the shift-exp (Eq. 4)
    if k_positions is not None:
        return False
    if not (isinstance(q_offset, int) and q_offset == 0
            and isinstance(k_offset, int) and k_offset == 0):
        return False
    if _is_packed(q) or _is_packed(k):
        return False              # packed reads are a decode-kernel feature
    if (spec.window is not None and k.shape[2] > 2 * spec.window
            and window_veto()):
        # Escape hatch (REPRO_PALLAS_WINDOW_VETO=1): pre-streaming
        # behaviour, where narrow local windows over long keys used the
        # XLA path's key slicing.  The fused kernel's static live-block
        # map now bounds the DMA itself, so the default is to dispatch.
        return False
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    return sq > 0 and k.shape[2] > 0 and hq % hkv == 0 and d > 0


def decode_supported(q, k, spec, cfg, k_positions) -> bool:
    """Shape policy for the single-query ring-cache decode kernel.

    Sq must be 1 (the G GQA groups become the kernel's query rows) and the
    ring slot->position map must be a shared (span,) vector — what
    ``models.lm`` produces every decode step.
    """
    if cfg.attn_bits > MAX_PROB_BITS:
        return False
    if getattr(cfg, "softmax", "base2") != "base2":
        return False
    if k_positions is None or getattr(k_positions, "ndim", None) != 1:
        return False
    b, hq, sq, d = q.shape
    if sq != 1 or d == 0 or k.shape[2] == 0:
        return False
    hkv = k.shape[1]
    if hq % hkv:
        return False
    if _is_packed(k) and (k.bits != 4 or d % 2):
        return False
    return True


def maybe_attention(q, k, v, spec, cfg, *, q_offset=0, k_offset=0,
                    k_positions=None):
    """Pallas-backed attention() body; ``None`` -> caller's XLA path.

    Decode steps (Sq == 1 + ring ``k_positions``) hit the in-place decode
    kernel; everything else the fused prefill kernel, with per-site
    fallback to XLA for shapes neither kernel expresses.
    """
    if resolve_backend(cfg) == "pallas":
        if decode_supported(q, k, spec, cfg, k_positions):
            STATS["attention_decode_pallas"] += 1
            return _decode_call(q, k, v, spec, cfg, q_offset, k_positions)
        if attention_supported(q, k, spec, cfg, q_offset, k_offset,
                               k_positions):
            STATS["attention_pallas"] += 1
            return _fused_call(q, k, v, spec, cfg)
    STATS["attention_xla"] += 1
    return None


def _as_q(x, bits):
    return x if isinstance(x, quant.QTensor) \
        else quant.quantize_tensor(x, bits)


def chunk_len(sq: int, q_chunk: int) -> int:
    """The XLA path's query-recalibration chunk: largest c <= q_chunk
    dividing Sq (``layers.attention`` re-quantizes q once per such chunk)."""
    if sq <= q_chunk:
        return sq
    return next(c for c in range(q_chunk, 0, -1) if sq % c == 0)


def _fused_call(q, k, v, spec, cfg):
    """Fold batch into the kernel's head grid axis and GQA groups along the
    query rows (row r has position ``r % Sq`` via ``sq_mod``).

    Float inputs quantize on PER-SEQUENCE grids — k/v per batch row, q per
    (batch row, XLA query chunk) — exactly like the XLA int path, and the
    resulting (B*Hkv, nq) logit-scale matrix rides the kernel's
    scalar-prefetch stream so each bq-tile dequantizes with its own scale.
    This closes the pallas-vs-XLA granularity gap at Sq > q_chunk (no more
    single per-tensor scale papering over per-chunk recalibration) and
    makes batched ragged prefill bit-identical per row to solo runs.
    Pre-quantized QTensor operands keep their own single grid.  Narrow
    local windows (Sk > 2*window) are the one remaining divergence: the
    XLA path quantizes per-chunk key SLICES there while the kernel grids
    the full key row per sequence, so those shapes agree to ~one prob
    code, not bitwise (test_windowed_dispatch_straddling_blocks_close).
    """
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    g = hq // hkv
    out_dtype = q.dtype if not isinstance(q, quant.QTensor) else jnp.float32
    scale = spec.softmax_scale or (1.0 / d ** 0.5)
    quantized_in = any(isinstance(x, quant.QTensor) for x in (q, k, v))
    if quantized_in:
        qq, kq, vq = (_as_q(x, cfg.a_bits) for x in (q, k, v))
        sc = quant.pin(scale * LOG2E * qq.scale) * kq.scale   # as XLA's
        vs = vq.scale
        qf = qq.q.reshape(b, hkv, g, sq, d).reshape(b * hkv, g * sq, d)
        kf, vf = kq.q, vq.q
        bq, bk = attention_blocks(g * sq, sk, d, window=spec.window)
    else:
        c = chunk_len(sq, spec.q_chunk)
        n_ch = sq // c
        qr = q.reshape(b, hkv, g, n_ch, c, d)
        qsc = quant.absmax_scale(qr, cfg.a_bits, axis=(1, 2, 4, 5))
        qf = quant.quantize(qr, qsc, cfg.a_bits) \
            .reshape(b, hkv, g, sq, d).reshape(b * hkv, g * sq, d)
        ksc = quant.absmax_scale(k, cfg.a_bits, axis=(1, 2, 3))
        vsc = quant.absmax_scale(v, cfg.a_bits, axis=(1, 2, 3))
        kf = quant.quantize(k, ksc, cfg.a_bits)
        vf = quant.quantize(v, vsc, cfg.a_bits)
        bq, bk = attention_blocks(g * sq, sk, d, window=spec.window,
                                  chunk=c)
        # One scale per bq-tile: tile i covers positions
        # [(i*bq) % sq, +bq) of group (i*bq) // sq — inside one chunk
        # because bq divides c.  The per-chunk product is formed before the
        # gather, on the same operands as the XLA path's.
        nq = (g * sq) // bq
        tile_chunk = (np.arange(nq) * bq % sq) // c
        sc = quant.pin(scale * LOG2E * qsc.reshape(b, n_ch)) \
            * ksc.reshape(b, 1)
        sc = sc[:, tile_chunk]
        sc = jnp.repeat(sc, hkv, axis=0)            # (b*hkv, nq)
        vs = jnp.repeat(vsc.reshape(b), hkv)        # (b*hkv,)
    kf = kf.reshape(b * hkv, sk, d)
    vf = vf.reshape(b * hkv, sk, d)
    out = int_attention_fused(qf, kf, vf, sc, vs,
                              attn_bits=cfg.attn_bits, causal=spec.causal,
                              window=spec.window, bq=bq, bk=bk, sq_mod=sq)
    out = out.reshape(b, hkv, g, sq, d).reshape(b, hq, sq, d)
    return out.astype(out_dtype)


def _decode_call(q, k, v, spec, cfg, q_offset, k_positions):
    """One decode step on the ring-cache kernel.

    The cache's packed codes go to the kernel exactly as stored (int8, or
    int4 nibbles with ``packed=True``) — the in-place read the tentpole is
    about: no unpacked/dequantized HBM copy, and only live ring blocks are
    DMA'd.  ``q_offset`` is the (possibly traced) absolute query position.
    The ring path keeps its PER-TENSOR query grid (the whole batch shares
    one ring cache and scale; per-sequence isolation is the paged path's
    contract), matching the XLA fallback bit for bit.
    """
    b, hq, _, d = q.shape
    hkv, span = k.shape[1], k.shape[2]
    g = hq // hkv
    out_dtype = q.dtype if not isinstance(q, quant.QTensor) else jnp.float32
    qq, kq, vq = (_as_q(x, cfg.a_bits) for x in (q, k, v))
    packed = _is_packed(kq)
    scale = spec.softmax_scale or (1.0 / d ** 0.5)
    sc = quant.pin(scale * LOG2E * qq.scale) * kq.scale   # as XLA's
    qf = qq.q.reshape(b, hkv, g, d).reshape(b * hkv, g, d)
    kf = kq.q.reshape(b * hkv, span, -1)
    vf = vq.q.reshape(b * hkv, span, -1)
    bk = decode_blocks(span, d)
    out = int_decode_attention(qf, kf, vf, sc, vq.scale,
                               jnp.asarray(k_positions, jnp.int32),
                               q_offset, attn_bits=cfg.attn_bits,
                               causal=spec.causal, window=spec.window,
                               bk=bk, packed=packed)
    return out.reshape(b, hq, 1, d).astype(out_dtype)


# ---------------------------------------------------------------------------
# Paged attention: shared page pools + per-sequence page tables
# ---------------------------------------------------------------------------

def quantize_rows(x, bits):
    """Per-sequence (leading-axis) activation quantization.

    Returns (codes int8, scale (B,)).  Decode queries must be quantized per
    sequence — a per-tensor scale over the batch would let one tenant's hot
    activations coarsen every other tenant's grid (and break solo-vs-batch
    parity).
    """
    scale = quant.absmax_scale(x, bits, axis=tuple(range(1, x.ndim)))
    return quant.quantize(x, scale, bits), scale.reshape(x.shape[0])


def paged_query_grid(q, spec, cfg, k_scale=None):
    """Per-sequence query codes + folded per-row softmax scale.

    The ONE place the paged decode grid is derived: both the Pallas call
    below and the XLA gather fallback in ``layers.attention`` consume this,
    so the emitted prob codes are bit-identical across backends by
    construction.  ``k_scale=None`` leaves the key dequantization step OUT
    of the fold — the per-PHYSICAL-page scale path (prefix sharing), where
    the kernel/oracle resolve each page's own grid instead.
    """
    qq, qscale = quantize_rows(q, cfg.a_bits)
    scale = spec.softmax_scale or (1.0 / q.shape[-1] ** 0.5)
    sc = quant.pin(scale * LOG2E * qscale.astype(jnp.float32))
    if k_scale is not None:
        sc = sc * jnp.asarray(k_scale, jnp.float32).reshape(-1)
    return qq, sc


def paged_read_grid(q, spec, cfg, k_scale, v_scale, page_scaled: bool):
    """(query codes, per-row logit scale, per-row v scale) for a paged read.

    The one derivation BOTH backends share for both scale layouts: with
    per-page scale pools the k/v steps stay out of the per-row fold (the
    kernel/oracle resolve each page's own grid; the per-row v factor
    becomes 1), otherwise the per-sequence ``k_scale`` folds into the
    logit scale exactly as before.
    """
    if page_scaled:
        qq, sc = paged_query_grid(q, spec, cfg)
        return qq, sc, jnp.ones((q.shape[0],), jnp.float32)
    qq, sc = paged_query_grid(q, spec, cfg, k_scale)
    return qq, sc, v_scale


def paged_decode_supported(q, k_pages, spec, cfg, page_table, pos) -> bool:
    """Shape policy for the paged decode kernel.

    Sq must be 1 (GQA groups become query rows), pools/page table must be
    the ``models.lm`` paged-cache layout, and one page per block must fit
    the VMEM budget (:func:`paged_decode_blocks`).
    """
    if cfg.attn_bits > MAX_PROB_BITS:
        return False
    if getattr(cfg, "softmax", "base2") != "base2":
        return False
    if getattr(k_pages, "ndim", None) != 4 or page_table.ndim != 2:
        return False
    b, hq, sq, d = q.shape
    num_phys, hkv, page_size, dk = k_pages.shape
    if sq != 1 or d == 0 or hq % hkv:
        return False
    if k_pages.dtype == jnp.uint8 and (dk * 2 != d or d % 2):
        return False                      # nibble-packed pools need even D
    if k_pages.dtype != jnp.uint8 and dk != d:
        return False
    return paged_decode_blocks(page_size, d) > 0


def maybe_paged_attention(q, k_pages, v_pages, k_scale, v_scale, spec, cfg,
                          *, page_table, pos, k_page_scale=None,
                          v_page_scale=None):
    """Pallas-backed paged decode; ``None`` -> caller's XLA gather path."""
    if resolve_backend(cfg) == "pallas" and \
            paged_decode_supported(q, k_pages, spec, cfg, page_table, pos):
        STATS["attention_paged_pallas"] += 1
        return _paged_call(q, k_pages, v_pages, k_scale, v_scale, spec, cfg,
                           page_table, pos, k_page_scale, v_page_scale)
    STATS["attention_paged_xla"] += 1
    return None


def _paged_call(q, k_pages, v_pages, k_scale, v_scale, spec, cfg,
                page_table, pos, k_page_scale=None, v_page_scale=None):
    """One continuous-batching decode step on the paged kernel.

    The page pools go to the kernel exactly as stored (int8 codes or int4
    nibbles) and each sequence's scales stay its own: the per-row softmax
    scale folds ``dq[b] * dk[b]`` so no tenant's grid leaks into another's.
    With per-PHYSICAL-page scale pools (``k_page_scale``/``v_page_scale``,
    the prefix-sharing layout) the kernel resolves each page's own stored
    grid instead — a page shared from a prefix owner dequantizes with the
    OWNER's scales, never the reading tenant's — and only ``dq[b]`` folds
    into the per-row logit scale.
    """
    b, hq, _, d = q.shape
    hkv = k_pages.shape[1]
    g = hq // hkv
    qq, sc, vs = paged_read_grid(q, spec, cfg, k_scale, v_scale,
                                 k_page_scale is not None)
    out = int_paged_decode_attention(
        qq.reshape(b, hkv, g, d), k_pages, v_pages, sc, vs,
        page_table, pos, k_page_scale=k_page_scale,
        v_page_scale=v_page_scale, attn_bits=cfg.attn_bits,
        window=spec.window, packed=k_pages.dtype == jnp.uint8)
    return out.reshape(b, hq, 1, d).astype(q.dtype)
