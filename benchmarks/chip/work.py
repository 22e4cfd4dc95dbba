"""Operations and bytes per kernel call, computed from shapes.

The counts are of the algorithm's work, not of what an implementation
pads or repeats: real (unpadded) prompt tokens, active decode rows, the
keys a query may attend, the pages that hold them.  So a change that
replaces a kernel cannot make its roofline share read over 100%.

The per-kernel formulas are here; which kernels a model calls, how often
and at what sizes is its architecture module's (``arch/<name>.py``),
which :func:`kernel_calls` and :func:`model_ops` pass to.  The formulas
copy ``benchmarks/kernel_bench.py``'s analytic functions
(``qmatmul_analytic``: ``m*k + n*k*w_bytes + m*n*4`` bytes, ``m*n*k``
MACs; ``paged_step_analytic``: K and V bytes of each row's live pages;
``attention_macs``: two int8 contractions per query-key pair);
``tests/test_selfcheck.py`` holds them equal.  An operation is a
multiply or an add, so one MAC is two operations.
"""
from __future__ import annotations

import math

import arch


def qmatmul(m: int, n: int, k: int, w_bytes: float):
    """(ops, bytes) of an (m, k) int8 x (n, k) weight-code contraction
    with an f32 (m, n) result; ``w_bytes`` bytes per stored weight."""
    return 2 * m * n * k, m * k + n * k * w_bytes + m * n * 4


def paged_decode(pos_live, hq: int, hkv: int, d: int, page_size: int,
                 kv_bytes: float):
    """(ops, bytes) of one paged decode attention call over the rows whose
    live key counts are ``pos_live``: QK and PV over every live key, the
    int8 query, the K and V of each row's live pages, an f32 output."""
    ops = sum(2 * 2 * hq * d * n for n in pos_live)
    pages = sum(math.ceil(n / page_size) for n in pos_live)
    byts = (2 * hkv * pages * page_size * d * kv_bytes
            + len(pos_live) * hq * d * (1 + 4))
    return ops, byts


def causal_pairs(start: int, length: int) -> int:
    """Query-key pairs of ``length`` queries at positions ``start ..``
    attending every key up to their own position."""
    return start * length + length * (length + 1) // 2


def fused_prefill(lens, hq: int, hkv: int, d: int):
    """(ops, bytes) of one fused prefill attention call (chunks that start
    at 0) over rows of real lengths ``lens``: int8 Q, K, V in, f32 out."""
    ops = sum(2 * 2 * hq * d * causal_pairs(0, n) for n in lens)
    byts = sum((hq + 2 * hkv) * n * d + hq * n * d * 4 for n in lens)
    return ops, byts


def weight_bytes(quant: dict) -> float:
    """Bytes a stored weight takes: w4 codes packed two to a byte, else
    one code a byte."""
    return 0.5 if quant["pack_weights"] and quant["w_bits"] == 4 else 1.0


def decode_rows(call):
    """Live key counts of the active rows of a recorded decode call."""
    pos = call[2]
    return [int(p) + 1 for p in pos if p >= 0]


def prefill_rows(call):
    """(start, real lengths of the rows) of a recorded prefill launch;
    no lengths where the run did not read them back."""
    start, w, s, lens = call[2]
    if lens is None:                # lengths are read in traced runs only
        return start, []
    return start, [int(n) for n in lens if n > 0]


def kernel_calls(config: dict, calls, kernel: str):
    """Yield (ops, bytes) for every call of ``kernel`` that the program
    calls in ``calls`` made (``harness.Recorder`` entries), as the
    configuration's architecture module counts them."""
    return arch.of(config).kernel_calls(config, calls, kernel)


def model_ops(config: dict, calls) -> float:
    """Operations the model needs for the work in ``calls``, as the
    configuration's architecture module counts them."""
    return arch.of(config).model_ops(config, calls)
