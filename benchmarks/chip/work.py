"""Operations and bytes per kernel call, computed from shapes.

The counts are of the algorithm's work, not of what an implementation
pads or repeats: real (unpadded) prompt tokens, active decode rows, the
keys a query may attend, the pages that hold them.  So a change that
replaces a kernel cannot make its roofline share read over 100%.

The formulas copy ``benchmarks/kernel_bench.py``'s analytic functions
(``qmatmul_analytic``: ``m*k + n*k*w_bytes + m*n*4`` bytes, ``m*n*k``
MACs; ``paged_step_analytic``: K and V bytes of each row's live pages;
``attention_macs``: two int8 contractions per query-key pair);
``tests/test_selfcheck.py`` holds them equal.  An operation is a
multiply or an add, so one MAC is two operations.
"""
from __future__ import annotations

import math


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


def linears(model: dict):
    """(n, k) of every linear layer in one block, then of the head."""
    d, hd = model["d_model"], model["head_dim"]
    hq, hkv, ff = model["n_heads"] * hd, model["kv_heads"] * hd, model["d_ff"]
    block = [(hq, d), (hkv, d), (hkv, d), (d, hq), (ff, d), (ff, d), (d, ff)]
    return block, (model["vocab"], d)


def _decode_rows(call):
    pos = call[2]
    return [int(p) + 1 for p in pos if p >= 0]


def _prefill_rows(call):
    start, w, s, lens = call[2]
    if lens is None:                # lengths are read in traced runs only
        return start, []
    return start, [int(n) for n in lens if n > 0]


def kernel_calls(config: dict, calls, kernel: str):
    """Yield (ops, bytes) for every call of ``kernel`` that the program
    calls in ``calls`` made (``harness.Recorder`` entries)."""
    m, q, eng = config["model"], config["quant"], config["engine"]
    w_bytes = 0.5 if q["pack_weights"] and q["w_bits"] == 4 else 1.0
    kv_bytes = q["kv_bits"] / 8
    block, head = linears(m)
    hq, hkv, d, L = m["n_heads"], m["kv_heads"], m["head_dim"], m["n_layers"]
    for call in calls:
        if call[0] == "decode":
            live = _decode_rows(call)
            if not live:
                continue
            if kernel == "qmatmul":
                for _ in range(L):
                    for n, k in block:
                        yield qmatmul(len(live), n, k, w_bytes)
                yield qmatmul(len(live), *head, w_bytes)
            elif kernel == "paged_decode":
                for _ in range(L):
                    yield paged_decode(live, hq, hkv, d, eng["page_size"],
                                       kv_bytes)
        else:
            start, lens = _prefill_rows(call)
            if not lens:
                continue
            if kernel == "qmatmul":
                for _ in range(L):
                    for n, k in block:
                        yield qmatmul(sum(lens), n, k, w_bytes)
                yield qmatmul(len(lens), *head, w_bytes)
            elif kernel == "fused_prefill" and start == 0:
                for _ in range(L):
                    yield fused_prefill(lens, hq, hkv, d)


def model_ops(config: dict, calls) -> float:
    """Operations the model needs for the work in ``calls``: two per
    weight per token through every linear layer (the head only where the
    program takes logits), plus QK and PV over the keys each query may
    attend."""
    m = config["model"]
    block, head = linears(m)
    per_tok = 2 * sum(n * k for n, k in block) * m["n_layers"]
    head_ops = 2 * head[0] * head[1]
    att = 2 * 2 * m["n_heads"] * m["head_dim"] * m["n_layers"]
    total = 0.0
    for call in calls:
        if call[0] == "decode":
            live = _decode_rows(call)
            total += len(live) * (per_tok + head_ops) + att * sum(live)
        else:
            start, lens = _prefill_rows(call)
            total += sum(per_tok * n + att * causal_pairs(start, n)
                         for n in lens) + head_ops * len(lens)
    return total
