"""Kernel micro-benchmarks.

CPU container: wall-clock of the XLA integer paths (relative CPU numbers,
useful for regression tracking) plus the ANALYTIC v5e roofline time per
kernel call (bytes & MACs are exact functions of shape — this is the number
that matters for the TPU target).

``--json [PATH]`` additionally writes ``BENCH_kernels.json`` (default name)
with per-kernel timings, the attention kernel-design comparison (two-pass
vs single-pass analytic MXU MACs / HBM bytes), the DECODE section: a
real prefill+decode loop timed under both kernel backends (tok/s plus the
dispatch STATS proving the Pallas decode kernel actually served it) and
the analytic per-step bytes-read / MAC comparison of the in-place
ring-cache decode kernel vs the XLA fallback — and the PAGED section:
a timed multi-tenant continuous-batching loop through
``launch.engine.PagedEngine`` under both backends, the analytic
per-step KV bytes of the per-sequence paged kernel vs the contiguous
ring (which always streams the batch-max live span for every row), and
the ADMISSION section: a timed N-arrival admission drain, burst (one
batched prefill, the PR-4 path) vs the same N arrivals dripped one per
drain (the PR-3 cost model: N batch=1 prefills), both backends with
pre-warmed jits.  The LATENCY section measures decode inter-token
latency while a burst admits: one-shot admission prefill (stall = the
whole prompt) vs the chunked-prefill token-budget scheduler (stall
bounded by the budget), with the analytic per-step token bound riding
the ``--check`` guard.  The loops' ``stats`` snapshots also carry
``STATS["blocks"]`` — the dispatch layer's chosen tile sizes per shape,
the baseline a future measured autotuner diffs against.
``--quick`` restricts to the smallest shapes (CI-sized run).

``--check [PATH]`` loads a previous ``--json`` dump and exits nonzero if
any analytic bytes/step or MAC count regressed (wall-clocks excluded —
CPU noise).  No timed loops run, so it is fast enough for the ``smoke``
pre-push subset (see pytest.ini).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.core.integerize import int_linear, make_qlinear
from repro.kernels import ref as kref
from repro.kernels.int_attention import attention_macs

PEAK_INT8 = 394e12
PEAK_BF16 = 197e12
HBM = 819e9

# Shapes shared by run() and the --check analytic recomputation.
ATTN_DESIGN_SHAPE = (4, 1024, 64)
DECODE_SHAPES = [
    (8, 4, 8192, 1024, 128, 8),
    (8, 4, 8192, 1024, 128, 4),
    (8, 4, 8192, 512, 128, 8),       # window=512
]
PAGED_SHAPES = [
    (8, 4, 128, [127, 1023, 8191], 128, 8),
    (8, 4, 128, [127, 1023, 8191], 128, 4),
    (8, 4, 256, [255, 255, 255, 16383], 128, 8),
]
# (n_requests, prefix_tokens, tail_tokens, page_size, hkv, d, kv_bits)
PREFIX_SHAPES = [
    (8, 2048, 128, 128, 8, 128, 8),      # system-prompt-heavy chat traffic
    (8, 2048, 128, 128, 8, 128, 4),
    (32, 8192, 256, 128, 8, 128, 8),     # long shared context, many tenants
]
# (prompt_tokens, tokens_generated_at_preemption, max_new_tokens,
#  page_size, hkv, d, kv_bits)
PREEMPT_SHAPES = [
    (2048, 64, 256, 128, 8, 128, 8),     # preempted early in generation
    (2048, 64, 256, 128, 8, 128, 4),
    (8192, 192, 256, 128, 8, 128, 8),    # long context, deep into decode
]
# (prompt_tokens, chunk_tokens, prefill_budget_tokens)
LATENCY_SHAPES = [
    (96, 16, 16),                        # the timed-loop scenario below
    (2048, 128, 256),                    # chat prompt under serving budget
    (8192, 256, 256),                    # long-context admission
]


def _time(f, *args, n=20):
    # Warmup/compile: evaluate ONCE (a second eval here used to skew the
    # denominator-free first measurement).
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def qmatmul_analytic(m, n, k, w_bits=8):
    macs = m * n * k
    bytes_ = m * k + n * k * (w_bits / 8) + m * n * 4
    return {"t_compute_us": macs * 2 / PEAK_INT8 * 1e6,
            "t_memory_us": bytes_ / HBM * 1e6,
            "macs": macs}


def attention_design_analytic(h, s, d, *, bq=256):
    """Two-pass vs single-pass fused kernel: exact per-call MXU MACs and
    K/V-tile HBM traffic (K re-read once per query block in each pass)."""
    nq = -(-s // bq)
    kv_bytes = h * s * d                       # one int8 K (or V) sweep
    return {
        "h": h, "s": s, "d": d,
        "two_pass_macs": attention_macs(h, s, s, d, design="two_pass"),
        "single_pass_macs": attention_macs(h, s, s, d, design="single"),
        "two_pass_kv_hbm_bytes": nq * (2 * kv_bytes + kv_bytes),  # K,K,V
        "single_pass_kv_hbm_bytes": nq * 2 * kv_bytes,            # K,V
        "v5e_two_pass_compute_us":
            attention_macs(h, s, s, d, design="two_pass")
            * 2 / PEAK_INT8 * 1e6,
        "v5e_single_pass_compute_us":
            attention_macs(h, s, s, d, design="single")
            * 2 / PEAK_INT8 * 1e6,
    }


def decode_step_analytic(h, g, span, live, d, kv_bits, *, bk=None):
    """Per-decode-step K/V HBM bytes and MXU MACs: XLA fallback vs the
    in-place ring-cache decode kernel.

    The XLA path reads the whole ``span``-slot ring every step (and for a
    nibble-packed cache first writes+reads an unpacked int8 copy); the
    Pallas kernel DMAs only ring blocks holding a live key, in the stored
    width, unpacking nibbles on the VPU.  ``pallas_bytes_per_step`` models
    the unwrapped filling-up phase (live slots are the ring prefix, so
    ``ceil(live/bk)`` blocks); ``pallas_bytes_per_step_wrapped`` is the
    worst case after wrap-around, where the live span can straddle one
    extra block boundary.  The two-pass design would additionally re-read
    K per step (3 sweeps).
    """
    from repro.kernels.dispatch import decode_blocks
    bk = bk or decode_blocks(span, d)
    unit = kv_bits / 8
    n_live = -(-live // bk)
    touched = min(n_live * bk, span)
    touched_wrapped = min((n_live + 1) * bk, span)
    xla_bytes = 2 * h * span * d * unit
    if kv_bits == 4:
        xla_bytes += 2 * 2 * h * span * d      # unpacked int8 copy: w + r
    return {
        "h": h, "g": g, "span": span, "live": live, "d": d,
        "kv_bits": kv_bits, "bk": bk,
        "xla_bytes_per_step": int(xla_bytes),
        "pallas_bytes_per_step": int(2 * h * touched * d * unit),
        "pallas_bytes_per_step_wrapped":
            int(2 * h * touched_wrapped * d * unit),
        "xla_macs_per_step": attention_macs(h, g, span, d, design="single"),
        "decode_macs_per_step": attention_macs(h, g, touched, d,
                                               design="decode"),
        "two_pass_macs_per_step": attention_macs(h, g, span, d,
                                                 design="two_pass"),
    }


def paged_step_analytic(h, g, page_size, pos_list, d, kv_bits):
    """Per-decode-step K/V HBM bytes: paged kernel vs contiguous ring.

    The paged kernel DMAs ``ceil((pos_b + 1) / page_size)`` pages for row b
    — proportional to THAT sequence's live keys.  A contiguous per-batch
    ring (PR 2) must size every row's span to the batch max sequence, so a
    ragged batch pays ``max_len`` per row; the XLA paged fallback gathers
    the same live pages (equal bytes) but materializes an unpacked copy
    for int4.  MACs scale identically (2 int8 contractions per live key).
    """
    unit = kv_bits / 8
    live_pages = [p // page_size + 1 for p in pos_list]
    paged_bytes = sum(2 * h * n * page_size * d * unit for n in live_pages)
    ring_span = max(p + 1 for p in pos_list)
    ring_bytes = len(pos_list) * 2 * h * ring_span * d * unit
    return {
        "h": h, "g": g, "page_size": page_size, "pos": list(pos_list),
        "d": d, "kv_bits": kv_bits,
        "paged_bytes_per_step": int(paged_bytes),
        "ring_bytes_per_step": int(ring_bytes),
        "ring_over_paged": ring_bytes / max(paged_bytes, 1),
        "paged_macs_per_step": sum(
            attention_macs(h, g, n * page_size, d, design="decode")
            for n in live_pages),
        "ring_macs_per_step": len(pos_list) * attention_macs(
            h, g, ring_span, d, design="decode"),
    }


def prefix_burst_analytic(n, prefix, tail, page_size, hkv, d, kv_bits):
    """N same-prefix admissions, shared vs unshared: prefill token work,
    KV bytes written into the pool (per attention layer) and pool pages
    consumed.

    Unshared, every request prefills prefix + tail and owns all its pages;
    with prefix sharing the prefix prefills ONCE into ``ceil(prefix/ps)``
    refcounted pages that all N page tables alias, so prefill work drops
    to ``prefix + n * tail`` tokens and the pool holds ``(n - 1) * P``
    more tenants' worth of pages.  (Worst case — a non-page-aligned
    breakpoint — adds one CoW page copy per sharer; the aligned numbers
    here are the guarded lower bound.)
    """
    unit = kv_bits / 8
    p_pages = -(-prefix // page_size)
    t_pages = -(-tail // page_size)
    page_bytes = 2 * hkv * page_size * d * unit          # K + V, one layer
    unshared_pages = n * (p_pages + t_pages)
    shared_pages = p_pages + n * t_pages
    return {
        "n": n, "prefix": prefix, "tail": tail, "page_size": page_size,
        "hkv": hkv, "d": d, "kv_bits": kv_bits,
        "unshared_prefill_tokens": n * (prefix + tail),
        "shared_prefill_tokens": prefix + n * tail,
        "unshared_pages_consumed": unshared_pages,
        "shared_pages_consumed": shared_pages,
        "unshared_kv_bytes_written": int(unshared_pages * page_bytes),
        "shared_kv_bytes_written": int(shared_pages * page_bytes),
        "pages_saved": unshared_pages - shared_pages,
        "admission_capacity_gain": unshared_pages / max(shared_pages, 1),
    }


def preempt_resume_analytic(prompt, gen, max_new, page_size, hkv, d,
                            kv_bits):
    """Victim preemption economics: pages recovered per preemption vs the
    recompute bill of the bit-exact resume.

    Preempting a victim returns its whole worst-case reservation
    (``ceil((prompt + max_new)/ps)`` pages; shared prefix pages would stay
    pinned — this is the conservative unshared bound).  The resume
    re-prefills the PROMPT (one admission prefill, a pure function of the
    prompt so codes/scales land bit-identically) and replays the ``gen``
    already-generated tokens through the ordinary decode step — so the
    KV bytes rewritten per attention layer are exactly the bytes the
    victim held, and the token bill is ``prompt + gen`` with zero new
    sampling work.  Fields here are shape-derived lower bounds, guarded by
    --check (a scheme change that rewrites more bytes or recomputes more
    tokens per preemption is a regression).
    """
    unit = kv_bits / 8
    pages = -(-(prompt + max_new) // page_size)
    tok_bytes = 2 * hkv * d * unit               # K + V, one token, 1 layer
    return {
        "prompt": prompt, "gen": gen, "max_new": max_new,
        "page_size": page_size, "hkv": hkv, "d": d, "kv_bits": kv_bits,
        "pages_recovered_per_preemption": pages,
        "resume_recompute_tokens": prompt + gen,
        "resume_replay_steps": gen,
        "resume_kv_bytes_rewritten": int((prompt + gen) * tok_bytes),
        "steal_bytes_freed": int(pages * page_size * tok_bytes),
        # recompute bytes per freed byte: < 1 means preemption is cheaper
        # than the capacity it returns (it always is while gen << max_len)
        "rewrite_per_freed_byte": (prompt + gen) / (pages * page_size),
    }


def burst_latency_analytic(prompt, chunk, budget):
    """Inter-token stall while a prompt admits: one-shot vs budgeted.

    With one-shot admission prefill, every running decode stalls for the
    WHOLE prompt (the serial prefill blocks the step).  Chunked prefill
    under a token budget bounds the prompt tokens interleaved into any
    single step by ``max(chunk, budget floored to whole chunks)`` (the
    packer's floor of one chunk per step), so the worst-case inter-token
    stall is a constant set by configuration, not by the longest arrival.
    ``budgeted_max_tokens_per_step`` is the guarded bound: a scheduler
    change that lets more prompt tokens into one step is a latency
    regression.
    """
    c = max(1, min(chunk, prompt))
    per_step = min(max(c, budget - budget % c), prompt)
    return {
        "prompt": prompt, "chunk": chunk, "budget": budget,
        "oneshot_stall_tokens": prompt,
        "budgeted_max_tokens_per_step": per_step,
        "prefill_steps": -(-prompt // per_step),
        "stall_reduction": prompt / per_step,
    }


def _bench_lm():
    """One smoke LM + integerized params shared by the timed loops."""
    from repro.core.api import QuantConfig, integerize_params
    from repro.models import lm

    qc = QuantConfig(w_bits=8, a_bits=8, attn_bits=7, mode="int")
    cfg = lm.LMConfig(name="bench", n_layers=2, d_model=64, n_heads=4,
                      kv_heads=2, d_ff=128, vocab=128, dtype="float32",
                      q_chunk=16, remat=False, quant=qc)
    params = integerize_params(
        lm.init_params(jax.random.PRNGKey(0), cfg.replace(quant=None)), qc)
    return cfg, params


def admission_burst(quick=False):
    """Timed N-arrival admission drain: burst vs one-at-a-time.

    Burst submits all N same-bucket requests before one drain — ONE
    batched admission prefill writes every prompt's KV codes straight into
    the shared pools (the PR-4 path).  Serial feeds the same requests one
    drain at a time — N batch-width-1 prefills, the PR-3 cost model (its
    page-copy pass excluded, so the measured speedup is conservative).
    Jits are pre-warmed and shared, so wall-clocks compare drain work, not
    compile time; ``prefill_calls`` proves the batching.
    """
    import numpy as np

    from repro.kernels import dispatch
    from repro.launch.engine import PagedEngine, Request

    cfg, params = _bench_lm()
    n = 2 if quick else 4
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab, 12).astype(np.int32)
               for _ in range(n)]

    def engine(share_from=None):
        eng = PagedEngine(cfg, params, batch_size=n, max_len=32,
                          page_size=8, prefill_buckets=(16,))
        if share_from is not None:      # same cfg/params: traces are reusable
            eng._step = share_from._step
            eng._admit_prefill = share_from._admit_prefill
        return eng

    def reqs():
        return [Request(rid=i, prompt=p, max_new_tokens=1)
                for i, p in enumerate(prompts)]

    res = {}
    for backend in ("xla", "pallas"):
        with dispatch.use_backend(backend):
            warm = engine()
            warm.run(reqs())                        # compiles the W=n trace
            drip_warm = engine(warm)
            for r in reqs():                        # compiles the W=1 trace
                drip_warm.submit(r)
                drip_warm.step()

            burst = engine(warm)
            for r in reqs():
                burst.submit(r)
            t0 = time.perf_counter()
            burst._drain_queue()
            jax.block_until_ready(burst.cache)
            burst_s = time.perf_counter() - t0

            serial = engine(warm)
            t0 = time.perf_counter()
            for r in reqs():
                serial.submit(r)
                serial._drain_queue()
            jax.block_until_ready(serial.cache)
            serial_s = time.perf_counter() - t0

            res[backend] = {
                "requests": n,
                "burst_drain_s": burst_s,
                "serial_drain_s": serial_s,
                "burst_speedup": serial_s / max(burst_s, 1e-9),
                "prefill_calls_burst": burst.prefill_calls,
                "prefill_calls_serial": serial.prefill_calls,
            }
    return res


def prefix_burst(quick=False):
    """Timed N same-prefix admission drain: shared vs unshared.

    N requests carrying one system prompt, either declaring it as a cache
    breakpoint (``Request.prefix_len`` — 1 prefix prefill + 1 batched tail
    prefill, prefix pages aliased refcounted) or not (the PR-4 path: one
    batched full prefill, every request owning private prefix pages).
    Wall-clocks are relative CPU numbers; the counters (prefill calls,
    prefix prefills, pool pages in use) and the analytic section above
    carry the real story.  Jits are pre-warmed so drains compare work, not
    compile time.
    """
    import numpy as np

    from repro.kernels import dispatch
    from repro.launch.engine import PagedEngine, Request

    cfg, params = _bench_lm()
    n = 2 if quick else 4
    ps, plen = 8, 16
    rng = np.random.RandomState(0)
    prefix = rng.randint(0, cfg.vocab, plen).astype(np.int32)
    tails = [rng.randint(0, cfg.vocab, 6).astype(np.int32) for _ in range(n)]

    def engine(share_from=None):
        eng = PagedEngine(cfg, params, batch_size=n, max_len=48,
                          page_size=ps, prefill_buckets=(16, 32))
        if share_from is not None:
            eng._step = share_from._step
            eng._admit_prefill = share_from._admit_prefill
        return eng

    def reqs(shared):
        # max_new 2: rows stay admitted after the drain (a 1-token request
        # finishes AT prefill), so pages_in_use reflects real occupancy
        return [Request(rid=i, prompt=np.concatenate([prefix, t]),
                        max_new_tokens=2,
                        prefix_len=plen if shared else 0)
                for i, t in enumerate(tails)]

    res = {}
    for backend in ("xla", "pallas"):
        with dispatch.use_backend(backend):
            warm = engine()
            warm.run(reqs(True))                 # compile prefix+tail traces
            warm2 = engine(warm)
            warm2.run(reqs(False))               # compile the unshared trace

            out = {}
            for mode, shared in (("shared", True), ("unshared", False)):
                eng = engine(warm)
                for r in reqs(shared):
                    eng.submit(r)
                t0 = time.perf_counter()
                eng._drain_queue()
                jax.block_until_ready(eng.cache)
                out[mode] = {
                    "drain_s": time.perf_counter() - t0,
                    "prefill_calls": eng.prefill_calls,
                    "prefix_prefills": eng.prefix_prefills,
                    "pages_in_use": eng.num_pages - eng.alloc.free_count,
                }
            out["requests"] = n
            out["prefix_tokens"] = plen
            out["pages_saved"] = (out["unshared"]["pages_in_use"]
                                  - out["shared"]["pages_in_use"])
            res[backend] = out
    return res


def preempt_loop(quick=False):
    """Timed victim preemption + bit-exact resume under both backends.

    A victim decodes on a pool sized for exactly one tenant; a
    high-priority arrival forces the engine to preempt it (steal latency =
    the drain that evicts the victim and admits the newcomer, measured on
    the host — it is pure allocator work plus the newcomer's prefill).
    After the newcomer finishes the victim readmits: one prompt
    re-prefill plus recorded-token replay through the shared decode step.
    ``bit_identical`` asserts the acceptance bar (resumed stream ==
    uninterrupted run); pages_recovered and the resume token bill are the
    measured counterparts of ``preempt_resume_analytic``.
    """
    import numpy as np

    from repro.kernels import dispatch
    from repro.launch.engine import PagedEngine, Request

    cfg, params = _bench_lm()
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg.vocab, 16).astype(np.int32)
    hi_prompt = rng.randint(0, cfg.vocab, 16).astype(np.int32)
    gen = 6 if quick else 8                  # victim still mid-flight when
    steps_before = 2 if quick else 3         # the high-priority rival lands
    kw = dict(batch_size=2, max_len=32, page_size=8, prefill_buckets=(16,))

    res = {}
    for backend in ("xla", "pallas"):
        with dispatch.use_backend(backend):
            dispatch.reset_stats()
            base = PagedEngine(cfg, params, **kw)
            probe = Request(rid=0, prompt=prompt, max_new_tokens=gen)
            base.run([probe])                   # warm traces + baseline

            # 3 pages = one (16 + 8)/8 tenant: admission MUST preempt.
            eng = PagedEngine(cfg, params, **{**kw, "num_pages": 3},
                              preempt_after_steps=1)
            eng._step = base._step
            eng._admit_prefill = base._admit_prefill
            victim = Request(rid=1, prompt=prompt, max_new_tokens=gen)
            eng.submit(victim)
            for _ in range(steps_before):
                eng.step()
            held = sum(len(p) for p in eng.row_pages)
            assert held > 0 and not victim.done   # genuinely mid-flight
            hi = Request(rid=2, prompt=hi_prompt, max_new_tokens=2,
                         priority=5)
            eng.submit(hi)
            t0 = time.perf_counter()
            eng._drain_queue()                  # preempt + admit + prefill
            jax.block_until_ready(eng.cache)
            steal_s = time.perf_counter() - t0
            replay = len(victim.tokens)
            t0 = time.perf_counter()
            while eng.step():
                pass
            resume_s = time.perf_counter() - t0
            assert victim.done and hi.done
            res[backend] = {
                "preemptions": eng.preempt_count,
                "resumes": eng.resume_count,
                # no sharing here: the whole reservation comes back
                "pages_recovered": held,
                "steal_latency_ms": steal_s * 1e3,
                "resume_recompute_tokens": len(prompt) + replay,
                "resume_replay_steps": replay,
                "resume_s": resume_s,
                "bit_identical": victim.tokens == probe.tokens,
                "stats": {k: dispatch.STATS[k]
                          for k in ("preemptions", "resumes")},
            }
    return res


def burst_latency(quick=False):
    """Timed inter-token latency of a running decode through a burst.

    A foreground request decodes while two long prompts arrive, under two
    schedulers on the same engine: one-shot admission prefill (the
    pre-chunking path — the whole burst prefills inside one step, so the
    foreground stalls for prompt-length work) and chunked prefill under a
    16-token/step budget (the stall is bounded by the budget).  Per-step
    wall p50/p99 while the foreground runs are the latency story (CPU
    numbers — relative only); the structural counters are the guarantees:
    ``budgeted`` never spends more prompt tokens in one step than the
    analytic bound, ``oneshot`` provably spends the whole burst in one,
    and the foreground's tokens are bit-identical under both schedulers
    (chunking is invisible in the streams).  Jits pre-warmed per arm.
    """
    import numpy as np

    from repro.kernels import dispatch
    from repro.launch.engine import PagedEngine, Request

    cfg, params = _bench_lm()
    rng = np.random.RandomState(0)
    long_len = 48 if quick else 96
    chunk = budget = 16
    fg_prompt = rng.randint(0, cfg.vocab, 8).astype(np.int32)
    long_prompts = [rng.randint(0, cfg.vocab, long_len).astype(np.int32)
                    for _ in range(2)]
    arms = {
        "oneshot": dict(prefill_buckets=(long_len,)),
        "budgeted": dict(prefill_buckets=(chunk,), prefill_chunk=chunk,
                         prefill_budget=budget),
    }

    def run_arm(mode, share_from=None):
        eng = PagedEngine(cfg, params, batch_size=3,
                          max_len=long_len + 32, page_size=8, **arms[mode])
        if share_from is not None:
            eng._step = share_from._step
            eng._admit_prefill = share_from._admit_prefill
        fg = Request(rid=0, prompt=fg_prompt, max_new_tokens=24)
        eng.submit(fg)
        eng.step()                               # fg admitted, decoding
        for i, p in enumerate(long_prompts):
            eng.submit(Request(rid=1 + i, prompt=p, max_new_tokens=2))
        dts, spends = [], []
        while not fg.done:
            t0 = time.perf_counter()
            s0 = eng.prefill_tokens
            if not eng.step():
                break
            dts.append(time.perf_counter() - t0)
            spends.append(eng.prefill_tokens - s0)
        while eng.step():
            pass
        return eng, fg, dts, spends

    bound = burst_latency_analytic(long_len, chunk, budget)[
        "budgeted_max_tokens_per_step"]
    res = {}
    for backend in ("xla", "pallas"):
        with dispatch.use_backend(backend):
            out = {"long_len": long_len, "chunk": chunk, "budget": budget,
                   "requests": len(long_prompts)}
            fg_tokens = {}
            for mode in arms:
                warm, _, _, _ = run_arm(mode)    # compile the arm's traces
                eng, fg, dts, spends = run_arm(mode, warm)
                assert fg.done and not fg.failed
                dts.sort()
                fg_tokens[mode] = list(fg.tokens)
                out[mode] = {
                    "p50_step_ms": dts[len(dts) // 2] * 1e3,
                    "p99_step_ms": dts[int(len(dts) * 0.99)] * 1e3,
                    "max_prefill_tokens_step": max(spends, default=0),
                    "prefill_chunks": eng.prefill_chunks,
                }
            # the structural guarantees (wall-clock-free)
            out["budget_bounded"] = \
                out["budgeted"]["max_prefill_tokens_step"] <= bound
            out["oneshot_stalls_whole_burst"] = \
                out["oneshot"]["max_prefill_tokens_step"] >= long_len
            out["fg_bit_identical"] = \
                fg_tokens["oneshot"] == fg_tokens["budgeted"]
            res[backend] = out
    return res


def paged_loop(quick=False):
    """Timed multi-tenant continuous-batching loop under both backends.

    Staggered prompts through ``launch.engine.PagedEngine`` (admits/evicts
    mid-run); CPU wall-clocks again matter only relatively — the dispatch
    STATS prove the Pallas paged kernel served the decode, the analytic
    bytes above carry the v5e story.
    """
    import numpy as np

    from repro.kernels import dispatch
    from repro.launch.engine import PagedEngine, Request

    cfg, params = _bench_lm()
    rng = np.random.RandomState(0)
    lens = [5, 11] if quick else [5, 11, 17, 8]
    gen = 2 if quick else 4
    res = {}
    for backend in ("xla", "pallas"):
        with dispatch.use_backend(backend):
            dispatch.reset_stats()
            reqs = [Request(rid=i,
                            prompt=rng.randint(0, cfg.vocab,
                                               n).astype(np.int32),
                            max_new_tokens=gen)
                    for i, n in enumerate(lens)]
            eng = PagedEngine(cfg, params, batch_size=2, max_len=32,
                              page_size=8, prefill_buckets=(32,))
            t0 = time.perf_counter()
            eng.run(reqs)
            dt = time.perf_counter() - t0
            res[backend] = {
                "requests": len(reqs), "engine_steps": eng.step_count,
                "prefill_calls": eng.prefill_calls,
                "tok_per_s": sum(len(r.tokens) for r in reqs) / dt,
                "stats": dispatch.snapshot()}
    return res


def decode_loop(quick=False):
    """Timed prefill + decode loop on a smoke LM under both backends.

    CPU wall-clocks (interpret-mode Pallas is slow by design — the number
    that matters is the dispatch STATS and the analytic bytes above); kept
    tiny so it runs in CI.
    """
    from repro.kernels import dispatch
    from repro.models import lm

    cfg, params = _bench_lm()
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    gen = 2 if quick else 8
    res = {}
    for backend in ("xla", "pallas"):
        with dispatch.use_backend(backend):
            dispatch.reset_stats()
            step = jax.jit(lambda p, t, c: lm.decode_step(p, t, c, cfg))
            logits, cache = lm.prefill(params, {"tokens": toks}, cfg,
                                       max_len=32)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            logits, cache = step(params, tok, cache)     # warmup/compile
            jax.block_until_ready(logits)
            t0 = time.perf_counter()
            for _ in range(gen):
                logits, cache = step(params, tok, cache)
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
            jax.block_until_ready(tok)
            dt = time.perf_counter() - t0
            res[backend] = {"tok_per_s": toks.shape[0] * gen / dt,
                            "stats": dispatch.snapshot()}
    return res


def run(quick=False):
    key = jax.random.PRNGKey(0)
    rows = []

    # Reordered integer linear vs float linear (XLA paths, CPU).
    shapes = [(256, 1024, 1024)]
    if not quick:
        shapes.append((1024, 4096, 4096))
    for m, n, k in shapes:
        x = jax.random.normal(key, (m, k))
        w = jax.random.normal(jax.random.fold_in(key, 1), (k, n)) * 0.02
        p = make_qlinear(w.T, None, 8)
        xq = quant.quantize_tensor(x, 8)
        f_int = jax.jit(lambda xq, p: int_linear(xq, p))
        f_fp = jax.jit(lambda x, w: x @ w)
        us_int = _time(f_int, xq, p)
        us_fp = _time(f_fp, x, w)
        ana = qmatmul_analytic(m, n, k)
        rows.append({"name": f"int_linear_{m}x{n}x{k}", "wall_us": us_int,
                     "wall_us_fp32": us_fp, **ana})

    # pq-layernorm fused vs LN-then-quant (XLA, CPU).
    x = jax.random.normal(key, (4096, 1024))
    g = jnp.ones((1024,))
    b = jnp.zeros((1024,))
    f_fused = jax.jit(lambda x: kref.pq_layernorm_ref(x, g, b, 0.05, bits=4))
    rows.append({"name": "pq_layernorm_4096x1024",
                 "wall_us": _time(f_fused, x),
                 "t_memory_us": (x.size * 4 + x.size) / HBM * 1e6})

    # int attention (XLA ref path) + kernel-design analytics.
    h, s, d = ATTN_DESIGN_SHAPE
    qq = jax.random.randint(key, (h, s, d), -8, 8).astype(jnp.int8)
    f_attn = jax.jit(lambda q: kref.int_attention_ref(q, q, q, 0.002, 0.01))
    us_attn = _time(f_attn, qq, n=2 if quick else 5)
    design = attention_design_analytic(h, s, d)
    rows.append({"name": f"int_attention_h{h}_s{s}", "wall_us": us_attn,
                 "macs": attention_macs(h, s, s, d),
                 "t_compute_us": design["v5e_single_pass_compute_us"]})

    # Decode: in-place ring-cache kernel vs XLA fallback (serving shapes:
    # long full ring early in decode, and a windowed ring).
    decode = {
        "analytic": [decode_step_analytic(*sh) for sh in DECODE_SHAPES],
        "loop": decode_loop(quick=quick),
    }

    # Paged multi-tenant decode: per-sequence pages vs the batch-max ring;
    # admission: batched burst prefill vs one-at-a-time; prefix: N
    # same-prefix admissions shared (1 prefix prefill, aliased pages) vs
    # unshared.
    paged = {
        "analytic": [paged_step_analytic(*sh) for sh in PAGED_SHAPES],
        "loop": paged_loop(quick=quick),
        "admission": admission_burst(quick=quick),
        "prefix": {
            "analytic": [prefix_burst_analytic(*sh)
                         for sh in PREFIX_SHAPES],
            "burst": prefix_burst(quick=quick),
        },
        # failure handling: pages recovered per victim preemption vs the
        # bit-exact resume recompute bill, analytic + timed on both
        # backends (steal latency, replay cost, parity flag).
        "preemption": {
            "analytic": [preempt_resume_analytic(*sh)
                         for sh in PREEMPT_SHAPES],
            "loop": preempt_loop(quick=quick),
        },
        # chunked prefill: inter-token stall under an arrival burst,
        # one-shot admission vs the token-budget packer (analytic bound
        # + timed foreground-decode p50/p99 on both backends).
        "latency": {
            "analytic": [burst_latency_analytic(*sh)
                         for sh in LATENCY_SHAPES],
            "loop": burst_latency(quick=quick),
        },
    }
    return rows, design, decode, paged


# ---------------------------------------------------------------------------
# Regression guard (--check)
# ---------------------------------------------------------------------------

# Analytic fields where a larger value is strictly worse (bytes / MACs).
GUARDED_DESIGN = ("single_pass_macs", "single_pass_kv_hbm_bytes")
GUARDED_DECODE = ("pallas_bytes_per_step", "pallas_bytes_per_step_wrapped",
                  "decode_macs_per_step")
GUARDED_PAGED = ("paged_bytes_per_step", "paged_macs_per_step")
GUARDED_PREFIX = ("shared_prefill_tokens", "shared_pages_consumed",
                  "shared_kv_bytes_written")
GUARDED_PREEMPT = ("resume_recompute_tokens", "resume_kv_bytes_rewritten")
GUARDED_LATENCY = ("budgeted_max_tokens_per_step",)


def analytic_payload():
    """The shape-derived (timer-free) subset of the --json payload."""
    return {
        "attention_design": attention_design_analytic(*ATTN_DESIGN_SHAPE),
        "decode": {"analytic": [decode_step_analytic(*sh)
                                for sh in DECODE_SHAPES]},
        "paged": {"analytic": [paged_step_analytic(*sh)
                               for sh in PAGED_SHAPES],
                  "prefix": {"analytic": [prefix_burst_analytic(*sh)
                                          for sh in PREFIX_SHAPES]},
                  "preemption": {"analytic": [preempt_resume_analytic(*sh)
                                              for sh in PREEMPT_SHAPES]},
                  "latency": {"analytic": [burst_latency_analytic(*sh)
                                           for sh in LATENCY_SHAPES]}},
    }


def check_regressions(cur, prev):
    """Regressions (new > old) in analytic bytes/step or MAC counts.

    Entries are matched by shape key, so adding/removing shapes never
    trips the guard; wall-clocks are never compared (CPU noise).
    """
    regs = []
    pd = prev.get("attention_design", {})
    for k in GUARDED_DESIGN:
        if k in pd and cur["attention_design"][k] > pd[k]:
            regs.append(f"attention_design.{k}: "
                        f"{pd[k]} -> {cur['attention_design'][k]}")

    def by_key(entries, fields):
        return {tuple(str(e[f]) for f in fields): e for e in entries}

    dkey = ("span", "live", "d", "kv_bits")
    prev_d = by_key(prev.get("decode", {}).get("analytic", []), dkey)
    for e in cur["decode"]["analytic"]:
        old = prev_d.get(tuple(str(e[f]) for f in dkey))
        for k in GUARDED_DECODE:
            if old and e[k] > old[k]:
                regs.append(f"decode[span={e['span']},live={e['live']},"
                            f"kv={e['kv_bits']}].{k}: {old[k]} -> {e[k]}")
    pkey = ("page_size", "pos", "d", "kv_bits")
    prev_p = by_key(prev.get("paged", {}).get("analytic", []), pkey)
    for e in cur["paged"]["analytic"]:
        old = prev_p.get(tuple(str(e[f]) for f in pkey))
        for k in GUARDED_PAGED:
            if old and e[k] > old[k]:
                regs.append(f"paged[ps={e['page_size']},pos={e['pos']}]."
                            f"{k}: {old[k]} -> {e[k]}")
    xkey = ("n", "prefix", "tail", "page_size", "kv_bits")
    prev_x = by_key(prev.get("paged", {}).get("prefix", {})
                    .get("analytic", []), xkey)
    for e in cur["paged"]["prefix"]["analytic"]:
        old = prev_x.get(tuple(str(e[f]) for f in xkey))
        for k in GUARDED_PREFIX:
            if old and e[k] > old[k]:
                regs.append(f"prefix[n={e['n']},prefix={e['prefix']}]."
                            f"{k}: {old[k]} -> {e[k]}")
    mkey = ("prompt", "gen", "max_new", "page_size", "kv_bits")
    prev_m = by_key(prev.get("paged", {}).get("preemption", {})
                    .get("analytic", []), mkey)
    for e in cur["paged"]["preemption"]["analytic"]:
        old = prev_m.get(tuple(str(e[f]) for f in mkey))
        for k in GUARDED_PREEMPT:
            if old and e[k] > old[k]:
                regs.append(f"preemption[prompt={e['prompt']},"
                            f"gen={e['gen']},kv={e['kv_bits']}]."
                            f"{k}: {old[k]} -> {e[k]}")
    lkey = ("prompt", "chunk", "budget")
    prev_l = by_key(prev.get("paged", {}).get("latency", {})
                    .get("analytic", []), lkey)
    for e in cur["paged"]["latency"]["analytic"]:
        old = prev_l.get(tuple(str(e[f]) for f in lkey))
        for k in GUARDED_LATENCY:
            if old and e[k] > old[k]:
                regs.append(f"latency[prompt={e['prompt']},"
                            f"chunk={e['chunk']},budget={e['budget']}]."
                            f"{k}: {old[k]} -> {e[k]}")
    return regs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", nargs="?", const="BENCH_kernels.json",
                    default=None, metavar="PATH",
                    help="write results to JSON (default BENCH_kernels.json)")
    ap.add_argument("--quick", action="store_true",
                    help="smallest shapes only (CI-sized)")
    ap.add_argument("--check", nargs="?", const="BENCH_kernels.json",
                    default=None, metavar="PATH",
                    help="compare analytic bytes/MACs against a previous "
                         "--json dump and exit 1 on regression (timer-free)")
    args = ap.parse_args(argv)

    if args.check:
        with open(args.check) as f:
            prev = json.load(f)
        regs = check_regressions(analytic_payload(), prev)
        for r in regs:
            print(f"REGRESSION: {r}")
        if regs:
            raise SystemExit(1)
        print(f"--check OK: no analytic bytes/MAC regressions vs "
              f"{args.check}")
        return None

    rows, design, decode, paged = run(quick=args.quick)
    for r in rows:
        derived = " ".join(f"{k}={v:.1f}" for k, v in r.items()
                           if k not in ("name", "wall_us", "macs")
                           and isinstance(v, float))
        print(f"{r['name']},{r['wall_us']:.1f},{derived}")
    print(f"attention_design,s={design['s']},"
          f"two_pass_macs={design['two_pass_macs']},"
          f"single_pass_macs={design['single_pass_macs']}")
    for a in decode["analytic"]:
        print(f"decode_step,span={a['span']},live={a['live']},"
              f"kv_bits={a['kv_bits']},"
              f"xla_bytes={a['xla_bytes_per_step']},"
              f"pallas_bytes={a['pallas_bytes_per_step']},"
              f"decode_macs={a['decode_macs_per_step']},"
              f"two_pass_macs={a['two_pass_macs_per_step']}")
    for backend, r in decode["loop"].items():
        st = r["stats"]
        print(f"decode_loop[{backend}],{r['tok_per_s']:.2f} tok/s,"
              f"decode_pallas={st['attention_decode_pallas']},"
              f"attention_xla={st['attention_xla']}")
    for a in paged["analytic"]:
        print(f"paged_step,ps={a['page_size']},pos={a['pos']},"
              f"kv_bits={a['kv_bits']},"
              f"paged_bytes={a['paged_bytes_per_step']},"
              f"ring_bytes={a['ring_bytes_per_step']},"
              f"ring_over_paged={a['ring_over_paged']:.2f}x")
    for backend, r in paged["loop"].items():
        st = r["stats"]
        print(f"paged_loop[{backend}],{r['tok_per_s']:.2f} tok/s,"
              f"steps={r['engine_steps']},"
              f"prefills={r['prefill_calls']},"
              f"paged_pallas={st['attention_paged_pallas']},"
              f"paged_xla={st['attention_paged_xla']}")
    for backend, r in paged["admission"].items():
        print(f"admission_burst[{backend}],n={r['requests']},"
              f"burst={r['burst_drain_s'] * 1e3:.1f}ms,"
              f"serial={r['serial_drain_s'] * 1e3:.1f}ms,"
              f"speedup={r['burst_speedup']:.2f}x,"
              f"prefills={r['prefill_calls_burst']}"
              f"/{r['prefill_calls_serial']}")
    for a in paged["prefix"]["analytic"]:
        print(f"prefix_burst,n={a['n']},prefix={a['prefix']},"
              f"tail={a['tail']},kv_bits={a['kv_bits']},"
              f"shared_tokens={a['shared_prefill_tokens']},"
              f"unshared_tokens={a['unshared_prefill_tokens']},"
              f"pages_saved={a['pages_saved']},"
              f"capacity_gain={a['admission_capacity_gain']:.2f}x")
    for backend, r in paged["prefix"]["burst"].items():
        print(f"prefix_burst[{backend}],n={r['requests']},"
              f"shared={r['shared']['drain_s'] * 1e3:.1f}ms"
              f"(prefix_prefills={r['shared']['prefix_prefills']},"
              f"pages={r['shared']['pages_in_use']}),"
              f"unshared={r['unshared']['drain_s'] * 1e3:.1f}ms"
              f"(pages={r['unshared']['pages_in_use']}),"
              f"pages_saved={r['pages_saved']}")
    for a in paged["preemption"]["analytic"]:
        print(f"preempt_resume,prompt={a['prompt']},gen={a['gen']},"
              f"kv_bits={a['kv_bits']},"
              f"pages_recovered={a['pages_recovered_per_preemption']},"
              f"recompute_tokens={a['resume_recompute_tokens']},"
              f"kv_bytes_rewritten={a['resume_kv_bytes_rewritten']},"
              f"rewrite_per_freed_byte="
              f"{a['rewrite_per_freed_byte']:.3f}")
    for backend, r in paged["preemption"]["loop"].items():
        print(f"preempt_loop[{backend}],"
              f"pages_recovered={r['pages_recovered']},"
              f"steal={r['steal_latency_ms']:.1f}ms,"
              f"resume_tokens={r['resume_recompute_tokens']}"
              f"(replay={r['resume_replay_steps']}),"
              f"bit_identical={r['bit_identical']}")
    for a in paged["latency"]["analytic"]:
        print(f"burst_latency,prompt={a['prompt']},chunk={a['chunk']},"
              f"budget={a['budget']},"
              f"oneshot_stall={a['oneshot_stall_tokens']},"
              f"budgeted_max_per_step={a['budgeted_max_tokens_per_step']},"
              f"stall_reduction={a['stall_reduction']:.1f}x")
    for backend, r in paged["latency"]["loop"].items():
        print(f"burst_latency[{backend}],long={r['long_len']},"
              f"oneshot_p99={r['oneshot']['p99_step_ms']:.1f}ms"
              f"(max_tok={r['oneshot']['max_prefill_tokens_step']}),"
              f"budgeted_p99={r['budgeted']['p99_step_ms']:.1f}ms"
              f"(max_tok={r['budgeted']['max_prefill_tokens_step']}),"
              f"budget_bounded={r['budget_bounded']},"
              f"fg_bit_identical={r['fg_bit_identical']}")

    if args.json:
        payload = {"kernels": rows, "attention_design": design,
                   "decode": decode, "paged": paged,
                   "device": jax.devices()[0].platform}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json}")
    return rows, design, decode, paged


if __name__ == "__main__":
    main()
