"""Serving driver: a thin CLI over the continuous-batching paged engine.

The serving graph is the paper's contribution: weights stored as low-bit
codes, integer matmuls with reordered dequantization, low-bit paged KV
cache (read in place by the Pallas paged decode kernel under
``--backend pallas``), base-2 embedded softmax.  ``--mode float`` runs the
Q-ViT-style dequantize-first baseline for comparison.

Requests with ragged prompt lengths flow through
:class:`repro.launch.engine.PagedEngine`: admitted as batch rows free up,
decoded at per-sequence positions, evicted on their own EOS — finished
rows are never decoded again.  ``--shared-prefix N`` models
system-prompt-heavy traffic: every request carries the same N-token
prefix declared as a cache breakpoint, so the engine prefills it ONCE and
aliases its refcounted pages across all requests (``prefix_prefills`` /
``shared_prefix_hits`` in the report).  The report gives the run's
wall time and total tokens per second, and each sequence's time to first
token (``ttft_ms``: from the engine's ``submit_s`` stamp to its
``first_token_s`` stamp, taken when the first token is read back) with
its p50 and p95 over the sequences that produced one.  The run always
reports the kernel-dispatch STATS: in CI it is the regression signal
that the serving graph really traced onto the Pallas kernels
(``attention_paged_pallas`` > 0 for the decode loop) instead of silently
falling back to XLA.  ``--json`` emits the whole report as one JSON
object on stdout so CI parses it instead of grepping log lines.

``--prefill-chunk`` / ``--prefill-budget`` engage the chunked-prefill
token-budget scheduler: prompts prefill in page-aligned chunks and every
engine step spends at most the budget in prompt tokens, so decode latency
under an arrival burst is bounded by the budget, not the longest prompt.
The report splits prefill accounting into ``prefill_calls`` (logical
admissions), ``prefill_chunks`` (ragged launches) and ``prefill_tokens``
(real, unpadded).

Failure handling (see the ``launch/engine.py`` module docstring for the
full request state machine): the loop installs the
:mod:`repro.runtime.preemption` SIGTERM/SIGUSR1 handlers and polls
``should_stop()`` every engine step.  On a signal it stops admitting,
releases in-flight rows with their partial outputs kept, and still emits
the final report — flagged ``"preempted": true`` — before exiting with
``PREEMPTED_EXIT_CODE``.  ``--deadline-s`` expires queued requests,
``--audit-every N`` runs the engine-wide invariant audit every N steps
(failures are counted, never fatal, in production; one more audit runs when
the loop ends, so ``audit_violations`` always covers the final state), and
the report's ``failures`` section surfaces the engine's preemption /
resume / cancel / expiry / watchdog / audit counters.

On a TPU the int graph runs the compiled Pallas kernels by default
(``--backend`` overrides).  Run as a script, the CLI keeps JAX's
persistent compile cache where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``.jax_cache/`` at the root of the checkout (:func:`use_compile_cache`).
"""
from __future__ import annotations

import argparse
import json
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import QuantConfig
from repro.kernels import dispatch
from repro.launch.engine import PagedEngine, Request
from repro.models import lm
from repro.runtime import preemption

_FAILURE_KEYS = ("preemptions", "resumes", "cancelled", "expired",
                 "watchdog_fires", "audit_failures", "forced_xla_steps",
                 "quarantined")

_EPILOG = """\
failure handling:
  SIGTERM / SIGUSR1   graceful drain: stop admitting, release in-flight
                      rows keeping their partial outputs, emit the final
                      report with "preempted": true, exit with code 42.
  pool pressure       victim preemption with bit-exact resume: the evicted
                      request re-enters admission as a recompute and its
                      resumed tokens are bit-identical to an uninterrupted
                      run (capped backoff, terminal rejection after
                      repeated preemption).
  --deadline-s        queued requests past the deadline expire instead of
                      stalling decode behind an unservable queue.
  --audit-every N     engine-wide invariant audit (page conservation,
                      refcounts vs. registry pins, scale-pool health)
                      every N steps; failures are counted in the report's
                      "failures" section, never fatal in serving.
"""


CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache():
    """Point JAX's persistent compile cache at a fixed directory.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself when it is set; otherwise
    the cache goes to ``.jax_cache/`` at the root of the checkout.  The
    path is part of the cache key, so it must not move between runs.  For
    entry points only: library code never sets a cache.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT / ".jax_cache"))


def _ttft_ms(req: Request) -> float | None:
    """Submit to first generated token on the engine's own stamps, in ms
    (None when the request produced no token)."""
    if req.first_token_s is None:
        return None
    return (req.first_token_s - req.submit_s) * 1e3


def serve(cfg: lm.LMConfig, params, prompts, *, gen_tokens: int = 16,
          max_len: int | None = None, page_size: int = 16,
          eos_id: int | None = None, batch_size: int | None = None,
          prefix_len: int = 0, deadline_s: float | None = None,
          audit_every: int = 0, preempt_after_step: int | None = None,
          prefill_chunk: int | None = None,
          prefill_budget: int | None = None):
    """prompts: (B, S) int32 (or a list of ragged 1-D prompts) ->
    (generated (B, gen_tokens) int32, stats).

    Runs the continuous-batching engine; with equal-length prompts and no
    EOS this reproduces the old lockstep loop, but rows finish (and new
    work is admitted) independently.  ``prefix_len`` declares a shared
    cache breakpoint on every request (system-prompt traffic): requests
    whose leading ``prefix_len`` tokens agree alias the same refcounted
    physical pages and prefill that prefix ONCE.

    The step loop polls :func:`repro.runtime.preemption.should_stop`
    (SIGTERM/SIGUSR1 when the CLI installed the handlers): on a signal
    the engine shuts down gracefully — queued requests are preempted
    unserved, in-flight rows keep their partial outputs — and the stats
    carry ``preempted: True``.  ``preempt_after_step`` trips the same
    path from inside the loop at a fixed step (deterministic
    graceful-shutdown testing without racing a real signal).

    ``prefill_chunk`` / ``prefill_budget`` engage the chunked-prefill
    token-budget scheduler (engine module docstring): prompts prefill in
    page-aligned chunks and each engine step spends at most
    ``prefill_budget`` prompt tokens on prefill, so a burst of arrivals
    never stalls running decodes for a whole prompt.  The cut plan is
    canonical — chunking changes WHEN chunks launch, never the tokens.
    """
    if hasattr(prompts, "shape"):
        prompts = [np.asarray(prompts[i], np.int32)
                   for i in range(prompts.shape[0])]
    lens = [len(p) for p in prompts]
    max_len = max_len or (max(lens) + gen_tokens)
    bucket = max(lens)
    buckets = {bucket}
    if prefill_chunk is not None or prefill_budget is not None:
        # a bucket sized to the chunk keeps chunk launches unpadded
        c = prefill_chunk if prefill_chunk is not None else prefill_budget
        buckets.add(max(page_size, min(c, bucket) // page_size * page_size))
    reqs = [Request(rid=i, prompt=p, max_new_tokens=gen_tokens,
                    eos_id=eos_id, prefix_len=prefix_len,
                    deadline_s=deadline_s)
            for i, p in enumerate(prompts)]

    t0 = time.perf_counter()
    engine = PagedEngine(cfg, params, batch_size=batch_size or len(reqs),
                         max_len=max_len, page_size=page_size,
                         prefill_buckets=tuple(sorted(buckets)),
                         prefill_chunk=prefill_chunk,
                         prefill_budget=prefill_budget,
                         audit_every=audit_every, audit_raises=False)
    for r in reqs:
        engine.submit(r)
    preempted = False
    while True:
        if preemption.should_stop() or (
                preempt_after_step is not None
                and engine.step_count >= preempt_after_step):
            engine.shutdown()
            preempted = True
            break
        if not engine.step():
            break
    engine.audit(raise_on_fail=False)
    total_s = time.perf_counter() - t0

    gen = np.zeros((len(reqs), gen_tokens), np.int32)
    for i, r in enumerate(reqs):
        gen[i, :len(r.tokens)] = r.tokens
    n_tok = sum(len(r.tokens) for r in reqs)
    ttft = [_ttft_ms(r) for r in reqs]
    served = [t for t in ttft if t is not None]
    snap = dispatch.snapshot()
    return jnp.asarray(gen), {
        "total_s": total_s,
        "tok_per_s": n_tok / max(total_s, 1e-9),
        "ttft_p50_ms": float(np.percentile(served, 50)) if served else None,
        "ttft_p95_ms": float(np.percentile(served, 95)) if served else None,
        "per_seq": [{"rid": r.rid, "prompt_len": len(r.prompt),
                     "gen": len(r.tokens),
                     "status": r.status,
                     "admitted_step": r.admitted_step,
                     "finished_step": r.finished_step,
                     "ttft_ms": t,
                     "error": r.error} for r, t in zip(reqs, ttft)],
        "engine_steps": engine.step_count,
        "prefill_calls": engine.prefill_calls,
        "prefill_chunks": engine.prefill_chunks,
        "prefill_tokens": engine.prefill_tokens,
        "prefix_prefills": engine.prefix_prefills,
        "shared_prefix_hits": engine.shared_prefix_hits,
        "registered_prefixes": len(engine.prefix_registry),
        "rejected": len(engine.rejected),
        "preempted": preempted,
        "failures": {k: snap[k] for k in _FAILURE_KEYS},
        "audit_violations": list(engine.violations),
        "dispatch": snap,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog=_EPILOG)
    ap.add_argument("--arch", default="qwen2.5-32b")
    ap.add_argument("--mode", choices=["int", "float"], default="int")
    ap.add_argument("--backend", choices=["xla", "pallas"], default=None,
                    help="kernel backend for the int serving graph "
                         "(default: REPRO_KERNEL_BACKEND, else pallas on "
                         "a TPU and xla elsewhere)")
    ap.add_argument("--wbits", type=int, default=4)
    ap.add_argument("--kv-bits", type=int, default=8, choices=[4, 8])
    ap.add_argument("--batch", type=int, default=4,
                    help="decode batch rows (continuous batching admits "
                         "more requests than rows as they free up)")
    ap.add_argument("--requests", type=int, default=None,
                    help="number of requests (default: --batch)")
    ap.add_argument("--prompt-len", type=int, default=64,
                    help="max prompt length; requests get staggered "
                         "lengths up to this")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend a common system prompt of this many "
                         "tokens to every request and declare it as a "
                         "cache breakpoint: the engine prefills it once "
                         "and aliases its pages (refcounted) across all "
                         "requests")
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prefill prompts in page-aligned chunks of this "
                         "many tokens (chunked-prefill scheduler; default: "
                         "derived from --prefill-budget when set)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="max prompt tokens prefilled per engine step "
                         "(vLLM/Sarathi-style token budget: an arrival "
                         "burst never stalls decode for a whole prompt; "
                         "floor of one chunk per step)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="expire requests still queued after this many "
                         "wall seconds (TIMED_OUT, never stalls decode)")
    ap.add_argument("--audit-every", type=int, default=32,
                    help="run the engine-wide invariant audit every N "
                         "steps (0 disables; failures are counted in the "
                         "report, not fatal)")
    ap.add_argument("--preempt-after-step", type=int, default=None,
                    help="trip the graceful-shutdown path (as if SIGUSR1 "
                         "arrived) once the engine reaches this step — "
                         "deterministic drill for the preemption machinery")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as one JSON object on stdout")
    args = ap.parse_args(argv)
    if args.backend:
        dispatch.set_backend(args.backend)

    from repro.configs.registry import smoke_config
    cfg = smoke_config(args.arch)
    key = jax.random.PRNGKey(0)
    if args.mode == "int":
        qc = QuantConfig(w_bits=args.wbits, a_bits=8, attn_bits=7,
                         kv_bits=args.kv_bits, mode="int")
        params = lm.init_int_params(key, cfg, qc)
        cfg = cfg.replace(quant=qc)
    else:
        params = lm.init_params(key, cfg)
    n_req = args.requests or args.batch
    rng = np.random.RandomState(0)
    # Staggered prompt lengths: the multi-tenant regime the paged cache is
    # for (equal lengths only when prompt-len leaves no room to stagger).
    lens = [max(1, args.prompt_len - (i * args.prompt_len) // (2 * n_req))
            for i in range(n_req)]
    prompts = [rng.randint(0, cfg.vocab, n).astype(np.int32) for n in lens]
    if args.shared_prefix:
        sys_prompt = rng.randint(0, cfg.vocab,
                                 args.shared_prefix).astype(np.int32)
        prompts = [np.concatenate([sys_prompt, p]) for p in prompts]
    dispatch.reset_stats()
    preemption.reset()
    preemption.install()
    try:
        toks, stats = serve(cfg, params, prompts, gen_tokens=args.gen,
                            page_size=args.page_size, eos_id=args.eos_id,
                            batch_size=args.batch,
                            prefix_len=args.shared_prefix,
                            deadline_s=args.deadline_s,
                            audit_every=args.audit_every,
                            preempt_after_step=args.preempt_after_step,
                            prefill_chunk=args.prefill_chunk,
                            prefill_budget=args.prefill_budget)
    finally:
        preemption.reset()
    if args.json:
        print(json.dumps({"mode": args.mode, "backend": args.backend,
                          "sample": toks[0, :12].tolist(), **stats},
                         indent=2))
    else:
        flag = "  PREEMPTED (partial)" if stats["preempted"] else ""
        ttft = "  ".join(f"ttft {q} {stats[f'ttft_{q}_ms']:.1f}ms"
                         for q in ("p50", "p95")
                         if stats[f"ttft_{q}_ms"] is not None)
        print(f"[serve:{args.mode}] total {stats['total_s']:.3f}s  "
              f"{stats['tok_per_s']:.1f} tok/s  {ttft}  "
              f"steps {stats['engine_steps']}  "
              f"prefills {stats['prefill_calls']}  "
              f"(chunks {stats['prefill_chunks']}, "
              f"tokens {stats['prefill_tokens']}, "
              f"prefix {stats['prefix_prefills']}, "
              f"hits {stats['shared_prefix_hits']})  "
              f"rejected {stats['rejected']}{flag}")
        for s in stats["per_seq"]:
            if s["error"]:
                tail = f"{s['status'].upper()}: {s['error']}"
            elif s["ttft_ms"] is None:
                tail = s["status"]
            else:
                tail = f"{s['status']}  ttft {s['ttft_ms']:.1f}ms"
            print(f"  [seq {s['rid']}] prompt {s['prompt_len']:4d}  "
                  f"gen {s['gen']:3d}  admitted@{s['admitted_step']}  "
                  f"finished@{s['finished_step']}  {tail}")
        print("[failures] " + "  ".join(
            f"{k}={v}" for k, v in stats["failures"].items()))
        print("[dispatch] " + "  ".join(
            f"{k}={v}" for k, v in stats["dispatch"].items()
            if not isinstance(v, dict) and k not in _FAILURE_KEYS))
        for k, v in sorted(stats["dispatch"].get("blocks", {}).items()):
            print(f"[blocks] {k} -> {v}")
        print("sample:", toks[0, :12].tolist())
    if stats["preempted"]:
        raise SystemExit(preemption.PREEMPTED_EXIT_CODE)


if __name__ == "__main__":
    use_compile_cache()
    main()
