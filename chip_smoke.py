"""Chip smoke: serve ChatGLM3-6B at its published widths on one TPU.

Drives the normal serving path (``repro.launch.serve.serve`` over the
``PagedEngine`` submit/step loop) once per kernel backend, in one process:
first the compiled Pallas kernels, then the XLA integer path.  The model is
ChatGLM3-6B (28 layers, d_model 4096, 32 heads, 2 KV heads, d_ff 13696,
vocab 65024) in ``mode="int"`` with w4 codes stored one per byte, a8
activations, 7-bit attention probabilities and an int8 paged KV cache.
Weights are random, made from ``--seed`` by the streamed integer builder
(``lm.init_int_params``), so the float model is never held.

Traffic: 8 requests, prompt lengths staggered from 512 down to 288 tokens,
16 greedy tokens each, on 8 batch rows, one-shot prefill (no chunking, no
shared prefix).

It fails unless all of these hold: greedy tokens are identical under both
backends; the Pallas run dispatched every op onto the kernels
(``qlinear_pallas``, ``attention_pallas``, ``attention_paged_pallas`` > 0)
and none to XLA; the engine audit found nothing; every request ended
``done``.  The numbers it prints (build and serve seconds, compile
included, and peak device bytes) describe this run, not the system's speed.

Run on a machine with one TPU:  ``python chip_smoke.py``.  Without a TPU it
exits non-zero before doing anything.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "chatglm3-6b"
# Page size of the paged KV cache: the paged decode kernel compiles for it
# on v5e (tests/test_tpu_compile.py), and 128-token pages keep the decode
# grid short (5 pages for the longest request).
PAGE_SIZE = 128
BATCH = 8
MAX_PROMPT = 512
GEN = 16


def require_tpu():
    """The JAX devices, if they are TPUs; otherwise exit non-zero."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX's default device "
                         f"is {devs[0].platform!r}); this check runs only "
                         f"on a TPU")
    return devs


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _check(runs) -> list[str]:
    """Every failed condition, as a message (empty when the smoke passed)."""
    bad = []
    toks_p, stats_p = runs["pallas"]
    toks_x, _ = runs["xla"]
    diff = toks_p != toks_x
    if diff.any():
        step = int(diff.any(axis=0).argmax())
        rows = [int(r) for r in diff[:, step].nonzero()[0]]
        bad.append(f"greedy tokens differ between pallas and xla: first at "
                   f"step {step}, rows {rows} (pallas "
                   f"{toks_p[rows, step].tolist()}, xla "
                   f"{toks_x[rows, step].tolist()})")
    d = stats_p["dispatch"]
    for k in ("qlinear_pallas", "attention_pallas", "attention_paged_pallas"):
        if d[k] <= 0:
            bad.append(f"pallas run: {k} = {d[k]}, expected > 0")
    for k in ("qlinear_xla", "attention_xla", "attention_paged_xla"):
        if d[k] != 0:
            bad.append(f"pallas run: {k} = {d[k]}, expected 0 (XLA fallback)")
    for backend, (_, stats) in runs.items():
        if stats["audit_violations"]:
            bad.append(f"{backend} run: audit violations "
                       f"{stats['audit_violations']}")
        not_done = [(s["rid"], s["status"]) for s in stats["per_seq"]
                    if s["status"] != "done"]
        if not_done:
            bad.append(f"{backend} run: requests not done {not_done}")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args(argv)
    devs = require_tpu()

    import jax
    import numpy as np

    from repro.configs.registry import get_config
    from repro.core.api import QuantConfig
    from repro.kernels import dispatch
    from repro.launch.serve import serve, use_compile_cache
    from repro.models import lm

    use_compile_cache()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}")
    qc = QuantConfig(w_bits=4, a_bits=8, attn_bits=7, kv_bits=8, mode="int")
    cfg = get_config(ARCH).replace(quant=qc)
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads} kv_heads={cfg.kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} w{qc.w_bits}a{qc.a_bits} "
          f"attn{qc.attn_bits} kv{qc.kv_bits} page_size={PAGE_SIZE}")

    t0 = time.perf_counter()
    params = jax.block_until_ready(
        lm.init_int_params(jax.random.PRNGKey(args.seed), cfg, qc))
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    print(f"build: {time.perf_counter() - t0:.1f}s (compile included)  "
          f"param_bytes={n_bytes}  peak_bytes_in_use={_peak_bytes(dev)}")

    rng = np.random.RandomState(args.seed)
    lens = [MAX_PROMPT - i * MAX_PROMPT // (2 * BATCH) for i in range(BATCH)]
    prompts = [rng.randint(0, cfg.vocab, n).astype(np.int32) for n in lens]
    print(f"traffic: {len(prompts)} requests, prompt lengths {lens}, "
          f"{GEN} generated tokens each, batch rows {BATCH}")

    runs = {}
    for backend in ("pallas", "xla"):
        dispatch.reset_stats()
        dispatch.set_backend(backend)
        t0 = time.perf_counter()
        toks, stats = serve(cfg, params, prompts, gen_tokens=GEN,
                            page_size=PAGE_SIZE, batch_size=BATCH,
                            audit_every=1)
        runs[backend] = (np.asarray(toks), stats)
        d = stats["dispatch"]
        print(f"[{backend}] serve: {time.perf_counter() - t0:.1f}s "
              f"(compile included)  engine_steps={stats['engine_steps']}  "
              f"prefill_calls={stats['prefill_calls']}  "
              f"peak_bytes_in_use={_peak_bytes(dev)}")
        print(f"[{backend}] dispatch: " + "  ".join(
            f"{k}={d[k]}" for k in ("qlinear_pallas", "qlinear_xla",
                                    "attention_pallas", "attention_xla",
                                    "attention_paged_pallas",
                                    "attention_paged_xla")))
        for k, v in sorted(d["blocks"].items()):
            print(f"[{backend}] blocks {k} -> {v}")
        print(f"[{backend}] tokens row 0: {runs[backend][0][0].tolist()}")

    bad = _check(runs)
    for msg in bad:
        print(f"FAIL: {msg}")
    if bad:
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
