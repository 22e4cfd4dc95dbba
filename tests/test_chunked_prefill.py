"""Chunked prefill + token-budget scheduling (engine module docstring).

The contract under test is SCHEDULING INVARIANCE: the cut plan — page-
aligned chunk boundaries at multiples of ``chunk_tokens`` — is a pure
function of (prompt length, prefix length, chunk size), so the budget,
the arrival pattern, batching width, prefix sharing and preemption can
only change WHEN a chunk launches, never which codes it writes or which
tokens are served.  Every test compares a scheduled run bitwise against
a solo run of the same request under the same cut plan, on both kernel
backends and at kv_bits 8 and 4 (the ISSUE-10 acceptance bar), plus the
satellite regressions: replay-drain finishing, over-bucket admission,
the prefill_calls / prefill_chunks / prefill_tokens accounting split,
and page conservation when a request dies between chunks.
"""
import jax
import numpy as np
import pytest

from repro.core.api import QuantConfig, integerize_params
from repro.kernels import dispatch
from repro.launch.engine import PagedEngine, Request, Status
from repro.models import lm


def _qcfg(kv_bits=8):
    qc = QuantConfig(w_bits=8, a_bits=8, attn_bits=7, kv_bits=kv_bits,
                     mode="int")
    cfg = lm.LMConfig(name="t", n_layers=2, d_model=48, n_heads=4,
                      kv_heads=2, d_ff=96, vocab=64, dtype="float32",
                      q_chunk=16, remat=False, quant=qc)
    params = integerize_params(
        lm.init_params(jax.random.PRNGKey(0), cfg.replace(quant=None)), qc)
    return cfg, params


def _prompts(lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 64, n).astype(np.int32) for n in lens]


def _share(base, cfg, params, **kw):
    """Fresh engine on the template's jitted traces (serving reality:
    one process, many tenants; also keeps the 4-way parametrize cheap)."""
    eng = PagedEngine(cfg, params, **kw)
    eng._step = base._step
    eng._admit_prefill = base._admit_prefill
    eng._step_xla = base._step_xla
    return eng


KW = dict(batch_size=2, max_len=64, page_size=8, prefill_buckets=(8, 16),
          prefill_chunk=8)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("kv_bits", [8, 4])
def test_chunked_parity_shared_prefix_and_resume(backend, kv_bits):
    """Tentpole acceptance: chunk scheduling is invisible in the tokens —
    a budget-paced burst over a shared prefix, and a victim preempted and
    resumed mid-decode, each serve streams bit-identical to the same
    request alone under the same cut plan; audit green, pool conserved."""
    cfg, params = _qcfg(kv_bits)
    rng = np.random.RandomState(17)
    prefix = rng.randint(0, 64, 16).astype(np.int32)     # 2 chunk-1 cuts
    tails = [rng.randint(0, 64, n).astype(np.int32) for n in (8, 4)]
    prompts = [np.concatenate([prefix, t]) for t in tails]
    with dispatch.use_backend(backend):
        base = PagedEngine(cfg, params, **KW)            # trace donor
        solos = []
        for p in prompts:
            eng = _share(base, cfg, params, **KW)
            ref = Request(rid=9, prompt=p, max_new_tokens=6, prefix_len=16)
            eng.run([ref])
            solos.append(list(ref.tokens))

        # -- burst under a one-chunk/step budget, sharing the prefix ----
        eng = _share(base, cfg, params, audit_every=1,
                     **{**KW, "prefill_budget": 8})
        reqs = [Request(rid=i, prompt=p, max_new_tokens=6, prefix_len=16)
                for i, p in enumerate(prompts)]
        eng.run(reqs)
        assert eng.shared_prefix_hits == 1
        assert eng.prefix_prefills == 1                  # prefilled once
        for r, s in zip(reqs, solos):
            assert r.done and r.tokens == s, (r.rid, r.tokens, s)
        assert eng.violations == []
        assert eng.alloc.free_count < eng.num_pages      # registry pins
        while eng._reclaim_one():
            pass
        assert eng.alloc.free_count == eng.num_pages

        # -- preempt mid-decode, resume through chunked re-prefill ------
        plain = _share(base, cfg, params, **KW)
        ref = Request(rid=9, prompt=prompts[0], max_new_tokens=6)
        plain.run([ref])
        # a request's stream is independent of declaring its prefix:
        # identical cuts -> identical grids -> identical tokens
        assert ref.tokens == solos[0]
        eng = _share(base, cfg, params, audit_every=1,
                     **{**KW, "num_pages": 4, "prefill_budget": 8})
        victim = Request(rid=1, prompt=prompts[0], max_new_tokens=6)
        eng.submit(victim)
        for _ in range(5):                # 3 chunk steps + 2 decode steps
            eng.step()
        assert 1 <= len(victim.tokens) < 6               # mid-flight
        hi = Request(rid=2, prompt=tails[0], max_new_tokens=2, priority=5)
        eng.submit(hi)
        while eng.step():
            pass
    assert eng.preempt_count >= 1 and eng.resume_count >= 1
    assert hi.done and not hi.failed
    assert victim.done and victim.tokens == solos[0]
    assert eng.violations == []
    assert eng.alloc.free_count == eng.num_pages


def test_budget_bounds_prefill_work_and_decode_never_stalls():
    """Tentpole: with a token budget, each engine step prefills at most
    max(chunk, budget rounded down to chunks) prompt tokens, and a running
    decode row emits exactly one token per step THROUGH the burst — the
    stall is bounded by the budget, not the longest prompt."""
    cfg, params = _qcfg()
    kw = dict(batch_size=3, max_len=64, page_size=8, prefill_buckets=(8,),
              prefill_chunk=8, prefill_budget=16)
    eng = PagedEngine(cfg, params, **kw)
    fg = Request(rid=0, prompt=_prompts([8], seed=1)[0], max_new_tokens=12)
    eng.submit(fg)
    eng.step()
    assert fg.status == Status.RUNNING
    burst = [Request(rid=1 + i, prompt=p, max_new_tokens=3)
             for i, p in enumerate(_prompts([24, 24], seed=2))]
    for r in burst:
        eng.submit(r)
    bound = 16                                 # budget - budget % chunk
    while not fg.done:
        spent0, fg0 = eng.prefill_tokens, len(fg.tokens)
        if not eng.step():
            break
        assert eng.prefill_tokens - spent0 <= bound
        if not fg.done:
            assert len(fg.tokens) == fg0 + 1   # decode never stalled
    while eng.step():
        pass
    assert fg.done and all(r.done and not r.failed for r in burst)
    # scheduling invariance: the burst changed nothing in the streams
    for r in burst:
        solo = PagedEngine(cfg, params, **kw)
        solo._step, solo._admit_prefill = eng._step, eng._admit_prefill
        ref = Request(rid=9, prompt=r.prompt, max_new_tokens=3)
        solo.run([ref])
        assert r.tokens == ref.tokens, (r.rid, r.tokens, ref.tokens)


def test_burst_accounting_calls_chunks_tokens():
    """Satellite: the accounting split — a burst of W same-plan admissions
    is ONE logical prefill call spread over the plan's chunk launches,
    while serial arrivals are W calls; prefill_tokens counts real
    (unpadded) prompt tokens either way, and STATS mirrors all three."""
    cfg, params = _qcfg()
    kw = dict(batch_size=4, max_len=64, page_size=8, prefill_buckets=(8,),
              prefill_chunk=8)
    prompts = _prompts([24, 24, 24, 24], seed=8)
    dispatch.reset_stats()
    burst = PagedEngine(cfg, params, **kw)
    burst_reqs = [Request(rid=i, prompt=p, max_new_tokens=4)
                  for i, p in enumerate(prompts)]
    burst.run(burst_reqs)
    assert burst.prefill_calls == 1            # PR-4 burst==1, preserved
    assert burst.prefill_chunks == 3           # 24 tokens / 8-token cuts
    assert burst.prefill_tokens == 96
    assert dispatch.STATS["prefill_calls"] == 1
    assert dispatch.STATS["prefill_chunks"] == 3
    assert dispatch.STATS["prefill_tokens"] == 96

    drip = PagedEngine(cfg, params, **kw)
    drip._step, drip._admit_prefill = burst._step, burst._admit_prefill
    drip_reqs = [Request(rid=i, prompt=p, max_new_tokens=4)
                 for i, p in enumerate(prompts)]
    for r in drip_reqs:
        drip.submit(r)
        drip.step()
    while drip.step():
        pass
    assert drip.prefill_calls == 4             # serial arrivals: W calls
    assert drip.prefill_tokens == 96
    for a, b in zip(burst_reqs, drip_reqs):
        assert a.tokens == b.tokens, (a.rid, a.tokens, b.tokens)


def test_replay_drain_finishes_terminal_request():
    """Satellite regression: a request preempted AFTER recording its final
    token must finish the moment its recompute catches up — at the resume
    prefill when nothing is left to replay, or at the decode step whose
    replay pops the last recorded token — never re-occupying a row to
    decode (and record) past its terminal state."""
    cfg, params = _qcfg()
    kw = dict(batch_size=2, max_len=64, page_size=8, prefill_buckets=(16,))
    prompt = _prompts([9], seed=30)[0]
    ref_eng = PagedEngine(cfg, params, **kw)
    ref = Request(rid=0, prompt=prompt, max_new_tokens=3)
    ref_eng.run([ref])
    solo = list(ref.tokens)
    assert len(solo) == 3

    # (a) replay empty at resume: terminal the moment the prefill lands
    eng = _share(ref_eng, cfg, params, audit_every=1, **kw)
    req = Request(rid=1, prompt=prompt, max_new_tokens=1)
    req.preemptions = 1                        # as _preempt_row left it
    req.tokens = [solo[0]]
    eng.run([req])
    assert req.done and not req.failed
    assert req.tokens == solo[:1]              # nothing recorded past it
    assert eng.resume_count == 1
    assert eng.violations == []
    assert eng.alloc.free_count == eng.num_pages

    # (b) replay drains exactly at max_new: finish on that step
    eng = _share(ref_eng, cfg, params, audit_every=1, **kw)
    req = Request(rid=2, prompt=prompt, max_new_tokens=3)
    req.preemptions = 1
    req.tokens = list(solo)
    eng.run([req])
    assert req.done and not req.failed
    assert req.tokens == solo                  # no 4th token recorded
    assert eng.violations == []                # replay never diverged
    assert eng.alloc.free_count == eng.num_pages


def test_cancel_and_preempt_between_chunks():
    """Satellite: a request that dies mid-prefill — cancelled between
    chunks, or preempted by a higher-priority arrival — releases every
    page, keeps the audit green, and (for the preemptee) restarts from
    chunk 0 to the same stream as an undisturbed run."""
    cfg, params = _qcfg()
    kw = dict(batch_size=2, max_len=64, page_size=8, prefill_buckets=(8,),
              prefill_chunk=8, prefill_budget=8)
    # -- cancel between chunk 1 and chunk 2 -----------------------------
    eng = PagedEngine(cfg, params, audit_every=1, **kw)
    req = Request(rid=0, prompt=_prompts([24], seed=5)[0], max_new_tokens=4)
    eng.submit(req)
    eng.step()
    assert req.status == Status.PREFILLING     # 1 of 3 chunks launched
    assert 0 < req._chunk_pos < len(req.prompt)
    req.cancel()
    eng.step()
    assert req.status == Status.CANCELLED and req.tokens == []
    assert eng.alloc.free_count == eng.num_pages
    assert eng.violations == []

    # -- preempt between chunks: restart from chunk 0 -------------------
    solo = PagedEngine(cfg, params, **kw)
    solo._step, solo._admit_prefill = eng._step, eng._admit_prefill
    ref = Request(rid=9, prompt=_prompts([24], seed=5)[0], max_new_tokens=6)
    solo.run([ref])
    eng2 = PagedEngine(cfg, params, audit_every=1,
                       **{**kw, "num_pages": 4})
    eng2._step, eng2._admit_prefill = eng._step, eng._admit_prefill
    victim = Request(rid=1, prompt=_prompts([24], seed=5)[0],
                     max_new_tokens=6)
    eng2.submit(victim)
    eng2.step()                                # PREFILLING, 1 chunk in
    assert victim.status == Status.PREFILLING
    hi = Request(rid=2, prompt=_prompts([8], seed=6)[0], max_new_tokens=2,
                 priority=5)
    eng2.submit(hi)
    while eng2.step():
        pass
    assert victim.preemptions >= 1             # evicted between chunks
    assert hi.done and not hi.failed
    assert victim.done and victim.tokens == ref.tokens
    assert eng2.violations == []
    assert eng2.alloc.free_count == eng2.num_pages


# -- request stamps, packer counters and profiler spans ----------------------

def test_stamps_ordered_and_kept_across_preemption():
    """``submit_s <= admit_s <= first_token_s`` on the host clock, and a
    request preempted mid-decode and readmitted keeps all three first
    values (``admitted_step`` still moves, for victim choice)."""
    cfg, params = _qcfg()
    kw = dict(batch_size=2, max_len=64, page_size=8, prefill_buckets=(8,),
              prefill_chunk=8, prefill_budget=8, num_pages=4)
    eng = PagedEngine(cfg, params, audit_every=1, **kw)
    victim = Request(rid=1, prompt=_prompts([24], seed=5)[0],
                     max_new_tokens=6)
    eng.submit(victim)
    for _ in range(4):                 # 3 chunk steps + 1 decode step
        eng.step()
    assert victim.status == Status.RUNNING and 1 <= len(victim.tokens) < 6
    first = (victim.submit_s, victim.admit_s, victim.first_token_s)
    assert first[0] <= first[1] <= first[2]
    hi = Request(rid=2, prompt=_prompts([8], seed=6)[0], max_new_tokens=2,
                 priority=5)
    eng.submit(hi)
    while eng.step():
        pass
    assert victim.preemptions >= 1 and victim.done and not victim.failed
    assert victim.admitted_step > 0                   # readmitted later
    assert (victim.submit_s, victim.admit_s, victim.first_token_s) == first
    assert hi.done and first[2] <= hi.submit_s <= hi.admit_s \
        <= hi.first_token_s
    assert eng.violations == []


def test_packer_counts_prefill_steps_and_stalls():
    """Two prompts of two chunks each under a budget of one chunk: the
    older row's chunks launch in steps 0 and 1, the younger waits both
    steps (two stalls) and launches in steps 2 and 3.  A one-shot
    admission never prefills through the packer and counts nothing."""
    cfg, params = _qcfg()
    kw = dict(batch_size=2, max_len=64, page_size=8, prefill_buckets=(8,),
              prefill_chunk=8, prefill_budget=8)
    eng = PagedEngine(cfg, params, audit_every=1, **kw)
    old, young = (Request(rid=i, prompt=p, max_new_tokens=3)
                  for i, p in enumerate(_prompts([16, 16], seed=9)))
    eng.run([old, young])
    assert old.done and young.done
    assert (old.prefill_steps, old.prefill_stalls) == (2, 0)
    assert (young.prefill_steps, young.prefill_stalls) == (4, 2)
    assert old.admit_s <= young.admit_s
    assert old.first_token_s < young.first_token_s

    one_shot = PagedEngine(cfg, params, batch_size=2, max_len=64,
                           page_size=8, prefill_buckets=(16,))
    req = Request(rid=3, prompt=_prompts([16], seed=9)[0], max_new_tokens=3)
    one_shot.run([req])
    assert req.done
    assert (req.prefill_steps, req.prefill_stalls) == (0, 0)
    assert req.submit_s <= req.admit_s <= req.first_token_s


class _SpanLog:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each span's
    name, nesting depth and metadata in the order spans open."""

    def __init__(self):
        self.spans, self.depth = [], 0

    def __call__(self, name, **meta):
        log = self

        class Span:
            def __enter__(self):
                self.meta = dict(meta)
                log.spans.append((name, log.depth, self.meta))
                log.depth += 1
                return self

            def __exit__(self, *exc):
                log.depth -= 1

            def set_metadata(self, **more):
                self.meta.update(more)

        return Span()


def test_step_spans_in_phase_order(monkeypatch):
    """One step that admits a one-chunk prompt, launches it and decodes it
    opens the engine's spans in phase order, each nested under
    ``engine.step``, with the launch shape and request id on the
    prefill span."""
    from repro.launch import engine as engine_mod
    cfg, params = _qcfg()
    eng = PagedEngine(cfg, params, batch_size=2, max_len=64, page_size=8,
                      prefill_buckets=(8,), prefill_chunk=8,
                      prefill_budget=8)
    req = Request(rid=7, prompt=_prompts([8], seed=3)[0], max_new_tokens=3)
    eng.submit(req)
    log = _SpanLog()
    monkeypatch.setattr(engine_mod, "TraceAnnotation", log)
    assert eng.step()
    assert [(n, d) for n, d, _ in log.spans] == [
        ("engine.step", 0), ("engine.admit", 1), ("engine.prefill", 1),
        ("engine.prefill_sync", 1), ("engine.decode", 1),
        ("engine.decode_sync", 1), ("engine.commit", 1)]
    meta = {n: m for n, _, m in log.spans}
    assert meta["engine.step"] == {"step": 0}
    assert meta["engine.admit"] == {"admitted": 1}
    assert meta["engine.prefill"] == {"start": 0, "width": 1, "bucket": 8,
                                      "tokens": 8, "rids": "7"}
    assert meta["engine.decode"] == {"rows": 1}
    assert len(req.tokens) == 2 and log.depth == 0
