"""One run of one cell: set-up, the measured window, metrics, the check.

``run.py`` is the command; this module is the run itself, so that the
self-checks can drive a whole run at a tiny size on the CPU.  Everything
that belongs to one configuration, traffic mix or metric lives in a file
of its own and is found by the name ``BENCHMARK.json`` gives it:
``configs/<config>.json`` (sizes, quantization, engine settings and the
name of the plain reference, which also names the architecture module
``arch/<name>.py``: its weights, the reference's view of them and its
work counts), ``traffic/<mix>.json`` (read by ``generator.py``),
``metrics/<metric>.py`` (one reader each) and ``limits/<cell>.json``
(the limit of each number ``correct`` compares).

The window drives ``PagedEngine.submit``/``step``, the path ``serve()``
takes.  The harness wraps its own engine's two jitted programs only to
note what each call did (positions decoded, chunks prefilled); it never
changes what they compute, except under a planted ``fault`` in the
self-checks.
"""
from __future__ import annotations

import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import arch
import check
import generator
import trace_reduce
import weights
import work

HERE = Path(__file__).resolve().parent


# ---------------------------------------------------------------------------
# Files
# ---------------------------------------------------------------------------

def load_bench(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics
    (``--trace 0``) or its per-layer metrics (``--trace 1``)."""
    pool = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in pool if cell in m.get("workloads", [cell])]


def reader(name: str):
    return check.load_module(HERE / "metrics" / f"{name}.py",
                             f"metric_{name.replace('.', '_')}")


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

def build(config: dict):
    """(LMConfig, QuantConfig) of a configuration file; the model section
    is the architecture module's to read (``arch/<name>.py``)."""
    from repro.core.api import QuantConfig
    q = config["quant"]
    qc = QuantConfig(w_bits=q["w_bits"], a_bits=q["a_bits"],
                     attn_bits=q["attn_bits"], kv_bits=q["kv_bits"],
                     mode=q["mode"], softmax=q["softmax"],
                     pack_weights=q["pack_weights"],
                     quantize_embeddings=q["quantize_embeddings"])
    cfg = arch.of(config).lm_config(config["model"], qc)
    return cfg.replace(name=config["name"]), qc


def warm_shapes(traffic: dict, eng: dict) -> list[tuple[int, int, int]]:
    """Every (start, width, bucket) admission-prefill launch the traffic
    can reach under the engine's chunk and budget.

    The packer always takes the oldest pending row's next chunk and adds
    younger rows' chunks only while the step's tokens stay within the
    budget.  A chunk that starts past 0 belongs to a prompt longer than
    one chunk, whose first chunk was a full one; a younger row never fits
    a full chunk beside the oldest row's, so only the oldest row is ever
    past its first chunk, and every launch that starts past 0 holds one
    row.  Launches at 0 hold up to ``budget // shortest first chunk``
    rows, one bucket per launch.
    """
    chunk, budget = eng["prefill_chunk"], eng["prefill_budget"]
    buckets = sorted(eng["prefill_buckets"])
    pmin, pmax = traffic["prompt"]["min"], traffic["prompt"]["max"]

    def buckets_for(lo, hi):
        """Buckets that hold some chunk length in [lo, hi]."""
        out, below = [], 0
        for b in buckets:
            if lo <= b and hi > below:
                out.append((b, max(lo, below + 1)))
            below = b
        return out

    shapes = set()
    for b, shortest in buckets_for(min(pmin, chunk), min(pmax, chunk)):
        for w in range(1, budget // shortest + 1):
            shapes.add((0, w, b))
    for start in range(chunk, pmax, chunk):
        lo = max(1, min(chunk, pmin - start))
        for b, _ in buckets_for(lo, min(chunk, pmax - start)):
            shapes.add((start, 1, b))
    return sorted(shapes)


def warm_up(engine, traffic: dict, eng: dict, vocab: int, seed: int):
    """Run every launch shape of :func:`warm_shapes`, and the decode step,
    through the engine itself (so that the eager ops around each program
    compile too).  Requests ask for two tokens and are gone afterwards."""
    from repro.launch.engine import Request
    r = generator.rng(seed, 3)
    chunk, buckets = eng["prefill_chunk"], sorted(eng["prefill_buckets"])
    covered = set()
    rid = -1
    for start, w, b in sorted(warm_shapes(traffic, eng),
                              key=lambda s: (s[0] == 0, -s[0], s[1])):
        if (start, w, b) in covered:
            continue
        if start == 0:
            # w rows whose first chunks share one launch and one bucket
            n = max(1, min(b, eng["prefill_budget"] // w,
                           traffic["prompt"]["max"]))
            lens = [n] * w
            covered.add((0, w, b))
        else:
            # one prompt whose cut plan passes every earlier start too
            n = min(start + b, traffic["prompt"]["max"])
            lens = [n]
            for s in range(0, n, chunk):
                tail = min(chunk, n - s)
                covered.add((s, 1, next(x for x in buckets if tail <= x)))
        for n in lens:
            engine.submit(Request(rid=rid, prompt=r.integers(
                0, vocab, n, dtype=np.int32), max_new_tokens=2))
            rid -= 1
        while engine.step():
            pass


class Counter:
    """Compilations (and persistent-cache loads) while ``armed``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        self.armed = False
        self.seen: dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _note(self, event):
        if self.armed:
            self.seen[event] = self.seen.get(event, 0) + 1

    def _dur(self, event, duration, **kw):
        if event in self.EVENTS:
            self._note(event)

    def _ev(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self._note(event)

    @property
    def total(self) -> int:
        return sum(self.seen.values())


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

class Recorder:
    """What each call of the engine's two programs did, noted on the host.

    With ``detail`` on (traced runs), a prefill launch also reads its
    rows' real lengths back from the device (a few bytes)."""

    def __init__(self, engine, detail: bool, fault=None):
        self.engine = engine
        self.detail = detail
        self.calls: list[tuple] = []
        step, admit = engine._step, engine._admit_prefill

        def step_rec(params, tok, cache):
            self.calls.append(("decode", time.perf_counter(),
                               engine.pos.copy()))
            out = step(params, tok, cache)
            if fault is not None:
                out = fault(out, engine)
            return out

        def admit_rec(params, batch, cache, rows, page_table, prefix_len):
            w, s = batch["tokens"].shape
            lens = np.asarray(batch["lengths"]) if detail else None
            self.calls.append(("prefill", time.perf_counter(),
                               (prefix_len, w, s, lens)))
            return admit(params, batch, cache, rows, page_table, prefix_len)

        engine._step = step_rec
        engine._admit_prefill = admit_rec


@jax.jit
def _shift_top(logits, row):
    top = jnp.argmax(logits[row, 0])
    tok = (top + logits.shape[-1] // 2) % logits.shape[-1]
    return logits.at[row, 0, tok].set(logits[row, 0, top] + 1)


def token_fault(out, engine):
    """Planted fault: the first active row's served token is replaced by
    another, where the decode step produces it."""
    logits, cache = out
    active = np.nonzero(engine.pos >= 0)[0]
    if len(active):
        logits = _shift_top(logits, int(active[0]))
    return logits, cache


def percentile(values, q: float) -> float:
    """The q-th percentile with linear interpolation (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def _median_ms(values):
    return percentile(values, 50) * 1e3 if values else None


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, config: dict | None = None,
             traffic: dict | None = None, limits: dict | None = None,
             fault=None, backend: str | None = None,
             min_check_tokens: int = 256, min_check_requests: int = 3,
             max_check_requests: int = 8, trace_seconds: float = 3.0,
             control_bits: int | None = None,
             do_check: bool = True):
    """Run one cell once; returns the result line (a dict).

    ``control_bits`` (``control.py``): put the control in the program's
    place: judge the tokens that the reference at that activation
    precision puts first, at the same positions of the same sample, where
    the program's served tokens would be judged; the program's own reading
    stays under ``result["program"]``.  ``do_check=False`` (``sweep.py``) skips the check and
    adds the load's own numbers under ``result["load"]``."""
    from repro.kernels import dispatch
    from repro.launch.engine import PagedEngine, Request, Status
    from repro.models import lm

    cell = find(bench["workloads"], cell_name, "workload")
    if config is None:
        entry = find(bench["configs"], cell["config"], "config")
        config = json.loads((Path(bench["_root"]) / entry["file"]).read_text())
    if traffic is None:
        traffic = generator.load(cell["traffic"])
    if limits is None:
        limits = check.limits(cell_name)
    model, quant, eng = config["model"], config["quant"], config["engine"]
    counter = Counter()
    dispatch.set_backend(backend or eng["backend"])
    cfg, qc = build(config)
    blocks = arch.of(config)
    dev = jax.devices()[0]

    # -- weights, made on the device from the seed --------------------------
    params = blocks.make(seed, model, quant)
    weights.check_layout(
        blocks.shape_of(model, quant),
        jax.eval_shape(lambda k: lm.init_int_params(k, cfg, qc),
                       weights.prng_key(0)))
    jax.block_until_ready(params)

    # -- traffic and engine --------------------------------------------------
    specs = generator.make(traffic, seed, seconds, model["vocab"])
    rows = traffic["rows"]
    max_len = traffic["prompt"]["max"] + traffic["output"]["max"]
    engine = PagedEngine(
        cfg, params, batch_size=rows, max_len=max_len,
        page_size=eng["page_size"],
        prefill_buckets=tuple(eng["prefill_buckets"]),
        prefill_chunk=eng["prefill_chunk"],
        prefill_budget=eng["prefill_budget"])
    warm_up(engine, traffic, eng, model["vocab"], seed)
    rec = Recorder(engine, detail=trace, fault=fault)

    reqs: dict[int, dict] = {}
    inflight: list[dict] = []

    def submit(spec, now):
        req = Request(rid=spec.rid, prompt=spec.prompt,
                      max_new_tokens=spec.max_new)
        r = {"spec": spec, "req": req, "t_submit": now, "t_admit": None,
             "times": [], "seen": 0}
        reqs[spec.rid] = r
        inflight.append(r)
        engine.submit(req)

    def observe(now):
        keep = []
        for r in inflight:
            req = r["req"]
            if r["t_admit"] is None and req.status != Status.QUEUED:
                r["t_admit"] = now
            new = len(req.tokens) - r["seen"]
            if new > 0:
                r["times"].extend([now] * new)
                r["seen"] += new
            if not req.done:
                keep.append(r)
        inflight[:] = keep

    steps: list[tuple[float, float, bool]] = []   # (t0, t1, did work)
    load: list[tuple[float, int, int]] = []   # (t, queued, rows in use)

    def step():
        n_calls = len(rec.calls)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            more = engine.step()
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.observe"):
            observe(t1)
        steps.append((t0, t1, len(rec.calls) > n_calls))
        load.append((t1, len(engine.queue),
                     sum(q is not None for q in engine.row_req)))
        return more

    # -- the warm-up arrivals, then the window ------------------------------
    # Request i is sent at t_open + due_s; the warm-up's are due before the
    # window opens.  What the window counts starts at t_open.
    t_open = time.perf_counter() + traffic["arrival"]["warmup_s"]
    setup_s = t_open - t_start
    t_close = t_open + seconds
    opened = False
    trace_dir = None
    trace_on = None
    trace_off = None
    late = []
    nxt = 0
    while True:
        now = time.perf_counter()
        if not opened and now >= t_open:
            dispatch.reset_stats()
            counter.armed = True
            rec.calls.clear()
            steps.clear()
            late.clear()
            opened = True
            log(f"setup: {setup_s:.3f} s, of which warm-up arrivals "
                f"{traffic['arrival']['warmup_s']} s ({len(reqs)} "
                f"requests)")
        if trace and trace_on is None and now >= t_close - trace_seconds:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            trace_on = time.perf_counter()
        if trace_on is not None and trace_off is None and now >= t_close:
            jax.profiler.stop_trace()
            trace_off = now
        with jax.profiler.TraceAnnotation("bench.submit"):
            while nxt < len(specs) and t_open + specs[nxt].due_s <= now:
                late.append(now - t_open - specs[nxt].due_s)
                submit(specs[nxt], now)
                nxt += 1
        if now >= t_close:
            due = [r for r in reqs.values()
                   if 0 <= r["spec"].due_s < seconds]
            if all(r["times"] for r in due) or now >= t_close + 60:
                break
        if not step():
            if nxt >= len(specs):
                break
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, t_open + specs[nxt].due_s
                               - time.perf_counter()))
    if trace_on is not None and trace_off is None:
        jax.profiler.stop_trace()
        trace_off = time.perf_counter()
    counter.armed = False
    stats = dispatch.snapshot()
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    # -- the numbers ---------------------------------------------------------
    run = Run(config=config, seconds=seconds, t_open=t_open,
              t_close=t_close, setup_s=setup_s, reqs=list(reqs.values()),
              steps=steps, calls=rec.calls, trace_on=trace_on,
              trace_off=trace_off, device_kind=dev.device_kind)
    if trace_dir is not None:
        path = trace_reduce.find(trace_dir)
        run.trace = trace_reduce.reduce(path, t_on=trace_on, t_off=trace_off)
        shutil.rmtree(trace_dir, ignore_errors=True)
    out_metrics = {}
    for m in metrics_for(bench, cell_name, trace):
        value = reader(m["name"]).read(run)
        if value is not None:
            out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    # Every request sent counts; one fails if the engine refused or lost
    # it, or if it was due in the window and never got its first token.
    attempted = len(reqs)
    failed = sum(1 for r in reqs.values()
                 if r["req"].error is not None
                 or r["req"].status in (Status.REJECTED, Status.CANCELLED,
                                        Status.TIMED_OUT)
                 or (0 <= r["spec"].due_s < seconds and not r["times"]))
    log(f"window: {seconds} s; steps {len(steps)}; requests submitted "
        f"{len(reqs)}; finished {sum(r['req'].done for r in reqs.values())}"
        f"; compilations in window {counter.total} {counter.seen}")
    in_window = [x for x in load if t_open <= x[0] <= t_close]
    if in_window:
        log(f"load in window: queued max {max(x[1] for x in in_window)}, "
            f"rows in use mean "
            f"{np.mean([x[2] for x in in_window]):.1f} max "
            f"{max(x[2] for x in in_window)} of {rows}")
    ttft = sorted((r["times"][0] - t_open - r["spec"].due_s) * 1e3
                  for r in run.window_reqs if r["times"])
    if ttft:
        log("ttft ms over " + str(len(ttft)) + " requests: " + ", ".join(
            f"p{q} {percentile(ttft, q):.1f}" for q in (50, 90, 95, 99))
            + f", max {ttft[-1]:.1f}")
    if late:
        log(f"generator lateness: p50 {percentile(late, 50) * 1e3:.3f} ms, "
            f"max {max(late) * 1e3:.3f} ms over {len(late)} sends")
    log("dispatch: " + " ".join(f"{k}={v}" for k, v in stats.items()
                                if k != "blocks" and v))

    # -- the check, after the program's state is freed ----------------------
    finished = [(r["spec"].rid, r["spec"].prompt, list(r["req"].tokens))
                for r in reqs.values() if r["req"].done
                and r["req"].error is None]
    del engine, rec
    inflight.clear()
    for r in reqs.values():
        r.pop("req", None)
    gc.collect()
    if not do_check:
        due = run.window_reqs
        ttft = [r["times"][0] - t_open - r["spec"].due_s for r in due
                if r["times"]]
        return {"metrics": out_metrics, "load": {
            "due_in_window": len(due),
            "not_admitted_at_close": sum(
                1 for r in due if r["spec"].due_s < seconds - 2
                and (r["t_admit"] is None or r["t_admit"] > t_close)),
            "ttft_p50_ms": percentile(ttft, 50) * 1e3 if ttft else None,
            "ttft_p50_ms_first_fifth": _median_ms(
                [r["times"][0] - t_open - r["spec"].due_s for r in due
                 if r["times"] and r["spec"].due_s < 0.2 * seconds]),
            "ttft_p50_ms_last_fifth": _median_ms(
                [r["times"][0] - t_open - r["spec"].due_s for r in due
                 if r["times"] and r["spec"].due_s >= 0.8 * seconds]),
            "tokens_per_s": len(run.token_times()) / seconds,
            "preemptions": stats.get("preemptions", 0),
            "failed": failed}}
    picks = check.sample(finished, seed, min_check_tokens,
                         min_check_requests, max_check_requests)
    ref = check.reference(config)
    top, layer = blocks.reference_view(params, model)
    t_ref = time.perf_counter()

    if picks:
        gaps, low = check.served_gaps(ref, config, top, layer, picks,
                                      max_len, act_bits=control_bits)
    else:
        gaps = low = np.array([np.inf])

    def summary(g):
        return {"served_logit_gap_max": float(np.max(g)),
                "served_logit_gap_mean": float(np.mean(g))}

    compared = summary(gaps)
    # With ``control_bits`` the control takes the program's place: the
    # tokens that the reference at that activation precision puts first
    # are judged as if the program had served them.
    judged = compared if control_bits is None else summary(low)
    ref_s = time.perf_counter() - t_ref
    checked = {"failed": {"value": failed, "limit": 0},
               "compilations_in_window": {"value": counter.total,
                                          "limit": 0}}
    checked.update({k: {"value": judged[k], "limit": limits[k]["limit"]}
                    for k in limits})
    correct = bool(picks) and all(c["value"] <= c["limit"]
                                  for c in checked.values())
    log(f"check: {len(picks)} requests, {len(gaps)} served tokens "
        f"(rids {[p[0] for p in picks]}), gaps p50 "
        f"{percentile(gaps, 50):.6f} mean {float(np.mean(gaps)):.6f}, "
        f"reference {ref_s:.3f} s"
        + (f"; judged: the control at {control_bits}-bit activations"
           if control_bits is not None else ""))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": out_metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {
            "device_ops": run.trace["top_ops"][:10],
            "idle_gaps": run.trace["top_gaps"][:10]}
    result["compilations_in_window"] = counter.total
    result["program"] = compared
    result["checked"] = checked
    for k, c in checked.items():
        log(f"checked {k}: {c['value']!r} limit {c['limit']!r}")
    return result


class Run:
    """Everything a metric reader may read about one run."""

    def __init__(self, **kw):
        self.trace = None
        self.__dict__.update(kw)

    # -- helpers shared by the readers ---------------------------------------
    @property
    def window_reqs(self):
        """Requests due in the window."""
        return [r for r in self.reqs
                if 0 <= r["spec"].due_s < self.seconds]

    def token_times(self):
        """Every output token's host time, inside the window."""
        return [t for r in self.reqs for t in r["times"]
                if self.t_open <= t <= self.t_close]

    def peak(self, what: str) -> float:
        peaks = json.loads((HERE / "peaks.json").read_text())
        if self.device_kind not in peaks["devices"]:
            raise SystemExit(f"no peaks for device kind "
                             f"{self.device_kind!r} in peaks.json")
        return float(peaks["devices"][self.device_kind][what])

    def traced_calls(self):
        """Program calls made while the trace was on."""
        if self.trace_on is None:
            return []
        return [c for c in self.calls
                if self.trace_on <= c[1] <= self.trace_off]

    def work(self, calls, kernel: str):
        """(least seconds on this chip, ops, bytes) of ``kernel`` over
        ``calls``: per call the larger of its ops over the peak and its
        bytes over the bandwidth, summed."""
        ops_peak = self.peak("int8_ops_per_s")
        bw = self.peak("hbm_bytes_per_s")
        least = ops = byts = 0.0
        for o, b in work.kernel_calls(self.config, calls, kernel):
            least += max(o / ops_peak, b / bw)
            ops += o
            byts += b
        return least, ops, byts
