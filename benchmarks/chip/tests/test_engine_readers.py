"""CPU self-checks of the engine layer's readers.

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests

The readers of the engine's own request stamps and packer counters
(``admit_wait_p95_ms.chat``, ``prefill_phase_p95_ms.chat``,
``packer_stall_share.chat``) on synthetic runs and in a whole tiny run on
the CPU, and ``launches_per_step.chat`` on a synthetic trace reduction.
An engine without the stamps leaves each of them out of the line.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

import harness  # noqa: E402

ENGINE_READERS = ("admit_wait_p95_ms.chat", "prefill_phase_p95_ms.chat",
                  "packer_stall_share.chat")


def _run(reqs, steps=(), trace=None, trace_on=None, trace_off=None):
    run = harness.Run(config=None, seconds=10.0, t_open=100.0,
                      t_close=110.0, setup_s=7.5, reqs=reqs,
                      steps=list(steps), calls=[], trace_on=trace_on,
                      trace_off=trace_off, device_kind="TPU v5 lite")
    run.trace = trace
    return run


def _stamped(submit, admit, first, steps=0, stalls=0):
    """A request as the engine leaves it: its own stamps and counters."""
    return {"spec": SimpleNamespace(due_s=submit - 100.0), "times": [],
            "t_submit": submit, "t_admit": None,
            "req": SimpleNamespace(submit_s=submit, admit_s=admit,
                                   first_token_s=first, prefill_steps=steps,
                                   prefill_stalls=stalls)}


def _read(name, run):
    return harness.reader(name).read(run)


def test_engine_stamps():
    reqs = [_stamped(100.0 + i, 100.0 + 1.01 * i, 100.0 + 1.11 * i,
                     steps=i + 1, stalls=i) for i in range(5)]
    reqs.append(_stamped(95.0, 99.0, 99.5, steps=9, stalls=9))   # before
    reqs.append(_stamped(108.0, 109.0, None, steps=3, stalls=3))  # no token
    reqs.append(_stamped(109.5, None, None))                      # queued
    reqs.append(_stamped(109.0, 109.5, 110.5, steps=7, stalls=7))  # after
    run = _run(reqs)
    assert _read("admit_wait_p95_ms.chat", run) == pytest.approx(
        np.percentile([10.0 * i for i in range(5)] + [1000.0, 500.0], 95))
    assert _read("prefill_phase_p95_ms.chat", run) == pytest.approx(
        np.percentile([100.0 * i for i in range(5)], 95))
    assert _read("packer_stall_share.chat", run) == \
        pytest.approx(100 * 10 / 15)


def test_engine_stamps_absent():
    """Requests of an engine without the stamps, and one-shot admissions
    that never passed the packer: nothing to read."""
    plain = {"spec": SimpleNamespace(due_s=0.0), "times": [101.0],
             "t_submit": 100.0, "t_admit": 100.5,
             "req": SimpleNamespace(status="done", tokens=[1])}
    for name in ENGINE_READERS:
        assert _read(name, _run([plain])) is None
    one_shot = _run([_stamped(100.0, 100.5, 101.0)])
    assert _read("packer_stall_share.chat", one_shot) is None


def test_launches_per_step():
    trace = {"modules": {"jit_step_fn": [0.2, 2.0], "jit__argmax": [0.01, 3.0],
                         "jit_admit_fn": [0.1, 1.0]}}
    steps = [(104.0, 104.1, True),      # before the trace
             (105.0, 105.1, True), (105.2, 105.3, True),
             (105.4, 105.6, False),     # begun in the trace, no program
             (107.9, 108.1, True)]      # begun after the trace
    run = _run([], steps=steps, trace=trace, trace_on=105.0, trace_off=107.0)
    assert _read("launches_per_step.chat", run) == pytest.approx(6 / 3)
    assert _read("launches_per_step.chat", _run([], steps=steps)) is None
    empty = _run([], steps=steps[:1], trace=trace, trace_on=105.0,
                 trace_off=107.0)
    assert _read("launches_per_step.chat", empty) is None


def test_engine_readers_in_tiny_run():
    """A whole run at a tiny size on the CPU, with the engine's readers
    among the run's metrics: each reads the run's requests."""
    bench = harness.load_bench(ROOT)
    bench["_root"] = str(ROOT)
    bench["end_to_end"] += [m for m in bench["per_layer"]
                            if m["name"] in ENGINE_READERS]
    config = json.loads((HERE / "tiny.json").read_text())
    traffic = json.loads((HERE / "tiny_chat.json").read_text())
    res = harness.run_cell(
        bench, "chatglm3-6b.chat", 7, 2.0, False, time.perf_counter(),
        config=config, traffic=traffic,
        limits={"served_logit_gap_max": {"limit": 0.2}},
        min_check_tokens=48)
    assert res["correct"], res
    got = {k: res["metrics"][k]["value"] for k in ENGINE_READERS}
    assert got["admit_wait_p95_ms.chat"] >= 0
    assert got["prefill_phase_p95_ms.chat"] > 0
    assert 0 <= got["packer_stall_share.chat"] <= 100
