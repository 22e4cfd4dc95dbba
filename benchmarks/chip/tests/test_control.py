"""The check that decides ``correct``, driven through a whole run at a tiny
size on the CPU (the harness's look for a chip is skipped).

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests

- the program as configured passes;
- the control (the reference with 4-bit activations in its linear layers,
  int4 for the configuration's int8), put in the program's place in the
  same comparison, comes out as not correct;
- a served token altered where the decode step produces it makes
  ``correct`` false.

The tiny model (``tiny.json``, 2 layers of width 64, with ChatGLM3's QKV
bias and half-dim interleaved rotary) and its limit stand for the cells
at their own sizes, whose readings and limits ``PERF.md`` gives.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

import harness  # noqa: E402

# Readings at this size (seeds 7, 8, 11): program 0.0-0.05, control
# 0.49, altered token 4.5.
LIMIT = 0.2


def _run(seed, **kw):
    bench = harness.load_bench(ROOT)
    bench["_root"] = str(ROOT)
    config = json.loads((HERE / "tiny.json").read_text())
    traffic = json.loads((HERE / "tiny_chat.json").read_text())
    return harness.run_cell(
        bench, "chatglm3-6b.chat", seed, 2.0, False, time.perf_counter(),
        config=config, traffic=traffic,
        limits={"served_logit_gap_max": {"limit": LIMIT}},
        min_check_tokens=48, **kw)


def test_program_passes():
    res = _run(7)
    assert res["correct"], res
    assert res["checked"]["served_logit_gap_max"]["value"] <= LIMIT
    assert res["checked"]["served_logit_gap_max"]["value"] == \
        res["program"]["served_logit_gap_max"]
    assert list(res)[-1] == "checked"
    assert res["compilations_in_window"] == 0
    assert res["failed"] == 0 and res["attempted"] > 0


def test_control_fails():
    res = _run(7, control_bits=4)
    assert not res["correct"]
    assert res["checked"]["served_logit_gap_max"]["value"] > LIMIT
    assert res["program"]["served_logit_gap_max"] <= LIMIT


def test_altered_token_fails():
    res = _run(8, fault=harness.token_fault)
    assert not res["correct"]
    assert res["checked"]["served_logit_gap_max"]["value"] > LIMIT
