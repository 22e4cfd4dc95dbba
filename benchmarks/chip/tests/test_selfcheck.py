"""CPU self-checks of the benchmark's yardstick.

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests

The traffic generator, the percentile and rate arithmetic of the metric
readers, ``work.py`` against ``benchmarks/kernel_bench.py``'s analytic
numbers, the trace reduction on a trace recorded on a v5e, the weights'
layout against the program's, and the command's refusals.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
sys.path[:0] = [str(CHIP), str(ROOT / "src"), str(ROOT / "benchmarks")]

import arch  # noqa: E402
import generator  # noqa: E402
import harness  # noqa: E402
import trace_reduce  # noqa: E402
import weights  # noqa: E402
import work  # noqa: E402

TRAFFIC = ["chat"]


# -- traffic ----------------------------------------------------------------

@pytest.mark.parametrize("name", TRAFFIC)
def test_seeds_share_sizes_and_arrivals(name):
    t = generator.load(name)
    seconds = 40
    a = generator.make(t, 3, seconds, 1000)
    b = generator.make(t, 2 ** 31 + 11, seconds, 1000)
    again = generator.make(t, 3, seconds, 1000)
    assert [len(s.prompt) for s in a] == [len(s.prompt) for s in again]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, again))
    assert [len(s.prompt) for s in a] != [len(s.prompt) for s in b]
    assert [s.rid for s in a] == list(range(len(a)))
    assert all(x.due_s < y.due_s for x, y in zip(a, a[1:]))
    p, o = t["prompt"], t["output"]
    assert all(p["min"] <= len(s.prompt) <= p["max"] for s in a)
    assert all(o["min"] <= s.max_new <= o["max"] for s in a)
    rate, warm = t["arrival"]["rate_per_s"], t["arrival"]["warmup_s"]
    n = int(np.ceil(rate * seconds))
    p_len, o_len, due = generator.period(t, seconds)
    assert sorted(p_len) == sorted(generator.lengths(p, n))
    assert sorted(o_len) == sorted(generator.lengths(o, n))
    assert due[0] == 0 and np.all(np.diff(due) > 0) and due[-1] < seconds
    g = generator.gaps(rate, n)
    assert np.allclose(sorted(np.diff(np.append(due, seconds))),
                       g * seconds / g.sum())
    win = [[s for s in run if 0 <= s.due_s < seconds] for run in (a, b)]
    # every seed: the window holds one whole period, so the same requests
    # and gaps, each after the same neighbours, only from another start
    assert len(win[0]) == len(win[1]) == n and win[0][0].due_s == 0
    key = [[(len(s.prompt), s.max_new) for s in w] for w in win]
    gap = [np.diff([s.due_s for s in w] + [seconds]) for w in win]
    assert key[0] != key[1]
    shift = next(i for i in range(n) if key[0][i:] + key[0][:i] == key[1])
    assert np.allclose(np.roll(gap[0], -shift), gap[1])
    # the cycle goes on before and after the window
    for run in (a, b):
        first = [s for s in run if s.due_s < 0][0]
        assert -warm <= first.due_s < -warm + gap[0].max()
        for x, y in zip(run, run[n:]):
            assert (len(x.prompt), x.max_new) == (len(y.prompt), y.max_new)
            assert y.due_s - x.due_s == pytest.approx(seconds)
        assert run[-1].due_s < seconds + generator.DRAIN_S <= \
            run[-1].due_s + gap[0].max()
    assert abs(np.mean(generator.gaps(rate, 10000)) - 1 / rate) < 0.01 / rate


def test_lognormal_median_and_clip():
    d = {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 128,
         "max": 2048}
    v = generator.lengths(d, 1001)
    assert v[500] == 512 and v.min() == 128 and v.max() == 2048


def test_large_seeds():
    for seed in (0, 2 ** 31 - 1, 2 ** 31 + 5, 2 ** 33 + 7):
        weights.prng_key(seed)
        generator.rng(seed, 0).integers(0, 10)


# -- warm-up shapes ---------------------------------------------------------

def test_warm_shapes():
    eng = json.loads((CHIP / "configs" / "chatglm3-6b.json").read_text())[
        "engine"]
    chat = harness.warm_shapes(generator.load("chat"), eng)
    assert chat == [(0, w, 512) for w in range(1, 5)] + [
        (s, 1, 512) for s in (512, 1024, 1536)]
    long = harness.warm_shapes(dict(prompt={"min": 3072, "max": 3328}), eng)
    assert long == [(s, 1, 512) for s in range(0, 3073, 512)]


# -- metric arithmetic ------------------------------------------------------

class _Spec:
    def __init__(self, due):
        self.due_s = due


def _run(reqs, calls=(), steps=(), seconds=10.0):
    return harness.Run(config=None, seconds=seconds, t_open=100.0,
                       t_close=100.0 + seconds, setup_s=7.5, reqs=reqs,
                       steps=list(steps), calls=list(calls), trace_on=None,
                       trace_off=None, device_kind="TPU v5 lite")


def _req(due, times, submit=None, admit=None):
    return {"spec": _Spec(due), "times": times,
            "t_submit": submit if submit is not None else 100.0 + due,
            "t_admit": admit}


def test_ttft_p95_counts_from_due_and_missing_as_infinite():
    reqs = [_req(i * 0.1, [100.0 + i * 0.1 + 0.001 * (i + 1)])
            for i in range(20)]
    ttft = [0.001 * (i + 1) * 1e3 for i in range(20)]
    assert harness.reader("ttft_p95_ms").read(_run(reqs)) == pytest.approx(
        np.percentile(ttft, 95))
    reqs[5]["times"] = []            # a request that never got its token
    ttft[5] = np.inf
    expect = np.percentile(ttft, 95)
    got = harness.reader("ttft_p95_ms").read(_run(reqs))
    assert (got is None) == (not np.isfinite(expect))
    # due after the window, or in the warm-up before it: not counted
    reqs.append(_req(11.0, [200.0]))
    reqs.append(_req(-1.0, []))
    assert harness.reader("ttft_p95_ms").read(_run(reqs[:5])) == \
        pytest.approx(np.percentile(ttft[:5], 95))


def test_itl_only_inside_window():
    times = [99.0, 100.5, 101.0, 103.0, 110.5]
    run = _run([_req(0.0, times)])
    gaps = [1500.0, 500.0, 2000.0]          # the 110.5 gap ends outside
    assert harness.reader("itl_p95_ms").read(run) == pytest.approx(
        np.percentile(gaps, 95))
    assert harness.reader("setup_s").read(run) == 7.5


def test_queue_wait():
    reqs = [_req(0.0, [], submit=100.0 + i, admit=100.0 + i + 0.01 * i)
            for i in range(5)] + [_req(0.0, [], submit=100.0, admit=None)]
    run = _run(reqs)
    assert harness.reader("queue_wait_p95_ms.chat").read(run) == \
        pytest.approx(np.percentile([10.0 * i for i in range(5)], 95))


def test_mfu_from_model_ops():
    config = json.loads((CHIP / "configs" / "chatglm3-6b.json").read_text())
    calls = [("decode", 101.0, np.array([9, -1])),
             ("prefill", 101.5, (0, 1, 512, np.array([300])))]
    run = _run([], calls=calls, steps=[(101.0, 101.2, True),
                                       (101.3, 101.4, False)])
    run.config = config
    ops = work.model_ops(config, calls)
    m = config["model"]
    block, head = arch.of(config).linears(m)
    per_tok = 2 * sum(n * k for n, k in block) * m["n_layers"]
    att = 4 * m["n_heads"] * m["head_dim"] * m["n_layers"]
    expect = (per_tok + 2 * head[0] * head[1] + att * 10) + (
        per_tok * 300 + att * 300 * 301 // 2 + 2 * head[0] * head[1])
    assert ops == expect
    assert harness.reader("mfu.chat").read(run) == pytest.approx(
        100 * ops / (0.2 * 393e12))


# -- work.py against kernel_bench's analytic functions ----------------------

def test_work_matches_kernel_bench():
    import kernel_bench as kb
    from repro.kernels.int_attention import attention_macs
    for m, n, k, bits in [(128, 4608, 4096, 8), (1, 13696, 4096, 4),
                          (512, 65024, 4096, 8)]:
        ops, byts = work.qmatmul(m, n, k, bits / 8)
        a = kb.qmatmul_analytic(m, n, k, w_bits=bits)
        assert ops == 2 * a["macs"]
        assert byts * 1e6 / kb.HBM == pytest.approx(a["t_memory_us"])
    # paged decode: K/V bytes of each row's live pages; MACs of full pages
    pos = [127, 128, 1000]
    a = kb.paged_step_analytic(2, 16, 128, pos, 128, 8)
    ops, byts = work.paged_decode([128, 256, 1024], 32, 2, 128, 128, 1.0)
    assert byts - 3 * 32 * 128 * 5 == a["paged_bytes_per_step"]
    assert ops == 2 * a["paged_macs_per_step"]
    # fused prefill: causal pairs only, two contractions per pair
    ops, _ = work.fused_prefill([512], 32, 2, 128)
    full = attention_macs(32, 512, 512, 128)
    assert ops == 2 * full * (512 * 513 // 2) / (512 * 512)


# -- the trace reduction on a recorded chip trace ----------------------------

def test_trace_reduce_on_recorded_trace(tmp_path):
    import lzma
    expected = json.loads((CHIP / "testdata" / "trace_expected.json")
                          .read_text())
    path = tmp_path / "trace.xplane.pb"
    path.write_bytes(lzma.decompress(
        (CHIP / "testdata" / expected["file"]).read_bytes()))
    got = trace_reduce.reduce(str(path))
    assert got["devices"] == 1
    assert 0 < got["busy_s"] <= got["span_s"]
    for k in ("busy_s", "span_s"):
        assert got[k] == pytest.approx(expected[k], rel=1e-9)
    for group in ("modules", "ops"):
        for name, (sec, n) in expected[group].items():
            assert got[group][name][0] == pytest.approx(sec, rel=1e-9)
            assert got[group][name][1] == n
    # one decode step per program execution: 28 layers of paged attention
    steps = got["modules"]["jit_step_fn"][1]
    assert got["ops"]["int_paged_decode_attention"][1] == 28 * steps
    # the gaps lie between busy intervals: never more than the idle time
    assert sum(g for _, g in got["top_gaps"]) <= \
        got["span_s"] - got["busy_s"] + 1e-9
    assert set(got["gaps_by_span"]) <= {"bench.step", "bench.observe",
                                         "bench.submit", "bench.wait",
                                         "no harness span"}
    assert "while" not in dict(got["top_ops"])


# -- weights and the command ------------------------------------------------

def test_weights_match_program_layout():
    import jax
    from repro.models import lm
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for entry in bench["configs"]:
        config = json.loads((ROOT / entry["file"]).read_text())
        cfg, qc = harness.build(config)
        weights.check_layout(
            arch.of(config).shape_of(config["model"], config["quant"]),
            jax.eval_shape(lambda k: lm.init_int_params(k, cfg, qc),
                           weights.prng_key(0)))


def test_config_files_match_published():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for entry in bench["configs"]:
        config = json.loads((ROOT / entry["file"]).read_text())
        assert config["source"] == entry["source"]
        assert sorted(config["reduced"]) == sorted(entry["reduced"])
        for ours, theirs in config["maps_to_published"].items():
            if theirs in config["reduced"]:
                assert config["model"][ours] != config["published"][theirs]
            else:
                assert config["model"][ours] == config["published"][theirs]


def _command(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "chatglm3-6b.chat", "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_accelerator():
    p = _command(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_command_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
