"""CPU self-checks of the architecture modules (``arch/<name>.py``).

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests

- the dense module makes the same weights and counts the same work as the
  harness did before the architecture-specific code left it (digests and
  counts pinned from that code);
- ``lm_config`` refuses a ``model`` key that neither the program nor the
  reference reads;
- a second architecture (``fixture/``: sparse experts, a sliding window on
  every other layer) goes through the harness as two new files, a
  configuration and its module, dropped into a copy of the benchmark.
"""
from __future__ import annotations

import copy
import hashlib
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
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

import arch  # noqa: E402
import harness  # noqa: E402
import work  # noqa: E402

TINY = json.loads((HERE / "tiny.json").read_text())
GLM = json.loads((CHIP / "configs" / "chatglm3-6b.json").read_text())
CONFIGS = {"tiny": TINY, "chatglm3-6b": GLM}

# Recorded program calls (``harness.Recorder`` entries): decode rows with
# their positions (negative: inactive), prefill launches with (start,
# width, bucket, real lengths; None where an untraced run did not read
# them back).
CALLS = [("decode", 1.0, np.array([9, -1, 130, 0])),
         ("decode", 1.1, np.array([-1, -1])),
         ("prefill", 1.2, (0, 2, 512, np.array([300, 17]))),
         ("prefill", 1.3, (512, 1, 512, np.array([200]))),
         ("prefill", 1.4, (0, 1, 512, None)),
         ("decode", 1.5, np.array([2047, 511, 512, 3]))]


def _digest(tree):
    import jax
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = np.asarray(x)
        h.update(f"{jax.tree_util.keystr(path)}:{x.dtype}:{x.shape};"
                 .encode())
        h.update(x.tobytes())
    return h.hexdigest()


# -- the dense module, bit for bit -------------------------------------------

@pytest.mark.parametrize("seed, digest", [
    (7, "756724ee1973acd111171d692555df9b43bb0fd7891578cad88ec4fe1a69f1ef"),
    (2 ** 33 + 7,
     "3803dade50b12f916a07a80bd8208c62f66b3b8f5baec920cf2357153816cec3")])
def test_dense_weights_pinned(seed, digest):
    params = arch.of(TINY).make(seed, TINY["model"], TINY["quant"])
    assert _digest(params) == digest


@pytest.mark.parametrize("name, kernel, n, ops, byts, digest", [
    ("tiny", "qmatmul", 60, 64716800, 2703232.0,
     "ae883b77fa66da1a1a0ca7deb070287f7eb247f068ee91caf85a49cd32a844ab"),
    ("tiny", "paged_decode", 4, 1648128, 420224.0,
     "3331298d9e1d3af927582944e209b2a25c2b0e2bfb79550fac2f50f40e7ff6e6"),
    ("tiny", "fused_prefill", 2, 23195136, 243456,
     "37f79cd2d91662d1e936ec796d7cb9dd9e29a55a422d16861362b412c496d9e0"),
    ("chatglm3-6b", "qmatmul", 788, 5989977817088, 26830489600.0,
     "04b852a88dddcbcc841a4cea745505df3ed71f7e2debbe06825e4ef8ec7d774c"),
    ("chatglm3-6b", "paged_decode", 56, 1476722688, 59064320.0,
     "1a06da16cddb39e525b01022a5a414fd7ad04e38e4c37806fca047fcf5a536a6"),
    ("chatglm3-6b", "fused_prefill", 28, 20782841856, 186324992,
     "b8ce828396de89366becdbc83a414c6275e5541ef276af9f0fe76ed4749355d8")])
def test_dense_kernel_calls_pinned(name, kernel, n, ops, byts, digest):
    got = [[o, b] for o, b in work.kernel_calls(CONFIGS[name], CALLS, kernel)]
    assert len(got) == n
    assert sum(o for o, _ in got) == ops and sum(b for _, b in got) == byts
    assert hashlib.sha256(json.dumps(got).encode()).hexdigest() == digest


@pytest.mark.parametrize("name, ops", [("tiny", 152280064.0),
                                       ("chatglm3-6b", 6068434501632.0)])
def test_dense_model_ops_pinned(name, ops):
    assert work.model_ops(CONFIGS[name], CALLS) == ops


# -- no model key dropped without a word --------------------------------------

@pytest.mark.parametrize("key, value", [
    ("attn_window", 8), ("block_pattern", ["local", "attn"]),
    ("moe", {"n_experts": 4, "top_k": 2}), ("sliding_window", 1024)])
def test_lm_config_refuses_unread_key(key, value):
    config = copy.deepcopy(TINY)
    config["model"][key] = value
    with pytest.raises(ValueError, match=key):
        harness.build(config)


def test_dense_reads_every_key():
    mod = arch.of(GLM)
    assert set(GLM["model"]) == mod.PROGRAM | mod.REFERENCE_ONLY
    assert mod.REFERENCE_ONLY == {"rope_layout", "norm_eps"}


# -- a second architecture as new files only ----------------------------------

DRIVE = r"""
import json, sys, time
from pathlib import Path
import numpy as np
sys.path[:0] = [str(Path("benchmarks/chip").resolve()), sys.argv[1]]
import jax
import arch, harness, weights, work
from repro.models import lm

config = json.loads(Path("benchmarks/chip/configs/tiny-moe-local.json")
                    .read_text())
cfg, qc = harness.build(config)
blocks = arch.of(config)
weights.check_layout(
    blocks.shape_of(config["model"], config["quant"]),
    jax.eval_shape(lambda k: lm.init_int_params(k, cfg, qc),
                   weights.prng_key(0)))
calls = [("decode", 1.0, np.array([9, -1, 30, 0])),
         ("prefill", 1.2, (0, 2, 16, np.array([12, 5]))),
         ("prefill", 1.3, (16, 1, 16, np.array([7])))]
counts = {k: [sum(o for o, _ in c), sum(b for _, b in c), len(c)]
          for k in ("qmatmul", "paged_decode", "fused_prefill")
          for c in [list(work.kernel_calls(config, calls, k))]}
bench = harness.load_bench(Path.cwd())
bench["_root"] = str(Path.cwd())
bench["configs"].append({"name": config["name"],
                         "file": "benchmarks/chip/configs/tiny-moe-local.json"})
bench["workloads"].append({"name": "tiny-moe-local.chat",
                           "config": config["name"], "traffic": "chat",
                           "chips": 1})
res = harness.run_cell(bench, "tiny-moe-local.chat", 7, 2.0, False,
                       time.perf_counter(), traffic=json.loads(sys.argv[2]),
                       limits={}, do_check=False)
print(json.dumps({"kinds": list(cfg.block_pattern), "window": cfg.attn_window,
                  "experts": [cfg.moe.n_experts, cfg.moe.top_k],
                  "counts": counts,
                  "model_ops": work.model_ops(config, calls), **res}))
"""


def test_fixture_architecture_as_new_files(tmp_path):
    assert not (CHIP / "arch" / "tiny_moe_local.py").exists()
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(HERE / "fixture" / "tiny-moe-local.json", chip / "configs")
    shutil.copy(HERE / "fixture" / "tiny_moe_local.py", chip / "arch")
    traffic = (HERE / "tiny_chat.json").read_text()
    p = subprocess.run(
        [sys.executable, "-c", DRIVE, str(ROOT / "src"), traffic],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["kinds"] == ["local", "attn"] and res["window"] == 8
    assert res["experts"] == [4, 2]
    for kernel, (ops, byts, n) in res["counts"].items():
        assert ops > 0 and byts > 0 and n > 0, kernel
    assert res["model_ops"] > 0
    load = res["load"]
    assert load["due_in_window"] > 0 and load["failed"] == 0
    assert load["not_admitted_at_close"] == 0 and load["tokens_per_s"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0
