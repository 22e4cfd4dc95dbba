"""The comparison that decides ``correct``.

Once the window has closed, a sample of the finished requests (drawn from
the seed, the longest request always in it) is run through the plain
reference of the configuration (``references/<name>.py``), teacher-forced
over each prompt and its served tokens.  For every served token the gap
by which its reference logit lies below the reference's best logit at that
position is read; the number compared is the widest gap.  It is 0 where
the program served the reference's own greedy token.

The control (``control.py``) reads the same gap for the token that the
reference computed at a lower precision puts first.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np

import generator

HERE = Path(__file__).resolve().parent


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(config: dict):
    return load_module(HERE / "references" / f"{config['reference']}.py",
                       f"reference_{config['reference']}")


def limits(cell: str) -> dict:
    return json.loads((HERE / "limits" / f"{cell}.json").read_text())


def sample(finished, seed: int, min_tokens: int, min_requests: int,
           max_requests: int):
    """A seeded sample of finished requests: the longest one (prompt plus
    served tokens) first, then others in a seeded order until the sample
    holds ``min_tokens`` served tokens and ``min_requests`` requests, or
    ``max_requests`` requests.
    ``finished``: [(rid, prompt, tokens)]."""
    if not finished:
        return []
    order = sorted(finished, key=lambda f: f[0])
    longest = max(order, key=lambda f: (len(f[1]) + len(f[2]), f[0]))
    rest = [f for f in order if f[0] != longest[0]]
    rest = [rest[i] for i in generator.rng(seed, 2).permutation(len(rest))]
    picks = [longest]
    for f in rest:
        if len(picks) >= max_requests or (
                sum(len(p[2]) for p in picks) >= min_tokens
                and len(picks) >= min_requests):
            break
        picks.append(f)
    return picks


def _inputs(picks):
    seqs = [np.concatenate([p[1], np.asarray(p[2][:-1], np.int32)])
            for p in picks]
    wanted = [np.arange(len(p[1]) - 1, len(p[1]) - 1 + len(p[2]))
              for p in picks]
    served = np.concatenate([np.asarray(p[2], np.int32) for p in picks])
    return seqs, wanted, served


def served_gaps(ref, config, top, layer, picks, pad_to: int,
                act_bits=None):
    """Per served token: the reference's best logit minus the reference's
    logit of the token served; with ``act_bits``, also of the token that
    the reference at that activation precision puts first (the control),
    else None.  One request at a time, padded to ``pad_to`` tokens, so
    that every run of a cell uses one compiled reference program."""
    model, softmax = config["model"], config["quant"]["softmax"]
    served_out, control_out = [], []
    for pick in picks:
        seqs, wanted, served = _inputs([pick])
        ref_logits = ref.logits_at(model, softmax, top, layer, seqs, wanted,
                                   pad_to=pad_to)
        n = len(served)
        best = jnp.max(ref_logits, axis=-1)[:n]

        def gap(chosen):
            got = jnp.take_along_axis(ref_logits[:n], chosen[:, None],
                                      axis=-1)[:, 0]
            return np.asarray(best - got, np.float64)

        served_out.append(gap(jnp.asarray(served)))
        if act_bits is not None:
            low = ref.logits_at(model, softmax, top, layer, seqs, wanted,
                                act_bits=act_bits, pad_to=pad_to)
            control_out.append(gap(jnp.argmax(low[:n], axis=-1)))
            del low
    return (np.concatenate(served_out),
            np.concatenate(control_out) if act_bits is not None else None)
