"""The one traffic generator: a traffic file's parameters plus a seed give
the requests of one run.

The arrivals are periodic with the window's length as their period.  One
period holds ``n = ceil(rate * seconds)`` requests: stratified quantiles
of the file's prompt-length, output-length and inter-arrival-gap
distributions (the gaps scaled to fill the period), arranged once in an
order that the traffic file's ``pattern_seed`` fixes.  The run's seed
picks where in that cycle the window opens, and draws the token ids.  So
every seed sends the same requests with the same neighbours, and the
window holds exactly one period: the same work, in another order.  The
engine reaches the window in steady state: the ``warmup_s`` before it
repeat the cycle's end, and a minute of drain after it goes on with the
cycle while the window's late requests finish.  A traffic file
(``traffic/<name>.json``) holds:

- ``arrival``: ``{"kind": "poisson", "rate_per_s": r, "warmup_s": w,
  "pattern_seed": p}``, an open loop with exponential gaps;
- ``prompt`` / ``output``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}`` or ``{"dist": "uniform", "min", "max"}`` in tokens;
- ``rows``: the engine's decode batch rows.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Spec:
    rid: int
    length: int                 # prompt tokens
    max_new: int
    due_s: float                # seconds after the window opens
    seed: int
    vocab: int

    @functools.cached_property
    def prompt(self) -> np.ndarray:
        """(length,) int32 token ids, drawn when first asked for."""
        return rng(self.seed, 1, self.rid).integers(
            0, self.vocab, self.length, dtype=np.int32)


def load(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """n stratified lengths of ``dist`` (sorted, before any shuffle)."""
    u = _quantiles(n)
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.round(v), dist["min"], dist["max"]).astype(np.int64)


def gaps(rate: float, n: int) -> np.ndarray:
    """n stratified exponential inter-arrival gaps at ``rate`` per second."""
    return -np.log1p(-_quantiles(n)) / rate


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


DRAIN_S = 60.0


def period(traffic: dict, seconds: float):
    """One period of ``seconds`` in its fixed order: (prompt lengths,
    output lengths, due times from the period's start)."""
    arr = traffic["arrival"]
    if arr["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    n = int(math.ceil(arr["rate_per_s"] * seconds))
    r = rng(arr["pattern_seed"], 0)
    p_len = r.permutation(lengths(traffic["prompt"], n))
    o_len = r.permutation(lengths(traffic["output"], n))
    g = r.permutation(gaps(arr["rate_per_s"], n))
    g *= seconds / g.sum()
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    return p_len, o_len, due


def make(traffic: dict, seed: int, seconds: float, vocab: int) -> list[Spec]:
    """The run's requests, in the order they are sent: the cycle from
    ``warmup_s`` before the window opens to ``DRAIN_S`` after it closes,
    the window opening at the seeded request of the period."""
    p_len, o_len, due = period(traffic, seconds)
    n = len(due)
    shift = int(rng(seed, 0).integers(n))
    rel = due - due[shift]

    def at(k):
        """Due time of the k-th request after the window opens."""
        q, i = divmod(shift + k, n)
        return q * seconds + rel[i]

    k = 0
    while at(k - 1) >= -traffic["arrival"]["warmup_s"]:
        k -= 1
    specs = []
    while at(k) < seconds + DRAIN_S:
        i = (shift + k) % n
        specs.append(Spec(len(specs), int(p_len[i]), int(o_len[i]),
                          float(at(k)), seed, vocab))
        k += 1
    return specs
