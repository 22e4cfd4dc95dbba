"""Norm layers (float path) — quantized variants live in repro.core.pqln.

Row means sum in one fixed pairwise order (:func:`tree_sum`), never by a
plain reduce: XLA picks a reduce's order per fusion, and the fusion a norm
lands in depends on its producer (a residual add after XLA's own dot, or
after a Pallas kernel), so the two kernel backends would normalize one row
differently (seen on a v5e).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.softmax2 import tree_sum


def _row_mean(x):
    return tree_sum(x) / x.shape[-1]


def layernorm(x, gamma, beta, *, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    mean = _row_mean(xf)
    var = _row_mean(jnp.square(xf - mean))
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * gamma + beta).astype(x.dtype)


def rmsnorm(x, gamma, *, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    ms = _row_mean(jnp.square(xf))
    return (xf * jax.lax.rsqrt(ms + eps) * gamma).astype(x.dtype)


def apply_norm(x, p: dict, kind: str, *, eps: float = 1e-6):
    if kind == "layernorm":
        return layernorm(x, p["gamma"], p["beta"], eps=eps)
    if kind == "rmsnorm":
        return rmsnorm(x, p["gamma"], eps=eps)
    raise ValueError(kind)


def init_norm(d: int, kind: str, dtype=jnp.float32) -> dict:
    p = {"gamma": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["beta"] = jnp.zeros((d,), dtype)
    return p
