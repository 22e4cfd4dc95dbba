"""Plain float32 reference of a dense decoder with grouped-query attention.

The architecture of ChatGLM3 (and of the other dense GQA decoders with
rotary on interleaved pairs that a configuration file may name): pre-norm
blocks with RMSNorm, rotary positions, causal grouped-query attention and
a SwiGLU MLP, a final RMSNorm and an untied head.  It follows the published description in
straightforward ``jax.numpy`` at float32 with ``Precision.HIGHEST``: no
kernels, no cache, no batching tricks, and it imports nothing of the
program.

Weights arrive as the codes and steps the benchmark made (w4 rows, int8
embedding rows), and are dequantized here; activations, keys, values and
probabilities stay float32.  The one departure from an exact softmax is
the configuration's own ``softmax: "base2"`` (the paper's Eq. 4), the
shift exponential ``(1 + r) * 2**floor(x)`` of ``x = log2(e) * s``, which
is part of the model this repository serves, not a precision.

``act_bits`` (the control): quantize every linear layer's input to that
many bits, one absmax grid per token, as a lower-precision stand-in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
LOG2E = math.log2(math.e)


def _fake_quant(x, bits):
    qmax = (1 << (bits - 1)) - 1
    step = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                       1e-30) / qmax
    return jnp.clip(jnp.round(x / step), -qmax - 1, qmax) * step


def _linear(x, w, act_bits):
    if act_bits:
        x = _fake_quant(x, act_bits)
    weight = w["codes"].astype(jnp.float32) * w["step"][:, None]
    y = jnp.einsum("...i,oi->...o", x, weight, precision=HI)
    if "bias" in w:
        y = y + w["bias"].astype(jnp.float32)
    return y


def _rmsnorm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, positions, theta, frac, layout):
    """x: (N, H, T, hd) at ``positions`` (T,)."""
    hd = x.shape[-1]
    rd = int(hd * frac) // 2 * 2
    inv = 1.0 / theta ** (np.arange(0, rd, 2, dtype=np.float64) / rd)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xr, rest = x[..., :rd], x[..., rd:]
    if layout != "interleaved":
        raise ValueError(f"unknown rope layout {layout!r}")
    a, b = xr[..., 0::2], xr[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], -1)
    out = out.reshape(xr.shape)
    return jnp.concatenate([out, rest], -1)


def _exp2_shift(y):
    f = jnp.floor(y)
    return jnp.ldexp(1.0 + (y - f), f.astype(jnp.int32))


def _attention(q, k, v, softmax, q_block):
    """Causal GQA.  q: (N, Hq, T, hd); k, v: (N, Hkv, T, hd)."""
    n, hq, t, hd = q.shape
    hkv = k.shape[1]
    qg = q.reshape(n, hkv, hq // hkv, t, hd)
    scale = hd ** -0.5
    outs = []
    for start in range(0, t, q_block):
        stop = min(t, start + q_block)
        s = jnp.einsum("nhgqd,nhkd->nhgqk", qg[:, :, :, start:stop],
                       k[:, :, :stop], precision=HI) * scale
        valid = (jnp.arange(stop)[None, :]
                 <= jnp.arange(start, stop)[:, None])
        if softmax == "base2":
            y = jnp.where(valid, s * LOG2E, -jnp.inf)
            m = jnp.floor(jnp.max(y, axis=-1, keepdims=True))
            e = jnp.where(valid, _exp2_shift(jnp.where(valid, y - m, 0.0)),
                          0.0)
        elif softmax == "exact":
            y = jnp.where(valid, s, -jnp.inf)
            e = jnp.exp(y - jnp.max(y, axis=-1, keepdims=True))
        else:
            raise ValueError(f"unknown softmax {softmax!r}")
        p = e / jnp.sum(e, axis=-1, keepdims=True)
        outs.append(jnp.einsum("nhgqk,nhkd->nhgqd", p, v[:, :, :stop],
                               precision=HI))
    out = jnp.concatenate(outs, axis=3)
    return out.reshape(n, hq, t, hd)


def _heads(y, h, hd):
    n, t, _ = y.shape
    return y.reshape(n, t, h, hd).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _block(x, w, frozen, act_bits, q_block):
    m = dict(frozen)
    hd = m["head_dim"]
    positions = jnp.arange(x.shape[1])
    h = _rmsnorm(x, w["ln1"], m["norm_eps"])
    q = _heads(_linear(h, w["wq"], act_bits), m["n_heads"], hd)
    k = _heads(_linear(h, w["wk"], act_bits), m["kv_heads"], hd)
    v = _heads(_linear(h, w["wv"], act_bits), m["kv_heads"], hd)
    rope = (m["rope_theta"], m["rotary_frac"], m["rope_layout"])
    q, k = _rope(q, positions, *rope), _rope(k, positions, *rope)
    o = _attention(q, k, v, m["softmax"], q_block)
    n, _, t, _ = o.shape
    x = x + _linear(o.transpose(0, 2, 1, 3).reshape(n, t, -1), w["wo"],
                    act_bits)
    h = _rmsnorm(x, w["ln2"], m["norm_eps"])
    mid = jax.nn.silu(_linear(h, w["gate"], act_bits)) * _linear(
        h, w["up"], act_bits)
    return x + _linear(mid, w["down"], act_bits)


@jax.jit
def _embed(codes, step, tokens):
    return codes[tokens].astype(jnp.float32) * step[tokens][..., None]


@functools.partial(jax.jit, static_argnums=(5, 6))
def _head(x, seq_idx, pos_idx, gain, head, eps, act_bits):
    return _linear(_rmsnorm(x[seq_idx, pos_idx], gain, eps), head, act_bits)


FROZEN_KEYS = ("n_layers", "d_model", "n_heads", "kv_heads", "head_dim",
               "d_ff", "vocab", "rotary_frac", "rope_theta", "rope_layout",
               "norm_eps")


def logits_at(model: dict, softmax: str, top, layer, seqs, wanted, *,
              act_bits=None, q_block: int = 512, pad_to: int = 0):
    """Next-token logits of each sequence at the wanted positions.

    ``seqs``: token arrays; ``wanted``: for each sequence, the positions
    whose next-token logits are asked for.  The sequences run together,
    right-padded to ``pad_to`` or their longest, rounded up to a multiple
    of ``q_block`` (padding comes after every real token, so causal
    attention never sees it), one layer at a time.  Returns an
    (R', vocab) float32 device array whose first R rows are the wanted
    positions in order; R' rounds R up to a multiple of ``q_block``, so
    that few shapes compile.
    """
    frozen = tuple((k, model[k]) for k in FROZEN_KEYS) + (
        ("softmax", softmax),)
    t = max([len(s) for s in seqs] + [pad_to])
    t = -(-t // q_block) * q_block
    toks = np.zeros((len(seqs), t), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    w = top()
    x = _embed(w["emb_codes"], w["emb_step"], jnp.asarray(toks))
    for l in range(model["n_layers"]):
        x = _block(x, layer(l), frozen, act_bits, q_block)
    seq_idx = np.concatenate([np.full(len(p), i) for i, p in
                              enumerate(wanted)])
    pos_idx = np.concatenate([np.asarray(p) for p in wanted])
    pad = -len(seq_idx) % q_block
    seq_idx = np.pad(seq_idx, (0, pad), mode="edge").astype(np.int32)
    pos_idx = np.pad(pos_idx, (0, pad), mode="edge").astype(np.int32)
    return _head(x, jnp.asarray(seq_idx), jnp.asarray(pos_idx),
                 w["final_gain"], w["head"], model["norm_eps"], act_bits)
