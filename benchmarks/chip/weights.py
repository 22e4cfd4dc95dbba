"""Random serving weights from a seed, made by the benchmark on the device.

The benchmark, not the program, makes the weights: one jitted call turns
``--seed`` into the integer parameter tree the serving engine reads (w4
codes one per byte with a float32 step per output channel, int8 embedding
rows with a step per row, bf16 biases, float32 norm gains), so the plain
reference can be handed the very same codes and steps without taking
anything the program made.  This module holds the pieces every
architecture shares; the blocks are laid out by the configuration's
architecture module (``arch/<name>.py``).  :func:`check_layout` holds the
tree against the program's own layout (``jax.eval_shape`` of
``lm.init_int_params``).

Distributions: each weight row is a standard normal row quantized on its
absmax grid, times ``fan_in ** -0.5``; embedding rows are standard normal
on an int8 absmax grid; biases ~ N(0, 0.1); norm gains 1 + N(0, 0.1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def prng_key(seed: int):
    """A JAX key from any non-negative seed (wider than 32 bits too)."""
    seed = int(seed)
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def _codes(z, bits):
    """Signed ``bits`` codes of the rows of ``z`` on their absmax grid,
    and the grid step of each row (in units of ``z``)."""
    qmax = (1 << (bits - 1)) - 1
    amax = jnp.max(jnp.abs(z), axis=-1, keepdims=True)
    codes = jnp.clip(jnp.round(z * (qmax / amax)), -qmax - 1, qmax)
    return codes.astype(jnp.int8), amax[..., 0] / qmax


def _linear(key, out, inn, w_bits, bias):
    k1, k2 = jax.random.split(key)
    codes, step = _codes(jax.random.normal(k1, (out, inn), jnp.float32),
                         w_bits)
    p = {"w_q": codes, "w_scale": step * inn ** -0.5}
    if bias:
        p["b"] = (0.1 * jax.random.normal(k2, (out,))).astype(jnp.bfloat16)
    return p


def _gain(key, d):
    return {"gamma": 1.0 + 0.1 * jax.random.normal(key, (d,), jnp.float32)}


def top(keys, vocab: int, d: int, w_bits: int):
    """Embedding, final norm and head, from three keys."""
    emb_q, emb_step = _codes(
        jax.random.normal(keys[0], (vocab, d), jnp.float32), 8)
    return {"embed": {"emb_q": emb_q, "emb_scale": emb_step},
            "final_norm": _gain(keys[1], d),
            "lm_head": _linear(keys[2], vocab, d, w_bits, False)}


def check_layout(params_shape, program_shape):
    """Raise unless the benchmark's tree has the program's structure,
    shapes and dtypes (both given as ``jax.eval_shape`` results)."""
    mine = jax.tree_util.tree_flatten_with_path(params_shape)[0]
    theirs = jax.tree_util.tree_flatten_with_path(program_shape)[0]
    a = {jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype))
         for p, x in mine}
    b = {jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype))
         for p, x in theirs}
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))[:8]
        raise RuntimeError(f"benchmark weights do not match the program's "
                           f"parameter layout: {diff}")


# ---------------------------------------------------------------------------
# The reference's view of the same weights
# ---------------------------------------------------------------------------

def reference_linear(p):
    """A linear layer as the reference reads it: codes, step, bias."""
    out = {"codes": p["w_q"], "step": p["w_scale"]}
    if "b" in p:
        out["bias"] = p["b"]
    return out


def reference_top(params):
    """Embedding, final gain and head, as the reference reads them."""
    return {"emb_codes": params["embed"]["emb_q"],
            "emb_step": params["embed"]["emb_scale"],
            "final_gain": params["final_norm"]["gamma"],
            "head": reference_linear(params["lm_head"])}
