"""Random serving weights from a seed, made by the benchmark on the device.

The benchmark, not the program, makes the weights: one jitted call turns
``--seed`` into the integer parameter tree the serving engine reads (w4
codes one per byte with a float32 step per output row, int8 embedding rows
with a step per row, bf16 QKV biases, float32 norm gains), so the plain
reference can be handed the very same codes and steps without taking
anything the program made.  :func:`check_layout` holds the tree against
the program's own layout (``jax.eval_shape`` of ``lm.init_int_params``).

Distributions: each weight row is a standard normal row quantized on its
absmax grid, times ``fan_in ** -0.5``; embedding rows are standard normal
on an int8 absmax grid; biases ~ N(0, 0.1); norm gains 1 + N(0, 0.1).
"""
from __future__ import annotations

import functools

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


def _layer(key, m, w_bits):
    d, hd = m["d_model"], m["head_dim"]
    hq, hkv, ff = m["n_heads"] * hd, m["kv_heads"] * hd, m["d_ff"]
    ks = jax.random.split(key, 9)
    return {
        "ln1": _gain(ks[0], d),
        "attn": {"wq": _linear(ks[1], hq, d, w_bits, m["qkv_bias"]),
                 "wk": _linear(ks[2], hkv, d, w_bits, m["qkv_bias"]),
                 "wv": _linear(ks[3], hkv, d, w_bits, m["qkv_bias"]),
                 "wo": _linear(ks[4], d, hq, w_bits, False)},
        "ln2": _gain(ks[5], d),
        "ffn": {"gate": _linear(ks[6], ff, d, w_bits, False),
                "up": _linear(ks[7], ff, d, w_bits, False),
                "down": _linear(ks[8], d, ff, w_bits, False)},
    }


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, frozen_model, w_bits):
    m = dict(frozen_model)
    ks = jax.random.split(key, 4)
    emb_q, emb_step = _codes(
        jax.random.normal(ks[0], (m["vocab"], m["d_model"]), jnp.float32), 8)
    units = jax.lax.map(lambda k: {"b0": _layer(k, m, w_bits)},
                        jax.random.split(ks[1], m["n_layers"]))
    return {"embed": {"emb_q": emb_q, "emb_scale": emb_step},
            "final_norm": _gain(ks[2], m["d_model"]),
            "lm_head": _linear(ks[3], m["vocab"], m["d_model"], w_bits, False),
            "units": units}


def _frozen(model: dict):
    keys = ("n_layers", "d_model", "n_heads", "kv_heads", "head_dim", "d_ff",
            "vocab", "qkv_bias")
    return tuple((k, model[k]) for k in keys)


def make(seed: int, model: dict, quant: dict):
    """The serving parameter tree of ``model`` for ``seed``, on the device."""
    return _make(prng_key(seed), _frozen(model), quant["w_bits"])


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


def shape_of(model: dict, quant: dict):
    return jax.eval_shape(lambda k: _make(k, _frozen(model),
                                          quant["w_bits"]), prng_key(0))


# ---------------------------------------------------------------------------
# The reference's view of the same weights
# ---------------------------------------------------------------------------

def reference_view(params, model: dict):
    """Callables the reference reads its weights through: ``top()`` and
    ``layer(l)``, each a dict of (codes, step[, bias]) per linear and the
    float gains.  Nothing is dequantized here."""
    if model["rope_layout"] != "interleaved":
        raise ValueError(f"the program rotates interleaved pairs; rope "
                         f"layout {model['rope_layout']!r} is not served")

    def lin(p):
        out = {"codes": p["w_q"], "step": p["w_scale"]}
        if "b" in p:
            out["bias"] = p["b"]
        return out

    def top():
        return {"emb_codes": params["embed"]["emb_q"],
                "emb_step": params["embed"]["emb_scale"],
                "final_gain": params["final_norm"]["gamma"],
                "head": lin(params["lm_head"])}

    def layer(l):
        u = jax.tree_util.tree_map(lambda x: x[l], params["units"]["b0"])
        a = u["attn"]
        return {"ln1": u["ln1"]["gamma"], "ln2": u["ln2"]["gamma"],
                "wq": lin(a["wq"]), "wk": lin(a["wk"]),
                "wv": lin(a["wv"]), "wo": lin(a["wo"]),
                "gate": lin(u["ffn"]["gate"]), "up": lin(u["ffn"]["up"]),
                "down": lin(u["ffn"]["down"])}

    return top, layer
