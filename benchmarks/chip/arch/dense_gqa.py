"""Dense decoder with grouped-query attention (ChatGLM3 and the like).

Every block is pre-norm attention (``wq``, ``wk``, ``wv``, ``wo``, QKV
bias where the configuration says so) and a SwiGLU MLP (``gate``, ``up``,
``down``), all of them linear layers the program serves through
``qmatmul``; the plain forward is ``references/dense_gqa.py``.

Weights: each linear is stored ``(out, in)`` with a step per output row;
the blocks are stacked under ``units.b0``.
"""
from __future__ import annotations

import functools

import jax

import arch
import weights
import work

# Keys of the configuration's ``model`` that only the reference reads.
# The program takes no norm epsilon (it uses 1e-6 whatever the
# configuration states) and rotates interleaved pairs only, which
# ``reference_view`` holds it to.
REFERENCE_ONLY = {"rope_layout", "norm_eps"}
PROGRAM = {"n_layers", "d_model", "n_heads", "kv_heads", "head_dim", "d_ff",
           "vocab", "rope_theta", "rotary_frac", "qkv_bias", "norm", "act"}


def lm_config(model: dict, qc):
    """The program's ``LMConfig`` of ``model``."""
    from repro.models.lm import LMConfig
    arch.refuse_unread(model, PROGRAM, REFERENCE_ONLY)
    return LMConfig(name=__name__, quant=qc,
                    **{k: model[k] for k in sorted(PROGRAM)})


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def _layer(key, m, w_bits):
    d, hd = m["d_model"], m["head_dim"]
    hq, hkv, ff = m["n_heads"] * hd, m["kv_heads"] * hd, m["d_ff"]
    ks = jax.random.split(key, 9)
    lin = weights._linear
    return {
        "ln1": weights._gain(ks[0], d),
        "attn": {"wq": lin(ks[1], hq, d, w_bits, m["qkv_bias"]),
                 "wk": lin(ks[2], hkv, d, w_bits, m["qkv_bias"]),
                 "wv": lin(ks[3], hkv, d, w_bits, m["qkv_bias"]),
                 "wo": lin(ks[4], d, hq, w_bits, False)},
        "ln2": weights._gain(ks[5], d),
        "ffn": {"gate": lin(ks[6], ff, d, w_bits, False),
                "up": lin(ks[7], ff, d, w_bits, False),
                "down": lin(ks[8], d, ff, w_bits, False)},
    }


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, frozen_model, w_bits):
    m = dict(frozen_model)
    ks = jax.random.split(key, 4)
    units = jax.lax.map(lambda k: {"b0": _layer(k, m, w_bits)},
                        jax.random.split(ks[1], m["n_layers"]))
    top = weights.top((ks[0], ks[2], ks[3]), m["vocab"], m["d_model"],
                      w_bits)
    return top | {"units": units}


def _frozen(model: dict):
    keys = ("n_layers", "d_model", "n_heads", "kv_heads", "head_dim", "d_ff",
            "vocab", "qkv_bias")
    return tuple((k, model[k]) for k in keys)


def make(seed: int, model: dict, quant: dict):
    """The serving parameter tree of ``model`` for ``seed``, on the device."""
    return _make(weights.prng_key(seed), _frozen(model), quant["w_bits"])


def shape_of(model: dict, quant: dict):
    return jax.eval_shape(lambda k: _make(k, _frozen(model),
                                          quant["w_bits"]),
                          weights.prng_key(0))


def reference_view(params, model: dict):
    """Callables the reference reads its weights through: ``top()`` and
    ``layer(l)``, each a dict of (codes, step[, bias]) per linear and the
    float gains.  Nothing is dequantized here."""
    if model["rope_layout"] != "interleaved":
        raise ValueError(f"the program rotates interleaved pairs; rope "
                         f"layout {model['rope_layout']!r} is not served")
    lin = weights.reference_linear

    def layer(l):
        u = jax.tree_util.tree_map(lambda x: x[l], params["units"]["b0"])
        a = u["attn"]
        return {"ln1": u["ln1"]["gamma"], "ln2": u["ln2"]["gamma"],
                "wq": lin(a["wq"]), "wk": lin(a["wk"]),
                "wv": lin(a["wv"]), "wo": lin(a["wo"]),
                "gate": lin(u["ffn"]["gate"]), "up": lin(u["ffn"]["up"]),
                "down": lin(u["ffn"]["down"])}

    return lambda: weights.reference_top(params), layer


# ---------------------------------------------------------------------------
# Work
# ---------------------------------------------------------------------------

def linears(model: dict):
    """(n, k) of every linear layer in one block, then of the head."""
    d, hd = model["d_model"], model["head_dim"]
    hq, hkv, ff = model["n_heads"] * hd, model["kv_heads"] * hd, model["d_ff"]
    block = [(hq, d), (hkv, d), (hkv, d), (d, hq), (ff, d), (ff, d), (d, ff)]
    return block, (model["vocab"], d)


def kernel_calls(config: dict, calls, kernel: str):
    """Yield (ops, bytes) for every call of ``kernel`` that the program
    calls in ``calls`` made (``harness.Recorder`` entries)."""
    m, q, eng = config["model"], config["quant"], config["engine"]
    w_bytes = work.weight_bytes(q)
    kv_bytes = q["kv_bits"] / 8
    block, head = linears(m)
    hq, hkv, d, L = m["n_heads"], m["kv_heads"], m["head_dim"], m["n_layers"]
    for call in calls:
        if call[0] == "decode":
            live = work.decode_rows(call)
            if not live:
                continue
            if kernel == "qmatmul":
                for _ in range(L):
                    for n, k in block:
                        yield work.qmatmul(len(live), n, k, w_bytes)
                yield work.qmatmul(len(live), *head, w_bytes)
            elif kernel == "paged_decode":
                for _ in range(L):
                    yield work.paged_decode(live, hq, hkv, d,
                                            eng["page_size"], kv_bytes)
        else:
            start, lens = work.prefill_rows(call)
            if not lens:
                continue
            if kernel == "qmatmul":
                for _ in range(L):
                    for n, k in block:
                        yield work.qmatmul(sum(lens), n, k, w_bytes)
                yield work.qmatmul(len(lens), *head, w_bytes)
            elif kernel == "fused_prefill" and start == 0:
                for _ in range(L):
                    yield work.fused_prefill(lens, hq, hkv, d)


def model_ops(config: dict, calls) -> float:
    """Operations the model needs for the work in ``calls``: two per
    weight per token through every linear layer (the head only where the
    program takes logits), plus QK and PV over the keys each query may
    attend."""
    m = config["model"]
    block, head = linears(m)
    per_tok = 2 * sum(n * k for n, k in block) * m["n_layers"]
    head_ops = 2 * head[0] * head[1]
    att = 2 * 2 * m["n_heads"] * m["head_dim"] * m["n_layers"]
    total = 0.0
    for call in calls:
        if call[0] == "decode":
            live = work.decode_rows(call)
            total += len(live) * (per_tok + head_ops) + att * sum(live)
        else:
            start, lens = work.prefill_rows(call)
            total += sum(per_tok * n + att * work.causal_pairs(start, n)
                         for n in lens) + head_ops * len(lens)
    return total
