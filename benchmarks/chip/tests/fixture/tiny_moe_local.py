"""Grouped-query decoder with sparse experts and a sliding window.

A second architecture for the self-checks, kept beside its configuration
(``tiny-moe-local.json``): the test drops both where the harness looks
(``configs/`` and ``arch/`` of a copy of the benchmark), and nothing else.

Blocks follow the configuration's ``block_pattern`` (``"local"`` attends
the last ``attn_window`` keys, ``"attn"`` every key), stacked as the
program stacks them: one pattern unit ``units.b<j>`` per pattern entry.
Every block's MLP is ``moe.n_experts`` SwiGLU experts of width ``d_ff``,
``moe.top_k`` of them routed per token by a bf16 ``router``.  Expert
weights are stored ``(E, in, out)`` with a step per expert and output
column ``(E, 1, out)``.  The attention linears run through ``qmatmul``;
the experts and the router are XLA contractions, counted in
:func:`model_ops` and in no kernel's roofline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import arch
import weights
import work

REFERENCE_ONLY = {"rope_layout", "norm_eps"}
PROGRAM = {"n_layers", "d_model", "n_heads", "kv_heads", "head_dim", "d_ff",
           "vocab", "rope_theta", "rotary_frac", "qkv_bias", "norm", "act",
           "block_pattern", "attn_window", "moe"}


def lm_config(model: dict, qc):
    from repro.layers.moe import MoEConfig
    from repro.models.lm import LMConfig
    arch.refuse_unread(model, PROGRAM, REFERENCE_ONLY)
    fields = {k: model[k] for k in PROGRAM}
    fields["block_pattern"] = tuple(model["block_pattern"])
    fields["moe"] = MoEConfig(**model["moe"])
    return LMConfig(name=__name__, quant=qc, **fields)


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def _experts(key, e, out, inn, w_bits):
    p = jax.vmap(lambda k: weights._linear(k, out, inn, w_bits, False))(
        jax.random.split(key, e))
    return {"w_q": jnp.swapaxes(p["w_q"], 1, 2),
            "w_scale": p["w_scale"][:, None, :]}


def _block(key, m, w_bits):
    d, hd, ff = m["d_model"], m["head_dim"], m["d_ff"]
    hq, hkv, e = m["n_heads"] * hd, m["kv_heads"] * hd, m["n_experts"]
    ks = jax.random.split(key, 10)
    lin = weights._linear
    router = jax.random.normal(ks[6], (d, e), jnp.float32) * d ** -0.5
    return {
        "ln1": weights._gain(ks[0], d),
        "attn": {"wq": lin(ks[1], hq, d, w_bits, m["qkv_bias"]),
                 "wk": lin(ks[2], hkv, d, w_bits, m["qkv_bias"]),
                 "wv": lin(ks[3], hkv, d, w_bits, m["qkv_bias"]),
                 "wo": lin(ks[4], d, hq, w_bits, False)},
        "ln2": weights._gain(ks[5], d),
        "ffn": {"router": {"w": router.astype(jnp.bfloat16)},
                "experts_gate": _experts(ks[7], e, ff, d, w_bits),
                "experts_up": _experts(ks[8], e, ff, d, w_bits),
                "experts_down": _experts(ks[9], e, d, ff, w_bits)},
    }


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, frozen_model, w_bits):
    m = dict(frozen_model)
    pattern = m["block_pattern"]
    ks = jax.random.split(key, 4)
    units = jax.lax.map(
        lambda k: {f"b{j}": _block(kj, m, w_bits) for j, kj in
                   enumerate(jax.random.split(k, len(pattern)))},
        jax.random.split(ks[1], m["n_layers"] // len(pattern)))
    top = weights.top((ks[0], ks[2], ks[3]), m["vocab"], m["d_model"],
                      w_bits)
    return top | {"units": units}


def _frozen(model: dict):
    if model["n_layers"] % len(model["block_pattern"]):
        raise ValueError("n_layers is not a whole number of pattern units")
    keys = ("n_layers", "d_model", "n_heads", "kv_heads", "head_dim", "d_ff",
            "vocab", "qkv_bias")
    return tuple((k, model[k]) for k in keys) + (
        ("block_pattern", tuple(model["block_pattern"])),
        ("n_experts", model["moe"]["n_experts"]))


def make(seed: int, model: dict, quant: dict):
    return _make(weights.prng_key(seed), _frozen(model), quant["w_bits"])


def shape_of(model: dict, quant: dict):
    return jax.eval_shape(lambda k: _make(k, _frozen(model),
                                          quant["w_bits"]),
                          weights.prng_key(0))


def reference_view(params, model: dict):
    """``top()`` and ``layer(l)``: layer ``l`` is block ``l % P`` of unit
    ``l // P`` for a pattern of ``P`` kinds, and names its kind."""
    pattern = model["block_pattern"]
    lin = weights.reference_linear

    def experts(p):
        return {"codes": p["w_q"], "step": p["w_scale"]}

    def layer(l):
        j = l % len(pattern)
        u = jax.tree_util.tree_map(lambda x: x[l // len(pattern)],
                                   params["units"][f"b{j}"])
        a, f = u["attn"], u["ffn"]
        return {"kind": pattern[j], "ln1": u["ln1"]["gamma"],
                "ln2": u["ln2"]["gamma"],
                "wq": lin(a["wq"]), "wk": lin(a["wk"]),
                "wv": lin(a["wv"]), "wo": lin(a["wo"]),
                "router": f["router"]["w"].astype(jnp.float32),
                "gate": experts(f["experts_gate"]),
                "up": experts(f["experts_up"]),
                "down": experts(f["experts_down"])}

    return lambda: weights.reference_top(params), layer


# ---------------------------------------------------------------------------
# Work
# ---------------------------------------------------------------------------

def _kinds(model: dict):
    pattern = model["block_pattern"]
    return [pattern[l % len(pattern)] for l in range(model["n_layers"])]


def _keys(kind, model, n):
    """Keys a query at position ``n - 1`` may attend in a ``kind`` layer."""
    return min(n, model["attn_window"]) if kind == "local" else n


def _pairs(kind, model, start, length):
    """Query-key pairs of ``length`` queries from position ``start``."""
    if kind != "local":
        return work.causal_pairs(start, length)
    return sum(_keys(kind, model, i + 1)
               for i in range(start, start + length))


def _attn_linears(model: dict):
    d, hd = model["d_model"], model["head_dim"]
    hq, hkv = model["n_heads"] * hd, model["kv_heads"] * hd
    return [(hq, d), (hkv, d), (hkv, d), (d, hq)]


def kernel_calls(config: dict, calls, kernel: str):
    m, q, eng = config["model"], config["quant"], config["engine"]
    w_bytes = work.weight_bytes(q)
    hq, hkv, d = m["n_heads"], m["kv_heads"], m["head_dim"]
    kinds, block, head = _kinds(m), _attn_linears(m), (m["vocab"],
                                                       m["d_model"])
    for call in calls:
        if call[0] == "decode":
            live = work.decode_rows(call)
            rows, start, lens = len(live), None, None
        else:
            start, lens = work.prefill_rows(call)
            live, rows = None, sum(lens)
        if not rows:
            continue
        if kernel == "qmatmul":
            for _ in kinds:
                for n, k in block:
                    yield work.qmatmul(rows, n, k, w_bytes)
            yield work.qmatmul(len(live or lens), *head, w_bytes)
        elif kernel == "paged_decode" and live:
            for kind in kinds:
                yield work.paged_decode(
                    [_keys(kind, m, n) for n in live], hq, hkv, d,
                    eng["page_size"], q["kv_bits"] / 8)
        elif kernel == "fused_prefill" and lens and start == 0:
            for kind in kinds:
                _, byts = work.fused_prefill(lens, hq, hkv, d)
                yield (sum(2 * 2 * hq * d * _pairs(kind, m, 0, n)
                           for n in lens), byts)


def model_ops(config: dict, calls) -> float:
    """Two operations per weight per token through the attention linears,
    the router and the ``top_k`` routed experts, the head where the
    program takes logits, and QK and PV over the keys each query may
    attend in each layer."""
    m = config["model"]
    e, k = m["moe"]["n_experts"], m["moe"]["top_k"]
    d, ff, kinds = m["d_model"], m["d_ff"], _kinds(m)
    per_tok = 2 * len(kinds) * (sum(n * kk for n, kk in _attn_linears(m))
                                + e * d + k * 3 * ff * d)
    head_ops = 2 * m["vocab"] * d
    att = 2 * 2 * m["n_heads"] * m["head_dim"]
    total = 0.0
    for call in calls:
        if call[0] == "decode":
            live = work.decode_rows(call)
            total += len(live) * (per_tok + head_ops) + att * sum(
                _keys(kind, m, n) for kind in kinds for n in live)
        else:
            start, lens = work.prefill_rows(call)
            total += sum(per_tok * n + att * sum(
                _pairs(kind, m, start, n) for kind in kinds)
                for n in lens) + head_ops * len(lens)
    return total
