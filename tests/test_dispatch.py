"""Kernel-backend dispatch: pallas and xla serving paths must agree.

Parity tolerances are tight (1e-5) because the integer contractions are
exact and the two paths share quantization grids; only f32 epilogue
association order differs.  This holds at dispatched shapes where one key
block covers the row (Sk <= 4096 at default budget — all of these tests
and every model in the zoo at smoke sizes); longer rows stream on the
running-m grid and are covered against the streamed oracle in
test_kernels.py instead.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Jaxpr

from repro.core.api import QuantConfig, dense, integerize_params
from repro.kernels import dispatch
from repro.kernels.qmatmul import qmatmul
from repro.layers.attention import AttnSpec, attention


def _rel_close(a, b, tol=1e-5):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    scale = np.abs(b).max() + 1e-9
    np.testing.assert_allclose(a / scale, b / scale, atol=tol)


# ---------------------------------------------------------------------------
# backend selection
# ---------------------------------------------------------------------------

def test_backend_selection_layers():
    assert dispatch.get_backend() in ("xla", "pallas")
    with dispatch.use_backend("pallas"):
        assert dispatch.get_backend() == "pallas"
        with dispatch.use_backend("xla"):
            assert dispatch.get_backend() == "xla"
        assert dispatch.get_backend() == "pallas"
    # QuantConfig.backend overrides the process default.
    qc = QuantConfig(mode="int", backend="pallas")
    assert dispatch.resolve_backend(qc) == "pallas"
    assert dispatch.resolve_backend(QuantConfig(mode="int")) \
        == dispatch.get_backend()
    with pytest.raises(ValueError):
        dispatch.set_backend("cuda")
    with pytest.raises(ValueError):
        dispatch.resolve_backend(QuantConfig(mode="int", backend="Pallas"))


def test_block_heuristics_budgeted():
    for shape in [(7, 33, 48), (512, 4096, 4096), (1, 10, 100000),
                  (300, 300, 300), (257, 513, 7000)]:
        for budget in (dispatch.VMEM_BUDGET, 2 ** 19):
            bm, bn, bk = dispatch.qmatmul_blocks(*shape, budget=budget)
            assert bm % 128 == 0 and bn % 128 == 0 and bk % 128 == 0
            assert (bm == bn == bk == 128
                    or bm * bk + bn * bk + 8 * bm * bn <= budget)
    for sq, sk, d in [(198, 198, 64), (4096, 4096, 128), (1, 100000, 64),
                      (300, 3000, 96)]:
        for budget in (dispatch.VMEM_BUDGET, 2 ** 19):
            bq, bk = dispatch.attention_blocks(sq, sk, d, budget=budget)
            assert bq % 128 == 0 and bk % 128 == 0
    # Model-sized rows fit one key block: online grid == full-row grid.
    assert dispatch.attention_blocks(198, 198, 64)[1] >= 198
    # Narrow window: bk capped near the live span, not the whole row.
    bq, bk = dispatch.attention_blocks(4096, 4096, 128, window=128)
    assert bk <= -(-(bq + 128) // 128) * 128
    # Decode: one block over the ring while it fits -> full-row parity.
    assert dispatch.decode_blocks(200, 64) >= 200
    assert dispatch.decode_blocks(100000, 64) % 128 == 0
    assert dispatch.decode_blocks(100000, 512, budget=2 ** 19) % 128 == 0


# ---------------------------------------------------------------------------
# dense: pallas qmatmul vs XLA int path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lead,m,n,k", [
    ((), 7, 33, 48),            # ragged everything
    ((2,), 200, 130, 300),      # 3D activation
    ((2, 3), 17, 96, 128),      # 4D activation
])
@pytest.mark.parametrize("bias", [True, False])
def test_dense_backend_parity(lead, m, n, k, bias):
    key = jax.random.PRNGKey(m + n + k)
    x = jax.random.normal(key, (*lead, m, k))
    p = {"w": jax.random.normal(jax.random.fold_in(key, 1), (k, n)) * 0.05}
    if bias:
        p["b"] = jax.random.normal(jax.random.fold_in(key, 2), (n,)) * 0.1
    cfg = QuantConfig(w_bits=6, a_bits=8, mode="int")
    ip = integerize_params({"l": p}, cfg)["l"]
    y_xla = dense(x, ip, cfg)
    with dispatch.use_backend("pallas"):
        y_pal = dense(x, ip, cfg)
    assert y_pal.shape == (*lead, m, n)
    _rel_close(y_pal, y_xla)


def test_dense_packed_int4_parity():
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (64, 128))
    p = {"w": jax.random.normal(jax.random.fold_in(key, 1), (128, 96)) * .05}
    cfg = QuantConfig(w_bits=4, a_bits=8, mode="int", pack_weights=True)
    ip = integerize_params({"l": p}, cfg)["l"]
    assert ip["w_q"].dtype == jnp.uint8        # stays nibble-packed in HBM
    y_xla = dense(x, ip, cfg)
    with dispatch.use_backend("pallas"):
        y_pal = dense(x, ip, cfg)
    _rel_close(y_pal, y_xla)


def test_dense_fallback_for_stacked_weights():
    """Scan-stacked (U, out, in) weights stay on the XLA path."""
    cfg = QuantConfig(w_bits=8, a_bits=8, mode="int")
    p = {"w_q": jnp.zeros((2, 8, 8), jnp.int8), "w_scale": jnp.ones((2, 8))}
    assert not dispatch.qlinear_supported(jnp.zeros((4, 8)), p)
    assert dispatch.maybe_qlinear(jnp.zeros((4, 8)), p, cfg) is None


def _pads_of(fn, shape, *args):
    """The pad equations of ``fn``'s program, nested jaxprs included, whose
    operand has ``shape``."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if (eqn.primitive.name == "pad"
                    and eqn.invars[0].aval.shape == shape):
                yield eqn
            for v in eqn.params.values():
                for sub in v if isinstance(v, (tuple, list)) else (v,):
                    sub = getattr(sub, "jaxpr", sub)
                    if isinstance(sub, Jaxpr):
                        yield from walk(sub)
    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


@pytest.mark.parametrize("packed", [False, True], ids=["int8", "nibbles"])
def test_qmatmul_ragged_never_pads_weight(packed):
    """On the 128 grid a ragged N and K edge copies nothing of the weight;
    off the grid the weight is still padded (which shows the walk sees)."""
    kw = dict(bm=128, bn=256, bk=512, packed=packed)
    for n, k, pads in [(384, 640, 0), (200, 640, 1)]:
        w = jnp.zeros((n, k // 2 if packed else k),
                      jnp.uint8 if packed else jnp.int8)
        found = _pads_of(partial(qmatmul, **kw), w.shape,
                         jnp.zeros((64, k), jnp.int8), w, jnp.ones((n,)))
        assert len(found) == pads, (n, k)


@pytest.mark.parametrize("n,k,ragged", [
    (384, 2176, 1),             # N and K ragged on the 128 grid
    (13696, 512, 1),            # ChatGLM3's gate/up width
    (512, 2048, 0),             # block multiples: today's grid
])
def test_qlinear_ragged_counted_and_unpadded(n, k, ragged):
    cfg = QuantConfig(w_bits=4, a_bits=8, mode="int", backend="pallas")
    p = {"w_q": jnp.zeros((n, k), jnp.int8), "w_scale": jnp.ones((n,))}
    x = jnp.zeros((1, 64, k), jnp.float32)
    dispatch.reset_stats()
    found = _pads_of(lambda x, p: dispatch.maybe_qlinear(x, p, cfg),
                     (n, k), x, p)
    assert found == []
    assert dispatch.STATS["qlinear_pallas"] == 1
    assert dispatch.STATS["qlinear_ragged"] == ragged
    assert dispatch.snapshot()["qlinear_ragged"] == ragged


@pytest.mark.parametrize("batch,seq", [(1, 512), (64, 1)],
                         ids=["prefill", "decode"])
def test_chatglm3_ragged_calls_are_the_ffn(batch, seq):
    """At ChatGLM3-6B's published widths (traced on abstract weights, never
    built) each program's ragged qmatmul calls are gate, up and down; the
    attention projections and lm_head divide their tiles."""
    from repro.configs.registry import get_config
    from repro.models import lm
    qc = QuantConfig(w_bits=4, a_bits=8, attn_bits=7, kv_bits=8, mode="int",
                     backend="pallas")
    cfg = get_config("chatglm3-6b").replace(quant=qc)
    params = jax.eval_shape(
        lambda: lm.init_int_params(jax.random.PRNGKey(0), cfg, qc))

    def logits(p, tokens):
        x, _, _ = lm.forward(p, {"tokens": tokens}, cfg)
        return lm.logits_fn(p, x, cfg)

    dispatch.reset_stats()
    jax.make_jaxpr(logits)(params,
                           jax.ShapeDtypeStruct((batch, seq), jnp.int32))
    # wq, wk, wv, wo, gate, up, down once in the scanned unit, and lm_head.
    assert dispatch.STATS["qlinear_pallas"] == 8
    assert dispatch.STATS["qlinear_ragged"] == 3
    unit = params["units"]["b0"]
    widths = {name: p["w_q"].shape[1:]          # (N, K) of one layer
              for name, p in [*unit["attn"].items(), *unit["ffn"].items()]}
    widths["lm_head"] = params["lm_head"]["w_q"].shape
    ragged = set()
    for name, (n, k) in widths.items():
        _, bn, bk = dispatch.qmatmul_blocks(batch * seq, n, k)
        if n % bn or k % bk:
            ragged.add(name)
    assert ragged == {"gate", "up", "down"}


# ---------------------------------------------------------------------------
# attention: pallas fused kernel vs XLA int path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,sq,sk,d,causal,window", [
    (2, 4, 4, 32, 32, 16, False, None),     # MHA, cross
    (2, 4, 2, 32, 32, 16, True, None),      # GQA g=2
    (1, 8, 1, 100, 100, 32, True, None),    # MQA, ragged seq
    (1, 6, 3, 48, 48, 16, True, 32),        # GQA g=2 + local window
    (1, 2, 2, 33, 77, 16, False, None),     # ragged cross-attention
])
def test_attention_backend_parity(b, hq, hkv, sq, sk, d, causal, window):
    key = jax.random.PRNGKey(b + hq + sq)
    q = jax.random.normal(key, (b, hq, sq, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, hkv, sk, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, hkv, sk, d))
    cfg = QuantConfig(w_bits=8, a_bits=8, attn_bits=7, mode="int")
    spec = AttnSpec(causal=causal, window=window, q_chunk=256)
    a_xla = attention(q, k, v, spec, cfg)
    dispatch.reset_stats()
    with dispatch.use_backend("pallas"):
        a_pal = attention(q, k, v, spec, cfg)
    assert dispatch.STATS["attention_pallas"] == 1
    assert a_pal.shape == a_xla.shape
    _rel_close(a_pal, a_xla)


def test_attention_fallback_policies(monkeypatch):
    cfg = QuantConfig(w_bits=8, a_bits=8, attn_bits=7, mode="int")
    q = jnp.zeros((1, 2, 4, 8))
    k = jnp.zeros((1, 2, 8, 8))
    spec = AttnSpec()
    ok = dispatch.attention_supported
    assert ok(q, k, spec, cfg, 0, 0, None)
    assert not ok(q, k, spec, cfg, 4, 0, None)            # decode offset
    assert not ok(q, k, spec, cfg, 0, 2, None)            # key offset
    assert not ok(q, k, spec, cfg, 0, 0, jnp.arange(8))   # ring positions
    assert ok(q, k, spec, cfg.replace(attn_bits=8), 0, 0, None)  # 8b biased
    assert not ok(q, k, spec, cfg.replace(attn_bits=9), 0, 0, None)
    qce = cfg.replace(softmax="exact")
    assert not ok(q, k, spec, qce, 0, 0, None)            # exact-exp ablation
    # Narrow window over long keys: the static live-block map bounds the
    # DMA, so it dispatches — unless the escape hatch restores the veto.
    wspec = AttnSpec(window=2)
    assert ok(q, k, wspec, cfg, 0, 0, None)
    monkeypatch.setenv("REPRO_PALLAS_WINDOW_VETO", "1")
    assert not ok(q, k, wspec, cfg, 0, 0, None)
    assert ok(q, k, AttnSpec(window=8), cfg, 0, 0, None)  # sk <= 2*window
    monkeypatch.delenv("REPRO_PALLAS_WINDOW_VETO")
    # Unsupported calls still produce correct results via the XLA path.
    key = jax.random.PRNGKey(0)
    qf = jax.random.normal(key, (1, 2, 1, 8))
    kf = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 8, 8))
    vf = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 8, 8))
    base = attention(qf, kf, vf, spec, cfg, q_offset=7)
    dispatch.reset_stats()
    with dispatch.use_backend("pallas"):
        out = attention(qf, kf, vf, spec, cfg, q_offset=7)
    assert dispatch.STATS["attention_pallas"] == 0
    assert dispatch.STATS["attention_xla"] == 1
    _rel_close(out, base)


@pytest.mark.parametrize("b,hq,hkv", [(1, 2, 2), (2, 4, 2)])
def test_attention_parity_beyond_q_chunk_nonuniform_chunks(b, hq, hkv):
    """Acceptance: at Sq > q_chunk with non-uniform per-chunk activation
    ranges — the case the old per-tensor kernel scale papered over — the
    fused kernel matches the XLA chunked-recalibration path at the house
    parity tolerance (1e-5: integer codes and grids are identical, the
    residual is f32 scale-product association between the kernel's
    precomputed per-block scales and XLA's fused graph), while the old
    per-tensor grid misses by >100x that."""
    sq = sk = 64
    d, q_chunk = 16, 16                            # 4 chunks per row
    key = jax.random.PRNGKey(b + hq)
    q = jax.random.normal(key, (b, hq, sq, d))
    # chunk c of each row scaled by 2^c (8x spread): one per-tensor scale
    # would coarsen chunk 0's codes by 3 bits and blow the tolerance.
    # (Kept moderate: larger boosts push |logits| high enough that
    # ulp(x) amplified through 2^x dominates — where the XLA path does
    # not match ITSELF across jit/eager association either.)
    boost = 2.0 ** (jnp.arange(sq) // q_chunk).astype(jnp.float32)
    q = q * boost[None, None, :, None]
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, hkv, sk, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, hkv, sk, d))
    cfg = QuantConfig(w_bits=8, a_bits=8, attn_bits=7, mode="int")
    spec = AttnSpec(causal=True, q_chunk=q_chunk)
    a_xla = attention(q, k, v, spec, cfg)
    dispatch.reset_stats()
    with dispatch.use_backend("pallas"):
        a_pal = attention(q, k, v, spec, cfg)
    assert dispatch.STATS["attention_pallas"] == 1
    _rel_close(a_pal, a_xla)
    # The pre-PR-4 kernel grid (one per-tensor q scale for all chunks)
    # really is the thing being fixed: reproduce it and show it misses.
    from repro.core import quant
    from repro.core.softmax2 import LOG2E
    from repro.kernels.int_attention import int_attention_fused
    g = hq // hkv
    qq = quant.quantize_tensor(q, cfg.a_bits)
    kq = quant.quantize_tensor(k, cfg.a_bits)
    vq = quant.quantize_tensor(v, cfg.a_bits)
    sc = (1.0 / d ** 0.5) * LOG2E * qq.scale * kq.scale
    old = int_attention_fused(
        qq.q.reshape(b, hkv, g, sq, d).reshape(b * hkv, g * sq, d),
        kq.q.reshape(b * hkv, sk, d), vq.q.reshape(b * hkv, sk, d),
        sc, vq.scale, attn_bits=cfg.attn_bits, bq=64, bk=128, sq_mod=sq)
    old = old.reshape(b, hkv, g, sq, d).reshape(b, hq, sq, d)
    err = np.abs(np.asarray(old) - np.asarray(a_xla)).max() \
        / (np.abs(np.asarray(a_xla)).max() + 1e-9)
    assert err > 1e-3, err


def test_block_choices_recorded_in_stats():
    """Satellite: every block-size decision lands in STATS['blocks'] (the
    future TPU autotuner's baseline) and survives snapshot()."""
    dispatch.reset_stats()
    bq, bk = dispatch.attention_blocks(256, 512, 64, chunk=32)
    assert 32 % bq == 0                            # tile within one chunk
    bkd = dispatch.decode_blocks(200, 64)
    psd = dispatch.paged_decode_blocks(128, 64)
    blocks = dispatch.snapshot()["blocks"]
    assert blocks["attention:sq256_sk512_d64_wNone_c32"] == [bq, bk]
    assert blocks["decode:span200_d64"] == [bkd]
    assert blocks["paged_decode:ps128_d64"] == [psd]
    dispatch.reset_stats()
    assert dispatch.STATS["blocks"] == {}


def test_windowed_dispatch_narrow_window_long_keys():
    """Narrow local window over long keys now dispatches to Pallas (the
    static live-block map bounds the DMA); with every live key of a query
    block inside one key tile the output is exact vs the XLA slicing path."""
    key = jax.random.PRNGKey(11)
    b, h, sq, sk, d, window = 1, 2, 64, 320, 16, 32
    q = jax.random.normal(key, (b, h, sq, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, h, sk, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, h, sk, d))
    cfg = QuantConfig(w_bits=8, a_bits=8, attn_bits=7, mode="int")
    spec = AttnSpec(causal=True, window=window, q_chunk=64)
    a_xla = attention(q, k, v, spec, cfg)
    dispatch.reset_stats()
    with dispatch.use_backend("pallas"):
        a_pal = attention(q, k, v, spec, cfg)
    assert dispatch.STATS["attention_pallas"] == 1
    assert dispatch.STATS["attention_xla"] == 0
    _rel_close(a_pal, a_xla)


def test_windowed_dispatch_straddling_blocks_close():
    """When a query block's window straddles key tiles the streamed
    running-m grid may differ from the XLA full-row grid by ~one prob code
    (the documented deviation) — close, but not bit-equal."""
    key = jax.random.PRNGKey(12)
    b, h, sq, sk, d, window = 1, 1, 512, 512, 16, 64
    q = jax.random.normal(key, (b, h, sq, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, h, sk, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, h, sk, d))
    cfg = QuantConfig(w_bits=8, a_bits=8, attn_bits=7, mode="int")
    spec = AttnSpec(causal=True, window=window, q_chunk=128)
    a_xla = attention(q, k, v, spec, cfg)
    dispatch.reset_stats()
    with dispatch.use_backend("pallas"):
        a_pal = attention(q, k, v, spec, cfg)
    assert dispatch.STATS["attention_pallas"] == 1
    xn, pn = np.asarray(a_xla), np.asarray(a_pal)
    scale = np.abs(xn).max() + 1e-9
    assert np.abs(pn - xn).max() / scale < 0.05
    assert float(np.corrcoef(pn.ravel(), xn.ravel())[0, 1]) > 0.999


# ---------------------------------------------------------------------------
# model level: a mode="int" ViT forward really runs on the Pallas kernels
# ---------------------------------------------------------------------------

def test_vit_int_forward_dispatches_to_pallas():
    from repro.models import vit
    qc = QuantConfig(w_bits=4, a_bits=8, attn_bits=7, mode="int",
                     pack_weights=True)
    cfg = vit.ViTConfig(n_layers=2, d_model=64, n_heads=4, d_ff=128,
                        img_size=32, patch=8, n_classes=10, quant=qc)
    params = vit.init_params(jax.random.PRNGKey(0), cfg)
    ip = integerize_params(params, qc)
    imgs = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    logits_xla = vit.forward(ip, imgs, cfg)
    dispatch.reset_stats()
    with dispatch.use_backend("pallas"):
        logits_pal = vit.forward(ip, imgs, cfg)
    # Every integerized linear and the attention interior hit the kernels.
    assert dispatch.STATS["qlinear_pallas"] > 0
    assert dispatch.STATS["attention_pallas"] > 0
    assert dispatch.STATS["qlinear_xla"] == 0
    assert dispatch.STATS["attention_xla"] == 0
    _rel_close(logits_pal, logits_xla)


def test_vit_int_forward_config_backend():
    """QuantConfig(backend=...) selects pallas without the global toggle."""
    from repro.models import vit
    qc = QuantConfig(w_bits=8, a_bits=8, attn_bits=7, mode="int",
                     backend="pallas")
    cfg = vit.ViTConfig(n_layers=1, d_model=32, n_heads=2, d_ff=64,
                        img_size=16, patch=8, n_classes=4, quant=qc)
    params = vit.init_params(jax.random.PRNGKey(0), cfg)
    ip = integerize_params(params, qc)
    imgs = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 16, 3))
    dispatch.reset_stats()
    logits = vit.forward(ip, imgs, cfg)
    assert dispatch.STATS["qlinear_pallas"] > 0
    assert dispatch.STATS["attention_pallas"] > 0
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.smoke
def test_lm_prefill_and_decode_both_dispatch():
    """LM prefill (static zero offset) runs the fused kernel AND the
    ring-cache decode step runs the decode kernel — the full int serving
    loop traces onto Pallas with zero attention fallbacks."""
    from repro.models import lm
    qc = QuantConfig(w_bits=8, a_bits=8, attn_bits=7, mode="int")
    cfg = lm.LMConfig(name="t", n_layers=2, d_model=48, n_heads=4,
                      kv_heads=2, d_ff=96, vocab=64, dtype="float32",
                      q_chunk=16, remat=False, quant=qc)
    params = integerize_params(
        lm.init_params(jax.random.PRNGKey(0), cfg.replace(quant=None)), qc)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16),
                                          0, cfg.vocab)}
    dispatch.reset_stats()
    with dispatch.use_backend("pallas"):
        logits, cache = lm.prefill(params, batch, cfg, max_len=20)
        assert dispatch.STATS["attention_pallas"] > 0
        assert dispatch.STATS["attention_decode_pallas"] == 0
        assert bool(jnp.all(jnp.isfinite(logits)))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        logits2, _ = lm.decode_step(params, tok, cache, cfg)
        assert dispatch.STATS["attention_decode_pallas"] > 0
        assert dispatch.STATS["attention_xla"] == 0
        assert bool(jnp.all(jnp.isfinite(logits2)))


def test_platform_picks_backend_and_kernel_mode(monkeypatch):
    """With no explicit override the platform decides: pallas and compiled
    kernels on a TPU, xla and the interpreter elsewhere — a caller can
    never run a kernel interpreted on the chip."""
    from repro.kernels import interpret_mode
    monkeypatch.setattr(dispatch, "_backend", [None])
    assert dispatch.get_backend() == "xla"
    assert interpret_mode() and interpret_mode(True)
    assert not interpret_mode(False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dispatch.get_backend() == "pallas"
    assert not interpret_mode() and not interpret_mode(True)
    with dispatch.use_backend("xla"):
        assert dispatch.get_backend() == "xla"


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_lm_bf16_backend_parity_exact(step):
    """bf16 activations: both backends serve the same bits.

    Each backend gets a fresh trace (the backend is read at trace time).
    Scale arithmetic runs in f32, so no bf16 rounding is left for XLA to
    place differently around a kernel than around its own dot.
    """
    from repro.configs.registry import smoke_config
    from repro.models import lm
    qc = QuantConfig(w_bits=4, a_bits=8, attn_bits=7, kv_bits=8, mode="int")
    cfg = smoke_config("chatglm3-6b").replace(dtype="bfloat16", q_chunk=32,
                                              quant=qc)
    params = lm.init_int_params(jax.random.PRNGKey(0), cfg, qc)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, cfg.vocab)
    out = {}
    for backend in ("pallas", "xla"):
        with dispatch.use_backend(backend):
            logits, cache = jax.jit(
                lambda p, t: lm.prefill(p, {"tokens": t}, cfg, max_len=66))(
                    params, toks)
            if step == "decode":
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
                logits, _ = jax.jit(
                    lambda p, t, c: lm.decode_step(p, t, c, cfg))(
                        params, tok, cache)
        out[backend] = np.asarray(logits, np.float32)
    np.testing.assert_array_equal(out["pallas"], out["xla"])


# ---------------------------------------------------------------------------
# benchmark harness
# ---------------------------------------------------------------------------

def test_kernel_bench_json(tmp_path):
    from benchmarks import kernel_bench
    out = tmp_path / "BENCH_kernels.json"
    rows, design, decode, paged = kernel_bench.main(
        ["--quick", "--json", str(out)])
    import json
    payload = json.loads(out.read_text())
    assert payload["kernels"] and all("wall_us" in r
                                      for r in payload["kernels"])
    ad = payload["attention_design"]
    assert ad["s"] == 1024
    assert ad["single_pass_macs"] < ad["two_pass_macs"]
    assert ad["single_pass_kv_hbm_bytes"] < ad["two_pass_kv_hbm_bytes"]
    # Decode: in-place ring kernel reads fewer bytes and runs fewer MACs
    # per step than the XLA fallback / two-pass design, and the timed loop
    # really dispatched onto the decode kernel.
    for a in payload["decode"]["analytic"]:
        assert a["pallas_bytes_per_step"] < a["xla_bytes_per_step"]
        assert a["decode_macs_per_step"] < a["two_pass_macs_per_step"]
    loop = payload["decode"]["loop"]
    assert loop["pallas"]["stats"]["attention_decode_pallas"] > 0
    assert loop["pallas"]["stats"]["attention_xla"] == 0
    assert loop["xla"]["stats"]["attention_decode_pallas"] == 0
    # Paged multi-tenant decode: per-sequence pages beat the batch-max
    # ring on bytes/step, and the timed continuous-batching loop really
    # dispatched onto the paged kernel.
    for a in payload["paged"]["analytic"]:
        assert a["paged_bytes_per_step"] < a["ring_bytes_per_step"]
        assert a["ring_over_paged"] > 1.0
    ploop = payload["paged"]["loop"]
    assert ploop["pallas"]["stats"]["attention_paged_pallas"] > 0
    assert ploop["xla"]["stats"]["attention_paged_pallas"] == 0
    assert ploop["xla"]["stats"]["attention_paged_xla"] > 0
    # Block-size decisions recorded for the future autotuner baseline.
    assert ploop["pallas"]["stats"]["blocks"]
    # Admission burst: ONE batched prefill vs one per arrival, recorded
    # under both backends.
    adm = payload["paged"]["admission"]
    for backend in ("xla", "pallas"):
        assert adm[backend]["prefill_calls_burst"] == 1
        assert adm[backend]["prefill_calls_serial"] == \
            adm[backend]["requests"]
        assert adm[backend]["burst_speedup"] > 0
    # Prefix burst: N same-prefix admissions prefill the prefix once and
    # consume (N-1)*P fewer pool pages than the unshared path.
    for a in payload["paged"]["prefix"]["analytic"]:
        assert a["shared_prefill_tokens"] < a["unshared_prefill_tokens"]
        assert a["shared_pages_consumed"] < a["unshared_pages_consumed"]
        assert a["admission_capacity_gain"] > 1.0
    for backend in ("xla", "pallas"):
        pb = payload["paged"]["prefix"]["burst"][backend]
        assert pb["shared"]["prefix_prefills"] == 1
        assert pb["unshared"]["prefix_prefills"] == 0
        assert pb["pages_saved"] > 0
    # Preemption: the analytic recompute bill stays under the recovered
    # capacity, and the timed loop really preempted, resumed, and landed
    # bit-identical on both backends.
    for a in payload["paged"]["preemption"]["analytic"]:
        assert a["pages_recovered_per_preemption"] > 0
        assert a["resume_recompute_tokens"] == a["prompt"] + a["gen"]
        assert a["rewrite_per_freed_byte"] < 1.0
    for backend in ("xla", "pallas"):
        pl = payload["paged"]["preemption"]["loop"][backend]
        assert pl["preemptions"] >= 1 and pl["resumes"] >= 1
        assert pl["pages_recovered"] > 0
        assert pl["steal_latency_ms"] > 0
        assert pl["bit_identical"] is True
    # Chunked-prefill latency under a burst: the token budget bounds
    # per-step prefill work, one-shot provably stalls for the whole
    # burst, and the foreground stream is identical under both
    # schedulers (chunk scheduling is invisible in the tokens).
    for a in payload["paged"]["latency"]["analytic"]:
        assert a["budgeted_max_tokens_per_step"] <= max(a["chunk"],
                                                        a["budget"])
        assert a["oneshot_stall_tokens"] >= \
            a["budgeted_max_tokens_per_step"]
        assert a["stall_reduction"] >= 1.0
    for backend in ("xla", "pallas"):
        lt = payload["paged"]["latency"]["loop"][backend]
        assert lt["budget_bounded"] is True
        assert lt["oneshot_stalls_whole_burst"] is True
        assert lt["fg_bit_identical"] is True


@pytest.mark.smoke
def test_kernel_bench_check_guard(tmp_path):
    """Satellite: --check exits cleanly against a faithful analytic dump
    and nonzero when the previous dump beats the current analytics (i.e.,
    bytes/step or MACs regressed).  Timer-free, so it rides the smoke
    subset."""
    import json

    from benchmarks import kernel_bench
    good = tmp_path / "prev.json"
    good.write_text(json.dumps(kernel_bench.analytic_payload()))
    assert kernel_bench.main(["--check", str(good)]) is None
    tampered = json.loads(good.read_text())
    tampered["decode"]["analytic"][0]["pallas_bytes_per_step"] -= 1
    bad = tmp_path / "tampered.json"
    bad.write_text(json.dumps(tampered))
    with pytest.raises(SystemExit):
        kernel_bench.main(["--check", str(bad)])
    # the prefix-burst analytics ride the same guard
    tampered = json.loads(good.read_text())
    tampered["paged"]["prefix"]["analytic"][0]["shared_pages_consumed"] -= 1
    bad2 = tmp_path / "tampered_prefix.json"
    bad2.write_text(json.dumps(tampered))
    with pytest.raises(SystemExit):
        kernel_bench.main(["--check", str(bad2)])
    # ... and so do the preempt-resume analytics
    tampered = json.loads(good.read_text())
    tampered["paged"]["preemption"]["analytic"][0][
        "resume_kv_bytes_rewritten"] -= 1
    bad3 = tmp_path / "tampered_preempt.json"
    bad3.write_text(json.dumps(tampered))
    with pytest.raises(SystemExit):
        kernel_bench.main(["--check", str(bad3)])
    # ... and the chunked-prefill latency bound
    tampered = json.loads(good.read_text())
    tampered["paged"]["latency"]["analytic"][0][
        "budgeted_max_tokens_per_step"] -= 1
    bad4 = tmp_path / "tampered_latency.json"
    bad4.write_text(json.dumps(tampered))
    with pytest.raises(SystemExit):
        kernel_bench.main(["--check", str(bad4)])
