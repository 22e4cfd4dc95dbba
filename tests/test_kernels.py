"""Per-kernel shape/dtype sweeps asserting allclose against ref.py oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import pack_int4
from repro.kernels import ref
from repro.kernels.int_attention import (attention_macs, int_attention,
                                         int_attention_fused)
from repro.kernels.pq_layernorm import pq_layernorm
from repro.kernels.qmatmul import qmatmul


def _rand_int8(key, shape, lo=-8, hi=8):
    return jax.random.randint(key, shape, lo, hi).astype(jnp.int8)


@pytest.mark.parametrize("m,n,k", [(32, 32, 64), (64, 96, 128),
                                   (200, 130, 300), (17, 5, 64)])
@pytest.mark.parametrize("with_bias", [True, False])
def test_qmatmul_matches_ref(m, n, k, with_bias):
    key = jax.random.PRNGKey(m * n + k)
    x = _rand_int8(key, (m, k))
    w = _rand_int8(jax.random.fold_in(key, 1), (n, k))
    scale = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (n,))) * .01
    bias = (jax.random.normal(jax.random.fold_in(key, 3), (n,))
            if with_bias else None)
    out = qmatmul(x, w, scale, bias, bm=32, bn=32, bk=64)
    want = ref.qmatmul_ref(x, w, scale, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_qmatmul_out_dtypes(out_dtype):
    key = jax.random.PRNGKey(0)
    x = _rand_int8(key, (64, 64))
    w = _rand_int8(jax.random.fold_in(key, 1), (64, 64))
    scale = jnp.full((64,), 0.01, jnp.float32)
    out = qmatmul(x, w, scale, out_dtype=out_dtype, bm=32, bn=32, bk=32)
    assert out.dtype == out_dtype


@pytest.mark.smoke
def test_qmatmul_int4_packed_matches_unpacked():
    key = jax.random.PRNGKey(7)
    x = _rand_int8(key, (64, 128))
    w = _rand_int8(jax.random.fold_in(key, 1), (96, 128))
    scale = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (96,))) * .01
    dense_out = qmatmul(x, w, scale, bm=32, bn=32, bk=64)
    packed_out = qmatmul(x, pack_int4(w), scale, bm=32, bn=32, bk=64,
                         packed=True)
    np.testing.assert_allclose(np.asarray(packed_out), np.asarray(dense_out),
                               rtol=1e-6)


# Widths on the 128 grid whose N or K is not a block multiple: the kernel
# reads the stored weight's edge as a partial block instead of padding it.
RAGGED_CASES = [
    (64, 384, 640, 256, 512),        # N and K ragged
    (64, 640, 1664, 256, 1024),      # d_ff-like: N tail 128, K tail 640
    (64, 384, 512, 256, 512),        # gate/up-like: N ragged only
    (64, 256, 640, 256, 512),        # down-like: K ragged only
]


def _ragged_operands(m, n, k):
    key = jax.random.PRNGKey(m + n + k)
    x = _rand_int8(key, (m, k), -128, 128)
    w = _rand_int8(jax.random.fold_in(key, 1), (n, k))
    scale = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (n,))) * .01
    bias = jax.random.normal(jax.random.fold_in(key, 3), (n,))
    return x, w, scale, bias


@pytest.mark.parametrize("packed", [False, True], ids=["int8", "nibbles"])
@pytest.mark.parametrize("m,n,k,bn,bk", RAGGED_CASES)
def test_qmatmul_ragged_bit_exact(m, n, k, bn, bk, packed):
    """Partial weight blocks give the padded product bit for bit."""
    x, w, scale, bias = _ragged_operands(m, n, k)
    stored = pack_int4(w) if packed else w
    kw = dict(bm=128, bn=bn, bk=bk, packed=packed)
    # Unit scales leave the int32 product itself, exact in f32 at these K.
    ones = jnp.ones((n,), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(qmatmul(x, stored, ones, **kw)),
        np.asarray(ref.qmatmul_ref(x, w, ones)))
    # The same product with both operands explicitly zero-padded to the
    # tile (what the kernel computed before it read partial blocks).
    out = qmatmul(x, stored, scale, bias, **kw)
    pn, pk = (-n) % bn, (-k) % bk
    w_pad = jnp.pad(w, ((0, pn), (0, pk)))
    padded = qmatmul(jnp.pad(x, ((0, 0), (0, pk))),
                     pack_int4(w_pad) if packed else w_pad,
                     jnp.pad(scale, (0, pn)), jnp.pad(bias, (0, pn)), **kw)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(padded[:, :n]))


ATTN_CASES = [
    (2, 128, 128, 64, True, None),
    (2, 100, 260, 64, True, None),       # unaligned
    (1, 128, 384, 128, True, 128),       # local window
    (2, 64, 64, 32, False, None),        # cross/non-causal
    (1, 64, 200, 64, False, None),       # non-causal ragged (padded keys)
    (1, 64, 512, 64, True, None),        # long keys
]


def _qkv(h, sq, sk, d):
    key = jax.random.PRNGKey(h * sq + sk)
    return (_rand_int8(key, (h, sq, d)),
            _rand_int8(jax.random.fold_in(key, 1), (h, sk, d)),
            _rand_int8(jax.random.fold_in(key, 2), (h, sk, d)))


@pytest.mark.parametrize("h,sq,sk,d,causal,window", ATTN_CASES)
def test_int_attention_matches_streamed_ref(h, sq, sk, d, causal, window):
    """Two-pass kernel == block-streamed oracle (same running-m grid)."""
    q, k, v = _qkv(h, sq, sk, d)
    sc, vs = 0.002, 0.01
    out = int_attention(q, k, v, sc, vs, causal=causal, window=window,
                        bq=64, bk=64)
    want = ref.int_attention_ref_streamed(q, k, v, sc, vs, bk=64,
                                          causal=causal, window=window)
    scale = float(jnp.max(jnp.abs(want))) + 1e-9
    np.testing.assert_allclose(np.asarray(out) / scale,
                               np.asarray(want) / scale, atol=1e-5)


@pytest.mark.parametrize("h,sq,sk,d,causal,window", ATTN_CASES)
def test_fused_matches_two_pass(h, sq, sk, d, causal, window):
    """Acceptance: single-pass == two-pass within 1e-5 (bit-identical
    running-m code sequence and f32 accumulation order)."""
    q, k, v = _qkv(h, sq, sk, d)
    sc, vs = 0.002, 0.01
    kw = dict(causal=causal, window=window, bq=64, bk=64)
    one = int_attention_fused(q, k, v, sc, vs, **kw)
    two = int_attention(q, k, v, sc, vs, **kw)
    scale = float(jnp.max(jnp.abs(two))) + 1e-9
    np.testing.assert_allclose(np.asarray(one) / scale,
                               np.asarray(two) / scale, atol=1e-5)


@pytest.mark.parametrize("h,sq,sk,d,causal,window", ATTN_CASES)
def test_fused_matches_fullrow_ref_single_kblock(h, sq, sk, d, causal,
                                                 window):
    """With one key block covering the row (bk >= Sk) the online grid is
    the full-row grid: the fused kernel matches the XLA-path oracle."""
    q, k, v = _qkv(h, sq, sk, d)
    sc, vs = 0.002, 0.01
    bk = -(-sk // 128) * 128
    out = int_attention_fused(q, k, v, sc, vs, causal=causal, window=window,
                              bq=64, bk=bk)
    want = ref.int_attention_ref(q, k, v, sc, vs, causal=causal,
                                 window=window)
    scale = float(jnp.max(jnp.abs(want))) + 1e-9
    np.testing.assert_allclose(np.asarray(out) / scale,
                               np.asarray(want) / scale, atol=1e-5)


def test_fused_coarse_vs_fullrow_ref_multiblock():
    """nk > 1 streams codes on the running grid: early blocks round finer
    than the final full-row grid — close, but not bit-equal."""
    q, k, v = _qkv(2, 64, 512, 64)
    out = int_attention_fused(q, k, v, 0.002, 0.01, bq=64, bk=64)
    want = ref.int_attention_ref(q, k, v, 0.002, 0.01)
    scale = float(jnp.max(jnp.abs(want))) + 1e-9
    d = np.abs(np.asarray(out - want)) / scale
    assert d.max() < 0.05
    corr = float(jnp.corrcoef(out.ravel(), want.ravel())[0, 1])
    assert corr > 0.999


@pytest.mark.smoke
def test_fused_gqa_folding_sq_mod():
    """G query groups stacked along Sq wrap positions modulo sq_mod."""
    h, g, sq, sk, d = 2, 3, 32, 64, 32
    key = jax.random.PRNGKey(9)
    q = _rand_int8(key, (h, g * sq, d))
    k = _rand_int8(jax.random.fold_in(key, 1), (h, sk, d))
    v = _rand_int8(jax.random.fold_in(key, 2), (h, sk, d))
    out = int_attention_fused(q, k, v, 0.002, 0.01, causal=True, bq=32,
                              bk=128, sq_mod=sq)
    want = ref.int_attention_ref(q, k, v, 0.002, 0.01, causal=True,
                                 sq_mod=sq)
    scale = float(jnp.max(jnp.abs(want))) + 1e-9
    np.testing.assert_allclose(np.asarray(out) / scale,
                               np.asarray(want) / scale, atol=1e-5)


def _expand_block_scales(sc_blocks, bq, sq):
    """(h, nq) per-q-block scales -> the oracle's (h, sq) per-row form."""
    return np.repeat(np.asarray(sc_blocks), bq, axis=1)[:, :sq]


@pytest.mark.parametrize("h,sq,sk,d,causal,window", [
    (2, 128, 128, 64, True, None),
    (1, 128, 384, 128, True, 128),       # local window
    (2, 96, 200, 32, False, None),       # cross, ragged keys
])
def test_fused_per_block_scales_match_fullrow_ref(h, sq, sk, d, causal,
                                                  window):
    """Acceptance: per-block scales bit-match the per-row oracle grid.

    Each bq-tile carries its OWN logit scale (non-uniform by 16x across
    blocks — the case one per-tensor scale papers over); with one key
    block covering the row the kernel's grid is the full-row oracle's."""
    q, k, v = _qkv(h, sq, sk, d)
    bq = 32
    nq = sq // bq
    key = jax.random.PRNGKey(nq)
    sc_blocks = 0.002 * 2.0 ** jax.random.randint(key, (h, nq), -2, 3) \
        .astype(jnp.float32)                       # 16x spread across tiles
    vs = 0.01 + 0.002 * jnp.arange(h, dtype=jnp.float32)
    bk = -(-sk // 128) * 128
    out = int_attention_fused(q, k, v, sc_blocks, vs, causal=causal,
                              window=window, bq=bq, bk=bk)
    sc_rows = _expand_block_scales(sc_blocks, bq, sq)
    want = ref.int_attention_ref(q, k, v, sc_rows, vs, causal=causal,
                                 window=window)
    scale = float(jnp.max(jnp.abs(want))) + 1e-9
    np.testing.assert_allclose(np.asarray(out) / scale,
                               np.asarray(want) / scale, atol=1e-6)


def test_per_block_scales_streamed_and_two_pass():
    """Streaming key blocks with per-block q scales: fused == two-pass ==
    streamed oracle on the same running-m grid (1-D (nq,) form shared
    across heads also accepted)."""
    h, sq, sk, d, bq, bk = 2, 64, 256, 32, 32, 64
    q, k, v = _qkv(h, sq, sk, d)
    sc_blocks = jnp.asarray([0.001, 0.004], jnp.float32)       # (nq,)
    sc_rows = np.repeat(np.asarray(sc_blocks)[None, :], h, 0)
    sc_rows = _expand_block_scales(sc_rows, bq, sq)
    want = ref.int_attention_ref_streamed(q, k, v, sc_rows, 0.01, bk=bk)
    scale = float(jnp.max(jnp.abs(want))) + 1e-9
    for kern in (int_attention_fused, int_attention):
        out = kern(q, k, v, sc_blocks, 0.01, bq=bq, bk=bk)
        np.testing.assert_allclose(np.asarray(out) / scale,
                                   np.asarray(want) / scale, atol=1e-6)


@pytest.mark.parametrize("attn_bits", [2, 3, 7, 8])
def test_int_attention_prob_bits(attn_bits):
    key = jax.random.PRNGKey(0)
    q = _rand_int8(key, (1, 64, 32))
    k = _rand_int8(jax.random.fold_in(key, 1), (1, 64, 32))
    v = _rand_int8(jax.random.fold_in(key, 2), (1, 64, 32))
    for kern in (int_attention, int_attention_fused):
        out = kern(q, k, v, 0.005, 0.01, attn_bits=attn_bits, bq=32, bk=32)
        want = ref.int_attention_ref_streamed(q, k, v, 0.005, 0.01, bk=32,
                                              attn_bits=attn_bits)
        scale = float(jnp.max(jnp.abs(want))) + 1e-9
        np.testing.assert_allclose(np.asarray(out) / scale,
                                   np.asarray(want) / scale, atol=1e-5)


def test_int_attention_rejects_9bit_probs():
    """8-bit codes ride int8 biased by -128 (exact un-bias in the PV
    epilogue); anything wider has no integer carrier and must assert."""
    q = jnp.zeros((1, 32, 32), jnp.int8)
    for kern in (int_attention, int_attention_fused):
        with pytest.raises(AssertionError):
            kern(q, q, q, 1.0, 1.0, attn_bits=9)


def test_single_pass_fewer_macs():
    """Acceptance: fewer analytic MXU MACs than two-pass at S=1024."""
    h, s, d = 4, 1024, 64
    assert attention_macs(h, s, s, d, design="single") \
        < attention_macs(h, s, s, d, design="two_pass")
    assert attention_macs(h, s, s, d, design="single") == 2 * h * s * s * d


@pytest.mark.parametrize("rows,d", [(32, 128), (100, 256), (7, 512)])
@pytest.mark.parametrize("bits", [3, 8])
@pytest.mark.parametrize("rms_only", [False, True])
def test_pq_layernorm_matches_ref(rows, d, bits, rms_only):
    key = jax.random.PRNGKey(rows + d)
    x = jax.random.normal(key, (rows, d)) * 3
    g = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (d,))) + 0.5
    b = None if rms_only else jax.random.normal(
        jax.random.fold_in(key, 2), (d,)) * 0.1
    out = pq_layernorm(x, g, b, 0.05, bits=bits, rms_only=rms_only, br=32)
    want = ref.pq_layernorm_ref(x, g, b, 0.05, bits=bits, rms_only=rms_only)
    diff = np.abs(np.asarray(out, np.int32) - np.asarray(want, np.int32))
    assert diff.max() <= 1 and (diff > 0).mean() < 0.001


@pytest.mark.parametrize("in_dtype", [jnp.float32, jnp.bfloat16])
def test_pq_layernorm_dtypes(in_dtype):
    x = (jax.random.normal(jax.random.PRNGKey(0), (16, 128)) * 2
         ).astype(in_dtype)
    g = jnp.ones((128,))
    out = pq_layernorm(x, g, None, 0.1, bits=4, rms_only=True, br=16)
    assert out.dtype == jnp.int8
