"""Serving-path invariants: decode continuation == teacher-forced forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import QuantConfig, integerize_params
from repro.models import lm

BASE = dict(n_layers=4, d_model=64, n_heads=4, kv_heads=2, d_ff=128,
            vocab=128, dtype="float32", q_chunk=8, remat=False)


def _cfg(**kw):
    return lm.LMConfig(name="t", **{**BASE, **kw})


def test_decode_matches_forward_float():
    """Prefill s tokens then decode the rest one-by-one; logits must match
    the teacher-forced full forward at every position."""
    cfg = _cfg()
    key = jax.random.PRNGKey(0)
    params = lm.init_params(key, cfg)
    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab)

    x, _, _ = lm.forward(params, {"tokens": toks}, cfg)
    full_logits = lm.logits_fn(params, x, cfg)          # (2, 16, V)

    _, cache = lm.prefill(params, {"tokens": toks[:, :8]}, cfg, max_len=16)
    for t in range(8, 16):
        logits, cache = lm.decode_step(params, toks[:, t:t + 1], cache, cfg)
        # decode at position t sees tokens[:t+1]; forward logits at pos t too
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   np.asarray(full_logits[:, t]),
                                   rtol=5e-4, atol=5e-4)


def test_decode_matches_forward_hybrid():
    cfg = _cfg(block_pattern=("rglru", "rglru", "local"), attn_window=6,
               d_rnn=64, n_layers=7)
    key = jax.random.PRNGKey(1)
    params = lm.init_params(key, cfg)
    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab)
    x, _, _ = lm.forward(params, {"tokens": toks}, cfg)
    full_logits = lm.logits_fn(params, x, cfg)
    _, cache = lm.prefill(params, {"tokens": toks[:, :8]}, cfg, max_len=16)
    for t in range(8, 16):
        logits, cache = lm.decode_step(params, toks[:, t:t + 1], cache, cfg)
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   np.asarray(full_logits[:, t]),
                                   rtol=5e-3, atol=5e-3)


def test_decode_matches_forward_ssd():
    from repro.layers.ssd import SSDConfig
    cfg = _cfg(d_ff=0, block_pattern=("ssd",),
               ssd=SSDConfig(d_state=16, head_dim=16, chunk=8))
    key = jax.random.PRNGKey(2)
    params = lm.init_params(key, cfg)
    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab)
    x, _, _ = lm.forward(params, {"tokens": toks}, cfg)
    full_logits = lm.logits_fn(params, x, cfg)
    _, cache = lm.prefill(params, {"tokens": toks[:, :8]}, cfg, max_len=16)
    for t in range(8, 16):
        logits, cache = lm.decode_step(params, toks[:, t:t + 1], cache, cfg)
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   np.asarray(full_logits[:, t]),
                                   rtol=5e-3, atol=5e-3)


def test_ring_cache_wraps_for_local_attention():
    """Generation far beyond the window: ring cache must keep working."""
    cfg = _cfg(block_pattern=("local",), attn_window=4, n_layers=2,
               q_chunk=4)
    key = jax.random.PRNGKey(3)
    params = lm.init_params(key, cfg)
    toks = jax.random.randint(key, (1, 4), 0, cfg.vocab)
    _, cache = lm.prefill(params, {"tokens": toks}, cfg, max_len=64)
    span = cache["units"]["b0"]["k"].shape[3]
    assert span < 64                                    # ring, not full
    tok = toks[:, -1:]
    for _ in range(24):                                 # wraps several times
        logits, cache = lm.decode_step(params, tok, cache, cfg)
        assert bool(jnp.all(jnp.isfinite(logits)))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    assert int(cache["pos"]) == 4 + 24


def test_int_serving_greedy_agreement():
    cfg_f = _cfg()
    qc = QuantConfig(w_bits=8, a_bits=8, attn_bits=7, mode="int")
    cfg_i = cfg_f.replace(quant=qc)
    key = jax.random.PRNGKey(4)
    params = lm.init_params(key, cfg_f)
    iparams = integerize_params(params, qc)
    toks = jax.random.randint(key, (2, 12), 0, cfg_f.vocab)
    lf, cf = lm.prefill(params, {"tokens": toks}, cfg_f, max_len=20)
    li, ci = lm.prefill(iparams, {"tokens": toks}, cfg_i, max_len=20)
    # Feed both paths the float model's greedy stream; logits must track
    # closely at every step (argmax on random-init logits is noise).
    for _ in range(6):
        corr = float(jnp.corrcoef(lf.ravel(), li.ravel())[0, 1])
        assert corr > 0.995, corr
        tf_ = jnp.argmax(lf, -1).astype(jnp.int32)
        lf, cf = lm.decode_step(params, tf_, cf, cfg_f)
        li, ci = lm.decode_step(iparams, tf_, ci, cfg_i)


def test_int8_kv_cache_dtype():
    qc = QuantConfig(w_bits=8, a_bits=8, attn_bits=7, mode="int")
    cfg = _cfg(quant=qc)
    cache = lm.init_cache(cfg, 2, 16)
    assert cache["units"]["b0"]["k"].dtype == jnp.int8
    assert "k_scale" in cache["units"]["b0"]


def test_int4_packed_kv_cache():
    """kv_bits=4: packed uint8 cache at half size, decode still tracks."""
    import jax
    cfg_f = _cfg()
    qc8 = QuantConfig(w_bits=8, a_bits=8, attn_bits=7, kv_bits=8, mode="int")
    qc4 = qc8.replace(kv_bits=4)
    key = jax.random.PRNGKey(7)
    params = lm.init_params(key, cfg_f)
    ip = integerize_params(params, qc8)
    toks = jax.random.randint(key, (2, 12), 0, cfg_f.vocab)
    c8 = lm.init_cache(cfg_f.replace(quant=qc8), 2, 16)
    c4 = lm.init_cache(cfg_f.replace(quant=qc4), 2, 16)
    assert c4["units"]["b0"]["k"].dtype == jnp.uint8
    assert c4["units"]["b0"]["k"].shape[-1] * 2 == \
        c8["units"]["b0"]["k"].shape[-1]
    l8, cache8 = lm.prefill(ip, {"tokens": toks}, cfg_f.replace(quant=qc8),
                            max_len=16)
    l4, cache4 = lm.prefill(ip, {"tokens": toks}, cfg_f.replace(quant=qc4),
                            max_len=16)
    for _ in range(3):
        tok = jnp.argmax(l8, -1).astype(jnp.int32)
        l8, cache8 = lm.decode_step(ip, tok, cache8, cfg_f.replace(quant=qc8))
        l4, cache4 = lm.decode_step(ip, tok, cache4, cfg_f.replace(quant=qc4))
        corr = float(jnp.corrcoef(l8.ravel(), l4.ravel())[0, 1])
        assert corr > 0.95, corr


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_streamed_int_builder_matches_integerize(dtype):
    """lm.init_int_params (one unit at a time, no float model held) gives
    the codes and scales of integerizing the whole float model, bit for bit.

    The reference runs under jit like the builder: XLA's CPU backend draws
    slightly different float weights op by op than fused.
    """
    from repro.configs.registry import smoke_config
    cfg = smoke_config("chatglm3-6b").replace(dtype=dtype)
    qc = QuantConfig(w_bits=4, a_bits=8, attn_bits=7, kv_bits=8, mode="int")
    key = jax.random.PRNGKey(3)
    got = lm.init_int_params(key, cfg, qc)
    want = jax.jit(lambda k: integerize_params(lm.init_params(k, cfg), qc))(
        key)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))


def test_chip_smoke_refuses_cpu():
    """chip_smoke.py never falls back: without a TPU it exits non-zero."""
    import chip_smoke
    with pytest.raises(SystemExit) as exc:
        chip_smoke.require_tpu()
    assert exc.value.code not in (0, None)
    assert "no TPU found" in str(exc.value.code)


def test_compile_cache_dir_of_entry_points(monkeypatch):
    """Entry points keep JAX's compile cache where JAX_COMPILATION_CACHE_DIR
    says, else in the checkout's git-ignored .jax_cache/ — a fixed path, so
    a later run finds what an earlier one compiled."""
    from repro.launch import serve
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/set/by/environment")
    serve.use_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        serve.use_compile_cache()
        path = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert path == str(serve.CHECKOUT / ".jax_cache")
    ignored = (serve.CHECKOUT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
