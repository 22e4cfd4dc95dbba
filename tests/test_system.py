"""End-to-end system tests: the paper's full pipeline on a small model.

QAT train -> post-integerize -> integer-only serving, validating the
paper's central claims end to end:
  (1) integerization after QAT costs ~no accuracy (reordering is exact),
  (2) the integerized graph's heavy ops consume integer operands,
  (3) low-bit storage shrinks the model by the expected factor.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import QuantConfig, integerize_params, model_bytes
from repro.data.synthetic import DataConfig, lm_batch
from repro.launch.train import make_train_step
from repro.models import lm
from repro.optim import OptConfig, init_opt_state


CFG = lm.LMConfig(name="sys", n_layers=2, d_model=48, n_heads=4, kv_heads=2,
                  d_ff=96, vocab=64, dtype="float32", q_chunk=16, remat=False)


def _train(cfg, steps=25):
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=4)
    ocfg = OptConfig(lr=3e-3, warmup_steps=3, total_steps=steps)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    opt = init_opt_state(params)
    step = jax.jit(make_train_step(cfg, ocfg))
    first = last = None
    for i in range(steps):
        params, opt, m = step(params, opt, lm_batch(dcfg, i))
        first = first if first is not None else float(m["loss"])
        last = float(m["loss"])
    return params, first, last


def test_qat_then_integerize_pipeline():
    qc_fake = QuantConfig(w_bits=6, a_bits=8, attn_bits=7, mode="fake")
    cfg_qat = CFG.replace(quant=qc_fake)
    params, first, last = _train(cfg_qat)
    assert last < first                       # QAT trains through fake quant

    qc_int = qc_fake.replace(mode="int")
    iparams = integerize_params(params, qc_int)
    cfg_int = CFG.replace(quant=qc_int)

    # claim (1): integerized == QAT graph on held-out data
    dcfg = DataConfig(vocab=CFG.vocab, seq_len=32, global_batch=4, seed=99)
    batch = lm_batch(dcfg, 0)
    x_f, _, _ = lm.forward(params, batch, cfg_qat)
    lg_f = lm.logits_fn(params, x_f, cfg_qat)
    x_i, _, _ = lm.forward(iparams, batch, cfg_int)
    lg_i = lm.logits_fn(iparams, x_i, cfg_int)
    corr = float(jnp.corrcoef(lg_f.ravel(), lg_i.ravel())[0, 1])
    assert corr > 0.995, corr

    # claim (2): integer operands in the serving params
    flat = {jax.tree_util.keystr(p): l for p, l in
            jax.tree_util.tree_flatten_with_path(iparams)[0]}
    wq_leaves = [v for k, v in flat.items() if k.endswith("['w_q']")]
    assert wq_leaves and all(v.dtype == jnp.int8 for v in wq_leaves)

    # claim (3): storage shrinks by the logical bit ratio for weights
    mb_f = model_bytes(params, None)
    mb_i = model_bytes(iparams, qc_int)
    assert mb_i < mb_f * 0.35                  # 6b weights + 8b emb vs f32


def test_serving_driver_smoke():
    from repro.launch.serve import serve
    qc = QuantConfig(w_bits=8, a_bits=8, attn_bits=7, mode="int")
    params = integerize_params(lm.init_params(jax.random.PRNGKey(1), CFG),
                               qc)
    cfg = CFG.replace(quant=qc)
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, CFG.vocab)
    toks, stats = serve(cfg, params, prompts.astype(jnp.int32), gen_tokens=4)
    assert toks.shape == (2, 4)
    assert stats["tok_per_s"] > 0
    ttft = [s["ttft_ms"] for s in stats["per_seq"]]
    assert all(t is not None and t > 0 for t in ttft)
    assert stats["ttft_p50_ms"] <= stats["ttft_p95_ms"] <= max(ttft)
    assert not {"decode_s", "prefill_s"} & set(stats)
    assert all("tok_per_s" not in s for s in stats["per_seq"])
