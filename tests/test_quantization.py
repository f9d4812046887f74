"""Weight-only int8 serving path: reconstruction accuracy, quantized-LM
logit fidelity, decode correctness, and the training guard."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.models import lm_transformer as lm
from keystone_tpu.ops.quantization import (
    QTensor,
    mm,
    quantization_error,
    quantize_int8,
)


def test_quantize_roundtrip_error_bounded(rng):
    w = rng.normal(size=(64, 32)).astype(np.float32) * 0.3
    qt = quantize_int8(jnp.asarray(w))
    assert qt.q.dtype == jnp.int8
    # per-column symmetric: error ≤ scale/2 per column
    err = np.abs(np.asarray(qt.dequantize()) - w)
    bound = np.asarray(qt.scale)[0] / 2 + 1e-7
    assert np.all(err <= bound)
    assert quantization_error(w) <= float(bound.max())


def test_mm_matches_dequantized(rng):
    w = rng.normal(size=(32, 48)).astype(np.float32)
    y = rng.normal(size=(4, 32)).astype(np.float32)
    qt = quantize_int8(jnp.asarray(w))
    out_q = mm(jnp.asarray(y), qt, jnp.float32)
    out_ref = y @ np.asarray(qt.dequantize())
    np.testing.assert_allclose(np.asarray(out_q), out_ref, atol=1e-4)


def test_quantized_lm_close_and_decodes():
    """Quantized logits stay close enough that a trained model's greedy
    continuation is unchanged, and perplexity moves only marginally."""
    from keystone_tpu.evaluation.perplexity import evaluate_perplexity

    corpus = lm.synthetic_corpus(20_000, 31, seed=1)
    model = lm.TransformerLM.create(
        jax.random.key(0), vocab=31, max_seq=64, dim=32, depth=2,
        num_heads=2,
    )
    model, _ = lm.train(
        model, corpus, steps=60, batch=8, seq=32, lr=2e-3, seed=1
    )
    qmodel = lm.quantize_for_decode(model)
    assert isinstance(qmodel.embed, QTensor)
    assert isinstance(qmodel.blocks[0].wq, QTensor)

    toks = jnp.asarray(
        np.random.default_rng(5).integers(0, 31, size=(2, 24))
    )
    full = np.asarray(model(toks))
    quant = np.asarray(qmodel(toks))
    # int8 per-channel on a tiny trained model: sub-decimal logit drift
    assert np.max(np.abs(full - quant)) < 0.15, np.max(np.abs(full - quant))

    held = corpus[-2000:]
    ppl_f = evaluate_perplexity(model, held, seq=32)["perplexity"]
    ppl_q = evaluate_perplexity(qmodel, held, seq=32)["perplexity"]
    assert ppl_q < 1.05 * ppl_f, (ppl_f, ppl_q)

    prompt = jnp.asarray([[1, 2, 3, 4]])
    g_f = np.asarray(lm.generate(model, prompt, max_new=12))
    g_q = np.asarray(lm.generate(qmodel, prompt, max_new=12))
    assert (g_f == g_q).mean() >= 0.75, (g_f, g_q)


def test_train_rejects_quantized_model():
    corpus = lm.synthetic_corpus(5_000, 31, seed=0)
    q = lm.quantize_for_decode(
        lm.TransformerLM.create(
            jax.random.key(0), vocab=31, max_seq=32, dim=32, depth=1,
            num_heads=2,
        )
    )
    with pytest.raises(ValueError, match="inference-only"):
        lm.train(q, corpus, steps=1, batch=2, seq=16)


def test_quantize_skips_moe_and_zero_width():
    model = lm.TransformerLM.create(
        jax.random.key(0), vocab=31, max_seq=16, dim=32, depth=2,
        num_heads=2, moe_every=2, num_experts=4,
    )
    q = lm.quantize_for_decode(model)
    # MoE block's zero-width dense placeholders stay plain arrays
    assert not isinstance(q.blocks[1].w1, QTensor)
    assert q.blocks[1].w1.shape[1] == 0
    # experts stay full precision (documented)
    assert not isinstance(q.blocks[1].moe.w1, QTensor)
    # ...and the quantized-MoE model still runs forward
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 31, size=(2, 8)))
    out = q(toks)
    assert np.isfinite(np.asarray(out)).all()


def test_int8_kv_cache_decode_close_to_full():
    """int8 KV cache: teacher-forced decode logits track the f32-cache
    decode closely, and greedy generations agree on a trained model."""
    corpus = lm.synthetic_corpus(20_000, 31, seed=2)
    model = lm.TransformerLM.create(
        jax.random.key(1), vocab=31, max_seq=64, dim=32, depth=2,
        num_heads=2,
    )
    model, _ = lm.train(
        model, corpus, steps=60, batch=8, seq=32, lr=2e-3, seed=2
    )
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, 31, size=(2, 20)))
    prompt, rest = toks[:, :10], toks[:, 10:]

    lo_f, cache_f = lm.prefill(model, prompt, 20)
    lo_q, cache_q = lm.prefill(model, prompt, 20, kv_dtype="int8")
    assert cache_q.k.dtype == jnp.int8 and cache_q.k_scale is not None
    np.testing.assert_allclose(
        np.asarray(lo_q), np.asarray(lo_f), atol=1e-4
    )  # prefill logits don't touch the cache
    for j in range(rest.shape[1] - 1):
        lo_f, cache_f = lm.decode_step(model, rest[:, j], cache_f)
        lo_q, cache_q = lm.decode_step(model, rest[:, j], cache_q)
        np.testing.assert_allclose(
            np.asarray(lo_q), np.asarray(lo_f), atol=0.08,
            err_msg=f"step {j}",
        )

    g_f = np.asarray(lm.generate(model, prompt, max_new=10))
    g_q = np.asarray(lm.generate(model, prompt, max_new=10,
                                 kv_dtype="int8"))
    assert (g_f == g_q).mean() >= 0.8, (g_f, g_q)
    with pytest.raises(ValueError, match="kv_dtype"):
        lm.prefill(model, prompt, 20, kv_dtype="int4")


def test_lm_serialization_roundtrip_including_quantized(tmp_path):
    """save_pipeline/load_pipeline round-trip the LM pytree — float and
    int8-quantized (QTensor leaves) — with identical generations after
    reload (the deploy-a-served-model path)."""
    from keystone_tpu.core.serialization import load_pipeline, save_pipeline

    model = lm.TransformerLM.create(
        jax.random.key(0), vocab=31, max_seq=32, dim=32, depth=2,
        num_heads=4, num_kv_heads=2, pos_encoding="rope",
    )
    prompt = jnp.asarray([[1, 2, 3]])
    for name, m in (
        ("float", model),
        ("int8", lm.quantize_for_decode(model)),
    ):
        p = str(tmp_path / f"lm_{name}.pkl")
        save_pipeline(m, p)
        m2 = load_pipeline(p)
        assert type(m2) is lm.TransformerLM
        g1 = np.asarray(lm.generate(m, prompt, max_new=8))
        g2 = np.asarray(lm.generate(m2, prompt, max_new=8))
        np.testing.assert_array_equal(g1, g2, err_msg=name)
        if name == "int8":
            assert isinstance(m2.blocks[0].wq, QTensor)
            assert m2.blocks[0].wq.q.dtype == jnp.int8
