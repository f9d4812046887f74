"""Fused int8-dequant Pallas matmul vs the XLA mm() path.

Runs in Pallas interpret mode on the CPU test mesh (``chip_smoke.py``
compiles and runs the kernel on the chip)."""

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.ops.int8_matmul import mm_fused
from keystone_tpu.ops.quantization import mm, quantize_int8


@pytest.mark.parametrize(
    "m,k,n",
    [
        (8, 256, 384),     # decode-ish: tiny M, K/N off the block grid
        (1, 512, 512),     # matvec, exactly one block
        (16, 700, 130),    # ragged K and N padding
    ],
)
def test_mm_fused_matches_mm(rng, m, k, n):
    """The kernel computes in y's dtype (quantization.mm semantics):
    compare like-for-like in both the bf16 policy and f32."""
    w = rng.normal(size=(k, n)).astype(np.float32)
    qt = quantize_int8(jnp.asarray(w))
    y = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    for dt, tol in ((jnp.bfloat16, 2e-2), (jnp.float32, 1e-4)):
        want = np.asarray(mm(y.astype(dt), qt, dt), np.float32)
        got = np.asarray(
            mm_fused(y.astype(dt), qt, block_n=256, block_k=256,
                     interpret=True),
            np.float32,
        )
        # same operand dtype, f32 accumulate, f32 scale — only the
        # padded-tile zeros and op order differ
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_mm_fused_large_m_falls_back_to_xla(rng):
    """Past the decode-regime M cap the kernel's single-tile layout would
    blow VMEM; mm_fused must route to the XLA path, not crash."""
    qt = quantize_int8(jnp.asarray(rng.normal(size=(128, 96)).astype(np.float32)))
    y = jnp.asarray(rng.normal(size=(300, 128)).astype(np.float32))
    got = mm_fused(y, qt, block_n=128, block_k=128, interpret=True)
    want = mm(y, qt, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_mm_fused_batched_leading_dims(rng):
    qt = quantize_int8(jnp.asarray(rng.normal(size=(128, 96)).astype(np.float32)))
    y = jnp.asarray(rng.normal(size=(2, 3, 128)).astype(np.float32))
    got = mm_fused(y, qt, block_n=128, block_k=128, interpret=True)
    assert got.shape == (2, 3, 96)
    flat = mm_fused(y.reshape(6, 128), qt, block_n=128, block_k=128,
                    interpret=True)
    np.testing.assert_allclose(
        np.asarray(got).reshape(6, 96), np.asarray(flat), atol=1e-5
    )


def test_decode_with_pallas_kernel_matches_xla_path(rng):
    """int8_kernel='pallas' routes the quantized block matmuls through
    mm_fused (interpret mode off-TPU): prefill logits and greedy decode
    must track the XLA convert-into-dot path."""
    import dataclasses

    import jax

    from keystone_tpu.models import lm_transformer as lm

    model = lm.TransformerLM.create(
        jax.random.key(0), vocab=64, max_seq=48, dim=32, depth=2,
        num_heads=4,
    )
    qm = lm.quantize_for_decode(model)
    qp = dataclasses.replace(qm, int8_kernel="pallas")
    prompt = jnp.asarray(rng.integers(0, 64, size=(2, 8)), jnp.int32)
    lx, _ = lm.prefill(qm, prompt, 24)
    lp, _ = lm.prefill(qp, prompt, 24)
    # same compute dtype both legs (the kernel honors y's dtype), so
    # only op order differs
    np.testing.assert_allclose(
        np.asarray(lx), np.asarray(lp), rtol=1e-4, atol=1e-4
    )
    tx = np.asarray(lm.generate(qm, prompt, max_new=8, kv_dtype="int8"))
    tp = np.asarray(lm.generate(qp, prompt, max_new=8, kv_dtype="int8"))
    # tiny numeric drift can flip an argmax on a random-init model; the
    # logits check above is the strict gate
    assert (tx == tp).mean() >= 0.9

    with pytest.raises(ValueError, match="int8_kernel"):
        lm.prefill(
            dataclasses.replace(qm, int8_kernel="nope"), prompt, 24
        )


def test_mm_fused_rejects_bad_scales(rng):
    qt = quantize_int8(
        jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32)),
        channel_axis=0,  # (64, 1) row scales — not per-output-channel
    )
    y = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
    with pytest.raises(ValueError, match="per-output-channel"):
        mm_fused(y, qt, interpret=True)
