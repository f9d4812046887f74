"""Ring / Ulysses attention must equal dense attention on a sharded mesh —
the long-context (sequence-parallel) core."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.ops.attention import (
    dense_attention,
    ring_attention,
    ulysses_attention,
)
from keystone_tpu.ops.vit import ViTFeaturizer
from keystone_tpu.parallel.mesh import data_sharding


def _qkv(rng, b=2, h=8, s=64, d=16):
    def one():
        return jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))

    return one(), one(), one()


def test_ring_equals_dense(mesh8, rng):
    q, k, v = _qkv(rng)
    ref = dense_attention(q, k, v)
    out = ring_attention(q, k, v, mesh8, seq_axis="data")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_causal_equals_dense(mesh8, rng):
    q, k, v = _qkv(rng)
    ref = dense_attention(q, k, v, causal=True)
    out = ring_attention(q, k, v, mesh8, seq_axis="data", causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_equals_dense(mesh8, rng):
    q, k, v = _qkv(rng)
    ref = dense_attention(q, k, v)
    out = ulysses_attention(q, k, v, mesh8, seq_axis="data")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_causal_and_head_check(mesh8, rng):
    q, k, v = _qkv(rng)
    ref = dense_attention(q, k, v, causal=True)
    out = ulysses_attention(q, k, v, mesh8, seq_axis="data", causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    with pytest.raises(ValueError):
        ulysses_attention(q[:, :3], k[:, :3], v[:, :3], mesh8)


def test_ring_long_sequence_under_jit(mesh8, rng):
    """Long-context shape: S=2048 sharded 8 ways, jitted end-to-end."""
    q, k, v = _qkv(rng, b=1, h=2, s=2048, d=8)
    out = jax.jit(
        lambda a, b, c: ring_attention(a, b, c, mesh8, seq_axis="data")
    )(q, k, v)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)


def test_vit_featurizer_shapes_and_mesh_parity(mesh8, rng):
    imgs = jnp.asarray(rng.normal(size=(8, 32, 32, 3)).astype(np.float32))
    vit = ViTFeaturizer.create(jax.random.key(0), image_size=32, patch_size=8)
    out = vit(imgs)
    assert out.shape == (8, 128)
    # sequence-parallel path: 16 patches over 8 devices
    vit_sp = ViTFeaturizer.create(
        jax.random.key(0), image_size=32, patch_size=8, mesh=mesh8
    )
    out_sp = vit_sp(imgs)
    np.testing.assert_allclose(np.asarray(out_sp), np.asarray(out), atol=1e-4)


def test_vit_ridge_synthetic_end_to_end():
    from keystone_tpu.models import vit_ridge as vr

    conf = vr.ViTRidgeConfig(synthetic=128, dim=64, depth=2, lam=5.0)
    res = vr.run(conf, mesh=None)
    assert res["train_error"] < 0.05  # separable synthetic classes
    assert res["test_error"] < 0.4


def _assert_ring_grads_match_dense(mesh8, q, k, v, causal, use_flash):
    def loss_ring(q, k, v):
        out = ring_attention(
            q, k, v, mesh8, seq_axis="data", causal=causal,
            use_flash=use_flash, trainable=True,
        )
        return jnp.sum(jnp.sin(out) * out)

    def loss_dense(q, k, v):
        out = dense_attention(q, k, v, causal=causal)
        return jnp.sum(jnp.sin(out) * out)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gr, gd, name in zip(g_ring, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gr), np.asarray(gd), atol=2e-3,
            err_msg=f"d{name} (causal={causal}, flash={use_flash})",
        )


@pytest.mark.parametrize("use_flash", [False, True])
@pytest.mark.parametrize("causal", [False, True])
def test_ring_trainable_grads_match_dense(mesh8, rng, causal, use_flash):
    """The custom-VJP ring backward (traveling dk/dv accumulators +
    per-hop blockwise recompute) must produce dense-attention gradients —
    for both the jnp and the flash-forward per-hop paths."""
    q, k, v = _qkv(rng, s=128, d=16)
    _assert_ring_grads_match_dense(mesh8, q, k, v, causal, use_flash)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_backward_sweeps_a_shard_in_many_blocks(
    mesh8, rng, monkeypatch, causal
):
    """A shard longer than the ring backward's block: 40 positions a
    device in blocks of 16, the third half padding. At the constant's
    512 a CPU-sized shard is one block."""
    import keystone_tpu.ops.attention as attention

    monkeypatch.setattr(attention, "_RING_BWD_BLOCK", 16)
    q, k, v = _qkv(rng, b=1, h=2, s=320, d=16)
    _assert_ring_grads_match_dense(mesh8, q, k, v, causal, False)


@pytest.mark.parametrize("use_flash", [False, True])
def test_ulysses_trainable_grads_match_dense(mesh8, rng, use_flash):
    q, k, v = _qkv(rng, h=8, s=64, d=16)

    def loss_uly(q, k, v):
        out = ulysses_attention(
            q, k, v, mesh8, seq_axis="data", causal=True,
            use_flash=use_flash, trainable=True,
        )
        return jnp.sum(out * out)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    g_uly = jax.grad(loss_uly, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gu, gd, name in zip(g_uly, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gu), np.asarray(gd), atol=2e-3,
            err_msg=f"d{name} (flash={use_flash})",
        )


def test_sequence_not_divisible_fails_loudly(mesh8, rng):
    q, k, v = _qkv(rng, s=100)  # 100 % 8 != 0
    with pytest.raises(ValueError, match="not divisible"):
        ring_attention(q, k, v, mesh8, seq_axis="data")
    with pytest.raises(ValueError, match="not divisible"):
        ulysses_attention(q, k, v, mesh8, seq_axis="data")
