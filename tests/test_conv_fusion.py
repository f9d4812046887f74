"""Convolver impl parity and the conv→rectify→pool fusion pass
(reference ConvolverSuite's shape/value checks, extended with the
normalize + whitener modes that make Convolver a non-plain convolution).

The Pallas im2col kernel that used to live in ``ops/conv_kernel.py`` was
retired in round 3 (per-image im2col with C=3 fills 3 of 128 lanes);
the conv-algebra impl these tests gate is the production path.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.ops.images import Convolver


@pytest.mark.parametrize(
    "h,w,c,k,f,norm,whiten",
    [
        (32, 32, 3, 6, 64, True, True),  # RandomPatchCifar shape
        (32, 32, 3, 6, 64, True, False),
        (28, 28, 1, 5, 32, False, False),  # plain convolution mode
        (17, 19, 3, 4, 20, True, True),  # non-square, unaligned dims
    ],
)
def test_conv_algebra_matches_xla(rng, h, w, c, k, f, norm, whiten):
    """The default conv-algebra impl (one dense conv + box-filter
    normalization) must match im2col at full precision."""
    batch = jnp.asarray(rng.normal(size=(3, h, w, c)).astype(np.float32))
    filters = jnp.asarray(
        rng.normal(size=(f, k * k * c)).astype(np.float32)
    )
    wm = (
        jnp.asarray(rng.normal(size=(k * k * c,)).astype(np.float32))
        if whiten
        else None
    )
    common = dict(
        filters=filters,
        whitener_means=wm,
        patch_size=k,
        normalize_patches=norm,
        precision="highest",
    )
    ref = Convolver(impl="xla", **common)(batch)
    out = Convolver(impl="conv", **common)(batch)
    assert out.shape == (3, h - k + 1, w - k + 1, f)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-4
    )


def test_retired_impls_rejected():
    filters = jnp.zeros((4, 27), jnp.float32)
    with pytest.raises(ValueError, match=r"expected auto\|conv\|xla"):
        Convolver(filters=filters, patch_size=3, impl="fused")(
            jnp.zeros((1, 8, 8, 3), jnp.float32)
        )


def test_fusion_pass_rewrites_conv_chain(rng):
    """optimize() swaps Convolver>>SymmetricRectifier>>Pooler for the fused
    node, leaves other nodes alone, and preserves numerics."""
    from keystone_tpu.core.fusion import optimize
    from keystone_tpu.ops.images import (
        FusedConvRectifyPool,
        ImageVectorizer,
        Pooler,
        SymmetricRectifier,
    )

    f, k = 8, 3
    filters = jnp.asarray(rng.normal(size=(f, k * k * 3)).astype(np.float32))
    pipe = (
        Convolver(filters=filters, patch_size=k, normalize_patches=True)
        >> SymmetricRectifier(alpha=0.1)
        >> Pooler(stride=3, pool_size=4)
        >> ImageVectorizer()
    )
    opt = optimize(pipe)
    assert [type(n).__name__ for n in opt.nodes] == [
        "FusedConvRectifyPool",
        "ImageVectorizer",
    ]
    fused = opt.nodes[0]
    assert isinstance(fused, FusedConvRectifyPool)
    batch = jnp.asarray(rng.normal(size=(2, 12, 12, 3)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(opt(batch)), np.asarray(pipe(batch)), atol=1e-4
    )


def test_fusion_pass_max_pool_and_skips(rng):
    """max pooling fuses too (pooling is channel-independent, so pooling
    each rectifier half before the concat is exact); pixel_fn pools must
    NOT be fused; non-Pipeline inputs come back unchanged."""
    from keystone_tpu.core.fusion import optimize
    from keystone_tpu.ops.images import Pooler, SymmetricRectifier

    f, k = 4, 3
    filters = jnp.asarray(rng.normal(size=(f, k * k * 3)).astype(np.float32))
    conv = Convolver(filters=filters, patch_size=k)
    maxpool_pipe = (
        conv >> SymmetricRectifier() >> Pooler(stride=3, pool_size=4, pool_fn="max")
    )
    opt = optimize(maxpool_pipe)
    assert len(opt.nodes) == 1
    batch = jnp.asarray(rng.normal(size=(2, 12, 12, 3)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(opt(batch)), np.asarray(maxpool_pipe(batch)), atol=1e-4
    )
    fnpool_pipe = (
        conv
        >> SymmetricRectifier()
        >> Pooler(stride=3, pool_size=4, pixel_fn=jnp.abs)
    )
    assert optimize(fnpool_pipe) is fnpool_pipe
    assert optimize(conv) is conv
    # explicitly configured convolvers asked for specific numerics or
    # scheduling — the pass must not override them
    for special in (
        Convolver(filters=filters, patch_size=k, precision="highest"),
        Convolver(filters=filters, patch_size=k, impl="xla"),
    ):
        pipe = special >> SymmetricRectifier() >> Pooler(stride=3, pool_size=4)
        assert optimize(pipe) is pipe


@pytest.mark.parametrize("impl", ["auto", "unfused"])
def test_fused_node_impls_agree(rng, impl):
    """Every FusedConvRectifyPool impl must match the literal chain."""
    from keystone_tpu.ops.images import (
        FusedConvRectifyPool,
        Pooler,
        SymmetricRectifier,
    )

    f, k = 16, 4
    filters = jnp.asarray(rng.normal(size=(f, k * k * 3)).astype(np.float32))
    wm = jnp.asarray(rng.normal(size=(k * k * 3,)).astype(np.float32))
    chain = (
        Convolver(filters=filters, whitener_means=wm, patch_size=k)
        >> SymmetricRectifier(alpha=0.1)
        >> Pooler(stride=4, pool_size=5)
    )
    node = FusedConvRectifyPool(
        filters=filters,
        whitener_means=wm,
        patch_size=k,
        alpha=0.1,
        pool_stride=4,
        pool_size=5,
        impl=impl,
    )
    batch = jnp.asarray(rng.normal(size=(2, 14, 15, 3)).astype(np.float32))
    ref = np.asarray(chain(batch))
    out = np.asarray(node(batch))
    scale = float(np.abs(ref).max()) or 1.0
    np.testing.assert_allclose(out, ref, atol=1e-5 * scale)
