"""Pallas flash-attention kernels must equal dense attention.

Runs in Pallas interpret mode on the CPU test mesh (the compiled path uses
the identical kernel body on TPU). Covers the full kernel (padding, causal,
cross-attention shapes), the online-softmax step kernel, and the fused
paths inside ring / Ulysses attention.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.ops.attention import (
    dense_attention,
    ring_attention,
    ulysses_attention,
)
from keystone_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_step,
)


def _qkv(rng, b=2, h=3, s=64, d=32, s_k=None):
    def one(s_):
        return jnp.asarray(rng.normal(size=(b, h, s_, d)).astype(np.float32))

    return one(s), one(s_k or s), one(s_k or s)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_equals_dense(rng, causal):
    q, k, v = _qkv(rng)
    ref = dense_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_unaligned_shapes(rng):
    """S and D not multiples of the block/lane sizes — padding is masked."""
    q, k, v = _qkv(rng, b=1, h=2, s=100, d=40)
    ref = dense_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_cross_attention(rng):
    """S_q != S_k (decoder-style cross attention)."""
    q, k, v = _qkv(rng, s=32, s_k=96)
    ref = dense_attention(q, k, v)
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_under_jit(rng):
    q, k, v = _qkv(rng, s=128, d=64)
    ref = dense_attention(q, k, v, causal=True)
    out = jax.jit(lambda a, b, c: flash_attention(a, b, c, causal=True))(
        q, k, v
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_step_accumulates_to_dense(rng):
    """Feeding K/V block by block through the step kernel == full softmax —
    the exactness invariant ring attention relies on."""
    b, h, s, d = 1, 2, 128, 64
    q, k, v = _qkv(rng, b=b, h=h, s=s, d=d)
    nblk, sk = 4, s // 4
    m = jnp.full((b, h, s), -1e30, jnp.float32)
    l = jnp.zeros((b, h, s), jnp.float32)
    acc = jnp.zeros((b, h, s, d), jnp.float32)
    for j in range(nblk):
        m, l, acc = flash_attention_step(
            q,
            k[:, :, j * sk : (j + 1) * sk],
            v[:, :, j * sk : (j + 1) * sk],
            m,
            l,
            acc,
            q_offset=0,
            k_offset=j * sk,
            causal=True,
            block_q=64,
            block_k=32,
        )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_fully_masked_rows_are_zero(rng):
    """A causal q window strictly before the k window: every row is fully
    masked and must output exactly 0 (not the mean of V)."""
    q, k, v = _qkv(rng, b=1, h=1, s=64, d=32)
    out = flash_attention(
        q, k, v, causal=True, q_offset=0, k_offset=64, block_q=64, block_k=64
    )
    assert float(jnp.max(jnp.abs(out))) == 0.0


def test_flash_step_uneven_shard(rng):
    """Shard length not divisible by the block size — padded and masked."""
    b, h, s, d = 1, 2, 192, 24
    q, k, v = _qkv(rng, b=b, h=h, s=s, d=d)
    m = jnp.full((b, h, s), -1e30, jnp.float32)
    l = jnp.zeros((b, h, s), jnp.float32)
    acc = jnp.zeros((b, h, s, d), jnp.float32)
    m, l, acc = flash_attention_step(
        q, k, v, m, l, acc, q_offset=0, k_offset=0, causal=True
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_streaming_variant(rng):
    """Force the long-context K/V-streaming kernel and compare to dense."""
    import keystone_tpu.ops.flash_attention as fa

    q, k, v = _qkv(rng, b=1, h=2, s=256, d=64)
    for causal in (False, True):
        out = fa.flash_attention(
            q, k, v, causal=causal, block_q=64, block_k=64,
            kv_resident=False,
        )
        ref = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5
        )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_equals_dense(mesh8, rng, causal):
    q, k, v = _qkv(rng, s=64, d=16)
    ref = dense_attention(q, k, v, causal=causal)
    out = ring_attention(
        q, k, v, mesh8, seq_axis="data", causal=causal, use_flash=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_flash_equals_dense(mesh8, rng):
    q, k, v = _qkv(rng, h=8, s=64, d=16)
    ref = dense_attention(q, k, v, causal=True)
    out = ulysses_attention(
        q, k, v, mesh8, seq_axis="data", causal=True, use_flash=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_flash_under_jit_long_sequence(mesh8, rng):
    q, k, v = _qkv(rng, b=1, h=2, s=1024, d=8)
    ref = dense_attention(q, k, v)
    out = jax.jit(
        lambda a, b, c: ring_attention(
            a, b, c, mesh8, seq_axis="data", use_flash=True
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def _probe_forward(mesh8, q):
    return lambda q: flash_attention(q, q, q, causal=True), q


def _probe_backward(mesh8, q):
    from keystone_tpu.ops.flash_attention import flash_attention_trainable

    return jax.grad(lambda q: jnp.sum(flash_attention_trainable(q, q, q, True))), q


def _probe_ring_backward(mesh8, q):
    def loss(q):
        out = ring_attention(
            q, q, q, mesh8, seq_axis="data", causal=True, use_flash=False,
            trainable=True,
        )
        return jnp.sum(out)

    return jax.grad(loss), jnp.tile(q, (1, 1, 5, 1))  # 40 positions a device


def _probe_local_lm(mesh8, q):
    from keystone_tpu.models.lm.model import TransformerLM

    model = TransformerLM.create(
        jax.random.key(0), vocab=31, max_seq=16, dim=16, depth=1, num_heads=2
    )
    return model, jnp.arange(16, dtype=jnp.int32)[None] % 31


# the names generation one's sweeps set, each with a value that moved
# the blocks or the path while the program read it
_RETIRED_NAMES = {
    "KST_FLASH_BLOCK_Q": ("16", _probe_forward),
    "KST_FLASH_BLOCK_K": ("16", _probe_forward),
    "KST_FLASH_DENSE_BWD_MAX": ("0", _probe_backward),
    "KST_FLASH_BWD_BLOCK": ("16", _probe_ring_backward),
    "KST_LOCAL_ATTN": ("flash", _probe_local_lm),
}


@pytest.mark.parametrize("name", _RETIRED_NAMES)
def test_no_environment_name_tunes_attention(mesh8, rng, monkeypatch, name):
    """Blocks and paths follow the shapes and the platform alone: with
    a retired name set, the traced program and its output are those of
    the unset run."""
    value, probe = _RETIRED_NAMES[name]
    fn, x = probe(mesh8, _qkv(rng, b=1, h=2, s=64, d=16)[0])

    def traced_and_run():
        # a function of its own, so that no cache answers for the trace
        def fresh(x):
            return fn(x)

        return str(jax.make_jaxpr(fresh)(x)), jax.jit(fresh)(x)

    monkeypatch.delenv(name, raising=False)
    want_program, want = traced_and_run()
    monkeypatch.setenv(name, value)
    program, got = traced_and_run()
    assert program == want_program
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_dense_bwd_limit_selects_path(rng, monkeypatch):
    """Transients over ``_DENSE_BWD_MAX_BYTES`` (read at call time) take
    the kernel backward: the fwd saves (out, lse) residuals only on the
    kernel's path, so their presence IS the path taken."""
    import keystone_tpu.ops.flash_attention as fa

    q = jnp.asarray(rng.normal(size=(1, 2, 128, 32)).astype(np.float32))
    _, res = fa._flash_trainable_fwd(q, q, q, False)
    assert res[3] is None, "small shape should default to the dense bwd"
    monkeypatch.setattr(fa, "_DENSE_BWD_MAX_BYTES", fa._dense_bwd_bytes(q, q) - 1)
    _, res = fa._flash_trainable_fwd(q, q, q, False)
    assert res[3] is not None, "over the limit must take the kernel bwd"
    monkeypatch.setattr(fa, "_DENSE_BWD_MAX_BYTES", fa._dense_bwd_bytes(q, q))
    _, res = fa._flash_trainable_fwd(q, q, q, False)
    assert res[3] is None


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s", [196, 1024])
def test_kernel_backward_matches_dense_grads(rng, causal, s, monkeypatch):
    """The long-context backward (the Pallas kernel over the forward's
    lse) must produce the same gradients as differentiating dense
    attention: forced on at small S by dropping the dense-path
    threshold, in blocks of 256 and, at 1024, two K segments."""
    import keystone_tpu.ops.flash_attention as fa

    monkeypatch.setattr(fa, "_DENSE_BWD_MAX_BYTES", 0)
    monkeypatch.setattr(fa, "_bwd_blocks", lambda *a: (256, 256, 2))
    q, k, v = (
        jnp.asarray(rng.normal(size=(2, 3, s, 32)).astype(np.float32))
        for _ in range(3)
    )

    def loss_flash(q, k, v):
        out = fa.flash_attention_trainable(q, k, v, causal)
        return jnp.sum(jnp.sin(out) * out)

    def loss_dense(q, k, v):
        out = dense_attention(q, k, v, causal=causal)
        return jnp.sum(jnp.sin(out) * out)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), atol=2e-3,
            err_msg=f"d{name} mismatch (causal={causal}, s={s})",
        )


# the kernel's blocks in these cases: 16 queries by 16 keys
_BWD_CASES = {
    # name: (heads, K/V heads, S, D, causal, window, K blocks a segment)
    "causal": (2, 2, 96, 8, True, 0, 6),
    "window_under_block": (2, 1, 96, 8, True, 5, 6),
    "window_is_block": (2, 1, 96, 8, True, 16, 6),
    "window_three_blocks": (2, 1, 96, 8, True, 48, 6),
    "group_of_1": (3, 3, 64, 8, True, 24, 4),
    "group_of_6": (6, 1, 64, 8, True, 0, 4),
    "group_of_8": (8, 1, 64, 8, True, 24, 4),
    "ragged_sequence": (4, 2, 70, 8, True, 0, 5),
    "ragged_window": (4, 2, 70, 8, True, 20, 5),
    "head_dim_64": (2, 1, 48, 64, True, 0, 3),
    # 5 blocks of keys in segments of 2: the sixth block is all padding
    "masked_padded_tail": (2, 1, 70, 8, False, 0, 2),
    "causal_segments": (4, 2, 96, 8, True, 0, 2),
    "window_segments": (4, 2, 96, 8, True, 24, 2),
    "not_causal": (4, 2, 64, 8, False, 0, 4),
    "blocks_16x32": (4, 2, 96, 8, True, 0, 3),
    "blocks_16x32_window": (4, 2, 96, 8, True, 24, 3),
    "blocks_32x16": (4, 2, 96, 8, True, 0, 6),
    "blocks_32x16_window": (4, 2, 96, 8, True, 24, 6),
}
# queries by keys of a block where a case's are not 16 x 16
_BWD_CASE_BLOCKS = {
    "blocks_16x32": (16, 32),
    "blocks_16x32_window": (16, 32),
    "blocks_32x16": (32, 16),
    "blocks_32x16_window": (32, 16),
}
# bfloat16 inputs against the float32 oracle on the same (rounded)
# inputs: the kernel rounds p and ds to bfloat16 for the MXU and its
# results to bfloat16, 2**-9 a rounding. The largest distance over
# these cases and three seeds read 3.9e-3; float32 inputs read 4.3e-7.
_BWD_LIMIT = {"float32": 1e-5, "bfloat16": 1e-2}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", _BWD_CASES)
def test_backward_kernel_matches_the_dense_vjp(rng, monkeypatch, case, dtype):
    """The forward at its block constants and the backward kernel
    (interpret mode) against ``dense_attention`` and its ``jax.vjp`` at
    full precision: live blocks only, grouped K/V summed in the kernel,
    padding masked."""
    import keystone_tpu.ops.flash_attention as fa

    h, kvh, s, d, causal, window, seg_blocks = _BWD_CASES[case]
    block_q, block_k = _BWD_CASE_BLOCKS.get(case, (16, 16))
    monkeypatch.setattr(fa, "_BLOCK_Q", block_q)
    monkeypatch.setattr(fa, "_BLOCK_K", block_k)
    monkeypatch.setattr(
        fa, "_bwd_blocks", lambda *a: (block_q, block_k, seg_blocks)
    )
    q, k, v, ct = (
        jnp.asarray(rng.normal(size=(2, heads, s, d)), dtype)
        for heads in (h, kvh, kvh, h)
    )
    f32 = [x.astype(jnp.float32) for x in (q, k, v, ct)]
    with jax.default_matmul_precision("highest"):
        want_out, vjp = jax.vjp(
            lambda q, k, v: dense_attention(
                q, k, v, causal=causal, window=window
            ),
            *f32[:3],
        )
        want = vjp(f32[3])
    out, lse = flash_attention(
        q, k, v, causal=causal, window=window, return_lse=True
    )
    err = float(
        jnp.linalg.norm(out.astype(jnp.float32) - want_out)
        / jnp.linalg.norm(want_out)
    )
    assert err <= _BWD_LIMIT[dtype], ("out", err)
    got = fa.flash_attention_bwd(
        q, k, v, ct, out, lse, causal=causal, window=window
    )
    for a, b, name in zip(got, want, "qkv"):
        assert a.shape == b.shape and a.dtype == q.dtype
        err = float(
            jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b)
        )
        assert err <= _BWD_LIMIT[dtype], (name, err)


@pytest.mark.parametrize("kv_resident", [True, False])
@pytest.mark.parametrize("causal", [False, True])
def test_forward_lse_matches_dense(rng, kv_resident, causal):
    """return_lse must equal the dense row logsumexp of the masked scaled
    scores in both kernel variants (it feeds the blockwise backward)."""
    import math

    q, k, v = (
        jnp.asarray(rng.normal(size=(2, 2, 200, 32)).astype(np.float32))
        for _ in range(3)
    )
    out, lse = flash_attention(
        q, k, v, causal=causal, kv_resident=kv_resident, return_lse=True
    )
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(flash_attention(q, k, v, causal=causal,
                                   kv_resident=kv_resident)),
        atol=1e-6,
    )
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones((200, 200), bool))
        s = jnp.where(mask, s, -jnp.inf)
    ref = jax.nn.logsumexp(s, axis=-1)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(ref), atol=2e-4
    )
