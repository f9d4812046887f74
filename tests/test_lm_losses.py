"""The chunked cross-entropy (``models/lm/losses.py``): under a gradient
it forms each chunk's gradient as it makes the chunk's logits, so its
value and every gradient leaf are the dense loss's under autodiff; a
NaN-scaled loss NaNs every leaf; in bfloat16 the gradient is autodiff's
of the chunked loss; the head's products number three a chunk under a
gradient and one without. Five heads at toy size on the CPU: the presets' tied head behind the
parameter-free LayerNorm, laguna's untied head, granite's tied head
with ``logits_scale`` 1/8, zaya's tied head, and nemotron's head read
twice, by the main stack and by its MTP module."""

import dataclasses
import json
import os

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.models import lm_transformer as lm
from keystone_tpu.models.lm import losses
from keystone_tpu.models.lm.model import head_matrix
from keystone_tpu.ops.quantization import quantize_int8

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 256
CASES = ("preset", "laguna_xs2", "granite_4_0_h_micro", "zaya1_8b", "nemotron_3_super")


def _unsettled(model, key):
    """Every leaf that starts at an exact value (norm scales) moved off
    it, so that the final norms' gradients are not those of ones."""
    leaves, tree = jax.tree.flatten(model)
    keys = jax.random.split(key, len(leaves))
    return tree.unflatten([
        l + 0.1 * jax.random.normal(k, l.shape) if l.ndim <= 1 else l
        for l, k in zip(leaves, keys)
    ])


def _model(name):
    if name == "preset":
        return lm.TransformerLM.create(
            jax.random.key(0), vocab=VOCAB, max_seq=64, dim=32, depth=1, num_heads=2)
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        over = json.load(f)["toy"]
    published = lm.load_architecture(name)
    toy = {**published, **{k: v for k, v in over.items() if k in published}}
    return _unsettled(lm.TransformerLM.from_config(jax.random.key(3), toy), jax.random.key(4))


@pytest.fixture(scope="module", params=CASES)
def case(request):
    m = _model(request.param)
    ahead = 2 if m.mtp is not None else 1
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, VOCAB, (2, 64 + ahead)), jnp.int32)
    return request.param, m, tokens


def test_the_heads_are_the_ones_named(case):
    name, m, _t = case
    tied = {"preset": True, "laguna_xs2": False, "granite_4_0_h_micro": True,
            "zaya1_8b": True, "nemotron_3_super": False}
    assert (m.head is None) == tied[name]
    assert (m.logits_scale == 1 / 8) == (name == "granite_4_0_h_micro")
    assert (m.final_norm is None) == (name == "preset")
    assert (m.mtp is not None) == (name == "nemotron_3_super")


def test_the_chunked_loss_and_every_gradient_are_the_dense_ones(case):
    """Autodiff of ``token_cross_entropy(output_logits(...))`` against the
    fused forward: the value, and every leaf (the stack's, the head or
    the embedding, the final norms, the MTP module's), at
    ``test_chunked_loss_matches_dense``'s tolerances."""
    _name, m, tokens = case
    want, gw = jax.jit(jax.value_and_grad(losses.next_token_loss))(m, tokens)
    for chunk in (16, 32):
        got, gg = jax.jit(jax.value_and_grad(
            lambda mm, t, c=chunk: losses.next_token_loss(mm, t, logit_chunk=c)))(m, tokens)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
        paths = jax.tree_util.tree_leaves_with_path(gw)
        assert len(paths) == len(jax.tree.leaves(gg))
        for (path, b), a in zip(paths, jax.tree.leaves(gg)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6,
                err_msg=jax.tree_util.keystr(path))


def test_a_nan_scaled_loss_nans_every_gradient_leaf(case):
    """The guarded step's poison: the loss's cotangent is NaN, and every
    gradient entry is NaN where autodiff of the dense loss makes it NaN
    (all of the head's, or the table's; not a table's empty rows, nor a
    leaf the loss does not reach, as zaya's first router's ``gamma``)."""
    _name, m, tokens = case

    def poisoned(chunk):
        return jax.jit(jax.grad(
            lambda mm, t: losses.next_token_loss(mm, t, chunk) * jnp.float32(np.nan)
        ))(m, tokens)

    got, want = poisoned(16), poisoned(0)
    for (path, b), a in zip(jax.tree_util.tree_leaves_with_path(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(
            np.isnan(np.asarray(a)), np.isnan(np.asarray(b)), err_msg=jax.tree_util.keystr(path))
    norms = [got.final_norm, None if got.mtp is None else got.mtp.final_norm]
    for leaf in [got.embed if got.head is None else got.head, *norms]:
        assert leaf is None or bool(jnp.isnan(leaf).all())


def _head_products(jaxpr, vocab: int) -> int:
    """``dot_general``s with the vocabulary in an operand or the result,
    in ``jaxpr`` and every jaxpr it holds (a scan's body once)."""
    n = 0
    for e in jaxpr.eqns:
        if e.primitive.name == "dot_general":
            n += any(vocab in v.aval.shape for v in e.invars + e.outvars)
        for p in e.params.values():
            for j in p if isinstance(p, (list, tuple)) else [p]:
                if isinstance(j, jax.extend.core.ClosedJaxpr):
                    n += _head_products(j.jaxpr, vocab)
                elif isinstance(j, jax.extend.core.Jaxpr):
                    n += _head_products(j, vocab)
    return n


def test_three_head_products_a_chunk_under_a_gradient_and_one_without(case):
    """No logits made again in the backward, and no gradient paid for by
    a forward-only call (``jax.jit(loss)``, evaluation): the loss alone,
    from hidden states, for each head the model has."""
    _name, m, tokens = case
    heads = 1 if m.mtp is None else 2
    x = jax.random.normal(jax.random.key(2), (2, 64, m.embed.shape[1]))
    t = tokens[:, 1:65]

    def loss(x, mm, chunk):
        return sum(losses._cross_entropy(mm, x, t, chunk) for _ in range(heads))

    def products(f, *args):
        return _head_products(jax.make_jaxpr(f)(*args).jaxpr, VOCAB)

    assert products(jax.value_and_grad(lambda x, mm: loss(x, mm, 16), (0, 1)), x, m) == 3 * heads
    assert products(lambda x, mm: loss(x, mm, 16), x, m) == heads
    assert products(lambda x, mm: loss(x, mm, 0), x, m) == heads


def test_in_bfloat16_the_gradient_is_autodiffs_of_the_chunked_loss(case):
    """The forward rule runs each chunk's own backward, in the dtypes
    autodiff gives it: bfloat16 rows and head, the rows' gradient rounded
    to bfloat16 and each chunk's head gradient too before the float32
    sum, the chunks summed last to first. Autodiff of the plain chunked
    loss (:func:`losses._chunked_ce`) forms the same gradient to the last
    bit."""
    _name, m, tokens = case
    w = head_matrix(m)
    xn = jax.random.normal(jax.random.key(5), (2, 64, w.shape[0])).astype(jnp.bfloat16)
    t = tokens[:, 1:65]
    scale = m.logits_scale

    def plain(xn, w):
        return losses._chunked_ce(
            lambda xx: losses.scaled_product(xx, w, scale, xx.dtype), xn, t, 16)

    want, (wx, ww) = jax.jit(jax.value_and_grad(plain, (0, 1)))(xn, w)
    got, (gx, gw) = jax.jit(jax.value_and_grad(
        lambda xn, w: losses._fused_ce(xn, w, t, scale, 16), (0, 1)))(xn, w)
    assert (gx.dtype, gw.dtype) == (jnp.bfloat16, w.dtype)
    assert float(got) == float(want)
    np.testing.assert_array_equal(np.asarray(gx, np.float32), np.asarray(wx, np.float32))
    np.testing.assert_array_equal(np.asarray(gw), np.asarray(ww))


def test_an_int8_head_keeps_the_plain_chunked_loss():
    """A serving model's int8 table trains nothing (a fit refuses it):
    its chunked loss is the dense one, through the plain chunked CE."""
    m = _model("preset")
    q = dataclasses.replace(m, embed=quantize_int8(m.embed, channel_axis=0))
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, VOCAB, (2, 65)), jnp.int32)
    got = losses.next_token_loss(q, tokens, 16)
    assert float(got) == pytest.approx(float(losses.next_token_loss(q, tokens)), rel=1e-6)
    assert head_matrix(q) is q.embed
    x = jax.random.normal(jax.random.key(2), (2, 64, m.embed.shape[1]))
    jaxpr = jax.make_jaxpr(lambda x: losses._cross_entropy(q, x, tokens[:, 1:], 16))(x)
    assert "custom_vjp_call" not in str(jaxpr)
