"""ZAYA1-8B through the one block definition, at a small size on the CPU
(two layers of width 128, 8 query heads over 2 K/V heads of 8, experts
of width 64 with 8 of 16 held, a router of 16, sequence 64, seeded
random weights): each equation of the layer alone, the program against
the plain reference, the two shares of the experts against the uncut
layer, the train step made once per process, and the operations the
benchmark's adapter counts (its check and its planted faults are run by
``tests/benchmarks/test_zaya1_8b_cell.py``)."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.models import lm_transformer as lm
from keystone_tpu.models.lm import zaya1_8b_reference as ref
from keystone_tpu.models.lm.losses import next_token_loss
from keystone_tpu.models.lm.model import _block_apply
from keystone_tpu.observe import spans
from keystone_tpu.ops import cca, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = "zaya1_8b"


@pytest.fixture(scope="module")
def published():
    return lm.load_architecture(NAME)


@pytest.fixture(scope="module")
def toy(published):
    """The benchmark's own toy sizes laid over the published config."""
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        over = json.load(f)["toy"]
    return {**published, **{k: v for k, v in over.items() if k in published}}


def _unsettled(model, key):
    """The model with every leaf that starts at an exact value (norms,
    ``tau``, ``gamma``, biases, ``beta``, the joining rows) moved off
    it, so that each one's place in the equations shows."""
    leaves, tree = jax.tree.flatten(model)
    keys = jax.random.split(key, len(leaves))
    return tree.unflatten([
        l + 0.1 * jax.random.normal(k, l.shape)
        if l.ndim <= 1 or (l.ndim == 2 and l.shape[0] == 4) else l
        for l, k in zip(leaves, keys)
    ])


@pytest.fixture(scope="module")
def model(toy):
    return _unsettled(lm.TransformerLM.from_config(jax.random.key(8), toy), jax.random.key(9))


@pytest.fixture(scope="module")
def adapter():
    sys.path[:0] = [BENCH]
    from harness import find

    cfg, mod = find.config(NAME)
    run = find.load_module("run.py")
    cell = find.cell(NAME + ".train_8k")
    return mod, lambda rehearse: run.sizes_of(cfg, cell, mod, rehearse)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 65)), jnp.int32)


def reference_params(m):
    sys.path[:0] = [BENCH]
    from harness import find

    return find.config(NAME)[1]._reference_params(m)


# ------------------------------------------------------------- each equation alone

def test_the_value_shift_reads_the_previous_position(rng):
    v = jnp.asarray(rng.normal(size=(2, 7, 12)), jnp.float32)
    got = cca.shift_values(v, 8)
    # the first eight channels stay; the rest are zero at position 0 and
    # position t - 1's after it
    np.testing.assert_array_equal(np.asarray(got[..., :8]), np.asarray(v[..., :8]))
    assert not np.asarray(got[:, 0, 8:]).any()
    np.testing.assert_array_equal(np.asarray(got[:, 1:, 8:]), np.asarray(v[:, :-1, 8:]))
    np.testing.assert_array_equal(
        np.asarray(got[0, :, 8:]), np.asarray(ref.shifted(v[0, :, 8:], 1)))


def test_both_convolutions_see_zeros_to_the_left(rng):
    from keystone_tpu.ops.ssm import causal_conv

    x = jnp.asarray(rng.normal(size=(2, 9, 3 * 4)), jnp.float32)
    w0 = jnp.asarray(rng.uniform(-0.7, 0.7, size=(12, 2)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(3, 2, 4, 4)), jnp.float32)
    b = jnp.asarray(rng.uniform(-0.7, 0.7, size=(12,)), jnp.float32)
    depth = causal_conv(x, w0, b)
    # kernel 2: position 0 is its own tap alone, position t adds t - 1's
    np.testing.assert_allclose(np.asarray(depth[:, 0]), np.asarray(x[:, 0] * w0[:, 1] + b), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(depth[:, 3]), np.asarray(x[:, 3] * w0[:, 1] + x[:, 2] * w0[:, 0] + b), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(depth[1]), np.asarray(ref.depthwise(x[1], w0, b)), atol=1e-6)
    heads = cca.head_conv(x, w1, b)
    first = jnp.einsum("bgi,gio->bgo", x[:, 0].reshape(2, 3, 4), w1[:, 1]).reshape(2, 12) + b
    np.testing.assert_allclose(np.asarray(heads[:, 0]), np.asarray(first), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(heads[0]), np.asarray(ref.within_heads(x[0], w1, b)), atol=1e-5)
    # a head reads its own channels and no other's: moving head 2's
    # input leaves heads 0 and 1 where they were
    moved = cca.head_conv(x.at[..., 8:].add(1.0), w1, b)
    np.testing.assert_array_equal(np.asarray(moved[..., :8]), np.asarray(heads[..., :8]))
    assert float(jnp.abs(moved[..., 8:] - heads[..., 8:]).max()) > 0.1
    # XLA's own grouped convolution, padded on the left alone
    xla = jax.lax.conv_general_dilated(
        x, w1.transpose(1, 2, 0, 3).reshape(2, 4, 12), (1,), [(1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=3,
    ) + b
    np.testing.assert_allclose(np.asarray(heads), np.asarray(xla), atol=1e-5)


def test_the_group_means_of_four_query_heads_a_key_head(rng):
    q = jnp.asarray(rng.normal(size=(1, 5, 8, 3)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 5, 2, 3)), jnp.float32)
    mu_q, mu_k = cca.group_means(q, k)
    assert mu_q.shape == q.shape and mu_k.shape == k.shape
    # query heads 0-3 are K/V head 0's, 4-7 head 1's
    np.testing.assert_allclose(np.asarray(mu_q[:, :, 2]), np.asarray((q[:, :, 2] + k[:, :, 0]) / 2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(mu_q[:, :, 5]), np.asarray((q[:, :, 5] + k[:, :, 1]) / 2), atol=1e-6)
    want = ((q[:, :, 4] + q[:, :, 5] + q[:, :, 6] + q[:, :, 7]) / 4 + k[:, :, 1]) / 2
    np.testing.assert_allclose(np.asarray(mu_k[:, :, 1]), np.asarray(want), atol=1e-6)


def test_heads_are_scaled_to_length_and_keys_by_their_temperature(toy, model, tokens):
    blk = model.blocks[0]
    seen = {}

    def attend(q, k, v):
        seen.update(q=q, k=k, v=v)
        return jnp.zeros_like(q)

    y = jax.random.normal(jax.random.key(1), (2, 16, 128))
    blk.cca(y, rotate=lambda t: t, attend=attend)
    hd = 8
    np.testing.assert_allclose(
        np.asarray(jnp.linalg.norm(seen["q"], axis=-1)), np.sqrt(hd), rtol=1e-3)
    np.testing.assert_allclose(
        np.asarray(jnp.linalg.norm(seen["k"], axis=-1)),
        np.broadcast_to(np.sqrt(hd) * np.abs(np.asarray(blk.cca.tau))[None, :, None], (2, 2, 16)),
        rtol=1e-3)
    assert seen["q"].shape == (2, 8, 16, 8) and seen["k"].shape == seen["v"].shape == (2, 2, 16, 8)


def _layer_and_scores(rng, **kw):
    layer = moe.MoELayer.create(jax.random.key(0), 16, 32, 4, top_k=1, swiglu=True, **kw)
    router = moe.CarriedRouter.create(jax.random.key(1), 16, 8, 4)
    x = jnp.asarray(rng.normal(size=(2, 24, 16)), jnp.float32)
    return layer, router, x


def test_the_gate_is_the_chosen_probability_and_reaches_the_router(rng):
    layer, router, x = _layer_and_scores(rng, renormalize=False)
    (p, select), r = router(x)
    assert r.shape == (2, 24, 8) and p.shape == (2, 24, 4)
    np.testing.assert_allclose(np.asarray(p.sum(-1)), 1.0, atol=1e-6)
    weights, idx = layer.route(x.reshape(48, 16), (p.reshape(48, 4), select.reshape(48, 4)))
    np.testing.assert_array_equal(np.asarray(idx[:, 0]), np.asarray(jnp.argmax(p, -1)).ravel())
    np.testing.assert_allclose(np.asarray(weights[:, 0]), np.asarray(p.max(-1)).ravel(), atol=1e-7)
    assert float(weights.max()) < 0.9  # no gate is 1

    def loss(router, layer):
        scores, _r = router(x)
        out, counters = layer(x, None, scores)
        return jnp.sum(out * out), counters

    (_l, counters), g = jax.value_and_grad(loss, has_aux=True)(router, layer)
    reached = float(jnp.linalg.norm(g.w_down))
    assert reached > 1e-3 and float(jnp.linalg.norm(g.w3)) > 1e-3
    assert not np.asarray(g.beta).any()  # read for the choice alone
    assert int(counters["routed_rows"]) == 48
    assert float(counters["gate_sum"]) == pytest.approx(float(p.max(-1).sum()), rel=1e-6)
    # renormalised, every gate is 1 and nothing reaches the router
    renorm = dataclasses.replace(layer, renormalize=True)
    (_l, counters), g = jax.value_and_grad(loss, has_aux=True)(router, renorm)
    # (p / p is 1 to rounding: what is left is rounding's)
    assert "gate_sum" not in counters and float(jnp.linalg.norm(g.w_down)) < 1e-4 * reached
    # the balancing bias moves the choice and not the gate
    biased = dataclasses.replace(router, beta=jnp.asarray([0.0, 0.0, 0.0, 5.0]))
    (p2, select2), _r = biased(x)
    w2, idx2 = layer.route(x.reshape(48, 16), (p2.reshape(48, 4), select2.reshape(48, 4)))
    assert set(np.asarray(idx2).ravel()) == {3}
    np.testing.assert_allclose(np.asarray(w2[:, 0]), np.asarray(p[..., 3]).ravel(), atol=1e-7)


def test_the_layers_own_router_is_as_it_was(rng):
    """Scores from the layer's own matrix: top-k of them, renormalised."""
    layer = moe.MoELayer.create(jax.random.key(0), 16, 32, 4, top_k=2)
    x = jnp.asarray(rng.normal(size=(48, 16)), jnp.float32)
    weights, idx = layer.route(x)
    scores = jax.nn.softmax(x @ layer.w_router, axis=-1)
    top, want_idx = jax.lax.top_k(scores, 2)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_array_equal(
        np.asarray(weights), np.asarray(top / top.sum(-1, keepdims=True)))
    assert "gate_sum" not in layer(x.reshape(2, 24, 16))[1]


@pytest.mark.parametrize("remat", [False, True])
def test_the_router_state_reaches_the_next_layer(model, tokens, remat, adapter):
    """``gamma`` of layer l weighs what layer l - 1 left: its gradient
    is zero in the first layer (a zero state) and not in the others,
    with and without remat; and layer l's router weights move layer
    l + 1's choice."""
    m = dataclasses.replace(model, remat=remat)
    g = jax.jit(jax.grad(next_token_loss))(m, tokens)
    gammas = [float(jnp.abs(b.router.gamma)) for b in g.blocks]
    assert gammas[0] == 0.0 and min(gammas[1:]) > 1e-7
    # a state handed to a block without a router comes out untouched
    plain = dataclasses.replace(model.blocks[0], router=None, moe=None,
                                w1=jnp.zeros((128, 4)), w2=jnp.zeros((4, 128)))
    carried = jnp.ones((2, 64, 16))
    out = _block_apply(
        jnp.zeros((2, 64, 128)), plain, jnp.float32,
        lambda y, b: (jnp.zeros_like(y), None), carried=carried)
    assert out[3] is carried and out[2] is None
    # without layer 0's state layer 1 chooses otherwise
    cut = dataclasses.replace(model, blocks=(
        model.blocks[0],
        dataclasses.replace(
            model.blocks[1],
            router=dataclasses.replace(model.blocks[1].router, gamma=jnp.float32(0.0)))))
    chosen = adapter[0].chosen_experts
    a, b = chosen(model, tokens[:, :-1]), chosen(cut, tokens[:, :-1])
    np.testing.assert_array_equal(a[0], b[0])
    assert (a[1] != b[1]).mean() > 0.02


def test_learned_scales_and_biases_join_each_branch(model):
    blk = model.blocks[0]
    x = jax.random.normal(jax.random.key(2), (1, 8, 128))
    branch = jax.random.normal(jax.random.key(3), (1, 8, 128))
    out = _block_apply(
        x, dataclasses.replace(blk, moe=None, router=None, scale2=None,
                               w1=jnp.zeros((128, 4)), w2=jnp.zeros((4, 128))),
        jnp.float32, lambda y, b: (branch, None))[0]
    s, b, t, u = blk.scale1
    np.testing.assert_allclose(
        np.asarray(out), np.asarray((s * x + b) + (t * branch + u)), atol=1e-6)


# ------------------------------------------------------------- the model

def test_from_config_reads_the_published_keys(toy, published):
    m = lm.TransformerLM.from_config(jax.random.key(3), toy)
    assert len(m.blocks) == 2
    blk = m.blocks[1]
    assert blk.ssm is None and blk.wq.shape == (128, 0) and blk.wo.shape == (0, 128)
    mix = blk.cca
    assert (mix.heads, mix.kv_heads, mix.head_dim, mix.eps) == (8, 2, 8, 1e-5)
    assert mix.wq.shape == (128, 64) and mix.wk.shape == mix.wv.shape == (128, 16)
    assert mix.conv0_w.shape == (80, 2) and mix.conv1_w.shape == (10, 2, 8, 8)
    assert mix.conv0_b.shape == mix.conv1_b.shape == (80,) and mix.tau.shape == (2,)
    spec = m.layer_spec(blk)
    assert (spec.num_heads, spec.num_kv_heads, spec.window, spec.scale) == (8, 2, 0, None)
    assert (spec.rope.theta, spec.rope.partial, spec.rope.yarn) == (5e6, 0.5, None)
    experts = blk.moe
    assert (experts.num_experts, experts.held, experts.first_expert, experts.top_k) == (16, 8, 0, 1)
    assert experts.w_router.shape == (0, 16) and not experts.renormalize
    assert experts.w1.shape == (8, 128, 64) and experts.shared_w1 is None
    r = blk.router
    assert r.w_down.shape == (128, 16) and r.w3.shape == (16, 16) and r.beta.shape == (16,)
    assert float(r.gamma) == 0.5 and not np.asarray(r.b1).any()
    np.testing.assert_array_equal(np.asarray(blk.scale1[:, 0]), [1.0, 0.0, 1.0, 0.0])
    assert blk.scale2.shape == (4, 128)
    assert m.head is None and m.final_norm.shape == (128,) and m.pos_embed.size == 0
    assert (m.embed_multiplier, m.residual_multiplier, m.logits_scale) == (1.0, 1.0, 1.0)
    assert published["model_type"] == "zaya" and set(published["layer_types"]) == {"hybrid"}
    # the second expert-parallel chip holds experts 8-15
    other = lm.TransformerLM.from_config(
        jax.random.key(3), {**toy, "deployment": {**toy["deployment"], "expert_shard": 1}})
    assert other.blocks[0].moe.first_expert == 8


def test_logits_match_the_reference(toy, model, tokens):
    want = jax.jit(lambda p, t: ref.logits(toy, p, t))(reference_params(model), tokens[:, :-1])
    got = jax.jit(lambda m, t: m(t))(model, tokens[:, :-1])
    # float32 both ways; the sums differ in order
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)
    assert float(jnp.abs(want).max()) > 0.05


def test_loss_and_every_gradient_match_the_reference(toy, model, tokens):
    want_loss, want = jax.jit(lambda p, t: ref.loss_and_grads(toy, p, t))(
        reference_params(model), tokens)
    got_loss, got = jax.jit(jax.value_and_grad(next_token_loss))(model, tokens)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    got = reference_params(got)
    paths = jax.tree_util.tree_leaves_with_path(want)
    # the table, the final norm, two layers of 27 leaves
    assert len(paths) == len(jax.tree.leaves(got)) == 2 + 2 * 27
    for (path, b), a in zip(paths, jax.tree.leaves(got)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['beta']") or name == "['layers'][0]['gamma']":
            assert not np.asarray(a).any() and not np.asarray(b).any(), name
            continue
        assert float(jnp.abs(b).max()) > 0, name
        # 1e-5 of the leaf's largest entry: float32 sums in another order
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5 * float(jnp.abs(b).max()) + 1e-8,
            err_msg=name,
        )


def test_one_adamw_step_matches_the_reference(toy, model, tokens):
    from keystone_tpu.models.lm.train import make_optimizer, make_train_step

    params = reference_params(model)
    _loss, grads = jax.jit(lambda p, t: ref.loss_and_grads(toy, p, t))(params, tokens)
    want = ref.adamw_first_step(params, grads, 3e-4)
    opt = make_optimizer(3e-4)
    copy = jax.tree.map(jnp.array, model)  # the step donates its arguments
    stepped, _state, _l = make_train_step(opt)(copy, opt.tx.init(copy), tokens)
    for (path, b), a, p0 in zip(
        jax.tree_util.tree_leaves_with_path(want),
        jax.tree.leaves(reference_params(stepped)), jax.tree.leaves(params),
    ):
        # an entry moves by the rate whatever its gradient's size, but
        # one whose gradient is near AdamW's epsilon (1e-8) moves by
        # less, and there the two sums' last bits show: a tenth of the
        # rate on those, 1e-3 of it on the leaf as a whole
        err = np.abs(np.asarray(a) - np.asarray(b))
        assert err.max() <= 0.1 * 3e-4 and err.mean() <= 1e-3 * 3e-4, jax.tree_util.keystr(path)
        if np.asarray(p0).size > 16:
            assert np.abs(np.asarray(b) - np.asarray(p0)).max() > 1e-4


def test_remat_and_the_chunked_loss_change_nothing(model, tokens):
    want, gw = jax.jit(jax.value_and_grad(next_token_loss))(model, tokens)
    other = dataclasses.replace(model, remat=True)
    got, gg = jax.jit(jax.value_and_grad(
        lambda m, t: next_token_loss(m, t, logit_chunk=16)
    ))(other, tokens)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(jax.tree.leaves(gg), jax.tree.leaves(gw)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)


def test_the_blocked_reference_is_the_plain_one(toy, model, tokens):
    params = reference_params(model)
    want_loss, want = jax.jit(lambda p, t: ref.loss_and_grads(toy, p, t))(params, tokens)
    got_loss, got, terms = ref.loss_and_grads_blocked(toy, params, tokens)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)
    only_loss, none, no_terms = ref.loss_and_grads_blocked(toy, params, tokens, want_grads=False)
    assert none is None and no_terms is None
    assert float(only_loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert set(terms) == {"layer0.tau", "layer1.tau", "layer1.gamma"}


def test_the_reference_sizes_the_terms_that_tau_and_gamma_sum(toy, model, tokens):
    """A position's own copy of ``tau`` or ``gamma`` has that position's
    term of the leaf's gradient as its gradient: the terms add up to the
    gradient, and their root sum of squares is what ``terms`` says."""
    params = reference_params(model)
    one_row = tokens[:1]
    _loss, grads, terms = ref.loss_and_grads_blocked(toy, params, one_row)

    def loss_of(tau, gamma):
        layers = [params["layers"][0], {**params["layers"][1], "tau": tau, "gamma": gamma}]
        return ref.loss(toy, {**params, "layers": layers}, one_row)

    last = params["layers"][1]
    g_tau, g_gamma = jax.grad(loss_of, argnums=(0, 1))(
        jnp.broadcast_to(last["tau"], (64, 2)), jnp.broadcast_to(last["gamma"], (64, 1))
    )
    np.testing.assert_allclose(g_tau.sum(0), grads["layers"][1]["tau"], atol=2e-6)
    np.testing.assert_allclose(g_gamma.sum(), grads["layers"][1]["gamma"], atol=2e-6)
    assert terms["layer1.tau"] == pytest.approx(float(jnp.sqrt(jnp.sum(g_tau**2))), rel=1e-4)
    assert terms["layer1.gamma"] == pytest.approx(float(jnp.sqrt(jnp.sum(g_gamma**2))), rel=1e-4)
    # terms of either sign: the sum is no larger than sqrt(positions) of them
    assert abs(float(g_gamma.sum())) <= 8 * terms["layer1.gamma"]


def test_the_program_and_the_reference_choose_the_same_experts(toy, model, tokens, adapter):
    want = ref.chosen_experts(toy, reference_params(model), tokens[:, :-1])
    got = adapter[0].chosen_experts(model, tokens[:, :-1])
    assert got.shape == (2, 2, 64) and want.shape == (2, 2, 64)
    np.testing.assert_array_equal(got, want)
    assert len(np.unique(want)) > 2  # not one expert for all
    # watching changes nothing of the forward
    np.testing.assert_array_equal(
        np.asarray(model.backbone(tokens[:, :-1])[0]),
        np.asarray(dataclasses.replace(model, remat=False).backbone(tokens[:, :-1])[0]))


def test_the_two_shares_add_up_to_the_uncut_layer(toy, tokens):
    """Experts 0-7 on one chip and 8-15 on the other: what each adds to
    the stream, with everything both chips compute alike (attention, the
    router, ``s2 x + b2`` and ``u2``) counted once, is what the uncut
    reference gives for the whole layer, in the reference and in the
    program."""
    whole = {**toy, "num_experts": 16}
    whole["deployment"] = {**toy["deployment"], "expert_shard": 0}
    full = _unsettled(lm.TransformerLM.from_config(jax.random.key(5), whole), jax.random.key(6))
    p = reference_params(full)["layers"][0]
    x = jax.random.normal(jax.random.key(7), (64, 128))
    r0 = 0.3 * jax.random.normal(jax.random.key(8), (64, 16))
    with jax.default_matmul_precision("highest"):
        want, r_want = ref.layer_forward(whole, p, x, r0, share=(0, 16))
        # what both chips compute alike, once
        eps = toy["rms_norm_eps"]
        mid = ref.join(x, ref.attention(whole, p, ref.rms(x, p["norm1"], eps), False), p["scale1"])
        h = ref.rms(mid, p["norm2"], eps)
        probs, r = ref.router(whole, p, h, r0)
        s2, b2, t2, u2 = p["scale2"]
        parts = []
        for first in (0, 8):
            held = {**p, **{k: p[k][first : first + 8] for k in ref.EXPERTS}}
            parts.append(ref.experts(held, h, probs, (first, 8)))
            # and the share's own layer_forward says the same
            alone, _r = ref.layer_forward(whole, held, x, r0, share=(first, 8))
            np.testing.assert_allclose(
                np.asarray(alone), np.asarray((s2 * mid + b2) + (t2 * parts[-1] + u2)), atol=1e-5)
        summed = (s2 * mid + b2) + (t2 * (parts[0] + parts[1]) + u2)
    np.testing.assert_allclose(np.asarray(summed), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(r), np.asarray(r_want), atol=1e-6)
    # each share is a real part: neither is nothing, and no token is in both
    assert min(float(jnp.abs(part).sum()) for part in parts) > 1.0
    assert not np.asarray(jnp.abs(parts[0]).sum(-1) * jnp.abs(parts[1]).sum(-1)).any()
    # the program's expert layer, told its share, gives that share's part
    blk = full.blocks[0]
    scores = jax.tree.map(lambda a: a[None], blk.router(h[None], r0[None])[0])
    for first, part in zip((0, 8), parts):
        layer = dataclasses.replace(
            blk.moe, first_expert=first,
            **{k: getattr(blk.moe, k)[first : first + 8] for k in ("w1", "w2", "w3")})
        got, counters = layer(h[None], None, scores)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(part), atol=1e-5)
        assert int(counters["routed_rows"]) == int(
            np.sum((np.asarray(ref.chosen(p, probs)) // 8) == first // 8))


def test_the_two_copies_of_the_reference_are_one_text():
    with open(os.path.join(BENCH, "configs", NAME + "_reference.py")) as f:
        bench = f.read()
    with open(ref.__file__) as f:
        assert f.read() == bench


def test_the_sizes_are_the_issues_counts(published):
    """601 743 535 parameters at the cut, by shapes alone (nothing
    allocated), and the table of PERF.md section 4."""
    def count(cfg):
        return jax.eval_shape(
            lambda k: lm.TransformerLM.from_config(k, cfg), jax.random.key(0)
        )

    def size(node):
        return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(node))

    cut = count(published)
    assert cut.num_params() == 601_743_535
    blk = cut.blocks[0]
    assert size(blk.moe) == 8 * 3 * 2048 * 2048 == 100_663_296
    # wq and wo 2 097 152 each, wk and wv 524 288, the convolutions
    # 3 840 + 328 960, tau 2
    assert size(blk.cca) == 2 * 2_097_152 + 2 * 524_288 + 3_840 + 328_960 + 2 == 5_575_682
    # down 524 544, two hidden layers of 65 792, the last 4 112, the
    # norm 256, gamma 1, beta 16
    assert size(blk.router) == 524_544 + 2 * 65_792 + 4_112 + 256 + 1 + 16 == 660_513
    assert size(blk) == 106_919_971 == size(blk.moe) + size(blk.cca) + size(blk.router) + 20_480
    assert cut.embed.shape == (32_784, 2048)
    assert cut.num_params() == 5 * 106_919_971 + 32_784 * 2048 + 2048
    mix = blk.cca
    assert mix.wq.shape == (2048, 1024) and mix.wk.shape == (2048, 256)
    assert mix.conv1_w.shape == (10, 2, 128, 128) and mix.conv0_w.shape == (1280, 2)
    whole = {**published, **published["published"]}
    whole["deployment"] = {**published["deployment"], "expert_shard": 0}
    uncut = count(whole)
    per_layer = 106_919_971 + 8 * 3 * 2048 * 2048
    assert uncut.num_params() == 40 * per_layer + 262_272 * 2048 + 2048 == 8_840_465_784
    # the benchmark's file describes the same architecture, and keeps
    # every published key of the catalog's row but the three it cuts
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        bench = json.load(f)
    for key, value in published.items():
        if key != "source":
            assert bench[key] == value, key
    assert bench["about"]["source"] == published["source"]
    assert bench["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert bench["published"] == {"num_hidden_layers": 40, "num_experts": 16, "vocab_size": 262272}
    assert (bench["hidden_size"], bench["head_dim"], bench["moe_intermediate_size"]) == (2048, 128, 2048)
    assert (bench["num_attention_heads"], bench["num_key_value_heads"]) == (8, 2)
    assert (bench["router_hidden_size"], bench["num_experts_per_tok"]) == (256, 1)
    assert (bench["cca_time0"], bench["cca_time1"]) == (2, 2)
    assert bench["rope_parameters"]["hybrid"] == {
        "partial_rotary_factor": 0.5, "rope_theta": 5000000, "rope_type": "default"}
    assert len(bench["layer_types"]) == 40
    dep = bench["deployment"]
    assert (dep["pipeline_stages"], dep["expert_parallel"], dep["vocab_parallel"]) == (8, 2, 8)
    assert "layer_equations" in bench["assumed"] and bench["assumed"]["gamma_init"] == 0.5


def test_flops_decode_and_sharding_know_the_layer(toy):
    from keystone_tpu.models.lm.sharding import shard_params
    from keystone_tpu.parallel.mesh import create_mesh

    model = lm.TransformerLM.from_config(jax.random.key(3), toy)
    flops = lm.train_step_flops(model, 2, 64)
    # every leaf but the final norm, a layer's 8 held experts at one
    # sixteenth each (one expert a token of 16); the tied table once
    experts = 2 * 8 * 3 * 128 * 64
    params = model.num_params() - 128 - experts * (1 - 1 / 16)
    attn = 2 * 12 * 64 * (65 / 2) * 128  # two layers, 8 heads of 8 in the latent
    assert flops == pytest.approx(6.0 * params * 128 + attn)
    with pytest.raises(NotImplementedError, match="layer 0 attends in a compressed latent"):
        lm.prefill(model, jnp.zeros((1, 8), jnp.int32), 16)
    reason = model.uniform_decode_reason()
    assert "convolutions' tails" in reason and "latent K and V" in reason
    assert "state carried from layer to layer" in reason and "holds nothing for it" in reason
    assert "learned scales and biases" in reason and "learned final norm" in reason
    # under `model` the mixer's and the router's leaves stay whole
    laid = shard_params(model, create_mesh(data=4, model=2))
    for node in (laid.blocks[0].cca, laid.blocks[0].router, laid.blocks[0].moe):
        for leaf in jax.tree.leaves(node):
            assert leaf.sharding.is_fully_replicated, leaf.sharding


# the tree before this PR gave these losses (seed 11, the benchmark's toy
# sizes, two steps, on the tests' CPU backend of 8 virtual devices: one
# device sums in another order and reads 6.044705867767334 for the
# second): the blocks that carry no router state compile to the step
# they had. In bfloat16 laguna's and granite's are those of the final
# norm run once over the whole sequence ahead of the chunked loss, where
# it had run on each chunk inside the loss's scan: the last block's
# residual sum, which the CPU's XLA adds in float32 and rounds to
# bfloat16, now meets the norm's cast back to float32 with nothing
# between, and XLA, allowed excess precision, drops the rounding, so the
# norm reads the unrounded sum. The loss's arithmetic is the old one:
# without that allowance both trees read the same losses to the last
# bit (the test below)
BEFORE = {
    ("laguna_xs2", "float32"): [6.073979377746582, 6.044705390930176],
    ("laguna_xs2", "bfloat16"): [6.071727752685547, 6.046442031860352],
    ("granite_4_0_h_micro", "float32"): [5.545891284942627, 5.543205261230469],
    ("granite_4_0_h_micro", "bfloat16"): [5.545920372009277, 5.543205261230469],
}


@pytest.mark.parametrize("name,dtype", sorted(BEFORE))
def test_the_other_configurations_losses_are_unchanged_to_the_last_bit(name, dtype):
    sys.path[:0] = [BENCH]
    from harness import find

    cfg, mod = find.config(name)
    sizes = find.load_module("run.py").sizes_of(cfg, find.cell(name + ".train_8k"), mod, True)
    sizes["compute_dtype"] = dtype
    assert mod.one_fit(11, sizes)["losses"] == BEFORE[name, dtype]


# what the tree before the chunked loss formed its gradient in its
# forward gave in bfloat16 with --xla_allow_excess_precision=false: the
# first step reads the loss, the second the gradient the first formed
EXACT = {
    "laguna_xs2": [6.0714521408081055, 6.045742034912109],
    "granite_4_0_h_micro": [5.545937538146973, 5.54318904876709],
}


def test_without_excess_precision_the_bfloat16_losses_are_the_old_ones():
    """The chunked loss's forward and its gradient in bfloat16, end to
    end: with XLA held to the precision the program states (a process of
    its own: the flag is read when the backend starts), two steps of
    laguna's and granite's toy fits read the losses they read before."""
    code = (
        "import json, sys\n"
        f"sys.path[:0] = [{BENCH!r}]\n"
        "from harness import find\n"
        "run = find.load_module('run.py')\n"
        "out = {}\n"
        f"for name in {sorted(EXACT)!r}:\n"
        "    cfg, mod = find.config(name)\n"
        "    sizes = run.sizes_of(cfg, find.cell(name + '.train_8k'), mod, True)\n"
        "    sizes['compute_dtype'] = 'bfloat16'\n"
        "    out[name] = mod.one_fit(11, sizes)['losses']\n"
        "print(json.dumps(out))\n"
    )
    env = {**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8 "
                        "--xla_allow_excess_precision=false"}
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert json.loads(r.stdout.splitlines()[-1]) == EXACT


# ------------------------------------------------------------- the fit

def _fit_conf(tmp_path, toy, **kw):
    path = tmp_path / "toy.json"
    path.write_text(json.dumps(toy))
    return lm.LMConfig(config=str(path), steps=2, batch=2, seq=64, seed=5,
                       logit_chunk=16, remat=True, **kw)


def test_a_second_fit_records_no_jit_span(tmp_path, toy):
    """The train step is one module-level program: the second fit of a
    process asks jax for nothing, returns the first fit's losses, and
    says what it mixed and how its router gated."""
    conf = _fit_conf(tmp_path, toy)
    _m, first, _v, _s = lm.fit(conf)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        _m, second, _v, _s = lm.fit(conf)
    finally:
        jax.profiler.stop_trace()
    recs = spans.profiled_spans()
    names = [r["name"] for r in recs]
    assert second == first and len(second) == 2
    assert not [n for n in names if n.startswith("jit.")], names
    assert names.count("fit") == 1 and names.count("train.step") == 2
    root = next(r for r in recs if r["name"] == "fit")
    assert (root["steps"], root["tokens_per_step"]) == (2, 128)
    assert (root["cca_layers"], root["ssm_layers"]) == (2, 0)
    counters = next(r for r in recs if r["name"] == "fit.counters")
    # two CCA layers x 128 positions x 2 steps
    assert counters["cca_rows"] == 2 * 128 * 2
    assert 0 < counters["routed_rows"] <= 2 * 128 * 2
    assert counters["mm_rows"] >= counters["routed_rows"] and counters["mm_rows"] % 8 == 0
    assert 1 / 16 < counters["router_gate_mean"] < 1.0
    assert counters["load_max_over_mean"] >= 1.0 and counters["ssm_rows"] == 0
    # half the experts held, top 1: every one of the 128 rows of a layer
    # and step moves (no window), so nothing runs beyond it
    assert (counters["dispatch_rows"], counters["extra_windows"]) == (2 * 128 * 2, 0)
    # and `observe trace` prints them
    shown = spans.render_traces(recs)
    assert "cca_layers=2" in shown and "cca_rows=512" in shown and "ssm_rows" not in shown
    assert "dispatch_rows=512" in shown and "extra_windows=0" in shown
    assert "router_gate_mean=0." in shown and f"routed_rows={counters['routed_rows']}" in shown


def test_four_devices_over_data_equal_one(tmp_path, toy, devices):
    from keystone_tpu.parallel.mesh import create_mesh

    conf = dataclasses.replace(_fit_conf(tmp_path, toy), batch=4)
    one = create_mesh(devices=devices[:1])
    four = create_mesh(data=4, devices=devices[:4])
    _m, want, _v, _s = lm.fit(conf, mesh=one)
    m, got, _v, _s = lm.fit(conf, mesh=four)
    assert got == pytest.approx(want, rel=2e-6)
    assert {str(l.dtype) for l in jax.tree.leaves(m)} == {"float32"}


def test_bfloat16_compute_runs_and_stays_near_float32(tmp_path, toy):
    _m, f32, _v, _s = lm.fit(_fit_conf(tmp_path, toy))
    m, bf16, _v, _s = lm.fit(_fit_conf(tmp_path, toy, compute_dtype="bfloat16"))
    assert {str(l.dtype) for l in jax.tree.leaves(m)} == {"float32"}
    assert bf16 == pytest.approx(f32, rel=2e-2)


def test_the_launcher_trains_the_packaged_name(tmp_path, toy, monkeypatch):
    """``python -m keystone_tpu lm --config zaya1_8b`` reaches ``fit()``
    by the packaged file's name; here the toy sizes stand in for it."""
    monkeypatch.setattr(lm, "load_architecture", lambda name: {"zaya1_8b": toy}[name])
    res = lm.run(lm.LMConfig(config="zaya1_8b", steps=2, batch=2, seq=64, seed=5,
                             logit_chunk=16, remat=True))
    assert res["loss_first"] == pytest.approx(np.log(256), rel=0.02)


# ------------------------------------------------------------- the benchmark

def test_operations_against_hand_worked_numbers(adapter):
    mod, sizes_of = adapter
    sizes = sizes_of(False)
    assert (sizes["steps"], sizes["batch"], sizes["seq"]) == (8, 4, 8192)
    assert sizes["train_rows"] == 262144
    work = mod.ops_and_bytes(sizes)
    # a layer, a token: wq wk wv wo 5 242 880, the convolutions 2 560 +
    # 327 680, the router 524 288 + 131 072 + 4 096, half an expert
    layer = 5_242_880 + 330_240 + 659_456 + 6_291_456
    touched = 5 * layer + 2048 * 32_784
    attn = 5 * 4 * 8 * 128 * (8192 * 8193 // 2) * 4
    want = 6 * touched * 32_768 + 3 * attn
    assert work["train_flops_per_step"] == pytest.approx(want, rel=1e-9)
    assert work["train_flops_per_step"] == pytest.approx(33.8e12, rel=5e-3)
    assert work["train_flops_per_fit"] == pytest.approx(8 * want)
    assert work["attn_full_flops_per_step"] == pytest.approx(3 * attn)
    assert work["moe_flops_per_row"] == 6 * 2048 * 2048
    assert work["moe_bytes_per_row"] == 2 * (3 * 2048 + 3 * 2048)
    assert work["moe_weight_bytes_per_layer"] == 2 * 8 * 3 * 2048 * 2048
    assert (work["moe_layers"], work["moe_passes"], work["steps"]) == (5, 4.0, 8)
    assert work["cca_rows_per_step"] == 5 * 32_768
