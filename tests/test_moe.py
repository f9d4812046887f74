"""Routed experts (``ops/moe.py``): top-k routing without drops over
the experts held here, against a plain loop over experts; the share
arithmetic; counters; and the toy LM that routes (the reference's
closest pattern is the weighted solver's one-class-per-partition
solves, BlockWeightedLeastSquares.scala:228-263)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.ops.moe import COUNTERS, WINDOW_C, MoELayer, window_rows

# 2 of 16 experts held, 512 tokens of top 2: the held share's window is
# 512 of the 1 024 rows
_WINDOWED = dict(
    num_experts=16, held=2, first_expert=6, swiglu=True, shared_ff=32,
    scoring="sigmoid", routed_scale=2.5, tokens=(2, 256),
)
# ``tokens`` (batch, sequence) where a case wants more than the default;
# ``force``: a constant first feature and that router weight on the held
# experts (every token chooses them, or none does)
CASES = {
    "toy_top2_softmax_gelu": dict(num_experts=4),
    "share_top2_sigmoid_swiglu_shared": dict(
        num_experts=8, held=2, first_expert=4, swiglu=True, shared_ff=32,
        scoring="sigmoid", routed_scale=2.5,
    ),
    "top3_of_all_held": dict(num_experts=8, top_k=3, swiglu=True),
    "window_one": _WINDOWED,
    "window_gelu_first_share": dict(num_experts=16, held=2, tokens=(2, 256)),
    "window_overflow": dict(_WINDOWED, force=9.0),
    "window_none_routed_here": dict(_WINDOWED, force=-9.0),
    "every_row_large_share": dict(
        num_experts=4, held=2, first_expert=2, swiglu=True, tokens=(2, 256)
    ),
}


def _layer(seed=0, dim=16, ff=32, **kw):
    return MoELayer.create(jax.random.key(seed), dim, ff, **kw)


def _case(case, rng, seed, tokens):
    """The case's layer and an input of its tokens (else ``tokens``)."""
    kw = dict(CASES[case])
    b, s = kw.pop("tokens", tokens)
    force = kw.pop("force", None)
    layer = _layer(seed=seed, **kw)
    x = jnp.asarray(rng.normal(size=(b, s, 16)).astype(np.float32))
    if force is not None:
        x = x.at[..., 0].set(1.0)
        lo = layer.first_expert
        layer = dataclasses.replace(
            layer,
            w_router=layer.w_router.at[0, lo : lo + layer.held].set(force),
        )
    return layer, x


def loop_over_experts(m: MoELayer, x):
    """The layer as a loop: each held expert on every token, weighted by
    the routing weight where the token chose it."""
    xf = x.reshape(-1, x.shape[-1])
    w, idx = m.route(xf)
    out = jnp.zeros_like(xf)

    def expert(w1, w2, w3):
        h = xf @ w1
        return (jax.nn.gelu(h) if w3 is None else jax.nn.silu(h) * (xf @ w3)) @ w2

    for e in range(m.held):
        chosen = jnp.sum(jnp.where(idx == e + m.first_expert, w, 0.0), -1)
        out = out + chosen[:, None] * expert(
            m.w1[e], m.w2[e], None if m.w3 is None else m.w3[e]
        )
    if m.shared_w1 is not None:
        out = out + expert(m.shared_w1, m.shared_w2, m.shared_w3)
    return out.reshape(x.shape)


def test_output_shape_and_counters(rng):
    layer = _layer(num_experts=4)
    x = jnp.asarray(rng.normal(size=(2, 8, 16)).astype(np.float32))
    out, counters = layer(x)
    assert out.shape == x.shape and np.isfinite(np.asarray(out)).all()
    assert set(counters) == set(COUNTERS)
    # every expert held: every one of the 16 x 2 assignments lands here
    assert int(counters["routed_rows"]) == 32
    assert 8 <= int(counters["max_expert_rows"]) <= 16
    assert int(counters["mm_rows"]) >= 32


def test_single_expert_matches_dense_ffn(rng):
    """One expert, one choice: routing is the identity and the weight 1."""
    layer = _layer(num_experts=1, top_k=1)
    x = jnp.asarray(rng.normal(size=(2, 8, 16)).astype(np.float32))
    out, _ = layer(x)
    dense = jax.nn.gelu(x @ layer.w1[0]) @ layer.w2[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=1e-5)


@pytest.mark.parametrize("case", sorted(CASES))
def test_layer_matches_the_loop_over_experts(rng, case):
    layer, x = _case(case, rng, 1, (2, 16))
    out, _ = jax.jit(lambda m, t: m(t))(layer, x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(loop_over_experts(layer, x)), atol=2e-5
    )


@pytest.mark.parametrize("case", sorted(CASES))
def test_gradients_match_the_loop_over_experts(rng, case):
    """Through the sort, the gathers (whose backward is a gather too)
    and the grouped products: weights, router and input."""
    layer, x = _case(case, rng, 2, (1, 16))
    got = jax.grad(lambda m, t: jnp.sum(jnp.sin(m(t)[0])), argnums=(0, 1))(layer, x)
    want = jax.grad(
        lambda m, t: jnp.sum(jnp.sin(loop_over_experts(m, t))), argnums=(0, 1)
    )(layer, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        # sums over 512 tokens reach ~100: float32 holds them to 1e-5 of themselves
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-5)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_counters_sum_as_stated(rng, case):
    """``dispatch_rows`` is every one of the ``tokens x top_k`` rows, or
    the windows run times the window; ``extra_windows`` the windows run
    beyond the first, as many as the rows routed here need."""
    layer, x = _case(case, rng, 1, (2, 16))
    _, c = jax.jit(lambda m, t: m(t))(layer, x)
    c = {k: int(v) for k, v in c.items()}
    rows = x.shape[0] * x.shape[1] * layer.top_k
    window = window_rows(rows, layer.held, layer.num_experts)
    assert case.startswith("window") == bool(window)
    if not window:
        assert (c["dispatch_rows"], c["extra_windows"]) == (rows, 0)
        return
    runs = -(-c["routed_rows"] // window)
    assert (c["dispatch_rows"], c["extra_windows"]) == (runs * window, max(runs - 1, 0))
    assert c["routed_rows"] <= c["mm_rows"] <= c["dispatch_rows"] + 2 * 512
    expected = {"window_overflow": (rows, 2), "window_none_routed_here": (0, 0)}
    if case in expected:  # every row routed here (two windows), or none
        assert (c["routed_rows"], runs) == expected[case]


@pytest.mark.parametrize(
    "shape,window",
    [
        ((2 * 8192 * 8, 32, 256), True),  # laguna_xs2.train_8k: 32 of 256 held
        ((4 * 8192 * 1, 8, 16), False),  # zaya1_8b.train_8k: top 1, 8 of 16
        ((8 * 1 * 8, 32, 256), False),  # laguna decoding 8 slots
        ((2 * 64 * 2, 1, 2), False),  # the toy presets
    ],
    ids=["laguna_xs2", "zaya1_8b", "decode", "toy"],
)
def test_the_shape_rule_picks_the_window_only_for_a_small_share(shape, window):
    rows, held, experts = shape
    got = window_rows(rows, held, experts)
    if window:
        assert got == -(-round(WINDOW_C * rows * held / experts) // 512) * 512
        assert 0 < got <= rows // 2 and got % 512 == 0
    else:
        assert got == 0


def test_no_token_is_dropped_when_every_token_picks_one_expert(rng):
    """A router forced to send everything to expert 2 (and, second, to
    expert 0): all 64 tokens go through it, none over any capacity."""
    layer = _layer(num_experts=4, swiglu=True)
    forced = dataclasses.replace(
        layer,
        w_router=jnp.zeros_like(layer.w_router),
    )
    x = jnp.asarray(rng.normal(size=(4, 16, 16)).astype(np.float32))
    # a constant feature makes the forced logits the same for every token
    x = x.at[..., 0].set(1.0)
    forced = dataclasses.replace(
        forced, w_router=forced.w_router.at[0].set(jnp.array([1.0, 0.0, 9.0, -9.0]))
    )
    out, counters = forced(x)
    assert int(counters["routed_rows"]) == 2 * 64
    assert int(counters["max_expert_rows"]) == 64
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(loop_over_experts(forced, x)), atol=2e-5
    )
    assert float(jnp.abs(out).min(axis=-1).max()) > 0  # every row moved


def test_the_shares_of_the_routed_sum_add_up(rng):
    """Four shares of two experts each, over the same router: their
    routed parts sum to the layer that holds all eight."""
    whole = _layer(seed=3, num_experts=8, swiglu=True, scoring="sigmoid",
                   routed_scale=2.5)
    x = jnp.asarray(rng.normal(size=(2, 16, 16)).astype(np.float32))
    want, _ = whole(x)
    total = jnp.zeros_like(want)
    routed = 0
    for shard in range(4):
        lo = 2 * shard
        share = dataclasses.replace(
            whole, w1=whole.w1[lo : lo + 2], w2=whole.w2[lo : lo + 2],
            w3=whole.w3[lo : lo + 2], first_expert=lo,
        )
        part, counters = share(x)
        total = total + part
        routed += int(counters["routed_rows"])
    assert routed == 2 * 32  # every assignment is some share's
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)


def test_the_product_runs_over_whole_row_tiles_of_the_held_experts(rng):
    layer = _layer(num_experts=8, held=2, first_expert=2)
    x = jnp.asarray(rng.normal(size=(8, 64, 16)).astype(np.float32))
    _, counters = layer(x)
    routed, rows = int(counters["routed_rows"]), int(counters["mm_rows"])
    # 1024 assignments in tiles of 512: the two held experts' rows lie in
    # at most all of them, and in no fewer rows than were routed
    assert routed <= rows <= 2 * 1024 and rows % 512 == 0
    assert routed < 1024  # six of eight experts are held elsewhere


@pytest.mark.parametrize(
    "batch,seq", [(8, 4), (6, 4), (8, 256)],
    ids=["split_over_data", "whole", "window_split_over_data"],
)
def test_on_a_mesh_the_layer_is_the_unsharded_one(mesh4x2, batch, seq):
    """Told its mesh, the layer shard_maps the routed part (GSPMD
    cannot partition the grouped kernel): the batch over ``data`` where
    it divides, whole where not. Output, every gradient and the counters
    are the one-device layer's. At 8 x 256 tokens each device moves a
    window of its own 1 024 rows (the shape rule follows its tokens)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    rng = np.random.default_rng(0)
    layer = _layer(**CASES["share_top2_sigmoid_swiglu_shared"])
    if seq > 4:
        layer = _case("window_one", rng, 0, None)[0]
    x = jnp.asarray(rng.normal(size=(batch, seq, 16)).astype(np.float32))

    def loss(m, t, mesh=None):
        out, counters = m(t, mesh)
        return jnp.sum(out * out), (out, counters)

    (_, (want, want_c)), want_g = jax.value_and_grad(loss, (0, 1), has_aux=True)(
        layer, x
    )
    split = P("data" if batch % 4 == 0 else None, None, None)
    xs = jax.device_put(x, NamedSharding(mesh4x2, split))
    (_, (out, got_c)), got_g = jax.jit(
        jax.value_and_grad(lambda m, t: loss(m, t, mesh4x2), (0, 1), has_aux=True)
    )(layer, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    # each device's rows, windows and tiles are its own
    own = ("mm_rows", "dispatch_rows", "extra_windows")
    assert {k: int(v) for k, v in got_c.items() if k not in own} == {
        k: int(v) for k, v in want_c.items() if k not in own
    }
    assert int(got_c["dispatch_rows"]) == (
        int(want_c["dispatch_rows"]) if seq == 4 else 4 * 512
    )
    # each device's product runs over its own whole row tiles
    assert int(got_c["mm_rows"]) >= int(got_c["routed_rows"])
    for got, ref in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        # 2 048 tokens' sums reach ~700: float32 holds them to 1e-5 of themselves
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4, rtol=1e-5)


def test_create_refuses_a_share_outside_the_model():
    with pytest.raises(ValueError, match="experts 6..10 of 8"):
        _layer(num_experts=8, held=4, first_expert=6)
    with pytest.raises(ValueError, match="scoring"):
        _layer(num_experts=4, scoring="tanh")


def test_lm_with_moe_trains_and_generates():
    from keystone_tpu.models import lm_transformer as lm

    model = lm.TransformerLM.create(
        jax.random.key(0),
        vocab=31,
        max_seq=64,
        dim=32,
        depth=2,
        num_heads=2,
        moe_every=2,
        num_experts=4,
    )
    # block 1 dense, block 2 routed; the dense FFN of the routed block is
    # zero-width (no dead params)
    assert model.blocks[0].moe is None
    assert model.blocks[1].moe is not None
    assert model.blocks[1].w1.shape[1] == 0
    corpus = lm.synthetic_corpus(20_000, 31, seed=1)
    model, losses = lm.train(
        model, corpus, steps=40, batch=8, seq=32, lr=2e-3, seed=1
    )
    assert np.mean(losses[-5:]) < 0.75 * losses[0], (
        losses[0],
        losses[-5:],
    )
    toks = lm.generate(
        model, jnp.asarray([[1, 2, 3]]), max_new=5
    )
    assert toks.shape == (1, 5)
    assert np.asarray(toks).min() >= 0 and np.asarray(toks).max() < 31


def test_moe_does_not_perturb_dense_seeding():
    """Adding MoE layers must not change the seeded init of the shared
    weights (attention, embeddings): MoE keys are folded in separately so
    pre-MoE recorded runs stay reproducible."""
    from keystone_tpu.models import lm_transformer as lm

    kw = dict(vocab=31, max_seq=32, dim=32, depth=2, num_heads=2)
    dense = lm.TransformerLM.create(jax.random.key(7), **kw)
    moe = lm.TransformerLM.create(
        jax.random.key(7), moe_every=2, num_experts=4, **kw
    )
    np.testing.assert_array_equal(
        np.asarray(dense.embed), np.asarray(moe.embed)
    )
    for db, mb in zip(dense.blocks, moe.blocks):
        np.testing.assert_array_equal(np.asarray(db.wq), np.asarray(mb.wq))
        np.testing.assert_array_equal(np.asarray(db.wo), np.asarray(mb.wo))
    # the dense block (index 0) keeps its FFN bit-identical too
    np.testing.assert_array_equal(
        np.asarray(dense.blocks[0].w1), np.asarray(moe.blocks[0].w1)
    )
