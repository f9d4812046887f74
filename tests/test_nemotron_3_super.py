"""Nemotron-3-Super through the one block definition, at a small size on
the CPU (three layers of width 64, ``M * E`` from the benchmark's toy
pattern; 8 Mamba heads of 16 in 2 groups, state 16, chunks of 16; 4
query heads over one K/V head of 16; 8 of 32 relu² experts of 48 in a
latent of 32, 6 a token, a shared expert of 96 over 4 tensor shares; an
MTP module of ``* E``; sequence 64, seeded random weights): each new part
alone, the program against the plain reference, the shares of a layer
against the uncut layer, the train step through the normal path, and the
operations the benchmark's adapter counts (its check and its planted
faults are run by ``tests/benchmarks/test_nemotron_3_super_cell.py``)."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.models import lm_transformer as lm
from keystone_tpu.models.lm import nemotron_3_super_reference as ref
from keystone_tpu.models.lm.losses import next_token_loss, next_token_loss_and_counters
from keystone_tpu.observe import spans
from keystone_tpu.ops import moe, ssm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = "nemotron_3_super"


@pytest.fixture(scope="module")
def published():
    return lm.load_architecture(NAME)


@pytest.fixture(scope="module")
def toy(published):
    """The benchmark's own toy sizes laid over the packaged config."""
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        over = json.load(f)["toy"]
    return {**published, **{k: v for k, v in over.items() if k in published}}


def _unsettled(model, key):
    """The model with every leaf that starts at an exact value (the
    norms' scales and ``D``) moved off it, so that each one's place in
    the equations shows."""
    leaves, tree = jax.tree.flatten(model)
    keys = jax.random.split(key, len(leaves))
    return tree.unflatten([
        l + 0.1 * jax.random.normal(k, l.shape) if l.ndim <= 1 else l
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
    """Two windows of 64 positions and the two targets ahead of each."""
    return jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 66)), jnp.int32)


def reference_params(m):
    sys.path[:0] = [BENCH]
    from harness import find

    return find.config(NAME)[1]._reference_params(m)


# ------------------------------------------------------------- each part alone

def test_the_pattern_makes_layers_of_one_part(toy, published):
    model = jax.eval_shape(lambda: lm.TransformerLM.from_config(jax.random.key(0), published))
    parts = "".join(
        "M" if b.ssm is not None else "*" if b.wq is not None else "E" for b in model.blocks
    )
    assert parts == published["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    for b in model.blocks:
        # one part alone: the other part and its norm are absent, not zero-width
        assert b.has_mixer != b.has_ffn
        assert (b.norm1 is None) == (not b.has_mixer) and (b.norm2 is None) == (not b.has_ffn)
        assert b.w1 is None and b.w2 is None and b.w3 is None
        if b.has_ffn:
            assert b.wq is None and b.ssm is None and b.cca is None
    mtp = "".join("*" if b.wq is not None else "E" for b in model.mtp.blocks)
    assert mtp == published["mtp_hybrid_override_pattern"] == "*E"
    small = lm.TransformerLM.from_config(jax.random.key(0), toy)
    assert [b.has_mixer for b in small.blocks] == [True, True, False]


def test_a_layer_of_one_part_runs_only_that_part(model, tokens):
    """An expert layer alone adds its experts' output to the stream and
    nothing else; a mixer alone adds its mixer's: the absent part runs no
    norm and adds nothing."""
    from keystone_tpu.models.lm.model import _block_apply, _norm

    x = jax.random.normal(jax.random.key(1), (2, 64, 64))
    cdt = jnp.float32
    mixer_blk, expert_blk = model.blocks[0], model.blocks[2]
    out, aux, counters, _c = _block_apply(x, mixer_blk, cdt, model._mixer, eps=1e-5)
    mixed, _ = mixer_blk.ssm(_norm(x, mixer_blk.norm1, 1e-5, cdt))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x + mixed), atol=1e-6)
    assert counters is None and aux is not None
    out, aux, counters, _c = _block_apply(x, expert_blk, cdt, model._mixer, eps=1e-5)
    f, _ = expert_blk.moe(_norm(x, expert_blk.norm2, 1e-5, cdt))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x + f), atol=1e-6)
    assert aux is None and counters is not None


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_the_gated_norm_normalises_each_group_alone(groups):
    rng = np.random.default_rng(groups)
    y, z = (jnp.asarray(rng.normal(size=(2, 5, 32)), jnp.float32) for _ in range(2))
    scale = jnp.asarray(rng.normal(size=(32,)), jnp.float32)
    got = ssm.gated_rms_norm(y, z, scale, 1e-5, groups)
    g = (y * jax.nn.silu(z)).reshape(2, 5, groups, 32 // groups)
    want = (g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True) + 1e-5)).reshape(2, 5, 32) * scale
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_the_gated_norm_of_one_group_is_the_whole_width_norm_as_it_was():
    """At one group (granite's mixer) the norm is the one it was, to the
    bit: the same function of the whole inner width."""
    rng = np.random.default_rng(0)
    y, z = (jnp.asarray(rng.normal(size=(3, 7, 48)), jnp.bfloat16) for _ in range(2))
    scale = jnp.asarray(rng.normal(size=(48,)), jnp.float32)

    def before(y, z, scale, eps):
        g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        return (g * scale.astype(jnp.float32)).astype(y.dtype)

    np.testing.assert_array_equal(
        np.asarray(ssm.gated_rms_norm(y, z, scale, 1e-5).astype(jnp.float32)),
        np.asarray(before(y, z, scale, 1e-5).astype(jnp.float32)))
    assert str(jax.make_jaxpr(lambda *a: ssm.gated_rms_norm(*a, 1e-5))(y, z, scale)) == str(
        jax.make_jaxpr(lambda *a: before(*a, 1e-5))(y, z, scale))


def test_heads_read_their_own_groups_B_and_C(toy, model):
    """A mixer of two groups: the heads of group 1 read group 1's B and
    C, so giving every head group 0's changes group 1's heads alone."""
    blk = model.blocks[0].ssm
    x = jax.random.normal(jax.random.key(2), (1, 32, 8, 16))
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(3), (1, 32, 8)))
    a = -jnp.exp(blk.A_log)
    b, c = (jax.random.normal(k, (1, 32, 2, 16)) for k in jax.random.split(jax.random.key(4)))
    want = ssm.ssd_scan(x, dt, a, b, c, chunk=16)
    shared = ssm.ssd_scan(x, dt, a, b[:, :, :1].repeat(2, 2), c[:, :, :1].repeat(2, 2), chunk=16)
    np.testing.assert_allclose(np.asarray(want[:, :, :4]), np.asarray(shared[:, :, :4]), atol=1e-5)
    assert float(jnp.abs(want[:, :, 4:] - shared[:, :, 4:]).max()) > 1e-2


def _relu2_layer(held=4, first=2, latent=16, dim=24):
    return moe.MoELayer.create(
        jax.random.key(3), dim, 40, 16, held=held, first_expert=first, top_k=6,
        shared_ff=20, scoring="sigmoid", routed_scale=5.0, latent=latent,
        activation="relu2",
    )


def test_a_relu2_expert_is_two_matrices_in_the_latent():
    layer = _relu2_layer()
    assert layer.w3 is None and layer.shared_w3 is None
    assert layer.w1.shape == (4, 16, 40) and layer.w2.shape == (4, 40, 16)
    assert layer.latent_down.shape == (24, 16) and layer.latent_up.shape == (16, 24)
    assert layer.shared_w1.shape == (24, 20) and layer.w_router.shape == (24, 16)
    x = jax.random.normal(jax.random.key(4), (2, 8, 24))
    got, counters = layer(x)
    # by hand: the router on the full width, the latent, relu², the gates
    xf = x.reshape(16, 24)
    s = jax.nn.sigmoid(xf @ layer.w_router)
    top, idx = jax.lax.top_k(s, 6)
    gates = 5.0 * top / top.sum(-1, keepdims=True)
    u = xf @ layer.latent_down
    routed = jnp.zeros((16, 16))
    for e in range(4):
        w = jnp.sum(jnp.where(idx == 2 + e, gates, 0.0), axis=-1)
        routed = routed + w[:, None] * (jnp.square(jax.nn.relu(u @ layer.w1[e])) @ layer.w2[e])
    shared = jnp.square(jax.nn.relu(xf @ layer.shared_w1)) @ layer.shared_w2
    want = routed @ layer.latent_up + shared
    np.testing.assert_allclose(np.asarray(got.reshape(16, 24)), np.asarray(want), atol=2e-5)
    assert int(counters["routed_rows"]) == int(np.sum((idx >= 2) & (idx < 6)))
    with pytest.raises(ValueError, match="relu2"):
        moe.MoELayer.create(jax.random.key(0), 8, 8, 4, swiglu=True, activation="relu2")


@pytest.mark.parametrize("windows", [1, 3])
def test_relu2_experts_agree_between_the_window_and_every_row(monkeypatch, windows):
    """Two matrices a pass through ``_expert_windows``' forward and
    backward and through ``_every_row``: the same output and gradients,
    in one window or several."""
    layer = _relu2_layer()
    x = jax.random.normal(jax.random.key(5), (2, 32, 24))

    def loss(m, x):
        out, _c = m(x)
        return jnp.sum(jnp.square(out)), out

    (l_all, out_all), g_all = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(layer, x)
    # 64 tokens x 6 = 384 rows, 4 of 16 experts held: about 96 routed here
    monkeypatch.setattr(moe, "_TM", 8)
    monkeypatch.setattr(moe, "WINDOW_C", 2.0 if windows == 1 else 0.3)
    assert moe.window_rows(384, 4, 16) > 0
    (l_win, out_win), g_win = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(layer, x)
    _o, counters = layer(x)
    assert (int(counters["extra_windows"]) > 0) == (windows > 1)
    np.testing.assert_allclose(np.asarray(out_win), np.asarray(out_all), atol=2e-5)
    for a, b in zip(jax.tree.leaves(g_win), jax.tree.leaves(g_all)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-4)


def test_the_mtp_module_reads_the_next_ids_and_trains_on_the_one_after(model, tokens):
    """CE_1 + 0.3 CE_2 over windows of S + 2: the main head predicts
    t+1 from the first S ids, the MTP module t+2 from the main stack's
    states and the ids one ahead."""
    from keystone_tpu.models.lm.losses import token_cross_entropy
    from keystone_tpu.models.lm.model import output_logits

    loss, counters = next_token_loss_and_counters(model, tokens)
    x, c = model.backbone(tokens[:, :64])
    h, _c = model.mtp_hidden(x, tokens[:, 1:65], c)
    ce1 = token_cross_entropy(output_logits(model, x, jnp.float32), tokens[:, 1:65])
    ahead = dataclasses.replace(model, final_norm=model.mtp.final_norm)
    ce2 = token_cross_entropy(output_logits(ahead, h, jnp.float32), tokens[:, 2:])
    assert float(loss) == pytest.approx(float(ce1 + 0.3 * ce2), rel=1e-6)
    assert float(counters["mtp_ce"]) == pytest.approx(float(ce2), rel=1e-6)
    assert int(counters["mtp_rows"]) == 128
    # the expert layers' counters cover the MTP module's expert layer too
    assert int(counters["dispatch_rows"]) == 2 * 128 * 6
    assert model.mtp.weight == 0.3


def test_perplexity_is_the_next_tokens_alone(model, tokens):
    """Held-out evaluation reads windows of S + 1 and scores the main
    head: the MTP module is no part of the perplexity."""
    from keystone_tpu.evaluation.perplexity import evaluate_perplexity
    from keystone_tpu.models.lm.losses import token_cross_entropy
    from keystone_tpu.models.lm.model import output_logits

    stream = np.asarray(tokens[0, :65])
    got = evaluate_perplexity(model, stream, seq=64, batch=1)
    x, _c = model.backbone(jnp.asarray(stream[None, :64]))
    want = token_cross_entropy(
        output_logits(model, x, jnp.float32), jnp.asarray(stream[None, 1:65]))
    assert got["loss"] == pytest.approx(float(want), rel=1e-5)
    assert got["tokens_scored"] == 64


# ------------------------------------------------------------- against the reference

def test_loss_mtp_term_and_every_gradient_match_the_reference(toy, model, tokens):
    params = reference_params(model)
    want_loss, want = jax.jit(lambda p, t: ref.loss_and_grads(toy, p, t))(params, tokens)
    (got_loss, counters), got = jax.jit(jax.value_and_grad(
        lambda m, t: next_token_loss_and_counters(m, t), has_aux=True))(model, tokens)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert float(counters["mtp_ce"]) == pytest.approx(
        float(ref.mtp_term(toy, params, tokens)), rel=1e-6)
    got = reference_params(got)
    paths = jax.tree_util.tree_leaves_with_path(want)
    assert len(paths) == len(jax.tree.leaves(got))
    for (path, b), a in zip(paths, jax.tree.leaves(got)):
        name = jax.tree_util.keystr(path)
        assert float(jnp.abs(b).max()) > 0, name
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5 * float(jnp.abs(b).max()) + 1e-8,
            err_msg=name,
        )
    # and by the check's groups
    want_norms, got_norms = ref.group_norms(want), ref.group_norms(got)
    assert set(want_norms) >= {"layer0.ssm.A_log", "layer1.attention", "layer2.experts",
                               "mtp.layer1.router", "mtp.eh", "head"}
    for k, v in want_norms.items():
        assert got_norms[k] == pytest.approx(v, rel=1e-4), k


def test_the_blocked_reference_is_the_plain_one(toy, model, tokens):
    params = reference_params(model)
    want_loss, want = jax.jit(lambda p, t: ref.loss_and_grads(toy, p, t))(params, tokens)
    got_loss, mtp, got = ref.loss_and_grads_blocked(toy, params, tokens)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert float(mtp) == pytest.approx(float(ref.mtp_term(toy, params, tokens)), rel=1e-6)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-6)
    only, mtp_only, none = ref.loss_and_grads_blocked(toy, params, tokens, want_grads=False)
    assert none is None and float(only) == pytest.approx(float(want_loss), rel=1e-6)
    assert float(mtp_only) == pytest.approx(float(mtp), rel=1e-6)


def test_the_program_and_the_reference_choose_the_same_experts(toy, model, tokens, adapter):
    want = ref.chosen_experts(toy, reference_params(model), tokens)
    got = adapter[0].chosen_experts(model, tokens)
    # two expert layers (the main stack's and the MTP module's), 2 x 64 tokens, 6 a token
    assert got.shape == want.shape == (2, 2, 64, 6)
    np.testing.assert_array_equal(got, want)
    assert (np.diff(got, axis=-1) > 0).all()  # sorted, no expert twice


def test_one_adamw_step_matches_the_reference(toy, model, tokens):
    from keystone_tpu.models.lm.train import make_optimizer, make_train_step

    params = reference_params(model)
    _loss, grads = jax.jit(lambda p, t: ref.loss_and_grads(toy, p, t))(params, tokens)
    want = ref.adamw_first_step(params, grads, 3e-4)
    opt = make_optimizer(3e-4)
    copy = jax.tree.map(jnp.array, model)  # the step donates its arguments
    stepped, _state, _l = make_train_step(opt)(copy, opt.tx.init(copy), tokens)
    for (path, b), a in zip(
        jax.tree_util.tree_leaves_with_path(want), jax.tree.leaves(reference_params(stepped)),
    ):
        # an entry whose gradient is near AdamW's epsilon moves by less
        # than the rate, and there the two sums' last bits show
        err = np.abs(np.asarray(a) - np.asarray(b))
        assert err.max() <= 0.1 * 3e-4 and err.mean() <= 1e-3 * 3e-4, jax.tree_util.keystr(path)


def test_remat_and_the_chunked_loss_change_nothing(model, tokens):
    want, gw = jax.jit(jax.value_and_grad(next_token_loss))(model, tokens)
    got, gg = jax.jit(jax.value_and_grad(
        lambda m, t: next_token_loss(m, t, logit_chunk=16)
    ))(dataclasses.replace(model, remat=True), tokens)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(jax.tree.leaves(gg), jax.tree.leaves(gw)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-6)


def test_the_two_copies_of_the_reference_are_one_text():
    with open(os.path.join(BENCH, "configs", NAME + "_reference.py")) as f:
        bench = f.read()
    with open(ref.__file__) as f:
        assert f.read() == bench


# ------------------------------------------------------------- the shares

def _uncut(toy):
    """The toy's published counts, every expert held: the uncut layers."""
    pub = toy["published"]
    return {
        **toy, "mamba_num_heads": pub["mamba_num_heads"], "n_groups": pub["n_groups"],
        "num_attention_heads": pub["num_attention_heads"],
        "num_key_value_heads": pub["num_key_value_heads"],
        "n_routed_experts": pub["n_routed_experts"],
        "deployment": {**toy["deployment"], "tensor_parallel": 1, "expert_shard": 0},
    }


def _tensor_share(p, kind, share, cfg):
    """Tensor share ``share`` of 4 of one uncut layer's reference leaves."""
    if kind == "M":
        h, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
        g, n = cfg["n_groups"], cfg["ssm_state_size"]
        inner, gn = h * hd, g * n
        heads = np.arange(share * h // 4, (share + 1) * h // 4)
        chans = (heads[:, None] * hd + np.arange(hd)).ravel()
        grp = np.arange(share * g // 4, (share + 1) * g // 4)
        gchans = (grp[:, None] * n + np.arange(n)).ravel()
        cols = np.concatenate([
            chans, inner + chans, 2 * inner + gchans, 2 * inner + gn + gchans,
            2 * inner + 2 * gn + heads,
        ])
        conv = np.concatenate([chans, inner + gchans, inner + gn + gchans])
        return {
            "norm": p["norm"], "in": p["in"][:, cols], "conv_w": p["conv_w"][conv],
            "conv_b": p["conv_b"][conv], "dt_bias": p["dt_bias"][heads],
            "A_log": p["A_log"][heads], "D": p["D"][heads], "gnorm": p["gnorm"][chans],
            "out": p["out"][chans],
        }
    if kind == "*":
        h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
        heads = np.arange(share * h // 4, (share + 1) * h // 4)
        kv_head = heads[0] // (h // kv)
        chans = (heads[:, None] * hd + np.arange(hd)).ravel()
        kvc = kv_head * hd + np.arange(hd)
        return {"norm": p["norm"], "wq": p["wq"][:, chans], "wk": p["wk"][:, kvc],
                "wv": p["wv"][:, kvc], "wo": p["wo"][chans]}
    cols = np.arange(share * p["s1"].shape[1] // 4, (share + 1) * p["s1"].shape[1] // 4)
    return {**p, "s1": p["s1"][:, cols], "s2": p["s2"][cols]}


@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_the_tensor_shares_add_up_to_the_uncut_layer(toy, kind):
    """The 4 tensor-parallel chips' shares of a Mamba layer (8 of 32
    heads, 2 of 8 groups), of the attention layer (4 of 16 query heads
    with the K/V head they read) and of the shared expert (24 of 96
    columns): each chip's output, summed, is the uncut reference layer's
    (what all four compute alike, the router and the routed experts,
    counted once), and the program's layer at the held counts gives
    share 0's part."""
    whole = _uncut(toy)
    full = _unsettled(lm.TransformerLM.from_config(jax.random.key(5), {
        **whole, "hybrid_override_pattern": "M*E"}), jax.random.key(6))
    layer = {"M": 0, "*": 1, "E": 2}[kind]
    p = reference_params(full)["layers"][layer]
    y = jax.random.normal(jax.random.key(7), (64, 64))
    part = {"M": ref.mamba, "*": ref.attention, "E": ref.experts}[kind]
    held = {**whole, "mamba_num_heads": whole["mamba_num_heads"] // 4,
            "n_groups": whole["n_groups"] // 4,
            "num_attention_heads": whole["num_attention_heads"] // 4,
            "num_key_value_heads": 1}
    with jax.default_matmul_precision("highest"):
        want = part(whole, p, y, False)
        shares = [_tensor_share(p, kind, s, whole) for s in range(4)]
        if kind == "E":
            # the routed part is every chip's alike: once
            routed = want - ref.relu2(y @ p["s1"]) @ p["s2"]
            parts = [ref.relu2(y @ q["s1"]) @ q["s2"] for q in shares]
            got = routed + sum(parts)
        else:
            parts = [part(held, q, y, False) for q in shares]
            got = sum(parts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
    assert min(float(jnp.abs(q).sum()) for q in parts) > 1.0
    if kind == "M":
        # the program's mixer of the held counts, given share 0's weights
        mixer = ssm.Mamba2Mixer.create(
            jax.random.key(0), 64, heads=8, head_dim=16, state=16, groups=2, chunk=16)
        q = shares[0]
        mixer = dataclasses.replace(
            mixer, w_in=q["in"], conv_w=q["conv_w"], conv_b=q["conv_b"], dt_bias=q["dt_bias"],
            A_log=q["A_log"], D=q["D"], norm=q["gnorm"], w_out=q["out"])
        with jax.default_matmul_precision("highest"):
            got0, _c = mixer(y[None])
        np.testing.assert_allclose(np.asarray(got0[0]), np.asarray(parts[0]), rtol=1e-4, atol=1e-4)


def test_the_expert_shares_add_up_to_the_uncut_routed_part(toy):
    """Experts 0-7, 8-15, 16-23 and 24-31 on four expert-parallel chips:
    the program's expert layer told each share gives that share's part,
    and the parts add up to the uncut reference's routed part (the
    router, the latent pair and the shared expert counted once)."""
    whole = _uncut(toy)
    full = _unsettled(lm.TransformerLM.from_config(jax.random.key(5), {
        **whole, "hybrid_override_pattern": "M*E"}), jax.random.key(6))
    blk = full.blocks[2].moe
    p = reference_params(full)["layers"][2]
    y = jax.random.normal(jax.random.key(7), (64, 64))
    with jax.default_matmul_precision("highest"):
        want = ref.experts(whole, p, y, False) - ref.relu2(y @ p["s1"]) @ p["s2"]
        parts, rows = [], 0
        for first in range(0, 32, 8):
            layer = dataclasses.replace(
                blk, first_expert=first, shared_w1=None, shared_w2=None,
                w1=blk.w1[first : first + 8], w2=blk.w2[first : first + 8])
            got, counters = layer(y[None])
            parts.append(got[0])
            rows += int(counters["routed_rows"])
            # and the reference told the same share
            q = {**p, "e1": p["e1"][first : first + 8], "e2": p["e2"][first : first + 8],
                 "s1": p["s1"][:, :0], "s2": p["s2"][:0]}
            cfg = {**whole, "n_routed_experts": 8,
                   "deployment": {**whole["deployment"], "expert_shard": first // 8}}
            np.testing.assert_allclose(
                np.asarray(ref.experts(cfg, q, y, False)), np.asarray(got[0]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(want), rtol=1e-4, atol=1e-4)
    assert rows == 64 * 6 and min(float(jnp.abs(q).sum()) for q in parts) > 1.0


# ------------------------------------------------------------- sizes and counts

def test_from_config_reads_the_published_keys(toy, published):
    model = jax.eval_shape(lambda: lm.TransformerLM.from_config(jax.random.key(0), published))
    m, e, a = model.blocks[0].ssm, model.blocks[1].moe, model.blocks[7]
    assert (m.heads, m.head_dim, m.state, m.groups, m.chunk, m.eps) == (32, 64, 128, 2, 128, 1e-5)
    assert m.conv_w.shape == (2048 + 2 * 2 * 128, 4) and m.conv_b is not None
    assert m.w_in.shape == (4096, 2 * 2048 + 2 * 256 + 32)
    assert (e.num_experts, e.held, e.first_expert, e.top_k) == (512, 8, 0, 22)
    assert (e.scoring, e.routed_scale, e.renormalize, e.activation) == ("sigmoid", 5, True, "relu2")
    assert e.w1.shape == (8, 1024, 2688) and e.w2.shape == (8, 2688, 1024) and e.w3 is None
    assert e.latent_down.shape == (4096, 1024) and e.latent_up.shape == (1024, 4096)
    assert e.shared_w1.shape == (4096, 5376 // 4) and e.shared_w3 is None
    spec = a.spec
    assert (spec.num_heads, spec.num_kv_heads, spec.window, spec.rope, spec.scale) == (8, 1, 0, None, None)
    assert a.wq.shape == (4096, 8 * 128) and a.wk.shape == (4096, 128)
    assert model.embed.shape == (16384, 4096) and model.head.shape == (4096, 16384)
    assert model.mtp.eh_proj.shape == (8192, 4096) and model.pos_encoding == "nope"
    assert published["published"]["n_routed_experts"] == 512
    other = lm.TransformerLM.from_config(
        jax.random.key(3), {**toy, "deployment": {**toy["deployment"], "expert_shard": 1}})
    assert other.blocks[2].moe.first_expert == 8
    with pytest.raises(ValueError, match="relu2"):
        lm.TransformerLM.from_config(jax.random.key(0), {**toy, "mlp_hidden_act": "silu"})


def test_num_params_is_the_files_count(published):
    """716 977 120 held parameters: a Mamba layer 27 413 088, the
    attention layer 9 441 280, an expert layer 65 540 096, the
    embedding and head slices with the final norm 134 221 824, the MTP
    module 108 548 096; 11.47 GB at 16 B a parameter."""
    model = jax.eval_shape(lambda: lm.TransformerLM.from_config(jax.random.key(0), published))
    assert model.num_params() == published["num_parameters_held"] == 716_977_120

    def n(t):
        return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(t))

    assert [n(model.blocks[i]) for i in (0, 1, 7)] == [27_413_088, 65_540_096, 9_441_280]
    assert n(model.mtp) == 108_548_096


def test_flops_decode_and_sharding_know_the_parts(toy):
    from keystone_tpu.models.lm.sharding import shard_params
    from keystone_tpu.parallel.mesh import create_mesh

    model = lm.TransformerLM.from_config(jax.random.key(0), toy)
    flops = lm.train_step_flops(model, 2, 64)
    # every leaf but the embedding (a gather) and the two final norms,
    # each expert layer's 8 held experts at 6 of 32; the head twice; two
    # attention layers' scores
    experts = 2 * 8 * 2 * 32 * 48
    params = (
        model.num_params() - model.embed.size - 2 * 64 - experts * (1 - 6 / 32)
        + model.head.size
    )
    attn = 2 * 12 * 64 * (65 / 2) * 128
    assert flops == pytest.approx(6.0 * params * 128 + attn)
    reason = model.uniform_decode_reason()
    assert "multi-token prediction" in reason and "one part alone" in reason
    assert "state-space layer" in reason and "untied" in reason
    with pytest.raises(NotImplementedError, match="multi-token prediction"):
        lm.prefill(model, jnp.zeros((1, 8), jnp.int32), 16)
    laid = shard_params(model, create_mesh(data=4, model=2))
    assert laid.blocks[0].wq is None and laid.blocks[2].w1 is None
    assert laid.blocks[1].wq.sharding.spec == jax.sharding.PartitionSpec(None, "model")


# the tree before this change gave these losses (seed 11, the benchmark's
# toy sizes, two steps, on the tests' CPU backend of 8 virtual devices):
# zaya's step is the one it was (``tests/test_zaya1_8b.py`` holds
# laguna's and granite's the same way)
BEFORE = {
    ("zaya1_8b", "float32"): [5.59979248046875, 5.539463043212891],
    ("zaya1_8b", "bfloat16"): [5.599784851074219, 5.539268493652344],
}


@pytest.mark.parametrize("name,dtype", sorted(BEFORE))
def test_the_other_configurations_losses_are_unchanged_to_the_last_bit(name, dtype):
    sys.path[:0] = [BENCH]
    from harness import find

    cfg, mod = find.config(name)
    sizes = find.load_module("run.py").sizes_of(cfg, find.cell(name + ".train_8k"), mod, True)
    sizes["compute_dtype"] = dtype
    assert mod.one_fit(11, sizes)["losses"] == BEFORE[name, dtype]


# ------------------------------------------------------------- the fit

def _fit_conf(tmp_path, toy, **kw):
    path = tmp_path / "toy.json"
    path.write_text(json.dumps(toy))
    return lm.LMConfig(config=str(path), steps=2, batch=2, seq=64, seed=5,
                       logit_chunk=16, remat=True, **kw)


def test_windows_carry_the_target_two_ahead(tmp_path, toy):
    from keystone_tpu.models.lm.train import _step_batch, synthetic_corpus

    history: dict = {}
    conf = _fit_conf(tmp_path, toy)
    lm.fit(conf, history=history)
    corpus = synthetic_corpus(200_000, 256, seed=5)
    assert [w.shape for w in history["windows"]] == [(2, 66), (2, 66)]
    np.testing.assert_array_equal(history["windows"][0], _step_batch(corpus, 5, 0, 2, 65))
    assert [int(c["mtp_rows"]) for c in history["counters"]] == [128, 128]


def test_a_second_fit_records_no_jit_span_and_says_what_it_predicted(tmp_path, toy):
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
    root = next(r for r in recs if r["name"] == "fit")
    assert (root["mtp_depth"], root["moe_latent"], root["ssm_layers"]) == (1, 32, 1)
    counters = next(r for r in recs if r["name"] == "fit.counters")
    assert counters["mtp_rows"] == 2 * 128
    assert counters["ssm_rows"] == 2 * 128 and counters["ssm_kernel_rows"] == 0
    # two expert layers, 128 positions, 6 a token, 2 steps
    assert counters["dispatch_rows"] == 2 * 2 * 128 * 6
    shown = spans.render_traces(recs)
    assert "mtp_depth=1" in shown and "moe_latent=32" in shown and "mtp_rows=256" in shown


def test_bfloat16_compute_runs_and_stays_near_float32(tmp_path, toy):
    _m, f32, _v, _s = lm.fit(_fit_conf(tmp_path, toy))
    m, bf16, _v, _s = lm.fit(_fit_conf(tmp_path, toy, compute_dtype="bfloat16"))
    assert {str(l.dtype) for l in jax.tree.leaves(m)} == {"float32"}
    assert bf16 == pytest.approx(f32, rel=2e-2)


def test_the_launcher_trains_the_packaged_name(toy, monkeypatch):
    """``python -m keystone_tpu lm --config nemotron_3_super`` reaches
    ``fit()`` by the packaged file's name; here the toy sizes stand in
    for it."""
    monkeypatch.setattr(lm, "load_architecture", lambda name: {NAME: toy}[name])
    res = lm.run(lm.LMConfig(config=NAME, steps=2, batch=2, seq=64, seed=5,
                             logit_chunk=16, remat=True))
    # CE_1 + 0.3 CE_2, each near ln 256 at the start
    assert res["loss_first"] == pytest.approx(1.3 * np.log(256), rel=0.1)
    assert res["steps_ran"] == 2


# ------------------------------------------------------------- the benchmark

def test_operations_against_hand_worked_numbers(adapter):
    mod, sizes_of = adapter
    sizes = sizes_of(False)
    assert (sizes["steps"], sizes["batch"], sizes["seq"]) == (8, 2, 8192)
    assert sizes["train_rows"] == 131072
    work = mod.ops_and_bytes(sizes)
    # a token's parameters: an M layer 4096 x 4640 + 2048 x 4096 + 2560 x 4;
    # an E layer's router 4096 x 512, latent pair 2 x 4096 x 1024, shared
    # 2 x 4096 x 1344, 22/512 of 8 experts of 2 x 1024 x 2688; the * layer
    # 4096 x 128 x 18; the head twice; eh_proj 8192 x 4096
    m = 4096 * 4640 + 2048 * 4096 + 2560 * 4
    e = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 1344 + 2 * 1024 * 2688 * 22 * 8 / 512
    a = 4096 * 128 * 18
    touched = 5 * m + 6 * e + 2 * a + 2 * 4096 * 16384 + 8192 * 4096
    attn = 2 * 4 * 8 * 128 * (8192 * 8193 // 2) * 2
    scan_row = 2 * 64 * 128 * 2 + 2 * 64 * 64 * 32 + 4 * 128 * 64 * 32
    want = 6 * touched * 16384 + 3 * attn + 3 * scan_row * 5 * 16384
    assert work["train_flops_per_step"] == pytest.approx(want, rel=1e-9)
    assert work["train_flops_per_fit"] == pytest.approx(8 * want)
    assert work["moe_flops_per_row"] == 4 * 1024 * 2688
    assert work["moe_weight_bytes_per_layer"] == 2 * 8 * 2 * 1024 * 2688
    assert (work["moe_layers"], work["moe_passes"], work["steps"]) == (6, 4.0, 8)
    assert work["ssm_rows_per_step"] == 5 * 16384 and work["ssm_scan_runs"] == 2
    assert work["mtp_rows_per_step"] == 16384
