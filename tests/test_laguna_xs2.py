"""Laguna-XS.2 through the one block definition, at a small size on the
CPU (same period of layers, 8 experts of 64 with 2 held a share, window
8, sequence 64, seeded random weights): the program against the plain
reference, the window kernel against masked dense attention, the shares
of the experts, the train step made once per process, and the
benchmark's adapter with its check."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.models import lm_transformer as lm
from keystone_tpu.models.lm import laguna_xs2_reference as ref
from keystone_tpu.models.lm.losses import next_token_loss
from keystone_tpu.models.lm.model import RopeSpec, _rope
from keystone_tpu.observe import spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")


@pytest.fixture(scope="module")
def published():
    return lm.load_architecture("laguna_xs2")


@pytest.fixture(scope="module")
def toy(published):
    """The benchmark's own toy sizes laid over the published config."""
    with open(os.path.join(BENCH, "configs", "laguna_xs2.json")) as f:
        over = json.load(f)["toy"]
    return {**published, **{k: v for k, v in over.items() if k in published}}


@pytest.fixture(scope="module")
def model(toy):
    return lm.TransformerLM.from_config(jax.random.key(3), toy)


def reference_params(m):
    layers = []
    for b in m.blocks:
        p = {k: getattr(b, k) for k in ("norm1", "wq", "wk", "wv", "wg", "wo", "norm2")}
        if b.moe is None:
            p.update(w1=b.w1, w3=b.w3, w2=b.w2)
        else:
            e = b.moe
            p.update(router=e.w_router, e1=e.w1, e3=e.w3, e2=e.w2,
                     s1=e.shared_w1, s3=e.shared_w3, s2=e.shared_w2)
        layers.append(p)
    return {"embed": m.embed, "head": m.head, "final_norm": m.final_norm,
            "layers": layers}


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 65)), jnp.int32)


def test_the_toy_keeps_the_period_and_the_share(toy, model):
    kinds = [(model.layer_spec(b).num_heads, model.layer_spec(b).window,
              b.moe is not None) for b in model.blocks]
    assert kinds == [(6, 0, False), (8, 8, True), (8, 8, True), (8, 8, True),
                     (6, 0, True)]
    moe = model.blocks[1].moe
    assert (moe.num_experts, moe.held, moe.first_expert, moe.top_k) == (8, 2, 2, 2)
    assert (moe.scoring, moe.routed_scale) == ("sigmoid", 2.5)
    assert model.head.shape == (64, 256) and model.blocks[0].wg.shape == (64, 6)
    full, window = model.layer_spec(model.blocks[0]).rope, model.layer_spec(model.blocks[1]).rope
    assert (full.theta, full.partial, full.yarn[0]) == (500000.0, 0.5, 4)
    assert (window.theta, window.partial, window.yarn) == (10000.0, 1.0, None)


def test_logits_match_the_reference(toy, model, tokens):
    want = ref.logits(toy, reference_params(model), tokens[:, :-1])
    got = model(tokens[:, :-1])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)


def test_loss_and_every_gradient_match_the_reference(toy, model, tokens):
    want_loss, want = ref.loss_and_grads(toy, reference_params(model), tokens)
    got_loss, got = jax.value_and_grad(next_token_loss)(model, tokens)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    got = reference_params(got)
    paths = jax.tree_util.tree_leaves_with_path(want)
    assert len(paths) == len(jax.tree.leaves(got)) == 3 + 10 + 4 * 14
    for (path, b), a in zip(paths, jax.tree.leaves(got)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, err_msg=str(path)
        )


def test_remat_and_the_chunked_loss_change_nothing(toy, model, tokens):
    want, gw = jax.value_and_grad(next_token_loss)(model, tokens)
    other = dataclasses.replace(model, remat=True)
    got, gg = jax.value_and_grad(
        lambda m, t: next_token_loss(m, t, logit_chunk=16)
    )(other, tokens)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(jax.tree.leaves(gg), jax.tree.leaves(gw)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_the_blocked_reference_is_the_plain_one(toy, model, tokens):
    params = reference_params(model)
    want_loss, want = ref.loss_and_grads(toy, params, tokens)
    got_loss, got = ref.loss_and_grads_blocked(toy, params, tokens)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    only_loss, none = ref.loss_and_grads_blocked(toy, params, tokens, want_grads=False)
    assert none is None and float(only_loss) == pytest.approx(float(want_loss), rel=1e-6)


def test_the_two_copies_of_the_reference_are_one_text():
    with open(os.path.join(BENCH, "configs", "laguna_xs2_reference.py")) as f:
        bench = f.read()
    with open(ref.__file__) as f:
        assert f.read() == bench


def masked_dense(q, k, v, window):
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1), v
    )


@pytest.mark.parametrize("kernel_backward", [False, True])
@pytest.mark.parametrize("heads,window", [(48, 0), (64, 24), (64, 512)])
def test_window_kernel_matches_masked_dense_attention(
    rng, monkeypatch, heads, window, kernel_backward
):
    """The Pallas forward (interpret mode) and both backwards, for the
    48-over-8 and 64-over-8 groupings, K and V never repeated; a window
    of 24 over blocks of 16 skips whole blocks, one of 512 over 96
    positions masks nothing."""
    import keystone_tpu.ops.flash_attention as fa

    monkeypatch.setattr(fa, "_BLOCK_Q", 16)
    monkeypatch.setattr(fa, "_BLOCK_K", 16)
    if kernel_backward:
        monkeypatch.setattr(fa, "_DENSE_BWD_MAX_BYTES", 0)
        monkeypatch.setattr(fa, "_bwd_blocks", lambda *a: (16, 16, 6))
    q = jnp.asarray(rng.normal(size=(1, heads, 96, 8)).astype(np.float32))
    k, v, ct = (
        jnp.asarray(rng.normal(size=(1, h, 96, 8)).astype(np.float32))
        for h in (8, 8, heads)
    )
    want, vjp = jax.vjp(lambda q, k, v: masked_dense(q, k, v, window), q, k, v)
    got, got_vjp = jax.vjp(
        lambda q, k, v: fa.flash_attention_trainable(q, k, v, True, window), q, k, v
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    for a, b in zip(got_vjp(ct), vjp(ct)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_the_streamed_kernel_and_dense_attention_take_the_window_too(rng):
    import keystone_tpu.ops.flash_attention as fa
    from keystone_tpu.ops.attention import dense_attention

    q = jnp.asarray(rng.normal(size=(2, 12, 64, 8)).astype(np.float32))
    k, v = (jnp.asarray(rng.normal(size=(2, 2, 64, 8)).astype(np.float32)) for _ in "kv")
    want = masked_dense(q, k, v, 24)
    streamed = fa.flash_attention(q, k, v, causal=True, window=24, block_q=16,
                                  block_k=16, kv_resident=False)
    np.testing.assert_allclose(np.asarray(streamed), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(dense_attention(q, k, v, causal=True, window=24)),
        np.asarray(want), atol=2e-5,
    )
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(q, k, v, window=24)
    with pytest.raises(ValueError, match="K/V heads"):
        fa.flash_attention(q, k[:, :1].repeat(5, axis=1), v, causal=True)


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_rotary_tables_agree_with_the_reference(toy, published, kind):
    """YaRN on half of each head (full layers) and plain rotary on the
    whole head (window layers), at the toy's and the published sizes."""
    for cfg in (toy, published):
        r = cfg["rope_parameters"][kind]
        yarn = None
        if r["rope_type"] == "yarn":
            yarn = (r["factor"], r["original_max_position_embeddings"],
                    r["beta_fast"], r["beta_slow"], r["attention_factor"])
        spec = RopeSpec(float(r["rope_theta"]), float(r["partial_rotary_factor"]), yarn)
        hd = cfg["head_dim"]
        x = jnp.asarray(np.random.default_rng(1).normal(size=(40, 3, hd)), jnp.float32)
        cos, sin = ref.rotary_table(cfg, kind, 40)
        want = ref.rotate(x, cos, sin)
        got = _rope(jnp.moveaxis(x, 0, 1), jnp.arange(40), spec)
        np.testing.assert_allclose(
            np.asarray(jnp.moveaxis(got, 0, 1)), np.asarray(want), atol=1e-5
        )
    if kind == "full_attention":
        inv, factor = spec.inv_freq(128)
        assert inv.shape == (32,) and factor == pytest.approx(1.41589, rel=1e-5)
        # the fastest pair keeps its frequency, the slowest is divided by 64
        assert inv[0] == pytest.approx(1.0)
        assert inv[-1] == pytest.approx(500000.0 ** (-62 / 64) / 64, rel=1e-5)


def test_the_shares_add_up_to_the_uncut_layer(toy, model, rng):
    """Expert layer 1 cut four ways: the routed parts the four shares
    give, with the shared expert counted once, equal the uncut
    reference's layer (all 8 experts held)."""
    whole_cfg = {**toy, "num_experts": 8, "deployment": {"expert_shard": 0}}
    keys = jax.random.split(jax.random.key(11), 3)
    d, ff = toy["hidden_size"], toy["moe_intermediate_size"]
    e1, e3 = (jax.random.normal(k, (8, d, ff)) / np.sqrt(d) for k in keys[:2])
    e2 = jax.random.normal(keys[2], (8, ff, d)) / np.sqrt(ff)
    held = model.blocks[1].moe
    y = jnp.asarray(rng.normal(size=(64, d)).astype(np.float32))
    uncut = ref.experts(
        whole_cfg,
        {"router": held.w_router, "e1": e1, "e3": e3, "e2": e2,
         "s1": held.shared_w1, "s3": held.shared_w3, "s2": held.shared_w2},
        y,
    )
    shared = ref.swiglu(y, held.shared_w1, held.shared_w3, held.shared_w2)
    total = shared
    for shard in range(4):
        lo = 2 * shard
        share = dataclasses.replace(
            held, w1=e1[lo : lo + 2], w3=e3[lo : lo + 2], w2=e2[lo : lo + 2],
            first_expert=lo,
        )
        part, _ = share(y[None])
        total = total + (part[0] - shared)  # the shared expert once
        # and each share is the reference's share
        want = ref.experts(
            {**toy, "deployment": {"expert_shard": shard}},
            {"router": held.w_router, "e1": e1[lo : lo + 2], "e3": e3[lo : lo + 2],
             "e2": e2[lo : lo + 2], "s1": held.shared_w1, "s3": held.shared_w3,
             "s2": held.shared_w2},
            y,
        )
        np.testing.assert_allclose(np.asarray(part[0]), np.asarray(want), atol=3e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=5e-5)


def test_decode_refuses_the_model_by_name(model):
    with pytest.raises(NotImplementedError, match="layer 1 attends through a window of 8"):
        lm.prefill(model, jnp.zeros((1, 8), jnp.int32), 16)
    with pytest.raises(NotImplementedError, match="cannot serve"):
        lm.quantize_for_decode(model)
    from keystone_tpu.serve.decode_loop import DecodeLoop

    with pytest.raises(NotImplementedError, match="window"):
        DecodeLoop(model, slots=2)
    # the toy presets are served as before
    plain = lm.TransformerLM.create(jax.random.key(0), vocab=31, max_seq=16,
                                    dim=32, depth=1, num_heads=2)
    assert plain.uniform_decode_reason() is None


def test_the_published_sizes_are_the_catalogs_count():
    """691.6 M parameters held here, by shapes alone (nothing allocated)."""
    cfg = lm.load_architecture("laguna_xs2")
    shapes = jax.eval_shape(
        lambda k: lm.TransformerLM.from_config(k, cfg), jax.random.key(0)
    )
    assert shapes.num_params() == 691_623_936
    blocks = shapes.blocks
    assert [b.wq.shape[1] // 128 for b in blocks] == [48, 64, 64, 64, 48]
    assert blocks[0].w1.shape == (2048, 8192) and blocks[0].moe is None
    assert blocks[1].moe.w_router.shape == (2048, 256)
    assert blocks[1].moe.w1.shape == (32, 2048, 512)
    assert blocks[1].moe.shared_w1.shape == (2048, 512)
    assert shapes.embed.shape == (12544, 2048) and shapes.head.shape == (2048, 12544)
    # the benchmark's file describes the same architecture
    with open(os.path.join(BENCH, "configs", "laguna_xs2.json")) as f:
        bench = json.load(f)
    for key, value in cfg.items():
        if key not in ("source", "assumed"):
            assert bench[key] == value, key
    assert bench["about"]["source"] == cfg["source"]


def _fit_conf(tmp_path, toy, **kw):
    path = tmp_path / "toy.json"
    path.write_text(json.dumps(toy))
    return lm.LMConfig(config=str(path), steps=2, batch=2, seq=64, seed=5,
                       logit_chunk=16, remat=True, **kw)


def test_a_second_fit_records_no_jit_span(tmp_path, toy):
    """The train step is one module-level program: the second fit of a
    process asks jax for nothing (neither do model and optimizer
    state), and returns the first fit's losses."""
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
    assert {"fit.init", "fit.load", "fit.h2d", "fit.solve", "fit.counters"} <= set(names)
    root = next(r for r in recs if r["name"] == "fit")
    assert (root["steps"], root["tokens_per_step"]) == (2, 128)
    assert root["chips"] == len(jax.devices())
    counters = next(r for r in recs if r["name"] == "fit.counters")
    # four expert layers, 128 tokens x 2 choices, a quarter of the experts
    assert 0 < counters["routed_rows"] < 2 * 4 * 256
    assert counters["mm_rows"] >= counters["routed_rows"]
    assert counters["load_max_over_mean"] >= 1.0


def test_bfloat16_compute_runs_and_stays_near_float32(tmp_path, toy):
    _m, f32, _v, _s = lm.fit(_fit_conf(tmp_path, toy))
    m, bf16, _v, _s = lm.fit(_fit_conf(tmp_path, toy, compute_dtype="bfloat16"))
    assert {str(l.dtype) for l in jax.tree.leaves(m)} == {"float32"}
    assert bf16 == pytest.approx(f32, rel=2e-2)


# ----------------------------------------------------------- the benchmark

@pytest.fixture(scope="module")
def adapter():
    sys.path[:0] = [BENCH]
    from harness import find

    cfg, mod = find.config("laguna_xs2")
    run = find.load_module("run.py")
    cell = find.cell("laguna_xs2.train_8k")
    return mod, lambda rehearse: run.sizes_of(cfg, cell, mod, rehearse)


def test_operations_against_hand_worked_numbers(adapter):
    mod, sizes_of = adapter
    sizes = sizes_of(False)
    assert (sizes["steps"], sizes["batch"], sizes["seq"]) == (8, 2, 8192)
    assert sizes["train_rows"] == 131072
    work = mod.ops_and_bytes(sizes)
    # parameters a token multiplies: layer 0 79.79 M; window layers 37.88
    # + 3.15 + 0.52 + one expert's 3.146 (8 chosen x 32/256 held); layer 4
    # 29.46 + 6.82; the head 25.69: 276.1 M, six FLOPs each a token
    touched = (79.79e6 + 3 * (37.88e6 + 3.146e6 + 0.524e6 + 3.146e6)
               + 29.46e6 + 6.816e6 + 25.69e6)
    # score and value products, forward: 4 x heads x 128 x pairs seen
    full = 2 * 4 * 48 * 128 * (8192 * 8193 // 2) * 2
    window = 3 * 4 * 64 * 128 * (512 * 513 // 2 + (8192 - 512) * 512) * 2
    want = 6 * touched * 16384 + 3 * (full + window)
    assert work["train_flops_per_step"] == pytest.approx(want, rel=2e-3)
    assert work["train_flops_per_step"] == pytest.approx(39.4e12, rel=5e-3)
    assert work["attn_window_flops_per_step"] == pytest.approx(3 * window)
    assert work["moe_flops_per_row"] == 2 * 3 * 2048 * 512
    assert work["moe_weight_bytes_per_layer"] == 2 * 32 * 3 * 2048 * 512
    assert (work["moe_layers"], work["steps"], work["moe_passes"]) == (4, 8, 4.0)
    # the kernel runs the forward twice a layer under remat
    assert work["attn_window_kernel_flops_per_step"] == pytest.approx(2 * window)


def test_the_check_passes_the_program(adapter):
    """The gate itself, at toy size: the program agrees with the
    reference, which drew the same windows itself and finds the stated
    init; a fit of the window that returned other losses is refused."""
    mod, sizes_of = adapter
    toy = sizes_of(True)
    got, want = mod.program_readings(7, toy), mod.reference_readings(7, toy)
    ok, detail = mod.compare(got, want, toy, [])
    assert ok, detail["mismatches"]
    assert detail["loss0_rel"] < 1e-5 and detail["grad_norms_rel_max"] < 1e-4
    assert detail["quiet_embedding_rows"] > 20 and detail["quiet_decay_rel"] < 0.05
    assert detail["windows_differ"] == 0 and detail["init_z_max"] < 5.0
    assert all(w.shape == (2, 65) and 0 <= w.min() and w.max() < 256
               for w in want["windows"])
    ok, again = mod.compare(got, want, toy, [{"losses": [detail["losses"][0], 0.0]}])
    assert not ok and "differs" in again["mismatches"][0][1]


# plant -> the limits that refuse it at toy size (float32 compute, so
# the rounding-sized limits read far under their chip readings)
PLANTS = {
    "bfloat16_state": {"quiet_decay_rel", "loss1_rel"},
    "half_batch": {"windows_differ", "grad_norms_rel_max"},
    "no_update": {"quiet_decay_rel", "loss1_rel"},
    "no_window": {"grad_norms_rel_max"},
    "init_scale": {"init_z_max"},
    "ids_outside_slice": {"windows_differ"},
}


@pytest.mark.parametrize("plant", sorted(PLANTS))
def test_the_check_refuses_a_planted_fault(adapter, plant):
    """The controls the builder runs on the chip
    (``benchmarks/configs/_laguna_xs2_controls.py``), at toy size: each
    fault comes out not correct, by the limits that are there for it."""
    from harness import find

    mod, sizes_of = adapter
    controls = find.load_module("configs", "_laguna_xs2_controls.py")
    assert set(controls.plants(mod)) == set(PLANTS) | {"sound"}
    line = controls.run_plant(mod, plant, 7, sizes_of(True))
    assert not line["correct"]
    assert PLANTS[plant] <= set(line["refused_by"]), line
    if plant in ("bfloat16_state", "no_update"):
        # a state that did not move
        assert line["quiet_decay_rel"] == pytest.approx(1.0, abs=0.05)
