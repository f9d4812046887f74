"""granite-4.0-h-micro through the one block definition, at a small size
on the CPU (three layers, an attention layer between two state-space ones, 8 state-space heads of 16 with state 16 and chunks of 16,
sequence 64, seeded random weights): the scan
and its kernel against the recurrence, the program against the plain
reference, the train step made once per process, and the benchmark's
adapter with its check and its planted faults."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.models import lm_transformer as lm
from keystone_tpu.models.lm import granite_4_0_h_micro_reference as ref
from keystone_tpu.models.lm.losses import next_token_loss
from keystone_tpu.observe import spans
from keystone_tpu.ops import ssm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = "granite_4_0_h_micro"


@pytest.fixture(scope="module")
def published():
    return lm.load_architecture(NAME)


@pytest.fixture(scope="module")
def toy(published):
    """The benchmark's own toy sizes laid over the published config."""
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        over = json.load(f)["toy"]
    return {**published, **{k: v for k, v in over.items() if k in published}}


@pytest.fixture(scope="module")
def model(toy):
    return lm.TransformerLM.from_config(jax.random.key(3), toy)


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


# ------------------------------------------------------------- the operators

def scan_inputs(rng, s, groups=1, n=2, h=16, p=8, state=16):
    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    return (
        normal(n, s, h, p),
        jnp.asarray(rng.uniform(0.01, 0.5, size=(n, s, h)), jnp.float32),
        -jnp.asarray(rng.uniform(1, 16, size=(h,)), jnp.float32),
        normal(n, s, groups, state),
        normal(n, s, groups, state),
    )


def by_recurrence(x, dt, a, b, c):
    skip = jnp.zeros_like(a)
    return jax.vmap(lambda x, dt, b, c: ref.recurrence(x, dt, a, b, c, skip))(x, dt, b, c)


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize(
    "s,chunk,groups,h,p",
    [
        (40, 16, 1, 16, 8), (32, 32, 1, 16, 8), (48, 16, 2, 16, 8),
        # the kernel's lane-sliced heads: head 64, two to a lane tile, in two
        # head blocks at a length that is no multiple of the chunk; head 128
        # in two groups; two blocks of sixteen heads to a tile; twelve heads,
        # one block of 96 lanes
        (40, 16, 1, 32, 64), (32, 16, 2, 8, 128), (32, 16, 1, 32, 8), (24, 8, 1, 12, 8),
    ],
)
def test_the_chunked_scan_is_the_recurrence(rng, monkeypatch, s, chunk, groups, h, p, kernel):
    """The ``jax.numpy`` chunked form and the ``ssd_chunk`` kernel in
    interpret mode against the scan position by position, forward and
    every gradient: at a length that is no multiple of the chunk, with
    one chunk the whole length, with two groups of B and C, and with
    heads of 64 and 128 whose block is and is not whole lane tiles."""
    monkeypatch.setattr(ssm, "_use_kernel", lambda *shape: kernel)
    args = scan_inputs(rng, s, groups, h=h, p=p)
    ct = jnp.asarray(rng.normal(size=args[0].shape), jnp.float32)
    want, vjp = jax.vjp(by_recurrence, *args)
    got, got_vjp = jax.vjp(lambda *a: ssm.ssd_scan(*a, chunk=chunk), *args)
    # 3e-5, or 4e-6 of the largest output where 64 x more outputs reach past 8
    atol = max(3e-5, 4e-6 * float(jnp.abs(want).max()))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol)
    for a, b in zip(got_vjp(ct), vjp(ct)):
        assert a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5 * float(jnp.abs(b).max()) + 1e-5
        )


@pytest.mark.parametrize("h,p", [(16, 8), (16, 64)])
def test_the_kernel_returns_the_states_the_backward_starts_from(rng, h, p):
    """``ssd_chunk`` takes x, dt, ``dt A``, B and C positions-major, as
    the mixer holds them, and returns y there and the state entering
    every chunk, as the ``jax.numpy`` path does."""
    x, dt, a, b, c = scan_inputs(rng, 64, n=1, h=h, p=p)
    la = dt * a
    y, states = ssm.ssd_chunk(x, dt, la, b[:, :, 0], c[:, :, 0], 16)
    want_y, want_states = ssm._forward_jnp(x, dt, la, b[:, :, 0], c[:, :, 0], 16)
    assert y.shape == x.shape and y.dtype == x.dtype
    assert states.shape == (1, 4, h, p, 16) and not np.asarray(states[:, 0]).any()
    np.testing.assert_allclose(np.asarray(states), np.asarray(want_states), atol=2e-5)
    # 2e-5, or the running sums' own rounding (1e-5 of a log decay past 100)
    atol = max(2e-5, 1e-5 * float(jnp.abs(want_y).max()))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=atol)


def test_the_kernels_running_sum_is_float32s(rng, published):
    """The running log decay the kernel makes for itself, a chunk of 256
    positions of 64 heads drawn where granite's ``A_log`` and ``dt_bias``
    start: ``jnp.cumsum`` in float32 within 1e-6 relative (the decays
    feed ``exp``; a bfloat16 pass would be off by 4e-3)."""
    from jax.experimental import pallas as pl

    heads, n_l = published["mamba_n_heads"], 256
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size=(n_l, heads)))
    la = jnp.asarray(-dt * rng.uniform(1.0, 16.0, size=(heads,)), jnp.float32)

    def kernel(la_ref, out_ref):
        out_ref[...] = ssm._running_sum(la_ref[...])

    got = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(la.shape, jnp.float32), interpret=True
    )(la)
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.cumsum(la, axis=0)), rtol=1e-6)
    exact = np.cumsum(np.asarray(la, np.float64), axis=0)
    np.testing.assert_allclose(np.asarray(got, np.float64), exact, rtol=1e-6)


def test_the_conv_is_four_shifted_products(rng):
    x = jnp.asarray(rng.normal(size=(2, 19, 12)), jnp.float32)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, size=(12, 4)), jnp.float32)
    b = jnp.asarray(rng.uniform(-0.5, 0.5, size=(12,)), jnp.float32)
    got = ssm.causal_conv(x, w, b)
    want = jnp.stack([ref.conv(row, w, b) for row in x])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    # and XLA's own depthwise convolution, padded on the left alone
    xla = jax.lax.conv_general_dilated(
        x, w.T[:, None, :], (1,), [(3, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=12,
    ) + b
    np.testing.assert_allclose(np.asarray(got), np.asarray(xla), atol=1e-5)
    # position 0 sees itself alone
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(x[:, 0] * w[:, 3] + b), atol=1e-6)


# ------------------------------------------------------------- the model

def reference_params(m):
    sys.path[:0] = [BENCH]
    from harness import find

    return find.config(NAME)[1]._reference_params(m)


def test_the_toy_keeps_the_period(toy, model):
    assert [b.ssm is not None for b in model.blocks] == [True, False, True]
    mixer = model.blocks[0].ssm
    assert (mixer.heads, mixer.head_dim, mixer.state, mixer.groups, mixer.chunk) == (
        8, 16, 16, 1, 16)
    assert mixer.w_in.shape == (64, 2 * 128 + 2 * 16 + 8)
    assert mixer.conv_w.shape == (128 + 32, 4) and mixer.conv_b.shape == (160,)
    assert model.blocks[0].wq.shape == (64, 0) and model.blocks[0].wo.shape == (0, 64)
    spec = model.layer_spec(model.blocks[1])
    assert (spec.num_heads, spec.num_kv_heads, spec.rope, spec.scale) == (4, 2, None, 0.015625)
    assert model.head is None and model.final_norm.shape == (64,)
    assert (model.embed_multiplier, model.residual_multiplier, model.logits_scale) == (
        12.0, 0.22, 0.125)
    assert model.pos_encoding == "nope" and model.pos_embed.size == 0


def test_logits_match_the_reference(toy, model, tokens):
    want = jax.jit(lambda p, t: ref.logits(toy, p, t))(
        reference_params(model), tokens[:, :-1])
    got = jax.jit(lambda m, t: m(t))(model, tokens[:, :-1])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    assert float(jnp.abs(want).max()) > 0.05


def test_loss_and_every_gradient_match_the_reference(toy, model, tokens):
    want_loss, want = jax.jit(lambda p, t: ref.loss_and_grads(toy, p, t))(
        reference_params(model), tokens)
    got_loss, got = jax.jit(jax.value_and_grad(next_token_loss))(model, tokens)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    got = reference_params(got)
    paths = jax.tree_util.tree_leaves_with_path(want)
    # the table, the final norm, two mixers of 8 + 5 leaves, one attention layer of 4 + 5
    assert len(paths) == len(jax.tree.leaves(got)) == 2 + 2 * 13 + 9
    for (path, b), a in zip(paths, jax.tree.leaves(got)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5 * float(jnp.abs(b).max()) + 1e-8,
            err_msg=str(path),
        )


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
    got_loss, got = ref.loss_and_grads_blocked(toy, params, tokens)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)
    # the recurrence checkpointed every 16 positions is the recurrence
    x, dt, a, b, c = scan_inputs(np.random.default_rng(1), 64, n=1)
    x, dt, b, c = x[0], dt[0], b[0], c[0]
    plain = ref.recurrence(x, dt, a, b, c, jnp.ones_like(a))
    np.testing.assert_allclose(
        np.asarray(ref.recurrence(x, dt, a, b, c, jnp.ones_like(a), 16)),
        np.asarray(plain), atol=1e-6,
    )


def test_the_two_copies_of_the_reference_are_one_text():
    with open(os.path.join(BENCH, "configs", NAME + "_reference.py")) as f:
        bench = f.read()
    with open(ref.__file__) as f:
        assert f.read() == bench


def test_the_sizes_are_the_issues_counts(published):
    """772 160 448 parameters at the cut and 3 191 396 096 uncut, by
    shapes alone (nothing allocated)."""
    def count(cfg):
        return jax.eval_shape(
            lambda k: lm.TransformerLM.from_config(k, cfg), jax.random.key(0)
        )

    cut = count(published)
    assert cut.num_params() == 772_160_448
    whole = {**published, **published["published"]}
    assert count(whole).num_params() == 3_191_396_096
    mixer = cut.blocks[0].ssm
    assert mixer.w_in.shape == (2048, 8512) and mixer.w_out.shape == (4096, 2048)
    assert mixer.conv_w.shape == (4352, 4) and mixer.A_log.shape == (64,)
    assert sum(int(np.prod(l.shape)) for l in jax.tree.leaves(mixer)) == 25_847_232
    assert cut.blocks[5].wq.shape == (2048, 2048) and cut.blocks[5].wk.shape == (2048, 512)
    assert cut.blocks[0].w1.shape == (2048, 8192) and cut.embed.shape == (12544, 2048)
    # the benchmark's file describes the same architecture, and keeps
    # every published key of the catalog's row but the two it cuts
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        bench = json.load(f)
    for key, value in published.items():
        if key not in ("source", "assumed"):
            assert bench[key] == value, key
    assert bench["about"]["source"] == published["source"]
    assert bench["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert bench["published"] == {"num_hidden_layers": 40, "vocab_size": 100352}
    assert len(bench["layer_types"]) == 40 and bench["layer_types"].count("attention") == 4


def test_flops_decode_and_sharding_know_a_state_space_layer(model):
    from keystone_tpu.models.lm.sharding import shard_params
    from keystone_tpu.parallel.mesh import create_mesh

    # a block without wq counts its parameters and no score products
    flops = lm.train_step_flops(model, 2, 64)
    # every leaf but the final norm; the tied table once, as the head
    params = model.num_params() - 64
    attn = 12 * 64 * (65 / 2) * 128  # the one attention layer, 4 heads of 16
    assert flops == pytest.approx(6.0 * params * 128 + attn)
    with pytest.raises(NotImplementedError, match="layer 0 is a state-space layer"):
        lm.prefill(model, jnp.zeros((1, 8), jnp.int32), 16)
    reason = model.uniform_decode_reason()
    assert "recurrent state" in reason and "learned final norm" in reason
    assert "layer 1 scales its scores" in reason
    # under `model` the mixer's leaves stay whole, the FFN is split
    mesh = create_mesh(data=4, model=2)
    laid = shard_params(model, mesh)
    whole = jax.sharding.PartitionSpec()
    for leaf in jax.tree.leaves(laid.blocks[0].ssm):
        assert leaf.sharding.is_fully_replicated, leaf.sharding
    assert laid.blocks[0].w1.sharding.spec == jax.sharding.PartitionSpec(None, "model")
    assert whole == jax.sharding.PartitionSpec()


def test_the_tied_head_lies_behind_the_learned_final_norm(model, tokens, monkeypatch):
    from keystone_tpu.models.lm.model import output_logits

    x, counters = model.backbone(tokens[:, :-1])
    assert int(counters["ssm_rows"]) == 2 * 2 * 64 and int(counters["ssm_chunks"]) == 2 * 2 * 4
    # off the TPU no position is scanned by the kernel; where the kernel
    # runs (here in interpret mode) every one is
    assert int(counters["ssm_kernel_rows"]) == 0
    with monkeypatch.context() as patched:
        patched.setattr(ssm, "_use_kernel", lambda *shape: True)
        in_kernel, counters = model.backbone(tokens[:, :-1])
    assert int(counters["ssm_kernel_rows"]) == int(counters["ssm_rows"]) == 2 * 2 * 64
    np.testing.assert_allclose(np.asarray(in_kernel), np.asarray(x), atol=1e-5)
    doubled = dataclasses.replace(model, final_norm=2.0 * model.final_norm)
    np.testing.assert_allclose(
        np.asarray(output_logits(doubled, x, jnp.float32)),
        2.0 * np.asarray(output_logits(model, x, jnp.float32)), rtol=1e-5, atol=1e-7,
    )
    want = ref.rms(x, model.final_norm, 1e-5) @ model.embed.T / 8
    np.testing.assert_allclose(
        np.asarray(output_logits(model, x, jnp.float32)), np.asarray(want), atol=1e-6
    )


# ------------------------------------------------------------- the fit

def _fit_conf(tmp_path, toy, **kw):
    path = tmp_path / "toy.json"
    path.write_text(json.dumps(toy))
    return lm.LMConfig(config=str(path), steps=2, batch=2, seq=64, seed=5,
                       logit_chunk=16, remat=True, **kw)


def test_a_second_fit_records_no_jit_span(tmp_path, toy):
    """The train step is one module-level program: the second fit of a
    process asks jax for nothing, returns the first fit's losses, and
    says what it scanned."""
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
    assert (root["steps"], root["tokens_per_step"], root["ssm_layers"]) == (2, 128, 2)
    counters = next(r for r in recs if r["name"] == "fit.counters")
    # two state-space layers x 128 positions x 2 steps, in chunks of 16
    assert (counters["ssm_rows"], counters["ssm_chunks"]) == (2 * 2 * 128, 2 * 2 * 8)
    assert counters["ssm_kernel_rows"] == 0  # the CPU runs the jax.numpy scan
    assert (counters["routed_rows"], counters["mm_rows"]) == (0, 0)


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


# ------------------------------------------------------------- the benchmark

def test_operations_against_hand_worked_numbers(adapter):
    mod, sizes_of = adapter
    sizes = sizes_of(False)
    assert (sizes["steps"], sizes["batch"], sizes["seq"]) == (8, 1, 8192)
    assert sizes["train_rows"] == 65536
    work = mod.ops_and_bytes(sizes)
    # every parameter but the norms and the per-head vectors multiplies a
    # token: 772.16 M less 9 x (192 + 4096 + 4352) + 10 x 4096 + 2048
    touched = 772_160_448 - 9 * (192 + 4096 + 4352) - 10 * 2 * 2048 - 2048
    attn = 4 * 32 * 64 * (8192 * 8193 // 2)
    # a position a layer: scores 2 x 128 x 128, apply 2 x 128 x 64 x 64,
    # state 4 x 128 x 64 x 64
    scan = 2 * 128 * 128 + 2 * 128 * 64 * 64 + 4 * 128 * 64 * 64
    assert work["ssm_scan_flops_per_row"] == scan == 3_178_496
    want = 6 * touched * 8192 + 3 * attn + 3 * scan * 73728
    assert work["train_flops_per_step"] == pytest.approx(want, rel=1e-6)
    assert work["train_flops_per_step"] == pytest.approx(39.5e12, rel=2e-3)
    assert work["attn_full_flops_per_step"] == pytest.approx(3 * attn)
    # x and y of 4096 in bfloat16, dt of 64 in float32, B and C of 128
    assert work["ssm_scan_bytes_per_row"] == 2 * (2 * 4096 + 2 * 128) + 4 * 64
    assert (work["ssm_rows_per_step"], work["ssm_scan_runs"], work["steps"]) == (73728, 2, 8)


@pytest.fixture(scope="module")
def sound(adapter):
    mod, sizes_of = adapter
    toy = sizes_of(True)
    return mod.program_readings(7, toy), mod.reference_readings(7, toy)


def test_the_check_passes_the_program(adapter, sound):
    """The gate itself, at toy size: the program agrees with the
    reference, which drew the same windows itself and finds the stated
    init; a fit of the window that returned other losses is refused."""
    mod, sizes_of = adapter
    got, want = sound
    ok, detail = mod.compare(got, want, sizes_of(True), [])
    assert ok, detail["mismatches"]
    assert detail["loss0_rel"] < 1e-5 and detail["grad_norms_rel_max"] < 1e-4
    assert detail["grad_norms_per_head_rel_max"] < 1e-4
    assert detail["grad_norms_per_head_worst"].rsplit(".", 1)[-1] in ("A_log", "dt_bias", "D")
    assert detail["grad_norms_worst"].rsplit(".", 1)[-1] not in ("A_log", "dt_bias", "D")
    assert len(detail["grad_norms_rel"]) == 1 + 2 * 9 + 2
    assert {"layer0.ssm.A_log", "layer0.ssm.dt_bias", "layer0.ssm.D", "layer0.ssm.conv_w",
            "layer0.ssm.conv_b", "layer0.ssm.norm", "layer0.ssm.in", "layer0.ssm.out",
            "layer1.attention", "layer1.ffn", "embed"} <= set(detail["grad_norms_rel"])
    assert detail["first_move_rel"] < 0.02 and detail["ssm_rows_per_step"] == 2 * 128
    assert detail["windows_differ"] == 0 and detail["init_z_max"] < 5.0
    assert all(w.shape == (2, 65) and 0 <= w.min() and w.max() < 256
               for w in want["windows"])
    ok, again = mod.compare(got, want, sizes_of(True), [{"losses": [detail["losses"][0], 0.0]}])
    assert not ok and "differs" in again["mismatches"][0][1]


# plant -> the limits that refuse it at toy size (float32 compute, so
# the rounding-sized limits read far under their chip readings)
PLANTS = {
    "state_dropped": {"grad_norms_rel_max", "grad_norms_per_head_rel_max"},
    "no_conv": {"grad_norms_rel_max", "grad_norms_per_head_rel_max"},
    "no_softplus": {"grad_norms_rel_max", "grad_norms_per_head_rel_max"},
    "gate_after_norm": {"grad_norms_rel_max", "grad_norms_per_head_rel_max"},
    "attention_scale_eighth": {"grad_norms_rel_max"},
    "residual_one": {"grad_norms_rel_max"},
    "bfloat16_state": {"first_move_rel"},
    "no_update": {"first_move_rel"},
    "init_scale": {"init_z_max"},
}


@pytest.mark.parametrize("plant", sorted(PLANTS))
def test_the_check_refuses_a_planted_fault(adapter, sound, plant):
    """The controls the builder runs on the chip
    (``benchmarks/configs/_granite_4_0_h_micro_controls.py``), at toy
    size: each fault comes out not correct, by the limits that are there
    for it."""
    from harness import find

    mod, sizes_of = adapter
    controls = find.load_module("configs", "_granite_4_0_h_micro_controls.py")
    assert set(controls.plants(mod)) == set(PLANTS) | {"sound"}
    line = controls.run_plant(mod, plant, 7, sizes_of(True), sound[1])
    assert not line["correct"]
    assert PLANTS[plant] <= set(line["refused_by"]), line
    if plant == "no_update":
        # a state that did not move
        assert line["first_move_rel"] == pytest.approx(1.0, abs=5e-3)
