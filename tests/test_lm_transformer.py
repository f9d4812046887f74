"""Transformer LM training: loss decreases, sharded-step parity, and the
sequence-parallel attention modes plug into the same model."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.models import lm_transformer as lm


def _tiny(seq_mode="local", mesh=None, dim=64, depth=2, vocab=31, heads=4):
    return lm.TransformerLM.create(
        jax.random.key(0),
        vocab=vocab,
        max_seq=64,
        dim=dim,
        depth=depth,
        num_heads=heads,
        seq_mode=seq_mode,
        mesh=mesh,
    )


def test_loss_decreases_on_markov_corpus():
    model = _tiny()
    corpus = lm.synthetic_corpus(20_000, 31, seed=1)
    model, losses = lm.train(
        model, corpus, steps=60, batch=8, seq=32, lr=2e-3, seed=1
    )
    assert np.mean(losses[-5:]) < 0.6 * losses[0], (losses[0], losses[-5:])


def test_forward_shapes_and_causality():
    model = _tiny()
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, 31, size=(2, 24))
    )
    logits = model(toks)
    assert logits.shape == (2, 24, 31)
    # causality: changing a future token must not change past logits
    toks2 = toks.at[:, 20].set((toks[:, 20] + 1) % 31)
    logits2 = model(toks2)
    np.testing.assert_allclose(
        np.asarray(logits[:, :20]), np.asarray(logits2[:, :20]), atol=1e-5
    )


def test_tp_sharded_step_matches_single_device(mesh4x2):
    """dp×tp sharded training step computes the same update as unsharded.

    The train step donates its input buffers and device_put may alias the
    source buffer for same-device shards, so the two runs each build their
    own (same-seed, identical) model."""
    model = _tiny(dim=64, depth=2)
    sharded = lm.shard_params(_tiny(dim=64, depth=2), mesh4x2)
    corpus = lm.synthetic_corpus(5_000, 31, seed=2)
    m1, l1 = lm.train(model, corpus, steps=3, batch=8, seq=32, seed=3)
    m2, l2 = lm.train(
        sharded, corpus, steps=3, batch=8, seq=32, seed=3, mesh=mesh4x2
    )
    np.testing.assert_allclose(l1, l2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(m1.blocks[0].wq),
        np.asarray(m2.blocks[0].wq),
        rtol=2e-4,
        atol=2e-4,
    )


@pytest.mark.parametrize("seq_mode", ["ring", "ulysses"])
def test_sequence_parallel_forward_parity(mesh8, seq_mode):
    """ring/Ulysses causal attention inside the LM matches local attention."""
    # Ulysses reshards heads over the axis: needs heads % axis == 0
    local = _tiny(dim=64, depth=2, heads=8)
    sp = dataclasses.replace(local, seq_mode=seq_mode, mesh=mesh8)
    toks = jnp.asarray(
        np.random.default_rng(4).integers(0, 31, size=(2, 64))
    )
    np.testing.assert_allclose(
        np.asarray(local(toks)), np.asarray(sp(toks)), rtol=2e-4, atol=2e-4
    )


def test_bf16_compute_policy():
    """bfloat16 compute: f32 params/logits, forward ≈ f32 forward, and a
    train step keeps params f32 while the loss still decreases."""
    f32 = _tiny()
    bf16 = dataclasses.replace(f32, compute_dtype="bfloat16")
    toks = jnp.asarray(
        np.random.default_rng(7).integers(0, 31, size=(4, 32))
    )
    lo32, lo16 = f32(toks), bf16(toks)
    assert lo16.dtype == jnp.float32  # loss-facing logits stay f32
    # bf16 has ~3 decimal digits; activations are O(1) post-LN
    np.testing.assert_allclose(
        np.asarray(lo32), np.asarray(lo16), rtol=0.12, atol=0.12
    )
    corpus = lm.synthetic_corpus(20_000, 31, seed=1)
    model, losses = lm.train(
        bf16, corpus, steps=60, batch=8, seq=32, lr=2e-3, seed=1
    )
    assert model.blocks[0].wq.dtype == jnp.float32
    assert np.mean(losses[-5:]) < 0.6 * losses[0], (losses[0], losses[-5:])


def test_kv_cache_decode_matches_full_forward_logits():
    """Teacher-forced decode: driving decode_step along a fixed token
    sequence yields the same per-position logits as one full forward.
    Comparing logits (not chained argmax) keeps the test robust to
    last-ulp reduction-order differences between the two attention paths."""
    model = _tiny()
    rng = np.random.default_rng(9)
    toks = jnp.asarray(rng.integers(0, 31, size=(3, 22)))
    prompt, rest = toks[:, :12], toks[:, 12:]
    full = model(toks)  # (3, 22, 31)
    logits, cache = lm.prefill(model, prompt, 22)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full[:, 11]), atol=1e-4
    )
    for j in range(rest.shape[1] - 1):
        logits, cache = lm.decode_step(model, rest[:, j], cache)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full[:, 12 + j]), atol=1e-4
        )
    # greedy generate: shape, dtype, determinism
    out = lm.generate(model, prompt, max_new=10)
    out2 = lm.generate(model, prompt, max_new=10)
    assert out.shape == (3, 10) and out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_generate_sampled_and_bounds():
    model = _tiny()
    prompt = jnp.asarray(np.random.default_rng(3).integers(0, 31, size=(2, 8)))
    toks = lm.generate(
        model, prompt, max_new=6, temperature=1.0, key=jax.random.key(5)
    )
    assert toks.shape == (2, 6)
    assert np.all((np.asarray(toks) >= 0) & (np.asarray(toks) < 31))
    with pytest.raises(ValueError):
        lm.generate(model, prompt, max_new=1000)


def test_remat_gradients_match():
    """jax.checkpoint per block changes memory, not math: grads with
    remat off / full remat / dots-saveable policy all agree (the dots
    policy keeps matmul outputs so the MXU never re-runs)."""
    base = _tiny()
    toks = jnp.asarray(np.random.default_rng(11).integers(0, 31, size=(4, 32)))
    for cdt in ("float32", "bfloat16"):
        m = dataclasses.replace(base, compute_dtype=cdt)
        g_plain = jax.grad(lm.next_token_loss)(m, toks)
        for policy in ("full", "dots"):
            g_remat = jax.grad(lm.next_token_loss)(
                dataclasses.replace(m, remat=True, remat_policy=policy),
                toks,
            )
            for a, b in zip(
                jax.tree_util.tree_leaves(g_plain),
                jax.tree_util.tree_leaves(g_remat),
            ):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
                )
    with pytest.raises(ValueError):
        lm.next_token_loss(
            dataclasses.replace(base, remat=True, remat_policy="nope"),
            toks,
        )


def test_cli_main_tiny():
    res = lm.main(
        [
            "--steps", "4", "--batch", "2", "--seq", "32", "--dim", "32",
            "--depth", "1", "--num-heads", "2", "--vocab", "17",
        ]
    )
    assert res["params"] > 0 and np.isfinite(res["loss_last"])


def test_checkpoint_resume_exact_trajectory(tmp_path):
    """A preempted run resumed from its checkpoint must land on the same
    weights as an uninterrupted run — batches are derived from (seed, i),
    so the resumed trajectory replays identically (the LM analog of
    resumable_fit's warm-start-exactness test)."""
    corpus = lm.synthetic_corpus(5_000, 31, seed=3)
    kw = dict(steps=6, batch=4, seq=16, lr=1e-3, seed=3)

    ref_model, ref_losses = lm.train(_tiny(), corpus, **kw)

    ckdir = str(tmp_path / "lm_ck")
    # "preempted" after 3 steps...
    lm.train(_tiny(), corpus, **{**kw, "steps": 3},
             checkpoint_dir=ckdir)
    # ...rerun to completion (restores step 3: the fresh model/opt passed
    # in are discarded in favor of the checkpoint)
    res_model, res_losses = lm.train(
        _tiny(), corpus, **kw, checkpoint_dir=ckdir
    )
    assert len(res_losses) == 3  # only steps 3..6 ran here
    for a, b in zip(
        jax.tree_util.tree_leaves(ref_model),
        jax.tree_util.tree_leaves(res_model),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6
        )
    np.testing.assert_allclose(ref_losses[3:], res_losses, atol=1e-5)


def test_checkpoint_rejects_mismatched_run(tmp_path):
    corpus = lm.synthetic_corpus(3_000, 31, seed=4)
    ckdir = str(tmp_path / "lm_ck2")
    kw = dict(steps=2, batch=4, seq=16, seed=4)
    lm.train(_tiny(), corpus, lr=1e-3, **kw, checkpoint_dir=ckdir)
    # different lr = different run identity -> loud failure
    with pytest.raises(ValueError, match="different training run"):
        lm.train(_tiny(), corpus, lr=5e-4, **kw, checkpoint_dir=ckdir)
    # over-trained guard: asking for fewer steps than are checkpointed
    with pytest.raises(ValueError, match="over-trained"):
        lm.train(
            _tiny(), corpus, lr=1e-3, **{**kw, "steps": 1},
            checkpoint_dir=ckdir,
        )


def test_rope_trains_decodes_and_extends():
    """RoPE positions: loss decreases, KV-cache decode matches the full
    forward, and generation runs past any learned-table bound (the model
    has no pos_embed params at all)."""
    model = lm.TransformerLM.create(
        jax.random.key(0), vocab=31, max_seq=16, dim=32, depth=2,
        num_heads=2, pos_encoding="rope",
    )
    assert model.pos_embed.shape[0] == 0
    corpus = lm.synthetic_corpus(20_000, 31, seed=1)
    model, losses = lm.train(
        model, corpus, steps=40, batch=8, seq=32, lr=2e-3, seed=1
    )
    assert np.mean(losses[-5:]) < 0.75 * losses[0]

    # greedy decode == argmax of the full forward, step by step
    prompt = jnp.asarray([[1, 2, 3, 4]])
    toks = lm.generate(model, prompt, max_new=6)
    seq = np.asarray(prompt)[0].tolist()
    for t in range(6):
        logits = model(jnp.asarray([seq]))
        nxt = int(jnp.argmax(logits[0, -1]))
        assert nxt == int(toks[0, t]), (t, nxt, int(toks[0, t]))
        seq.append(nxt)
    # max_seq=16 would bound a learned model; rope ran to 10 tokens of
    # context and could go further — also check the learned guard still
    # fires for comparison
    learned = lm.TransformerLM.create(
        jax.random.key(0), vocab=31, max_seq=8, dim=32, depth=2,
        num_heads=2,
    )
    with pytest.raises(ValueError, match="exceeds max_seq"):
        lm.generate(learned, prompt, max_new=8)


@pytest.mark.parametrize("seq_mode", ["ring", "ulysses"])
def test_sequence_parallel_training_decreases_loss(mesh8, seq_mode):
    """Training THROUGH the sequence-parallel attention (custom-VJP ring
    backward / flash-trainable Ulysses) — not just the forward."""
    model = lm.TransformerLM.create(
        jax.random.key(0), vocab=31, max_seq=64, dim=32, depth=2,
        num_heads=8, seq_mode=seq_mode, mesh=mesh8,
    )
    corpus = lm.synthetic_corpus(20_000, 31, seed=2)
    model, losses = lm.train(
        model, corpus, steps=30, batch=4, seq=64, lr=2e-3, seed=2,
        mesh=mesh8,
    )
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < 0.8 * losses[0], (losses[0], losses[-5:])


def test_topk_topp_sampling():
    model = _tiny()
    prompt = jnp.asarray([[1, 2, 3]])
    greedy = lm.generate(model, prompt, max_new=8)
    # top_k=1 at any temperature IS greedy
    k1 = lm.generate(
        model, prompt, max_new=8, temperature=1.0, top_k=1,
        key=jax.random.key(9),
    )
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(greedy))
    # tiny nucleus keeps only the argmax token
    p_small = lm.generate(
        model, prompt, max_new=8, temperature=1.0, top_p=1e-6,
        key=jax.random.key(9),
    )
    np.testing.assert_array_equal(np.asarray(p_small), np.asarray(greedy))
    # permissive settings still emit valid tokens
    free = lm.generate(
        model, prompt, max_new=8, temperature=1.2, top_k=10, top_p=0.9,
        key=jax.random.key(3),
    )
    arr = np.asarray(free)
    assert arr.shape == (1, 8) and arr.min() >= 0 and arr.max() < 31
    # top_k beyond the vocab is a config error, not a silent clamp
    with pytest.raises(ValueError, match="top_k"):
        lm.generate(
            model, prompt, max_new=2, temperature=1.0, top_k=1000,
            key=jax.random.key(1),
        )


def test_pp_forward_matches_local():
    """GPipe block chain == the plain forward, logits-exact (modulo f32
    reduction order)."""
    from keystone_tpu.parallel.mesh import create_mesh

    mesh = create_mesh(data=2, model=4)
    model = lm.TransformerLM.create(
        jax.random.key(0), vocab=31, max_seq=32, dim=32, depth=4,
        num_heads=2,
    )
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, 31, size=(8, 32), dtype=np.int32)
    )
    ref = model(toks)
    out = lm.pp_forward(model, toks, mesh, n_micro=4)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-4
    )


def test_pp_train_step_matches_local_grads():
    """One pipeline-parallel train step lands on the same loss and
    updated params as the plain step (AD-derived reverse schedule)."""
    import optax

    from keystone_tpu.parallel.mesh import create_mesh

    mesh = create_mesh(data=2, model=4)

    def fresh():
        # both steps donate their model buffers — each needs its own copy
        return lm.TransformerLM.create(
            jax.random.key(1), vocab=31, max_seq=32, dim=32, depth=4,
            num_heads=2,
        )

    toks = jnp.asarray(
        np.random.default_rng(1).integers(0, 31, size=(8, 33), dtype=np.int32)
    )
    optimizer = optax.adamw(1e-3)

    ref_step = lm.make_train_step(optimizer)
    model = fresh()
    m_ref, _, loss_ref = ref_step(model, optimizer.init(model), toks)

    pp_step = lm.make_pp_train_step(optimizer, mesh, n_micro=4)
    model = fresh()
    m_pp, _, loss_pp = pp_step(model, optimizer.init(model), toks)

    np.testing.assert_allclose(float(loss_pp), float(loss_ref), atol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(m_pp), jax.tree_util.tree_leaves(m_ref)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4
        )


def test_pp_rejects_moe_and_ragged_depth():
    from keystone_tpu.parallel.mesh import create_mesh

    mesh = create_mesh(data=2, model=4)
    toks = jnp.zeros((4, 8), jnp.int32)
    moe_model = lm.TransformerLM.create(
        jax.random.key(0), vocab=31, max_seq=16, dim=32, depth=4,
        num_heads=2, moe_every=2, num_experts=4,
    )
    with pytest.raises(ValueError, match="dense blocks only"):
        lm.pp_forward(moe_model, toks, mesh, n_micro=2)
    shallow = lm.TransformerLM.create(
        jax.random.key(0), vocab=31, max_seq=16, dim=32, depth=3,
        num_heads=2,
    )
    with pytest.raises(ValueError, match="not divisible"):
        lm.pp_forward(shallow, toks, mesh, n_micro=2)
    ring = lm.TransformerLM.create(
        jax.random.key(0), vocab=31, max_seq=16, dim=32, depth=4,
        num_heads=2, seq_mode="ring", mesh=mesh,
    )
    with pytest.raises(ValueError, match="seq_mode"):
        lm.pp_forward(ring, toks, mesh, n_micro=2)


def test_pp_batch_equal_to_n_micro():
    """B == n_micro (microbatch size 1) must work — regression for the
    gpipe reshape-heuristic ambiguity."""
    from keystone_tpu.parallel.mesh import create_mesh

    mesh = create_mesh(data=2, model=4)
    model = lm.TransformerLM.create(
        jax.random.key(0), vocab=31, max_seq=16, dim=32, depth=4,
        num_heads=2,
    )
    toks = jnp.asarray(
        np.random.default_rng(2).integers(0, 31, size=(4, 16), dtype=np.int32)
    )
    out = lm.pp_forward(model, toks, mesh, n_micro=4)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(model(toks)), atol=2e-4
    )


def test_sp_tp_composed_on_one_mesh(mesh4x2):
    """Ring sequence parallelism over `data` with Megatron-style TP over
    `model`, one mesh, one train step — the matrix composes, not just its
    rows in isolation."""
    import optax

    def fresh(seq_mode, mesh):
        return lm.TransformerLM.create(
            jax.random.key(0), vocab=31, max_seq=64, dim=32, depth=2,
            num_heads=8, seq_mode=seq_mode,
            mesh=mesh, seq_axis="data",
        )

    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, 31, size=(2, 64), dtype=np.int32)
    )
    # forward parity vs the plain local model (same weights)
    comp = lm.shard_params(fresh("ring", mesh4x2), mesh4x2)
    ref = fresh("local", None)
    np.testing.assert_allclose(
        np.asarray(comp(toks)), np.asarray(ref(toks)), atol=2e-4
    )
    # and a full composed train step stays finite and learns
    optimizer = optax.adamw(1e-3)
    step = lm.make_train_step(optimizer)
    toks1 = jnp.asarray(
        np.random.default_rng(1).integers(0, 31, size=(2, 65), dtype=np.int32)
    )
    comp, _, loss = step(comp, optimizer.init(comp), toks1)
    assert np.isfinite(float(loss))


def test_pp_dp_composed_shards_batch(mesh4x2):
    """dp x pp: microbatches sharded over `data`, stages over `model` —
    same loss/params as the replicated pipeline and the local step."""
    import optax

    def fresh():
        return lm.TransformerLM.create(
            jax.random.key(1), vocab=31, max_seq=32, dim=32, depth=2,
            num_heads=2,
        )

    toks = jnp.asarray(
        np.random.default_rng(1).integers(0, 31, size=(8, 33), dtype=np.int32)
    )
    optimizer = optax.adamw(1e-3)

    ref_step = lm.make_train_step(optimizer)
    model = fresh()
    m_ref, _, loss_ref = ref_step(model, optimizer.init(model), toks)

    dp_pp = lm.make_pp_train_step(
        optimizer, mesh4x2, n_micro=2, data_axis="data"
    )
    model = fresh()
    m_pp, _, loss_pp = dp_pp(model, optimizer.init(model), toks)

    np.testing.assert_allclose(float(loss_pp), float(loss_ref), atol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(m_pp), jax.tree_util.tree_leaves(m_ref)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_chunked_loss_matches_dense():
    """logit_chunk computes the same loss and gradients without ever
    materializing the (B, S, V) logits; non-divisible chunks rejected."""
    m = _tiny()
    toks = jnp.asarray(
        np.random.default_rng(5).integers(0, 31, size=(4, 33), dtype=np.int32)
    )
    want, gw = jax.value_and_grad(lm.next_token_loss)(m, toks)
    for chunk in (8, 16, 32):
        got, gg = jax.value_and_grad(
            lambda mm_, t: lm.next_token_loss(mm_, t, logit_chunk=chunk)
        )(m, toks)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
        for a, b in zip(
            jax.tree_util.tree_leaves(gg), jax.tree_util.tree_leaves(gw)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )
    with pytest.raises(ValueError, match="positive divisor"):
        lm.next_token_loss(m, toks, logit_chunk=7)
    with pytest.raises(ValueError, match="positive divisor"):
        lm.next_token_loss(m, toks, logit_chunk=-8)
    # and through the jitted train step factory
    import optax

    opt = optax.adamw(1e-3)
    ma, mb = _tiny(), _tiny()  # donated buffers: one fresh model each
    m1, _, l1 = lm.make_train_step(opt)(ma, opt.init(ma), toks)
    m2, _, l2 = lm.make_train_step(opt, logit_chunk=16)(mb, opt.init(mb), toks)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    for a, b in zip(
        jax.tree_util.tree_leaves(m1), jax.tree_util.tree_leaves(m2)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_chunked_loss_composes_with_moe_and_ring_sp(mesh8):
    """logit_chunk must preserve the MoE aux term (it rides backbone(),
    not the logits) and train through ring sequence parallelism."""
    moe = lm.TransformerLM.create(
        jax.random.key(4), vocab=31, max_seq=32, dim=32, depth=2,
        num_heads=2, moe_every=2, num_experts=4,
    )
    toks = jnp.asarray(
        np.random.default_rng(9).integers(0, 31, size=(4, 33), dtype=np.int32)
    )
    dense_l = lm.next_token_loss(moe, toks)
    chunk_l = lm.next_token_loss(moe, toks, logit_chunk=16)
    np.testing.assert_allclose(float(chunk_l), float(dense_l), rtol=1e-6)

    ring = lm.TransformerLM.create(
        jax.random.key(5), vocab=31, max_seq=64, dim=32, depth=2,
        num_heads=2, seq_mode="ring", mesh=mesh8,
    )
    # seq 64 shards 8 ways; chunk 16 operates on the gathered states
    toks64 = jnp.asarray(
        np.random.default_rng(10).integers(0, 31, size=(2, 65), dtype=np.int32)
    )
    ring_dense = lm.next_token_loss(ring, toks64)
    ring_chunk = lm.next_token_loss(ring, toks64, logit_chunk=16)
    np.testing.assert_allclose(float(ring_chunk), float(ring_dense), rtol=1e-6)


def test_pp_dp_tp_three_axis_composition(devices):
    """pp x dp x tp on a 3-axis mesh: stages manual over `pipe`,
    microbatch batch-dim manual over `data`, and the `model` axis left
    AUTO so the tp weight layout propagates INTO the stage bodies (the
    gpipe partial-manual shard_map). Loss and updated params must match
    the plain local train step."""
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(
        np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
        ("pipe", "data", "model"),
    )

    def fresh():
        return lm.TransformerLM.create(
            jax.random.key(2), vocab=31, max_seq=32, dim=32, depth=2,
            num_heads=2,
        )

    toks = jnp.asarray(
        np.random.default_rng(3).integers(0, 31, size=(8, 33), dtype=np.int32)
    )
    optimizer = optax.adamw(1e-3)

    model = fresh()
    m_ref, _, loss_ref = lm.make_train_step(optimizer)(
        model, optimizer.init(model), toks
    )

    model = lm.shard_params(fresh(), mesh)  # tp over "model"
    assert model.blocks[0].wq.sharding.spec == P(None, "model")
    step = lm.make_pp_train_step(
        optimizer, mesh, n_micro=2, axis="pipe", data_axis="data"
    )
    toks_sh = jax.device_put(toks, NamedSharding(mesh, P("data", None)))
    m_pp, _, loss_pp = step(model, optimizer.init(model), toks_sh)

    np.testing.assert_allclose(float(loss_pp), float(loss_ref), atol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(m_pp), jax.tree_util.tree_leaves(m_ref)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_cosine_schedule_and_grad_clip(tmp_path):
    """Warmup-cosine + clipping trains (and the optimizer factory rejects
    bad configs loudly)."""
    corpus = lm.synthetic_corpus(20_000, 31, seed=1)
    model, losses = lm.train(
        _tiny(), corpus, steps=40, batch=8, seq=32, lr=3e-3, seed=1,
        schedule="cosine", grad_clip=1.0,
    )
    assert np.mean(losses[-5:]) < 0.8 * losses[0]
    with pytest.raises(ValueError, match="constant|cosine"):
        lm.make_optimizer(1e-3, schedule="linear")
    with pytest.raises(ValueError, match="total steps"):
        lm.make_optimizer(1e-3, schedule="cosine")
    # resume identity: schedule/grad_clip are part of the run meta
    d = str(tmp_path / "sched_ck")
    lm.train(_tiny(), corpus, steps=2, batch=4, seq=16, seed=1,
             schedule="cosine", checkpoint_dir=d)
    with pytest.raises(ValueError, match="different training run"):
        lm.train(_tiny(), corpus, steps=4, batch=4, seq=16, seed=1,
                 schedule="constant", checkpoint_dir=d)


def test_gqa_trains_and_decode_matches_forward():
    """Grouped-query attention: kv cache carries num_kv_heads heads, the
    grouped decode path matches the (broadcast) training forward, and
    training still converges. MQA (kv=1) included."""
    for kvh in (2, 1):
        model = lm.TransformerLM.create(
            jax.random.key(0), vocab=31, max_seq=64, dim=32, depth=2,
            num_heads=4, num_kv_heads=kvh,
        )
        assert model.blocks[0].wk.shape == (32, kvh * 8)
        corpus = lm.synthetic_corpus(20_000, 31, seed=1)
        model, losses = lm.train(
            model, corpus, steps=40, batch=8, seq=32, lr=2e-3, seed=1
        )
        assert np.mean(losses[-5:]) < 0.8 * losses[0], (kvh, losses[:3])

        rng = np.random.default_rng(6)
        toks = jnp.asarray(rng.integers(0, 31, size=(2, 18)))
        prompt, rest = toks[:, :9], toks[:, 9:]
        full = model(toks)
        logits, cache = lm.prefill(model, prompt, 18)
        # cache holds kv heads, not query heads
        assert cache.k.shape[2] == kvh, cache.k.shape
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full[:, 8]), atol=1e-4
        )
        for j in range(rest.shape[1] - 1):
            logits, cache = lm.decode_step(model, rest[:, j], cache)
            np.testing.assert_allclose(
                np.asarray(logits), np.asarray(full[:, 9 + j]),
                atol=1e-4, err_msg=f"kvh={kvh} step {j}",
            )
    # invalid grouping fails loudly
    with pytest.raises(ValueError, match="not divisible"):
        lm.TransformerLM.create(
            jax.random.key(0), vocab=31, dim=32, num_heads=4,
            num_kv_heads=3,
        )


def test_gqa_composes_with_int8_kv():
    model = lm.TransformerLM.create(
        jax.random.key(2), vocab=31, max_seq=32, dim=32, depth=2,
        num_heads=4, num_kv_heads=2,
    )
    prompt = jnp.asarray([[1, 2, 3]])
    g_f = np.asarray(lm.generate(model, prompt, max_new=8))
    g_q = np.asarray(lm.generate(model, prompt, max_new=8,
                                 kv_dtype="int8"))
    assert g_f.shape == g_q.shape == (1, 8)
    assert (g_f == g_q).mean() >= 0.75


def test_pp_composes_with_bf16_rope_remat():
    """Pipeline parallelism under the bf16 policy + rope + remat — the
    configuration a real long-context pp run would use."""
    from keystone_tpu.parallel.mesh import create_mesh

    mesh = create_mesh(data=2, model=4)
    model = lm.TransformerLM.create(
        jax.random.key(3), vocab=31, max_seq=32, dim=32, depth=4,
        num_heads=2, compute_dtype="bfloat16", pos_encoding="rope",
    )
    model = dataclasses.replace(model, remat=True)
    toks = jnp.asarray(
        np.random.default_rng(3).integers(0, 31, size=(8, 32), dtype=np.int32)
    )
    out = lm.pp_forward(model, toks, mesh, n_micro=4, data_axis="data")
    ref = model(toks)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=5e-2
    )  # bf16 tolerance


def test_gqa_composes_with_ring_sp_training(mesh8):
    """GQA K/V broadcast up to query heads feeds the ring custom-VJP
    path; the composed train step stays finite and learns."""
    import optax

    model = lm.TransformerLM.create(
        jax.random.key(4), vocab=31, max_seq=64, dim=32, depth=2,
        num_heads=8, num_kv_heads=2, seq_mode="ring", mesh=mesh8,
    )
    optimizer = optax.adamw(2e-3)
    step = lm.make_train_step(optimizer)
    state = optimizer.init(model)
    corpus = lm.synthetic_corpus(20_000, 31, seed=4)
    losses = []
    for i in range(10):
        toks = jnp.asarray(lm._step_batch(corpus, 4, i, 4, 64))
        model, state, loss = step(model, state, toks)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_local_attention_path_follows_the_platform(monkeypatch):
    """Local mode takes the Pallas trainable wrapper on a TPU and the
    XLA path off it, and both compute the same attention."""
    import keystone_tpu.ops.flash_attention as fa

    model = _tiny()
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, 31, size=(1, 16))
    )
    calls = []
    real = fa.flash_attention_trainable

    def spy(q, k, v, causal):
        calls.append("flash")
        return real(q, k, v, causal)

    monkeypatch.setattr(fa, "flash_attention_trainable", spy)

    out_dense = model(toks)
    assert not calls, "off the TPU local mode must take the dense path"

    monkeypatch.setattr(fa, "on_tpu", lambda: True)  # interpret mode here
    out_flash = model(toks)
    assert calls == ["flash"] * len(model.blocks)
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_dense), atol=2e-4
    )


def test_local_flash_is_shard_mapped_under_a_mesh(monkeypatch, mesh4x2):
    """On a multi-chip TPU GSPMD refuses to partition the Mosaic flash
    kernel ("cannot be automatically partitioned" — the four-chip run,
    PR 21), so a local-mode model that carries a mesh shard_maps the
    kernel over it: batch over ``data``, heads over ``model``. Same
    logits as the unsharded model, and a batch the data axis does not
    divide still runs (whole on every device)."""
    from keystone_tpu.parallel.mesh import data_sharding

    import keystone_tpu.ops.flash_attention as fa

    monkeypatch.setattr(fa, "on_tpu", lambda: True)  # interpret mode here
    kw = dict(vocab=31, max_seq=16, dim=16, depth=1, num_heads=2)
    plain = lm.TransformerLM.create(jax.random.key(0), **kw)
    meshed = lm.shard_params(
        lm.TransformerLM.create(jax.random.key(0), mesh=mesh4x2, **kw),
        mesh4x2,
    )
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 31, size=(4, 16)))
    want = np.asarray(plain(toks))
    got = jax.jit(lambda m, t: m(t))(
        meshed, jax.device_put(toks, data_sharding(mesh4x2, ndim=2))
    )
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(meshed(toks[:3])), want[:3], atol=2e-5
    )
