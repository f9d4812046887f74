"""Training for the transformer LM: the jitted dp/tp step, the GPipe
pipeline-parallel step, the checkpointed loop, and corpora.

One buffer-donated XLA program per step is the design rule (the idiom the
framework's solvers use: one launch per step, no host round-trips), with
preemption-safe orbax checkpointing whose resumed trajectory is exactly
the uninterrupted one — batches derive from ``(seed, step)``, never from
sequential RNG state.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import numpy as np
import optax

from keystone_tpu.core.logging import get_logger
from keystone_tpu.models.lm.losses import (
    next_token_loss_and_counters,
    token_cross_entropy,
)
from keystone_tpu.models.lm.model import (
    TransformerLM,
    _block_apply,
    _embed,
    has_quantized_leaves,
    mtp_depth,
    output_logits,
)

logger = get_logger("keystone_tpu.models.lm_transformer")


def pp_forward(model: TransformerLM, tokens, mesh, *, n_micro: int,
               axis: str = "model", data_axis: str | None = None):
    """Pipeline-parallel forward: the block chain runs as GPipe stages
    over the mesh ``axis`` (one group of ``depth/n_stages`` blocks per
    device, microbatches streamed via ppermute —
    :func:`keystone_tpu.parallel.pipeline_parallel.gpipe`), embedding and
    tied logits replicated outside the pipe. Completes the LM's
    parallelism matrix (dp × tp × sp × ep × pp). Dense blocks only (MoE
    routing wants the expert axis, not the stage axis); parameters stay
    replicated in HBM — pp here parallelizes compute, the memory story
    is remat + the other axes.
    """
    import jax.numpy as jnp

    if any(b.moe is not None for b in model.blocks):
        raise ValueError(
            "pipeline-parallel path supports dense blocks only"
        )
    if model.seq_mode != "local":
        raise ValueError(
            "pipeline-parallel path requires seq_mode='local': the "
            f"{model.seq_mode!r} attention opens its own shard_map, which "
            "cannot nest inside the pipeline's"
        )
    n_stages = mesh.shape[axis]
    depth = len(model.blocks)
    if depth % n_stages:
        raise ValueError(
            f"depth {depth} not divisible by {n_stages} pipeline stages"
        )
    b = tokens.shape[0]
    if b % n_micro:
        raise ValueError(
            f"batch {b} not divisible by n_micro={n_micro}"
        )
    per = depth // n_stages
    cdt = jnp.dtype(model.compute_dtype)
    x = _embed(model, tokens, cdt)
    # pre-split microbatches HERE: gpipe's n_micro reshape heuristic is
    # ambiguous when B == n_micro (it would mistake (B, S, d) for an
    # already-microbatched (n_micro, S, d))
    x = x.reshape(n_micro, b // n_micro, *x.shape[1:])

    # stack the per-block pytrees: leading axis depth → (stages, per)
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *model.blocks
    )
    stacked = jax.tree_util.tree_map(
        lambda l: l.reshape(n_stages, per, *l.shape[1:]), stacked
    )

    def stage_fn(stage_params, act):
        for j in range(per):
            blk = jax.tree_util.tree_map(lambda l: l[j], stage_params)
            act = _block_apply(
                act, blk, cdt,
                lambda y, bb: (model._attention(y, bb), None),
            )[0]
        return act

    if model.remat:
        from keystone_tpu.models.lm.model import remat_wrap

        stage_fn = remat_wrap(stage_fn, model.remat_policy)
    from keystone_tpu.parallel.pipeline_parallel import gpipe

    out = gpipe(stage_fn, stacked, x, mesh, axis=axis, data_axis=data_axis)
    out = out.reshape(b, *out.shape[2:])
    return output_logits(model, out, cdt)


def next_token_loss_pp(model: TransformerLM, tokens, mesh, *,
                       n_micro: int, axis: str = "model",
                       data_axis: str | None = None):
    """Next-token CE through the GPipe forward (differentiable: scan,
    ppermute, and psum all have transposes — the backward is the reverse
    pipeline schedule, derived by AD rather than hand-scheduled)."""
    logits = pp_forward(
        model, tokens[:, :-1], mesh, n_micro=n_micro, axis=axis,
        data_axis=data_axis,
    )
    return token_cross_entropy(logits, tokens[:, 1:])


def make_pp_train_step(optimizer, mesh, *, n_micro: int,
                       axis: str = "model",
                       data_axis: str | None = None):
    """Buffer-donated jitted pipeline-parallel train step. ``data_axis``
    composes dp × pp: each data-row of devices pipelines its own batch
    slice (grad psums across rows come from XLA's sharding propagation —
    params are replicated over the data axis)."""

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(model, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda m, t: next_token_loss_pp(
                m, t, mesh, n_micro=n_micro, axis=axis,
                data_axis=data_axis,
            )
        )(model, tokens)
        updates, opt_state = optimizer.update(
            grads, opt_state, params=model
        )
        model = optax.apply_updates(model, updates)
        return model, opt_state, loss

    return step


class StepStats(NamedTuple):
    """What a step says of itself beside the loss, on the device: the
    squared norm of every parameter's gradient (the parameters' own
    tree) and the expert layers' counters (``ops/moe.py::COUNTERS``)."""

    grad_sq: object
    counters: dict


@functools.partial(
    jax.jit,
    static_argnames=("optimizer", "logit_chunk", "skip_nonfinite"),
    donate_argnums=(0, 1),
)
def _train_step(model, opt_state, tokens, poison, *, optimizer, logit_chunk,
                skip_nonfinite):
    """The one train-step program: grads + optimizer update + loss. A
    module-level jit, so jax's own cache answers every ``train()`` call
    after a process's first with the same optimizer (an
    :class:`OptimizerSpec`, hashed by its fields), shapes and model
    description. ``poison`` is None for the plain step; the guarded step
    (a different program) takes a scalar bool that NaNs the loss *and*
    the grads, and with ``skip_nonfinite`` applies the update only where
    the loss is finite."""
    import jax.numpy as jnp

    def lossfn(m, t):
        loss, counters = next_token_loss_and_counters(
            m, t, logit_chunk=logit_chunk
        )
        if poison is not None:
            # poison scales rather than adds so the backward pass NaNs
            # too — an injected bad batch corrupts exactly what a real
            # one would
            loss = loss * jnp.where(
                poison, jnp.float32(np.nan), jnp.float32(1.0)
            )
        return loss, counters

    (loss, counters), grads = jax.value_and_grad(lossfn, has_aux=True)(
        model, tokens
    )
    updates, new_opt = optimizer.tx.update(grads, opt_state, params=model)
    new_model = optax.apply_updates(model, updates)
    if poison is not None and skip_nonfinite:
        from keystone_tpu.resilience.guards import guarded_update

        ok = jnp.isfinite(loss)
        new_model = guarded_update(ok, new_model, model)
        new_opt = guarded_update(ok, new_opt, opt_state)
    grad_sq = jax.tree_util.tree_map(
        lambda g: jnp.sum(jnp.square(g.astype(jnp.float32))), grads
    )
    return new_model, new_opt, loss, StepStats(grad_sq, counters)


def make_train_step(
    optimizer, *, logit_chunk: int = 0, guarded: bool = False,
    skip_nonfinite: bool = True,
):
    """One buffer-donated jitted program: grads + AdamW update + loss.
    ``logit_chunk`` chunks the CE so the (B, S, V) f32 logits never
    materialize (the long-context memory/bandwidth lever — see
    :func:`keystone_tpu.models.lm.losses.chunked_token_cross_entropy`).

    ``guarded=True`` returns the poison-aware variant
    ``step(model, opt_state, tokens, poison)``: ``poison`` (scalar
    bool) NaNs the loss *and* grads for deterministic fault injection —
    multiplicative, so the unpoisoned path is bit-identical to itself
    across runs. With ``skip_nonfinite=True`` (a guard mode is on) the
    update is additionally applied only where the loss is finite (a
    leafwise ``where`` select — with buffer donation the pre-update
    state is unrecoverable on the host, so skip-batch MUST be decided
    in-program); with it False an injected NaN corrupts exactly what a
    real bad batch would. Still one XLA launch per step.

    The program itself is :func:`_train_step`, made once per process:
    an ``optimizer`` from :func:`make_optimizer` compares equal to
    another of the same settings, so this returns a thin binding and
    compiles nothing new on a later call."""
    bound = _bind_step(optimizer, logit_chunk, skip_nonfinite)
    if guarded:
        return lambda model, opt_state, tokens, poison: bound(
            model, opt_state, tokens, poison
        )[:3]
    return lambda model, opt_state, tokens: bound(
        model, opt_state, tokens, None
    )[:3]


def _bind_step(optimizer, logit_chunk: int, skip_nonfinite: bool):
    """:func:`_train_step` with its static arguments bound:
    ``step(model, opt_state, tokens, poison) -> (model, opt_state, loss,
    StepStats)``."""
    if not isinstance(optimizer, OptimizerSpec):
        # a bare optax transformation: hashed by identity, so the
        # program is this object's alone
        optimizer = OptimizerSpec(optimizer)
    return functools.partial(
        _train_step, optimizer=optimizer, logit_chunk=logit_chunk,
        skip_nonfinite=skip_nonfinite,
    )


def _step_batch(corpus, seed: int, i: int, batch: int, seq: int):
    """Step ``i``'s token windows of ``seq + 1`` ids, derived from
    ``(seed, i)`` alone — no sequential RNG state, so a resumed run
    regenerates the exact batch sequence an uninterrupted run would have
    seen. A model whose loss reads further ahead asks for a longer
    ``seq``."""
    rng = np.random.default_rng(np.random.SeedSequence((seed, i)))
    starts = rng.integers(0, len(corpus) - seq - 1, size=batch)
    return np.stack([corpus[s : s + seq + 1] for s in starts])


class OptimizerSpec:
    """The LM optimizer: an optax transformation and, when
    :func:`make_optimizer` made it, the settings it was made from. Two
    specs of the same settings are equal and hash alike, so they key the
    same compiled step; a spec around a caller's own transformation is
    equal to itself alone. ``init`` / ``update`` are the
    transformation's, so a spec goes wherever one went."""

    def __init__(self, tx, settings: tuple | None = None):
        self.tx = tx
        self.settings = settings

    def _key(self):
        return ("id", id(self.tx)) if self.settings is None else self.settings

    def __eq__(self, other):
        return isinstance(other, OptimizerSpec) and self._key() == other._key()

    def __hash__(self):
        return hash(self._key())

    def init(self, params):
        return self.tx.init(params)

    def update(self, grads, state, params=None):
        return self.tx.update(grads, state, params=params)


def make_optimizer(
    lr: float,
    *,
    steps: int = 0,
    schedule: str = "constant",
    warmup_frac: float = 0.05,
    grad_clip: float = 0.0,
    weight_decay: float = 0.01,
) -> OptimizerSpec:
    """The LM training optimizer: AdamW, optionally behind global-norm
    gradient clipping, with a constant or warmup-cosine learning rate.
    ``schedule="cosine"`` warms up over ``warmup_frac`` of ``steps`` and
    decays to lr/10 — the standard LM recipe. A constant schedule does
    not depend on ``steps``, so it is left out of the spec there."""
    if schedule not in ("constant", "cosine"):
        raise ValueError(
            f"schedule={schedule!r}; expected constant|cosine"
        )
    if schedule == "cosine":
        if steps <= 0:
            raise ValueError("schedule='cosine' needs the total steps")
        rate = optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=lr,
            warmup_steps=max(1, int(steps * warmup_frac)),
            decay_steps=steps,
            end_value=lr / 10.0,
        )
    else:
        rate, steps = lr, 0
    opt = optax.adamw(rate, weight_decay=weight_decay)
    if grad_clip > 0.0:
        opt = optax.chain(optax.clip_by_global_norm(grad_clip), opt)
    return OptimizerSpec(
        opt, (float(lr), steps, schedule, warmup_frac, grad_clip, weight_decay)
    )


def train(
    model: TransformerLM,
    corpus: np.ndarray,
    *,
    steps: int,
    batch: int,
    seq: int,
    lr: float = 3e-4,
    mesh=None,
    seed: int = 0,
    log_every: int = 0,
    checkpoint_dir: str = "",
    checkpoint_every: int = 0,
    schedule: str = "constant",
    grad_clip: float = 0.0,
    logit_chunk: int = 0,
    guard=None,
    step_timeout_s: float = 0.0,
    history: dict | None = None,
):
    """Train on random windows of ``corpus`` (1-D int array; one
    synthetic or real stream, no packing of documents). Returns
    (model, losses). A caller's ``history`` dict receives what the steps
    said of themselves, read from the device once at the end:
    ``grad_sq`` (step → the parameters' tree of squared gradient norms)
    and ``counters`` (step → the expert layers' counters), and
    ``windows`` (step → the token windows it trained on).

    While spans are on (``observe/spans.py``: an event sink or a
    profiler session) the call is one ``fit`` root span, or the
    caller's if one is open, with the fit path's names under it:
    ``fit.init`` (optimizer state), ``fit.solve`` around the step loop,
    and under each ``train.step`` the step's ``fit.load`` (its windows)
    and ``fit.h2d``; a recorded step waits for its loss. Batches are dp-sharded over the mesh ``data`` axis
    unless the model is sequence-parallel (then S is the sharded axis and
    the batch is replicated).

    ``checkpoint_dir`` makes the run preemption-safe: model + optimizer
    state are orbax-checkpointed every ``checkpoint_every`` steps (default
    0 = ``steps // 10``, ~10 checkpoints per run), and a rerun with the
    same arguments resumes from the last completed step on the *identical*
    trajectory — batches are derived per-step from ``(seed, i)``, not from
    sequential RNG state (the LM analog of the solvers' ``resumable_fit``).
    ``losses`` covers only the steps this invocation ran. Note:
    ``schedule="cosine"`` derives its decay horizon from THIS invocation's
    ``steps`` — resuming with a longer schedule is allowed (steps are not
    run identity) but stretches the cosine rather than replaying the
    original horizon. ``logit_chunk`` chunks the CE — equivalent to the
    dense loss up to FP reduction order, which is exactly why it IS part
    of the run identity (a resume must not silently change the low bits
    of the trajectory).

    Resilience (see :mod:`keystone_tpu.resilience`):

    - ``guard`` — a ``GuardConfig``, a mode string (``"skip"``/
      ``"halt"``), or None (→ the ``KEYSTONE_GUARD`` env default).
      ``skip`` leaves model+optimizer untouched on a non-finite-loss
      step (decided in-program — donation-safe); ``halt`` additionally
      stops at the next interval check and returns the last
      checkpointed state. Guard state syncs the loss window once per
      ``check_every`` steps, never per step.
    - with ``checkpoint_dir`` set, SIGTERM/SIGINT checkpoint the last
      completed step and return early, and every exit path attempts a
      final checkpoint in ``finally`` — a clean break (signal,
      preemption, a host-side exception between steps) loses at most
      the in-flight step. A hard device failure can poison the live
      buffers mid-step; the rescue save then fails (logged, never
      masking the original error) and the run falls back to the last
      periodic checkpoint.
    - ``step_timeout_s`` (or ``KEYSTONE_STEP_TIMEOUT_S``) arms a
      watchdog that logs thread stacks when a step stops completing;
      ``KEYSTONE_STEP_ESCALATE=N`` additionally hard-aborts the process
      after N consecutive stalls so a supervisor can replace it.
    - on a multihost run with an active cluster monitor
      (:mod:`keystone_tpu.resilience.cluster`), every completed step is
      reported to the heartbeat thread, checkpoint saves are
      coordinated behind a membership barrier, and a declared host loss
      exits the loop with :class:`HostLostError` on the last periodic
      checkpoint (the coordinated rescue save is impossible with a dead
      peer) — the run supervisor relaunches on the survivor set.
    - fault sites ``train.nan`` / ``train.preempt`` / ``train.sigterm``
      / ``cluster.host_kill`` (``KEYSTONE_FAULTS``, keyed by step index
      so schedules survive resume) inject each failure
      deterministically.
    """
    import hashlib
    import os as _os
    import signal as _signal
    import sys as _sys
    import threading as _threading
    import time as _time

    import jax.numpy as jnp

    from keystone_tpu.observe import devices as _observe_devices
    from keystone_tpu.observe import spans as _spans
    from keystone_tpu.observe import telemetry as _telemetry
    from keystone_tpu.observe import tracing as _tracing
    from keystone_tpu.parallel.mesh import data_sharding
    from keystone_tpu.resilience import cluster as _cluster
    from keystone_tpu.resilience import faults as _faults
    from keystone_tpu.resilience.retry import RetryExhausted
    from keystone_tpu.resilience.guards import (
        LossGuard,
        NumericalHealthError,
        resolve_guard,
    )

    if len(corpus) < seq + 2:
        raise ValueError(
            f"corpus of {len(corpus)} tokens is too short for seq={seq} "
            f"(needs at least seq+2 = {seq + 2}); shorten --seq or grow "
            "the corpus"
        )
    if has_quantized_leaves(model):
        raise ValueError(
            "model holds int8 QTensor weights (quantize_for_decode is "
            "inference-only) — gradients through the rounding would be "
            "silently zero; train the float model and re-quantize"
        )
    guard_cfg = resolve_guard(guard)
    plan = _faults.active()
    # the guarded step is a DIFFERENT compiled program (poison arg, and
    # the update select only under an actual guard mode — an injected
    # NaN with no guard must corrupt like the real thing); build it
    # only when asked, so the default hot loop is untouched
    skip_nonfinite = guard_cfg.mode != "off"
    guarded = skip_nonfinite or (
        plan is not None and plan.has_site("train.nan")
    )
    optimizer = make_optimizer(
        lr, steps=steps, schedule=schedule, grad_clip=grad_clip
    )
    step = _bind_step(optimizer, logit_chunk, skip_nonfinite)
    losses = []
    stats = []
    seen = []
    sharding = None
    if (
        mesh is not None
        and model.seq_mode == "local"
        and batch % mesh.shape.get("data", 1) == 0
    ):
        sharding = data_sharding(mesh, ndim=2)

    ckpt = None
    start = 0
    try:
        _nprocs = jax.process_count()
    except Exception:  # noqa: BLE001 — backend init failure
        _nprocs = 1
    if checkpoint_dir:
        from keystone_tpu.core.checkpoint import TrainCheckpointer

        # default cadence: ~10 checkpoints per run, not one per step — a
        # jitted LM step is milliseconds while a synchronous full-state
        # orbax save is not (resumable_fit's every=1 default amortizes
        # over whole BCD passes, a much coarser unit)
        every = checkpoint_every or max(steps // 10, 1)
        corpus_head = np.asarray(corpus[:64], np.int64)
        ckpt = TrainCheckpointer(
            checkpoint_dir,
            # `steps` is deliberately absent (resuming with a longer
            # schedule is the point — the over-trained guard below covers
            # the short case), mirroring resumable_fit's num_iter rule.
            # Everything else that shapes the trajectory is here: a
            # param-shape match alone would silently accept a different
            # model function (num_heads, dtype policy, seq_mode...)
            {
                "kind": "lm_transformer",
                "batch": batch,
                "seq": seq,
                "lr": lr,
                "seed": seed,
                "schedule": schedule,
                "grad_clip": grad_clip,
                "logit_chunk": logit_chunk,
                # the guarded step is a different program; like
                # logit_chunk it may move low bits, so it IS run
                # identity. False = plain step, "inject" = poison arg
                # only, "skip" = poison + non-finite update select
                "guarded": (
                    False if not guarded
                    else ("skip" if skip_nonfinite else "inject")
                ),
                "num_heads": model.num_heads,
                # normalized (kv_heads, never the 0 alias) so MHA spelled
                # either way compares equal
                "num_kv_heads": model.kv_heads,
                "seq_mode": model.seq_mode,
                "compute_dtype": model.compute_dtype,
                "pos_encoding": model.pos_encoding,
                "remat": model.remat,
                "remat_policy": model.remat_policy,
                # what each layer is: attention spec and expert layout
                "layers": [_layer_identity(model, b) for b in model.blocks],
                "corpus_len": int(len(corpus)),
                "corpus_head_sha": hashlib.sha256(
                    corpus_head.tobytes()
                ).hexdigest()[:16],
                "param_shapes": [
                    list(map(int, leaf.shape))
                    for leaf in jax.tree_util.tree_leaves(model)
                ],
            },
            # informational, EXCLUDED from the identity check: the
            # host set at save time, so the supervisor / a re-meshed
            # resume can see what the checkpoint was written by
            cluster_info={
                "num_processes": _nprocs,
                "mesh": (
                    {k: int(v) for k, v in mesh.shape.items()}
                    if mesh is not None
                    else None
                ),
            },
            # keys added after checkpoints already existed in the wild:
            # an older sidecar without them must compare as the value the
            # code used at the time, not brick the resume
            legacy_defaults={
                "pos_encoding": "learned",
                "schedule": "constant",
                "grad_clip": 0.0,
                # pre-chunked-CE checkpoints were all dense
                "logit_chunk": 0,
                # pre-resilience checkpoints all ran the plain step
                "guarded": False,
                # pre-policy checkpoints always full-rematerialized
                "remat_policy": "full",
                # pre-GQA checkpoints were all MHA
                "num_kv_heads": model.num_heads,
                # sidecars from before layers were described one by one
                # held dense blocks of the model-wide head counts
                "layers": [_layer_identity(model, b) for b in model.blocks],
            },
        )
    if step_timeout_s <= 0:
        step_timeout_s = float(
            _os.environ.get("KEYSTONE_STEP_TIMEOUT_S", "0") or 0
        )
    loss_guard = LossGuard(guard_cfg)
    # first signal → flag only; the loop checks it each step and the
    # finally path checkpoints, so SIGTERM/SIGINT lose at most the
    # in-flight step. A SECOND signal means the loop isn't getting back
    # to its check (a wedged step): restore the previous dispositions
    # and re-deliver so repeat Ctrl-C / SIGTERM actually escalates.
    stop_signal: dict = {"sig": None}
    prev_handlers: dict = {}
    if ckpt is not None and _threading.current_thread() is _threading.main_thread():
        def _on_signal(signum, frame):
            if stop_signal["sig"] is not None:
                for s, h in prev_handlers.items():
                    _signal.signal(s, h)
                prev = prev_handlers.get(signum)
                if callable(prev):
                    prev(signum, frame)
                else:
                    _signal.raise_signal(signum)
                return
            stop_signal["sig"] = signum

        for s in (_signal.SIGTERM, _signal.SIGINT):
            prev_handlers[s] = _signal.signal(s, _on_signal)

    dog = None
    if step_timeout_s > 0:
        from keystone_tpu.resilience.watchdog import Watchdog

        # created here, STARTED after the first step completes: the
        # first iteration includes jit compilation, which would
        # otherwise guarantee a spurious stall report on every run.
        # KEYSTONE_STEP_ESCALATE=N hard-aborts after N consecutive
        # stalls — a wedged main thread would otherwise heartbeat
        # forever from the cluster monitor's daemon thread
        escalate = int(
            _os.environ.get("KEYSTONE_STEP_ESCALATE", "0") or 0
        )
        dog = Watchdog(
            step_timeout_s,
            label="lm_train",
            escalate_after=escalate if escalate > 0 else None,
        )

    # live telemetry (observe/telemetry.py): per-step loss / tokens-per-s
    # / MFU into steps.jsonl whenever an observe sink is active, HBM
    # watermark sampling, and programmatic profiler windows
    # (KEYSTONE_PROFILE_STEPS / SIGUSR2). With no sink and no windows the
    # per-step cost is one global read (active_step_log) plus one no-op
    # tracer check.
    devmon = _observe_devices.DeviceMemoryMonitor()
    # the self-tuning controller (KEYSTONE_TUNE=1): per-step host-vs-
    # compute walls + token goodput feed its rolling attribution window.
    # tune_active is the cheap gate — no plan import on untuned runs.
    from keystone_tpu.core.staging import tune_active as _tune_active

    tuner = _tune_active()
    tracer = _tracing.StepTracer.from_env(
        install_signal=(
            _threading.current_thread() is _threading.main_thread()
        ),
        label="lm_train",
    )

    completed = last_saved = 0
    halted = False
    cluster_lost = False
    # one trace for the whole training run: every step/checkpoint span
    # shares it, so `observe trace` renders the loop as one causal unit
    import uuid as _uuid

    _train_trace = "train-" + _uuid.uuid4().hex[:8]
    import contextlib as _contextlib

    fit_root = (
        _spans.span(
            "fit",
            parent=None,
            steps=steps,
            tokens_per_step=batch * seq,
            chips=mesh.size if mesh is not None else 1,
            ssm_layers=ssm_layers(model),
            cca_layers=cca_layers(model),
            mtp_depth=mtp_depth(model),
            moe_latent=moe_latent(model),
        )
        if _spans.current() is None
        else _contextlib.nullcontext()
    )
    _stack = _contextlib.ExitStack()
    try:
        _stack.enter_context(fit_root)
        with _spans.span("fit.init"):
            opt_state = optimizer.init(model)
            _spans.force(opt_state)
        if ckpt is not None:
            with _spans.span(
                "train.restore", bucket="checkpoint", trace=_train_trace
            ):
                (model, opt_state), start = ckpt.restore((model, opt_state))
            if start > steps:
                raise ValueError(
                    f"{checkpoint_dir} holds a step-{start} checkpoint but "
                    f"this run is only {steps} steps — refusing to return "
                    "an over-trained model; point at a fresh directory"
                )
        completed = last_saved = start
        _stack.enter_context(_spans.span("fit.solve", bucket="compute"))
        for i in range(start, steps):
            if tracer is not None:
                tracer.step(i)
            t_step0 = _time.perf_counter()
            with _spans.span("train.step", step=i + 1) as s_ctx:
                with _spans.span("fit.load", bucket="wait_host"):
                    # an MTP module's targets lie one more position ahead
                    windows = _step_batch(
                        corpus, seed, i, batch, seq + mtp_depth(model)
                    )
                if history is not None:
                    seen.append(windows)
                with _spans.span(
                    "fit.h2d", bucket="wait_host", bytes=windows.nbytes
                ):
                    toks = jnp.asarray(windows)
                    if sharding is not None:
                        toks = jax.device_put(toks, sharding)
                    _spans.force(toks)
                t_host = _time.perf_counter() - t_step0
                poison = (
                    _faults.fire("train.nan", key=i) if guarded else None
                )
                model, opt_state, loss, step_stats = step(
                    model, opt_state, toks, poison
                )
                # a recorded step ends when its device work has
                _spans.force(loss)
            if history is not None or _spans.active_span_log() is not None:
                # what only a caller's history and a recorded
                # fit.counters span read: else dropped with the step
                stats.append(step_stats)
            # keep the loss on device: a float() here would block a host
            # round-trip into every step and serialize the dispatch queue
            # (exception: an active telemetry sink reads the scalar below
            # — that host read IS the live stream's cost, and it makes
            # the recorded per-step wall honest under async dispatch)
            losses.append(loss)
            completed = i + 1
            _cluster.note_step(completed)
            steplog = _telemetry.active_step_log()
            if steplog is not None or tuner is not None:
                # the float() below is the one per-step host sync the
                # live stream (and honest tuner walls) pays — measure
                # the wall AFTER it so the recorded step time is honest
                # under async dispatch
                loss_f = float(loss)
                wall = _time.perf_counter() - t_step0
                if tuner is not None:
                    # host-batch vs dispatched-compute attribution +
                    # token goodput for the self-tuning window
                    tuner.observe(
                        rows=batch * seq,
                        buckets={
                            "wait_host": t_host,
                            "compute": max(wall - t_host, 0.0),
                        },
                    )
            if steplog is not None:
                steplog.step(
                    step=i + 1,
                    loss=loss_f,
                    tokens=batch * seq,
                    wall_s=wall,
                    hbm_peak_bytes=devmon.maybe_sample(),
                )
                # the step's causal record: host-side batch production
                # vs dispatched device work, classified for the goodput
                # report (structural root; children carry the buckets)
                span_log = _spans.active_span_log()
                if span_log is not None and s_ctx is not None:
                    span_log.record_span(
                        "train.host_batch",
                        wall_s=t_host,
                        bucket="wait_host",
                        parent=s_ctx,
                    )
                    span_log.record_span(
                        "train.compute",
                        wall_s=max(wall - t_host, 0.0),
                        bucket="compute",
                        parent=s_ctx,
                    )
            # one host sync per check interval, not per step
            loss_guard.note(i, loss)
            if dog is not None:
                dog.pet() if dog.running else dog.start()
            if log_every and (i + 1) % log_every == 0:
                logger.info("step %d loss %.4f", i + 1, float(loss))
            if _faults.fire("cluster.host_kill", key=i):
                # a dying machine checkpoints nothing, flushes nothing,
                # cleans up nothing — SIGKILL models exactly that; the
                # survivors' failure detector and the run supervisor
                # take it from here (fires BEFORE the periodic save so
                # the drill actually loses in-interval steps)
                logger.warning(
                    "cluster.host_kill fault at step %d: killing this "
                    "process", i
                )
                _os.kill(_os.getpid(), _signal.SIGKILL)
            lost = _cluster.check_lost()
            if lost is not None:
                # exit BEFORE the periodic save: a coordinated save
                # with a known-dead peer can only time out at the
                # barrier
                raise _cluster.HostLostError(lost)
            if ckpt is not None and (
                (i + 1) % every == 0 or (i + 1) == steps
            ):
                try:
                    with _spans.span(
                        "train.checkpoint",
                        bucket="checkpoint",
                        trace=_train_trace,
                        step=i + 1,
                    ):
                        ckpt.save((model, opt_state), i + 1)
                    last_saved = i + 1
                except (OSError, RetryExhausted) as e:
                    # a full disk / exhausted IO retries at a PERIODIC
                    # save must not kill hours of training: the previous
                    # checkpoint is intact (atomic save), so degrade
                    # loudly and try again next interval — the risk
                    # window widens by one interval, the run survives.
                    # (A coordinated-barrier failure is a membership
                    # problem, not an IO one — ClusterBarrierError still
                    # propagates above.)
                    logger.warning(
                        "periodic checkpoint save at step %d failed "
                        "(%r); continuing on the step-%d checkpoint",
                        i + 1,
                        e,
                        last_saved,
                    )
                    _emit_resilience(
                        "ckpt_save_failed",
                        counter="ckpt_save_failures",
                        step=i + 1,
                        last_saved=last_saved,
                        error=repr(e),
                    )
            if _faults.fire("train.sigterm", key=i):
                if prev_handlers:
                    # a REAL signal to this process: exercises the
                    # handler path end to end, not a shortcut around it
                    _signal.raise_signal(_signal.SIGTERM)
                else:
                    # no handler installed (no checkpoint_dir, or not
                    # the main thread): a real SIGTERM would just kill
                    # the process — that tests nothing about us
                    logger.warning(
                        "train.sigterm fault fired at step %d but no "
                        "handler is installed; ignoring", i
                    )
            if stop_signal["sig"] is not None:
                logger.warning(
                    "signal %d at step %d: writing final checkpoint and "
                    "stopping early",
                    stop_signal["sig"],
                    i + 1,
                )
                _emit_resilience(
                    "signal_stop", signum=stop_signal["sig"], step=i + 1
                )
                break
            _faults.maybe_preempt(key=i)
        loss_guard.flush()
        _record_counters(model, stats)
    except _cluster.ClusterError as e:
        # a lost peer makes the coordinated rescue save impossible (its
        # barrier would wait on the dead host) — exit cleanly on the
        # last periodic checkpoint, at most one checkpoint interval
        # behind; the supervisor re-meshes and resumes from there
        cluster_lost = True
        logger.warning(
            "training stopped by cluster membership change at step %d: "
            "%s", completed, e,
        )
        _emit_resilience("host_lost_exit", step=completed, error=repr(e))
        raise
    except NumericalHealthError as e:
        # halt-with-last-good-checkpoint: training is unhealthy; return
        # the last checkpointed state rather than the post-spike one
        halted = True
        logger.warning("training halted by health guard: %s", e)
        _emit_resilience("guard_halt", step=completed, error=repr(e))
        if ckpt is None:
            raise
        (model, opt_state), restored = ckpt.restore((model, opt_state))
        if restored == 0:
            # nothing was ever checkpointed (saves start at step >= 1):
            # there is no "last good" state to return — restore() just
            # handed back the live post-spike template, so propagate
            raise
        losses = losses[: max(restored - start, 0)]
    finally:
        try:
            if (
                ckpt is not None
                and completed > last_saved
                and not halted
                and not cluster_lost
            ):
                # preemption / signal / crash path: the loop's periodic
                # save didn't cover the last completed step — write it
                # now so at most the in-flight step is lost
                with _spans.span(
                    "train.checkpoint",
                    bucket="checkpoint",
                    trace=_train_trace,
                    step=completed,
                    rescue=True,
                ):
                    ckpt.save((model, opt_state), completed)
                _emit_resilience("final_checkpoint", step=completed)
        except Exception:  # noqa: BLE001 — a failed rescue save must
            # not mask the original exception (the preemption itself)
            logger.exception(
                "final checkpoint save at step %d failed", completed
            )
        finally:
            if ckpt is not None:
                ckpt.close()
            if dog is not None:
                dog.stop()
            if tracer is not None:
                tracer.close()
            for s, h in prev_handlers.items():
                _signal.signal(s, h)
            # the open spans (fit.solve, the fit root) end here and see
            # the exception that is passing, if one is
            _stack.__exit__(*_sys.exc_info())
    if loss_guard.skipped:
        logger.warning(
            "guard skipped %d non-finite step(s): %s",
            len(loss_guard.skipped),
            loss_guard.skipped,
        )
    if history is not None:
        got = jax.device_get(stats)
        history["grad_sq"] = [g.grad_sq for g in got]
        history["counters"] = [g.counters for g in got]
        history["windows"] = seen
    return model, [float(l) for l in losses]


def _layer_identity(model: TransformerLM, blk) -> list:
    """What a checkpoint must agree on about one layer, as JSON."""
    spec = model.layer_spec(blk)
    m = blk.moe
    if blk.ssm is not None:
        x = blk.ssm
        return ["ssm", x.heads, x.head_dim, x.state, x.groups, x.chunk]
    if blk.cca is not None:
        x = blk.cca
        return [
            "cca", x.heads, x.kv_heads, x.head_dim, repr(spec.rope),
            [m.num_experts, m.held, m.first_expert, m.top_k, m.renormalize],
        ]
    return [
        spec.num_heads,
        spec.num_kv_heads,
        spec.window,
        repr(spec.rope),
        None
        if m is None
        else [m.num_experts, m.held, m.first_expert, m.top_k, m.scoring,
              m.routed_scale],
        *([] if spec.scale is None else [spec.scale]),
    ]


def ssm_layers(model: TransformerLM) -> int:
    """How many of the model's layers are state-space layers."""
    return sum(b.ssm is not None for b in model.blocks)


def cca_layers(model: TransformerLM) -> int:
    """How many of the model's layers attend in a compressed latent."""
    return sum(b.cca is not None for b in model.blocks)


def moe_latent(model: TransformerLM) -> int:
    """The width of the latent the model's routed experts live in, 0
    where they read the full width."""
    return max(
        (
            b.moe.latent_down.shape[1]
            for b in model.blocks
            if b.moe is not None and b.moe.latent_down is not None
        ),
        default=0,
    )


def _record_counters(model: TransformerLM, stats: list) -> None:
    """The expert, state-space and compressed-latent layers' counters of
    this fit as one zero-length ``fit.counters`` span under the open
    one, read from the device once, and only while spans are recorded
    and the model routes, scans or mixes. A model without experts leaves
    their counters at 0, and one without state-space layers theirs;
    ``cca_rows`` and ``router_gate_mean`` (the mean gate of the rows
    routed to held experts, where the gates are not renormalised) are
    there only for a model that counts them."""
    from keystone_tpu.observe import spans as _spans
    from keystone_tpu.ops.ssm import COUNTERS as SSM_COUNTERS

    slots = sum(b.moe.held for b in model.blocks if b.moe is not None)
    sl = _spans.active_span_log()
    if (
        sl is None
        or not (slots or ssm_layers(model) or cca_layers(model))
        or not stats
    ):
        return
    got = jax.device_get([s.counters for s in stats])
    routed = [int(c["routed_rows"]) for c in got]
    more = {}
    if "cca_rows" in got[0]:
        more["cca_rows"] = sum(int(c["cca_rows"]) for c in got)
    if "gate_sum" in got[0]:
        more["router_gate_mean"] = float(
            sum(float(c["gate_sum"]) for c in got) / max(sum(routed), 1)
        )
    if "mtp_rows" in got[0]:
        # positions the MTP module's loss covered
        more["mtp_rows"] = sum(int(c["mtp_rows"]) for c in got)
    sl.record_span(
        "fit.counters",
        wall_s=0.0,
        parent=_spans.current(),
        steps=len(got),
        routed_rows=sum(routed),
        mm_rows=sum(int(c["mm_rows"]) for c in got),
        # rows put through the grouped products, and windows run beyond
        # each expert layer's first (ops/moe.py)
        dispatch_rows=sum(int(c["dispatch_rows"]) for c in got),
        extra_windows=sum(int(c["extra_windows"]) for c in got),
        # positions scanned, chunks run and positions scanned by the
        # kernel, summed over state-space layers
        **{name: sum(int(c.get(name, 0)) for c in got) for name in SSM_COUNTERS},
        **more,
        # largest load of a held expert over the mean load, a step
        load_max_over_mean=float(
            np.mean(
                [
                    int(c["max_expert_rows"]) * slots / max(r, 1)
                    for c, r in zip(got, routed)
                ]
            )
        ),
    )


def _emit_resilience(action: str, **fields) -> None:
    from keystone_tpu.resilience.emit import decision

    decision(action, **fields)


def synthetic_corpus(n: int, vocab: int, seed: int = 0) -> np.ndarray:
    """A learnable-but-not-trivial token stream: an order-1 Markov chain
    with a sparse, deterministic-ish transition structure."""
    rng = np.random.default_rng(seed)
    succ = rng.integers(0, vocab, size=(vocab, 4))
    probs = np.array([0.7, 0.15, 0.1, 0.05])
    out = np.empty(n, np.int32)
    out[0] = 0
    choices = rng.choice(4, size=n, p=probs)
    for i in range(1, n):
        out[i] = succ[out[i - 1], choices[i]]
    return out
