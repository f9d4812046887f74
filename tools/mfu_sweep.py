"""Phase-split MFU measurement for the bench workloads (VERDICT r2 #2).

Times each phase of the MNIST bench solve separately — featurize (fused
single-gemm vs per-chain), Gram accumulation, Cholesky factor + refine —
at matmul precision None (bf16 MXU passes) and "highest" (full f32), plus
the TIMIT-shaped weighted solver phases. Emits one JSON dict (and writes
MFU_SWEEP.json at the repo root) with achieved TFLOP/s per phase and the
fraction of bf16 peak, so ROOFLINE.md can state per phase what the bound
is and how close we run.

Run ON CHIP (no JAX_PLATFORMS pin): phases are measured with the same
async-dispatch/one-sync discipline as bench.py.
"""

from __future__ import annotations

import datetime
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N = 60_000
D_IMG = 784
NUM_FFTS = 4
D_FEAT = 2048
CLASSES = 10

# roofline basis lives in keystone_tpu.observe.report (single home)


def _sync(x) -> float:
    import jax

    leaf = jax.tree_util.tree_leaves(x)[0]
    return float(np.asarray(leaf.ravel()[0]))


def _timed(step, iters: int = 6) -> float:
    _sync(step())
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = step()
    _sync(out)
    return (time.perf_counter() - t0) / iters


def _inprog(step_fn, args, reps: int) -> float:
    """Seconds per step with the repetition INSIDE one XLA program.

    The per-dispatch phases above embed the device launch latency, so
    they understate chip throughput where a phase is short. Here the
    step runs ``reps`` times under one ``lax.scan`` whose carry perturbs
    the input by a sub-ulp factor each iteration — a data dependence XLA
    cannot hoist or dead-code (the full output feeds a fused reduction),
    costing only an elementwise scale per step. The resulting rate is
    the chip's steady-state compute rate for the phase.
    """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(*a):
        x0 = a[0]

        def body(c, _):
            out = step_fn(x0 * (1.0 + c), *a[1:])
            s = sum(
                jnp.sum(leaf)
                for leaf in jax.tree_util.tree_leaves(out)
                if hasattr(leaf, "dtype")
                and jnp.issubdtype(leaf.dtype, jnp.floating)
            )
            return (s * 1e-30).astype(x0.dtype), None

        c, _ = jax.lax.scan(
            body, jnp.zeros((), x0.dtype), None, length=reps
        )
        return c

    return _timed(lambda: f(*args), iters=2) / reps


def main() -> None:
    import jax
    import jax.numpy as jnp

    from keystone_tpu.core.runtime import init_backend
    from keystone_tpu.models import mnist_random_fft as m
    from keystone_tpu.ops.linear import ridge_solve
    from keystone_tpu.ops.weighted_linear import (
        BlockWeightedLeastSquaresEstimator,
    )

    init_backend()
    dev = jax.devices()[0]
    from keystone_tpu.observe.report import peak_flops_for

    peak = peak_flops_for(dev.device_kind)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(N, D_IMG)).astype(np.float32))
    feats = m.build_batch_featurizers(NUM_FFTS, D_FEAT, seed=0)
    out: dict = {
        "device_kind": dev.device_kind,
        "backend": dev.platform,
        "timestamp": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "phases": {},
    }

    def record(name, sec, flops):
        tf = flops / sec / 1e12
        out["phases"][name] = {
            "ms": round(sec * 1e3, 3),
            "tflops_per_s": round(tf, 2),
            **(
                {"frac_bf16_peak": round(tf * 1e12 / peak, 4)}
                if peak
                else {}
            ),
        }

    # launch latency: everything per-dispatch below embeds ~this much
    from bench import dispatch_floor_ms

    out["dispatch_floor_ms"] = round(dispatch_floor_ms(), 3)

    # ---- featurize: fused single gemm vs per-chain path ----
    feat_flops = 2 * N * D_IMG * (NUM_FFTS * 512)
    sec = _timed(lambda: m.featurize(feats, x))
    record("featurize_fused", sec, feat_flops)
    sec = _timed(
        lambda: [
            m._featurize_batch(tuple(chains), x) for chains in feats
        ]
    )
    record("featurize_chains", sec, feat_flops)
    # same two paths with repetition inside one program (no launch
    # latency): the number that reflects what the chip actually does
    sec = _inprog(lambda xx: m.featurize(feats, xx), (x,), reps=24)
    record("featurize_fused_inprog", sec, feat_flops)
    sec = _inprog(
        lambda xx: [
            m._featurize_batch(tuple(chains), xx) for chains in feats
        ],
        (x,),
        reps=24,
    )
    record("featurize_chains_inprog", sec, feat_flops)

    a = jnp.concatenate(m.featurize(feats, x), axis=1)  # (N, 2048)
    _sync(a)
    d_feat = int(a.shape[-1])
    gram_flops = 2 * N * d_feat * d_feat

    for prec in (None, "highest"):
        tag = "bf16pass" if prec is None else "f32"
        ctx = (
            jax.default_matmul_precision(prec)
            if prec
            else __import__("contextlib").nullcontext()
        )
        with ctx:
            # everything precision-sensitive must be TRACED inside the
            # context (matmul precision is baked in at trace time — a
            # solve traced after the with-block would silently measure
            # default precision under an f32 label)
            gram = jax.jit(lambda a_: a_.T @ a_)
            sec = _timed(lambda: gram(a))
            record(f"gram_{tag}", sec, gram_flops)
            sec = _inprog(lambda a_: a_.T @ a_, (a,), reps=16)
            record(f"gram_{tag}_inprog", sec, gram_flops)
            g = gram(a)
            _sync(g)
            rhs = jnp.asarray(
                rng.normal(size=(d_feat, CLASSES)).astype(np.float32)
            )
            solve = jax.jit(lambda g_, r_: ridge_solve(g_, r_, 1e-2))
            sec = _timed(lambda: solve(g, rhs))
            # cholesky d^3/3 + refine 2 * 2d^2C
            chol_flops = d_feat**3 / 3 + 4 * d_feat * d_feat * CLASSES
            record(f"cholesky_refine_{tag}", sec, chol_flops)
            sec = _inprog(
                lambda g_, r_: ridge_solve(g_, r_, 1e-2), (g, rhs), reps=8
            )
            record(f"cholesky_refine_{tag}_inprog", sec, chol_flops)

    # ---- whole MNIST fit (featurize + BCD solve) as one program ----
    # bench.py's samples/s pays one launch per step (fit_fused); this is
    # the steady-state rate with the launch amortized away entirely
    from keystone_tpu.ops.linear import BlockLeastSquaresEstimator
    from keystone_tpu.ops.util import ClassLabelIndicators

    est = BlockLeastSquaresEstimator(
        block_size=D_FEAT, num_iter=1, lam=1e-2
    )
    y_cls = ClassLabelIndicators(num_classes=CLASSES)(
        rng.integers(0, CLASSES, size=N)
    )
    fit_flops = feat_flops + gram_flops + 2 * N * d_feat * CLASSES + d_feat**3 / 3
    sec = _inprog(
        lambda xx: est.fit(m.featurize(feats, xx), y_cls, n_valid=N),
        (x,),
        reps=6,
    )
    record("mnist_fit_e2e_inprog", sec, fit_flops)
    out["phases"]["mnist_fit_e2e_inprog"]["samples_per_s"] = round(
        N / sec, 1
    )

    # ---- e2e per-dispatch: fit_fused (ONE program) vs featurize + fit
    # as separate programs — the comparison VERDICT r3 #3 asks for (the
    # launch floor is paid once vs twice; phase numbers above isolate
    # whether the fused gemm itself also wins)
    from keystone_tpu.core.pipeline import ChainedLabelEstimator
    from keystone_tpu.models.mnist_random_fft import FeaturizerBank

    # wrap the SAME chains measured above — not a rebuild that only
    # matches while the seeds happen to agree
    bank = FeaturizerBank(batches=tuple(tuple(g) for g in feats))
    chained = ChainedLabelEstimator(prefix=bank, est=est)
    sec = _timed(lambda: chained.fit_fused(x, y_cls, n_valid=N)[-1], iters=3)
    record("fit_fused_e2e", sec, fit_flops)
    out["phases"]["fit_fused_e2e"]["samples_per_s"] = round(N / sec, 1)

    def split_fit():
        blocks = m.featurize(feats, x)  # dispatch 1 (fused gemm inside)
        return est.fit(blocks, y_cls, n_valid=N)  # dispatch 2+

    sec = _timed(split_fit, iters=3)
    record("fit_split_e2e", sec, fit_flops)
    out["phases"]["fit_split_e2e"]["samples_per_s"] = round(N / sec, 1)

    # ---- TIMIT-shaped weighted solver, both precisions ----
    n_w, d_w, c_w = 32_768, 1024, 147
    cls = rng.integers(0, c_w, size=n_w)
    centers = rng.normal(size=(c_w, d_w)).astype(np.float32)
    aw = jnp.asarray(
        (centers[cls] + rng.normal(size=(n_w, d_w))).astype(np.float32)
    )
    yw = -np.ones((n_w, c_w), np.float32)
    yw[np.arange(n_w), cls] = 1.0
    yw = jnp.asarray(yw)
    l_pad = max(-(-int(np.bincount(cls).max()) // 64) * 64, 64)
    lp1 = l_pad + 1
    w_flops = (
        2 * n_w * d_w * d_w * 2
        + 2 * c_w * d_w * d_w * lp1
        + 2 * c_w * d_w * lp1**2
        + 2 * (2 * n_w * d_w * c_w + 8 * c_w * d_w * d_w)
    )
    for prec in (None, "highest"):
        tag = "bf16pass" if prec is None else "f32"
        est = BlockWeightedLeastSquaresEstimator(
            block_size=d_w,
            num_iter=2,
            lam=1e-3,
            mixture_weight=0.5,
            class_chunk=16,
            precision=prec,
        )
        sec = _timed(lambda e=est: e.fit(aw, yw), iters=2)
        record(f"weighted_fit_{tag}", sec, w_flops)
        out["phases"][f"weighted_fit_{tag}"]["samples_per_s"] = round(
            n_w / sec, 1
        )

    # prep-vs-pass decomposition at the default precision: t(k passes) is
    # affine in k, so per_pass = (t3 - t1)/2 and prep = t1 - per_pass —
    # attributes the round-5 cuts (grid-identity removal, one-shot
    # Woodbury grouping) to the phase they land in (ROOFLINE §3)
    def _fit_iters(k):
        e = BlockWeightedLeastSquaresEstimator(
            block_size=d_w, num_iter=k, lam=1e-3, mixture_weight=0.5,
            class_chunk=16,
        )
        return _timed(lambda: e.fit(aw, yw), iters=2)

    t1, t3 = _fit_iters(1), _fit_iters(3)
    per_pass = max((t3 - t1) / 2, 0.0)
    out["phases"]["weighted_fit_split"] = {
        "prep_plus_gather_s": round(max(t1 - per_pass, 0.0), 4),
        "per_pass_s": round(per_pass, 4),
        "t1_s": round(t1, 4),
        "t3_s": round(t3, 4),
    }

    # ---- ImageNet-shaped weighted solver (d=4096 blocks, C=1000) ----
    # the shape the Woodbury redesign targets (VERDICT r3 weak #5);
    # problem + cost model live in bench.weighted_imagenet_problem.
    # TPU-only like bench.py's gate: the ~3.6 TFLOP fit is minutes of
    # host BLAS under a JAX_PLATFORMS=cpu pin, against a sweep that
    # should stay prompt
    if dev.platform != "cpu":
        from bench import weighted_imagenet_problem

        ai, yi, est_i, wi_flops = weighted_imagenet_problem()
        sec = _timed(lambda: est_i.fit(ai, yi), iters=1)
        record("weighted_imagenet_bf16pass", sec, wi_flops)
        out["phases"]["weighted_imagenet_bf16pass"]["samples_per_s"] = (
            round(int(ai.shape[0]) / sec, 1)
        )

    # ---- int8 decode matmul A/B (VERDICT r3 #4) ----
    # decode is HBM-bound: the metric is weight-stream GB/s, not FLOPs.
    # Three contenders at the decode shapes (tiny M, the LM's K, the MLP
    # and tied-logits N): bf16 weights (baseline bytes), int8 via XLA
    # convert-into-dot (ops/quantization.mm — the bet), int8 via the
    # fused Pallas kernel (ops/int8_matmul.mm_fused — the hedge). If
    # xla_int8 ≈ bf16 time, XLA did NOT fuse and the kernel is the path.
    if dev.platform != "cpu":
        from keystone_tpu.ops.int8_matmul import mm_fused
        from keystone_tpu.ops.quantization import mm as qmm, quantize_int8

        m_dec, k_dec = 8, 1024
        for n_dec in (4096, 32_768):
            wd = jnp.asarray(
                rng.normal(size=(k_dec, n_dec)).astype(np.float32)
            )
            qt = quantize_int8(wd)
            yd = jnp.asarray(
                rng.normal(size=(m_dec, k_dec)).astype(np.float32)
            ).astype(jnp.bfloat16)
            wb = wd.astype(jnp.bfloat16)
            variants = {
                "bf16": (lambda a, b: a @ b, (yd, wb), 2),
                "xla_int8": (
                    lambda a, q: qmm(a, q, jnp.bfloat16),
                    (yd, qt),
                    1,
                ),
                "pallas_int8": (
                    lambda a, q: mm_fused(a, q),
                    (yd, qt),
                    1,
                ),
            }
            for name, (fn, args, bytes_per_w) in variants.items():
                # _inprog, NOT per-dispatch: these matmuls are tens of
                # µs — a per-dispatch timing would measure only the
                # launch floor and the A/B verdict would be noise
                sec = _inprog(fn, args, reps=64)
                stream = k_dec * n_dec * bytes_per_w
                out["phases"][f"decode_mm_{name}_n{n_dec}"] = {
                    "ms": round(sec * 1e3, 4),
                    "weight_stream_gb_per_s": round(
                        stream / sec / 1e9, 1
                    ),
                }

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "MFU_SWEEP.json",
    )
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
