"""Validate the Pallas kernels COMPILED on real TPU hardware.

The CPU-mesh test suite runs the Pallas kernels in interpret mode, so
Mosaic lowering failures and tile/VMEM mistakes are invisible to it.
This script runs on the real chip (``chip_smoke.py`` at the repo root is
the standing compile check; this is the wider numeric sweep):

- ``flash_attention`` in both variants (K/V-resident fori and the
  streamed scratch-carry long-context path) compiled, vs the jnp dense
  softmax reference;
- ``flash_attention_step`` (the ring-attention inner kernel) chained over
  hops, both lane-1 and padded state;
- ``conv_convolver`` (the production conv-algebra Convolver) vs the XLA
  im2col path and an f64 numpy truth (the Pallas im2col kernel it also
  used to measure was retired in round 3 — ROOFLINE.md §5);

asserts numerical agreement and records compiled-vs-jnp timings in
``chiprun_out/TPU_VALIDATION.json``.

Run on the chip: ``python tools/tpu_validate.py`` (exits nonzero off-TPU
or on any numeric mismatch).
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

import numpy as np

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def _sync(x):
    # index on device BEFORE np.asarray: sync on one scalar, not a
    # full-array transfer
    return float(np.asarray(jax.tree_util.tree_leaves(x)[0].ravel()[0]))


def _time(fn, *args, iters: int = 10):
    """Median-free amortized timing: dispatch ``iters`` async calls and
    sync once, so the host round trip is paid once, not per call.
    Returns seconds per call (includes per-dispatch overhead)."""
    _sync(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / iters


def _max_err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


def _np_attention_f64(q, k, v, *, causal: bool):
    """Ground truth: dense softmax attention in numpy float64 on the host.

    TPU f32 matmuls default to bf16-pass MXU arithmetic (~1e-3), so the
    jnp dense path is not a precision reference; this is. Loops (b, h) to
    bound the score-matrix footprint.
    """
    q = np.asarray(q, np.float64)
    k = np.asarray(k, np.float64)
    v = np.asarray(v, np.float64)
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    out = np.empty((b, h, s_q, d), np.float64)
    scale = 1.0 / np.sqrt(d)
    mask = None
    if causal:
        mask = np.tril(np.ones((s_q, s_k), bool), k=s_k - s_q)
    for bi in range(b):
        for hi in range(h):
            s = (q[bi, hi] @ k[bi, hi].T) * scale
            if mask is not None:
                s = np.where(mask, s, -np.inf)
            s -= s.max(axis=-1, keepdims=True)
            p = np.exp(s)
            p /= p.sum(axis=-1, keepdims=True)
            out[bi, hi] = p @ v[bi, hi]
    return out.astype(np.float32)


def validate_flash_attention(results):
    from keystone_tpu.ops.attention import dense_attention
    from keystone_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(0)

    # --- variant 1: K/V resident (fits the VMEM budget) ---
    b, h, s, d = 4, 8, 1024, 64
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)

    for causal in (False, True):
        truth = _np_attention_f64(q, k, v, causal=causal)
        ref = jax.jit(lambda q, k, v: dense_attention(q, k, v, causal=causal))
        fl = jax.jit(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, kv_resident=True, interpret=False
            )
        )
        err = _max_err(fl(q, k, v), truth)
        err_jnp = _max_err(ref(q, k, v), truth)
        t_ref, t_fl = _time(ref, q, k, v), _time(fl, q, k, v)
        results[f"flash_fori_causal={causal}"] = {
            "shape": [b, h, s, d],
            "max_err_vs_f64": err,
            "jnp_err_vs_f64": err_jnp,
            "jnp_ms": round(t_ref * 1e3, 3),
            "pallas_ms": round(t_fl * 1e3, 3),
            "speedup": round(t_ref / t_fl, 2),
        }
        # MXU f32 default precision gives ~1e-3; require the kernel to be
        # no worse than 4x the jnp dense path's own error
        assert err < max(4 * err_jnp, 1e-4), (
            f"flash fori causal={causal}: err {err} (jnp {err_jnp})"
        )

    # --- both variants at the shape that OOM'd scoped VMEM in round 1
    # (K+V = 8MB; resident now rides the raised vmem limit, stream is
    # forced to prove the long-context path) ---
    b, h, s, d = 1, 2, 8192, 128
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    for causal in (False, True):
        truth = _np_attention_f64(q, k, v, causal=causal)
        ref = jax.jit(lambda q, k, v: dense_attention(q, k, v, causal=causal))
        err_jnp = _max_err(ref(q, k, v), truth)
        t_ref = _time(ref, q, k, v)
        for name, resident in (("stream", False), ("resident8mb", True)):
            fl = jax.jit(
                lambda q, k, v: flash_attention(
                    q,
                    k,
                    v,
                    causal=causal,  # noqa: B023
                    kv_resident=resident,  # noqa: B023
                    interpret=False,
                )
            )
            err = _max_err(fl(q, k, v), truth)
            t_fl = _time(fl, q, k, v)
            results[f"flash_{name}_causal={causal}"] = {
                "shape": [b, h, s, d],
                "max_err_vs_f64": err,
                "jnp_err_vs_f64": err_jnp,
                "jnp_ms": round(t_ref * 1e3, 3),
                "pallas_ms": round(t_fl * 1e3, 3),
                "speedup": round(t_ref / t_fl, 2),
            }
            assert err < max(4 * err_jnp, 1e-4), (
                f"flash {name} causal={causal}: err {err} (jnp {err_jnp})"
            )

    # bf16 MXU path
    b, h, s, d = 4, 8, 2048, 64
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    # unaligned short sequence (ViT's 14x14 = 196 patches): the clamped
    # block must round up to an 8-aligned Mosaic tile
    b, h, s, d = 2, 4, 196, 64
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    truth = _np_attention_f64(q, k, v, causal=False)
    out = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, interpret=False)
    )(q, k, v)
    err = _max_err(out, truth)
    err_jnp = _max_err(
        jax.jit(lambda q, k, v: dense_attention(q, k, v))(q, k, v), truth
    )
    results["flash_unaligned_s196"] = {
        "shape": [b, h, s, d],
        "max_err_vs_f64": err,
        "jnp_err_vs_f64": err_jnp,
    }
    assert err < max(4 * err_jnp, 1e-4), (
        f"flash unaligned s=196: err {err} (jnp {err_jnp})"
    )

    b, h, s, d = 4, 8, 2048, 64
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    truth = _np_attention_f64(q, k, v, causal=False)
    ref = jax.jit(lambda q, k, v: dense_attention(q, k, v))
    fl16 = jax.jit(
        lambda q, k, v: flash_attention(
            q, k, v, mxu_dtype=jnp.bfloat16, interpret=False
        )
    )
    err = _max_err(fl16(q, k, v), truth)
    t_ref, t_fl = _time(ref, q, k, v), _time(fl16, q, k, v)
    results["flash_bf16"] = {
        "shape": [b, h, s, d],
        "max_err_vs_f64": err,
        "jnp_ms": round(t_ref * 1e3, 3),
        "pallas_ms": round(t_fl * 1e3, 3),
        "speedup": round(t_ref / t_fl, 2),
    }
    assert err < 5e-2, f"flash bf16: err {err}"

    # --- throughput shape: the small entries above sit on the shared
    # chip's ~7ms dispatch floor and say nothing about kernel rate; this
    # one is big enough (~0.27 TFLOP causal) to read TFLOP/s off ---
    b, h, s, d = 4, 16, 4096, 128
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    fl = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False)
    )
    # numerics gate on a one-head slice: the full dense reference would
    # materialize a (4,16,4096,4096) logits tensor (~4.3GB + softmax
    # copies) and can OOM the shared chip; flash itself needs no such
    # buffer — that's the point
    ref1 = jax.jit(lambda q, k, v: dense_attention(q, k, v, causal=True))
    err_rel = _max_err(
        fl(q, k, v)[:1, :1], ref1(q[:1, :1], k[:1, :1], v[:1, :1])
    )
    t_fl = _time(fl, q, k, v, iters=4)
    flops = 4 * b * h * s * s * d / 2  # causal half
    results["flash_throughput_4x16x4096x128"] = {
        "shape": [b, h, s, d],
        "pallas_ms": round(t_fl * 1e3, 3),
        "pallas_tflops_per_s": round(flops / t_fl / 1e12, 2),
        "max_err_vs_jnp_slice": err_rel,
        "dense_jnp": "not timed: (B,H,S,S) logits ~4.3GB risks OOM on "
        "the shared chip",
    }
    assert err_rel < 5e-2, f"flash throughput shape: err {err_rel}"


def validate_flash_step(results):
    """Chain flash_attention_step over hops == ring attention's inner loop."""
    from keystone_tpu.ops.attention import dense_attention
    from keystone_tpu.ops.flash_attention import _LANE, flash_attention_step

    rng = np.random.default_rng(1)
    b, h, s, d = 2, 4, 512, 64
    hops = 4
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    ks = jnp.asarray(rng.normal(size=(hops, b, h, s, d)), jnp.float32)
    vs = jnp.asarray(rng.normal(size=(hops, b, h, s, d)), jnp.float32)
    k_full = jnp.concatenate(list(ks), axis=2)
    v_full = jnp.concatenate(list(vs), axis=2)
    ref = _np_attention_f64(q, k_full, v_full, causal=False)
    err_jnp = _max_err(jax.jit(dense_attention)(q, k_full, v_full), ref)

    for padded in (False, True):
        state_shape = (b, h, s, _LANE) if padded else (b, h, s)

        @jax.jit
        def run(q, ks, vs):
            m = jnp.full(state_shape, -1e30, jnp.float32)  # noqa: B023
            l = jnp.zeros(state_shape, jnp.float32)  # noqa: B023
            acc = jnp.zeros((b, h, s, d), jnp.float32)
            for i in range(hops):
                m, l, acc = flash_attention_step(
                    q,
                    ks[i],
                    vs[i],
                    m,
                    l,
                    acc,
                    q_offset=0,
                    k_offset=i * s,
                    padded_state=padded,  # noqa: B023
                    interpret=False,
                )
            lane = l[..., :1] if padded else l[..., None]  # noqa: B023
            return acc / jnp.maximum(lane, 1e-30)

        out = run(q, ks, vs)
        err = _max_err(out, ref)
        results[f"flash_step_padded={padded}"] = {
            "shape": [b, h, s, d],
            "hops": hops,
            "max_err_vs_f64": err,
            "jnp_err_vs_f64": err_jnp,
        }
        assert err < max(4 * err_jnp, 1e-4), (
            f"flash step padded={padded}: err {err} (jnp {err_jnp})"
        )


def validate_conv_convolver(results):
    from keystone_tpu.ops.images import extract_patches, normalize_patch_rows

    rng = np.random.default_rng(2)
    n, hh, ww, c, k, f = 256, 32, 32, 3, 6, 256  # CIFAR random-patch shape
    batch = jnp.asarray(rng.normal(size=(n, hh, ww, c)), jnp.float32)
    filters = jnp.asarray(rng.normal(size=(f, k * k * c)), jnp.float32)
    means = jnp.asarray(rng.normal(size=(k * k * c,)), jnp.float32)

    def xla_path(batch, filters, means):
        patches = extract_patches(batch, k)  # (N, oh, ow, k²C)
        oh, ow = patches.shape[1], patches.shape[2]
        mat = patches.reshape(n * oh * ow, k * k * c)
        mat = normalize_patch_rows(mat, 10.0) - means[None, :]
        return (mat @ filters.T).reshape(n, oh, ow, f)

    def np_truth():
        bat = np.asarray(batch, np.float64)
        d = k * k * c
        # same patch layout as extract_patches: (dy, dx, c), c fastest
        oh, ow = hh - k + 1, ww - k + 1
        pat = np.empty((n, oh, ow, d), np.float64)
        for dy in range(k):
            for dx in range(k):
                pat[..., (dy * k + dx) * c : (dy * k + dx + 1) * c] = bat[
                    :, dy : dy + oh, dx : dx + ow, :
                ]
        mat = pat.reshape(-1, d)
        mu = mat.mean(axis=1, keepdims=True)
        cent = mat - mu
        var = (cent * cent).sum(axis=1, keepdims=True) / (d - 1)
        mat = cent / np.sqrt(var + 10.0) - np.asarray(means, np.float64)
        out = mat @ np.asarray(filters, np.float64).T
        return out.reshape(n, oh, ow, f).astype(np.float32)

    from keystone_tpu.ops.images import conv_convolver

    truth = np_truth()
    ref = jax.jit(xla_path)
    conv = jax.jit(
        lambda b_, f_, m_: conv_convolver(
            b_,
            f_,
            patch_size=k,
            normalize_patches=True,
            var_constant=10.0,
            whitener_means=m_,
        )
    )
    err_jnp = _max_err(ref(batch, filters, means), truth)
    err_conv = _max_err(conv(batch, filters, means), truth)
    t_ref = _time(ref, batch, filters, means)
    t_conv = _time(conv, batch, filters, means)
    results["conv_convolver"] = {
        "shape": [n, hh, ww, c],
        "patch": k,
        "filters": f,
        "max_err_vs_f64": err_conv,
        "im2col_ms": round(t_ref * 1e3, 3),
        "conv_ms": round(t_conv * 1e3, 3),
        "speedup_vs_im2col": round(t_ref / t_conv, 2),
    }
    assert err_conv < max(4 * err_jnp, 1e-4), (
        f"conv convolver: err {err_conv} (jnp {err_jnp})"
    )


def validate_weighted_solver_scale(results):
    """Weighted-BCD scaling on the real chip (round-1 VERDICT #3 done
    criteria): (a) TIMIT shape (C=147) fit cost vs the unweighted BCD at
    the same shape, (b) an ImageNet-class-count feasibility run (C=1000,
    4096 feature columns) — the class-sorted grid layout keeps per-class
    Grams at N·d² total, so C only enters through the batched per-class
    solves (reference BlockWeightedLeastSquares.scala:228-263 runs these
    one-class-per-partition; here they are chunked batched Cholesky
    solves)."""
    from keystone_tpu.ops.linear import BlockLeastSquaresEstimator
    from keystone_tpu.ops.util import ClassLabelIndicators
    from keystone_tpu.ops.weighted_linear import (
        BlockWeightedLeastSquaresEstimator,
    )

    rng = np.random.default_rng(5)

    def run(n, d, block, c, chunk):
        """Returns (per-pass seconds, one-fit seconds, data, y).

        A fit call pays a one-time host round trip (the grid layout's
        class indices are read on the host before tracing), so
        single-fit wall time is dominated by dispatch at these sizes.
        Real fits run several BCD passes inside one jit — the steady-state
        metric is the marginal cost of a pass: (t(3 passes) − t(1))/2.
        """
        data = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        labels_i = rng.integers(0, c, size=n).astype(np.int32)
        y = jnp.asarray(np.asarray(ClassLabelIndicators(num_classes=c)(labels_i)))
        times = {}
        for iters in (1, 3):
            west = BlockWeightedLeastSquaresEstimator(
                block_size=block,
                num_iter=iters,
                lam=0.5,
                mixture_weight=0.3,
                class_chunk=chunk,
            )
            fitted = {}

            def step(west=west, fitted=fitted):
                fitted["model"] = west.fit(data, y, n_valid=n)
                return fitted["model"]

            times[iters] = _time(step, iters=3)
            model = fitted["model"]
            assert bool(jnp.isfinite(model.b).all()), "non-finite intercepts"
            for x in model.xs:
                assert bool(jnp.isfinite(x).all()), "non-finite model block"
        return max(times[3] - times[1], 0.0) / 2, times[1], data, y

    # (a) TIMIT shape: 147 classes, 2048 cols in 4 blocks
    n, d = 16384, 2048
    t_w_pass, t_w_fit, data, y = run(n, d, 512, 147, 21)
    blocks = [data[:, i : i + 512] for i in range(0, d, 512)]
    ut = {}
    for iters in (1, 3):
        est = BlockLeastSquaresEstimator(
            block_size=512, num_iter=iters, lam=0.5
        )
        ut[iters] = _time(
            lambda est=est: est.fit(blocks, y, n_valid=n), iters=3
        )
    t_u_pass = max(ut[3] - ut[1], 0.0) / 2
    # the unweighted fit sits near the dispatch floor: if timing noise
    # makes the marginal pass cost ~0, report the ratio as unmeasurable
    # rather than writing a nonsense number into the artifact
    ratio = (
        round(t_w_pass / t_u_pass, 2) if t_u_pass > 1e-3 else "unmeasurable"
    )
    results["weighted_solver_timit_c147"] = {
        "n": n,
        "d": d,
        "classes": 147,
        "weighted_ms_per_pass": round(t_w_pass * 1e3, 1),
        "unweighted_ms_per_pass": round(t_u_pass * 1e3, 1),
        "per_pass_ratio": ratio,
        "weighted_one_fit_ms": round(t_w_fit * 1e3, 1),
        "unweighted_one_fit_ms": round(ut[1] * 1e3, 1),
        "note": "per-pass = (t(3 BCD passes) - t(1))/2; one-fit wall "
        "time includes the one-time grid-layout host round trip "
        "and dispatch floor",
    }

    # (b) ImageNet class count: C=1000, 4096 cols in 2 blocks of 2048
    t_k_pass, t_k_fit, _, _ = run(16384, 4096, 2048, 1000, 8)
    results["weighted_solver_imagenet_c1000"] = {
        "n": 16384,
        "d": 4096,
        "classes": 1000,
        "ms_per_pass": round(t_k_pass * 1e3, 1),
        "one_fit_ms": round(t_k_fit * 1e3, 1),
        "note": "feasibility: class-sorted grid layout + Woodbury "
        "low-rank per-class solves (class_l+2 <= d_block/2)",
    }


# (b, h, s, d, reps) per in-program A/B point; module-level so
# tests/test_tpu_validate_probe.py can shrink them (interpret-mode
# flash at 4k would take minutes off-chip). _INPROG_INTERPRET exists
# for the same smoke path.
INPROG_SHAPES = [(1, 4, 4096, 128, 8), (1, 2, 8192, 128, 8)]
_INPROG_INTERPRET = False


def validate_flash_inprogram(results):
    """Flash vs dense at 4k-8k causal measured IN-PROGRAM (VERDICT r4
    weak #3): the per-dispatch A/B at these sizes is noise on the
    5-15 ms launch floor, so both paths are chained ``reps``x inside one
    jitted program with a carry-coupled scan (out_i feeds q_{i+1} — XLA
    cannot hoist or dedup the chain), and the per-iteration time is the
    steady-state kernel rate. Identical chaining for both paths keeps
    the comparison fair."""
    from keystone_tpu.ops.attention import dense_attention
    from keystone_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(11)
    diverged = []
    for b, h, s, d, reps in INPROG_SHAPES:
        q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)

        def chained(attn_fn):
            def prog(q, k, v):
                def body(carry, _):
                    out = attn_fn(carry, k, v)
                    # renormalize so the carry can't drift to inf/0
                    # over reps (values stay O(1) for both paths)
                    out = out / (
                        jnp.sqrt(jnp.mean(out * out)) + 1e-6
                    )
                    return out, None
                final, _ = jax.lax.scan(body, q, None, length=reps)
                return final
            return jax.jit(prog)

        dense_prog = chained(
            lambda qq, kk, vv: dense_attention(qq, kk, vv, causal=True)
        )
        flash_prog = chained(
            lambda qq, kk, vv: flash_attention(
                qq, kk, vv, causal=True, interpret=_INPROG_INTERPRET
            )
        )
        # equivalence first: the chained programs must agree
        err = _max_err(dense_prog(q, k, v), flash_prog(q, k, v))
        t_dense = _time(dense_prog, q, k, v, iters=3) / reps
        t_flash = _time(flash_prog, q, k, v, iters=3) / reps
        flops = 4 * b * h * s * s * d / 2
        results[f"flash_inprog_{s}_causal"] = {
            "shape": [b, h, s, d],
            "reps_in_program": reps,
            "max_abs_diff": err,
            "dense_ms_per_iter": round(t_dense * 1e3, 3),
            "flash_ms_per_iter": round(t_flash * 1e3, 3),
            "dense_tflops_per_s": round(flops / t_dense / 1e12, 2),
            "flash_tflops_per_s": round(flops / t_flash / 1e12, 2),
            "flash_vs_dense": round(t_dense / t_flash, 2),
        }
        # sanity only (same computation, chained): per-iter MXU-pass
        # differences (~1e-3 f32-as-bf16) compound over reps, so the
        # bound is loose; per-dispatch probes gate accuracy vs f64.
        # Collected rather than asserted mid-loop so every shape's
        # measurement lands in `results` (and gets flushed) first
        if err >= 0.1:
            diverged.append((s, err))
    assert not diverged, f"in-program chains diverge: {diverged}"


def validate_long_context(results):
    """32k-token causal attention: flash completes on one chip where the
    dense path cannot even compile (the (S, S) score tensor exceeds HBM).
    Opt-in via TPU_VALIDATE_LONG=1 — first compile takes ~100s."""
    from keystone_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(3)
    b, h, s, d = 1, 8, 32768, 128
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))
        for _ in range(3)
    )
    fl = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False)
    )
    t = _time(fl, q, k, v, iters=3)
    flops = 4 * b * h * s * s * d / 2
    results["flash_32k_causal"] = {
        "shape": [b, h, s, d],
        "pallas_ms": round(t * 1e3, 1),
        "tflops_per_s": round(flops / t / 1e12, 2),
        "dense_jnp": "fails to compile (score tensor exceeds HBM)",
    }

    # TRAINING at 32k: flash forward + the blockwise backward (round 3).
    # The dense-recompute backward cannot run here (one (32k, 32k) f32
    # tensor is 4 GB, and the VJP holds several); the blockwise scans
    # peak at O(S·block)
    from keystone_tpu.ops.flash_attention import flash_attention_trainable

    grad_fn = jax.jit(
        jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention_trainable(q, k, v, True) ** 2
            ),
            argnums=(0, 1, 2),
        )
    )
    t_g = _time(lambda *a: grad_fn(*a)[0], q, k, v, iters=3)
    # fwd (rerun inside vjp: lse pass) + bwd ≈ 3.5x the fwd flops
    results["flash_32k_causal_train"] = {
        "shape": [b, h, s, d],
        "grad_ms": round(t_g * 1e3, 1),
        "tflops_per_s": round(3.5 * flops / t_g / 1e12, 2),
        "note": "fwd+blockwise-bwd; dense bwd cannot fit HBM at 32k",
    }


def validate_long_decode(results):
    """Long-context SERVING probe (round 4): 16k-token prefill into a
    GQA int8 KV cache, then autoregressive decode — the full serving
    stack (flash prefill, grouped decode that never materializes
    repeated K/V, per-position int8 cache whose scales factor out of
    both dots) measured as one jitted generate program. Opt-in via
    TPU_VALIDATE_LONG=1."""
    import dataclasses

    from keystone_tpu.models import lm_transformer as lm

    rng = np.random.default_rng(7)
    s_prompt, new = 16_384, 64
    model = lm.TransformerLM.create(
        jax.random.key(0), vocab=32_768, max_seq=s_prompt + new, dim=512,
        depth=4, num_heads=8, num_kv_heads=2, compute_dtype="bfloat16",
        pos_encoding="rope",
    )
    # int8 WEIGHTS are the claim — quantize, then route through the
    # fused Pallas kernel (float weights would make the flag a no-op)
    model = dataclasses.replace(
        lm.quantize_for_decode(model), int8_kernel="pallas"
    )
    prompt = jnp.asarray(
        rng.integers(0, 32_768, size=(1, s_prompt), dtype=np.int32)
    )

    def gen(p):
        return lm.generate(model, p, max_new=new, kv_dtype="int8")

    t0 = time.perf_counter()
    toks = gen(prompt)
    jax.block_until_ready(toks)
    first_run_s = time.perf_counter() - t0
    t = _time(gen, prompt, iters=2)
    # int8 codes streamed per decode step — K AND V buffers, shapes
    # derived from the model so the record can't desync from create()
    n_layers = len(model.blocks)
    hd = model.embed.shape[-1] // model.num_heads
    s_max = s_prompt + new
    cache_mb = 2 * n_layers * 1 * model.kv_heads * s_max * hd / 1e6
    results["serve_16k_gqa_int8kv"] = {
        "prompt": s_prompt,
        "new_tokens": new,
        "kv_heads": f"{model.kv_heads} of {model.num_heads} (GQA)",
        "cache_int8_mb": round(cache_mb, 1),
        "compile_plus_first_run_s": round(first_run_s, 1),
        "generate_ms": round(t * 1e3, 1),
        "note": "one jitted program: flash prefill + lax.scan decode, "
        "int8 KV cache (k+v codes above, + ~1/64 of that in f32 "
        "scales) and int8 weights via the fused Pallas matmul",
    }


def main() -> int:
    import os

    from keystone_tpu.core.runtime import init_backend

    device = init_backend()
    if device["platform"] != "tpu":
        print(
            f"not on TPU (platform={device['platform']}); refusing to "
            "validate"
        )
        return 2
    results: dict = {
        "platform": device["platform"],
        "device_kind": device["device_kind"],
        "num_devices": device["count"],
        "note": "compare speedups only within one artifact, never across "
        "runs",
    }
    # under chiprun_out/: git-ignored, and what the chip tool brings back
    out = REPO / "chiprun_out" / "TPU_VALIDATION.json"
    out.parent.mkdir(exist_ok=True)

    succeeded: set[str] = set()

    def _flush() -> dict:
        # merge-update: opt-in sections (e.g. the 32k long-context
        # record) must survive runs that don't re-validate them. Written
        # after EVERY probe — the r5 session lost a full 60-minute
        # tpu_validate to one wedged long-context probe because the
        # artifact only flushed at exit; completed probes now persist.
        try:
            prior = json.loads(out.read_text())
        except Exception:  # noqa: BLE001 — first run / corrupt file
            prior = {}
        merged = {**prior, **results}
        # a probe that succeeded THIS run retires its stale _error key
        # from earlier runs — the merge would otherwise keep a failure
        # marker forever next to fresh passing numbers
        for name in succeeded:
            merged.pop(f"{name}_error", None)
        out.write_text(json.dumps(merged, indent=2) + "\n")
        return merged

    probes = [
        validate_flash_attention,
        validate_flash_inprogram,
        validate_flash_step,
        validate_conv_convolver,
        validate_weighted_solver_scale,
    ]
    if os.environ.get("TPU_VALIDATE_LONG"):
        probes += [validate_long_context, validate_long_decode]
    failed = []
    for probe in probes:
        try:
            probe(results)
            succeeded.add(probe.__name__)
            results.pop(f"{probe.__name__}_error", None)
        except Exception as e:  # noqa: BLE001 — record, keep validating
            failed.append(probe.__name__)
            results[f"{probe.__name__}_error"] = f"{type(e).__name__}: {e}"
        merged = _flush()
    results = merged
    print(json.dumps(results, indent=2))
    if failed:
        print(f"\nFAILED probes: {', '.join(failed)} -> {out}")
        return 1
    print(f"\nall compiled-kernel validations passed -> {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
