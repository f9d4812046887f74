"""AOT export: a fitted pipeline compiled for serving, before traffic.

A server must not pay tracing + XLA compilation on its first request —
or worse, one compilation per distinct request size. The exported form
fixes both:

- the fitted pipeline runs through the planner's operator-selection
  pass (``plan/``), so the served program is the optimized one,
- the apply is lowered and compiled **ahead of time** for a small set
  of batch *buckets* (``jit(...).lower().compile()``); requests pad to
  the nearest bucket, so every request size maps to an existing
  executable,
- the persistent compilation cache
  (:func:`keystone_tpu.core.runtime.enable_compilation_cache`) backs the
  build: a relaunched server reloads executables in seconds instead of
  recompiling for minutes — the elastic-rejoin fix doing double duty as
  the serving cold-start fix.

``export_pipeline`` accepts a fitted pipeline object or a
``save_fitted`` checkpoint path (loaded with the spec verified — spec
drift refuses to serve, see :mod:`keystone_tpu.core.serialization`).
"""

from __future__ import annotations

import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.core.logging import get_logger
from keystone_tpu.core.pipeline import Pipeline, Transformer, jit_apply
from keystone_tpu.core.runtime import enable_compilation_cache
from keystone_tpu.observe import metrics as _metrics
from keystone_tpu.serve.queue import buckets_from_env

logger = get_logger("keystone_tpu.serve.export")


class ExportedApply:
    """A fitted pipeline AOT-compiled over fixed batch buckets.

    ``__call__`` pads a (n, ...) batch up to the smallest compiled
    bucket, runs the stored executable, and trims back to n rows; a
    batch larger than the biggest bucket streams through it in
    bucket-size chunks. An input the AOT executable refuses is an
    error (the server answers 500 and logs it): a quiet fall-through to
    the jit path would recompile per request and hide a placement or
    layout bug on the device.
    """

    def __init__(
        self,
        pipe: Transformer,
        sample,
        *,
        buckets: Sequence[int] | None = None,
        optimize: bool = True,
    ):
        sample = np.asarray(sample)
        if sample.ndim < 1 or sample.shape[0] < 1:
            raise ValueError(
                f"sample shape {sample.shape}: need a (n, ...) batch probe"
            )
        self.row_shape = tuple(sample.shape[1:])
        self.dtype = sample.dtype
        self.buckets = tuple(sorted(buckets or buckets_from_env()))
        if not self.buckets or any(b <= 0 for b in self.buckets):
            raise ValueError(f"buckets={self.buckets}: need positive sizes")
        self.plan = None
        if optimize:
            # the KeystoneML operator-selection pass: the plan's rewrite
            # rules choose the physical operators the server will run
            from keystone_tpu import plan as plan_mod

            self.plan = plan_mod.plan_pipeline(pipe, sample=sample)
            pipe = self.plan.pipeline()
        self.pipe = pipe
        self._compiled: dict[int, Any] = {}
        self.cold_start_s = 0.0
        self.compile()

    def compile(self) -> float:
        """Lower + compile one executable per bucket (idempotent).
        Returns the wall seconds the build took — the cold-start cost
        the compilation cache amortizes across relaunches."""
        cache_dir = enable_compilation_cache()
        t0 = time.perf_counter()
        reg = _metrics.get_registry()
        for b in self.buckets:
            if b in self._compiled:
                continue
            probe = jnp.zeros((b, *self.row_shape), self.dtype)
            self._compiled[b] = jit_apply.lower(self.pipe, probe).compile()
            reg.counter("serve_aot_compiled", kind="pipeline").inc()
        self.cold_start_s = time.perf_counter() - t0
        logger.info(
            "exported apply: %d bucket executable(s) %s in %.2fs%s",
            len(self._compiled),
            list(self.buckets),
            self.cold_start_s,
            f" (compile cache: {cache_dir})" if cache_dir else "",
        )
        return self.cold_start_s

    # ------------------------------------------------------------- apply

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def _run_bucket(self, batch) -> Any:
        """Dispatch one exactly-bucket-sized batch through its AOT
        executable."""
        return self._compiled[batch.shape[0]](self.pipe, batch)

    def __call__(self, rows) -> Any:
        """(n, ...) rows → row-indexed outputs, any n >= 1."""
        rows = np.asarray(rows)
        if rows.shape[1:] != self.row_shape:
            raise ValueError(
                f"request row shape {rows.shape[1:]} != exported "
                f"{self.row_shape}"
            )
        rows = rows.astype(self.dtype, copy=False)
        n = rows.shape[0]
        cap = self.buckets[-1]
        if n > cap:
            # oversized batch: stream exactly-cap-sized chunks through
            # the largest executable via the plan executor's staged
            # drain (transfer of chunk k+1 overlaps dispatch k)
            from keystone_tpu.plan.executor import serve_stream

            return serve_stream(self._run_bucket, rows, cap)
        bucket = self._bucket_for(n)
        padded = rows
        if n < bucket:
            padded = np.concatenate(
                [rows, np.zeros((bucket - n, *self.row_shape), self.dtype)],
                axis=0,
            )
        out = self._run_bucket(jnp.asarray(padded))
        if n == bucket:
            return out
        return jax.tree_util.tree_map(lambda a: a[:n], out)


def export_pipeline(
    pipe_or_path: Transformer | str,
    sample,
    *,
    buckets: Sequence[int] | None = None,
    optimize: bool = True,
) -> ExportedApply:
    """Export a fitted pipeline (object, or a ``save_fitted`` /
    ``save_pipeline`` checkpoint path) as an AOT-compiled serving
    apply."""
    if isinstance(pipe_or_path, str):
        from keystone_tpu.core.serialization import load_pipeline

        pipe_or_path = load_pipeline(pipe_or_path)
    if not isinstance(pipe_or_path, Transformer):
        pipe_or_path = Pipeline.of(pipe_or_path)
    return ExportedApply(
        pipe_or_path, sample, buckets=buckets, optimize=optimize
    )


def export_lm(
    model,
    *,
    slots: int = 8,
    s_max: int = 512,
    quantize: bool = False,
    int8_kv: bool = False,
    warm: bool = True,
    **loop_kw: Any,
):
    """Export an LM for continuous-batching serve: optional weight-only
    int8 (+ int8 KV cache — the decode-bandwidth levers), a
    :class:`~keystone_tpu.serve.decode_loop.DecodeLoop` slot pool, and
    every program compiled up front (``warm=True``)."""
    from keystone_tpu.serve.decode_loop import DecodeLoop

    enable_compilation_cache()
    if quantize:
        from keystone_tpu.models.lm.decode import quantize_for_decode

        model = quantize_for_decode(model)
    loop = DecodeLoop(
        model,
        slots=slots,
        s_max=s_max,
        kv_dtype="int8" if int8_kv else None,
        **loop_kw,
    )
    if warm:
        loop.warm()
    return loop
