"""Tracing / profiling hooks.

The reference's observability is Spark's UI plus wall-clock brackets and
``RDD.setName`` tags (SURVEY.md §5). Here the same two ideas map to:

- :func:`trace` — capture an XLA/TPU profile (tensorboard-viewable) around
  a code block (``jax.profiler``); ``python -m keystone_tpu observe idle
  <dir>`` reduces it to device idle time by host span,
- ``keystone_tpu.observe.spans.span`` — name a host region: while a
  profile is being captured it shows up in the trace timeline under its
  name (the ``setName`` analog),
- :func:`log_time` (re-exported from core.logging) — wall-clock brackets.

``KEYSTONE_TRACE_DIR`` gates :func:`trace`: unset, the explicit
``log_dir`` argument is used as before; set to a path, it is the default
directory when no ``log_dir`` is passed; set to ``""``/``"0"``/``"off"``,
tracing is a NO-OP even when a directory is passed — the production kill
switch (a profiler failure must never take down a serving pipeline, and
neither should a profiler at all when ops has it disabled).
"""

from __future__ import annotations

import contextlib
import os

import jax

from keystone_tpu.core.logging import get_logger, log_time  # noqa: F401

logger = get_logger("keystone_tpu.profiling")

ENV_TRACE_DIR = "KEYSTONE_TRACE_DIR"
_DISABLED_VALUES = ("", "0", "off", "none")


def _effective_trace_dir(log_dir: str | None) -> str | None:
    env = os.environ.get(ENV_TRACE_DIR)
    if env is not None and env.lower() in _DISABLED_VALUES:
        return None  # explicit kill switch beats any argument
    if log_dir:
        return log_dir
    return env or None


@contextlib.contextmanager
def trace(log_dir: str | None = None):
    """Profile the enclosed block to ``log_dir`` (view with tensorboard).

    Degrades instead of aborting: a failure inside
    ``jax.profiler.start_trace`` (unwritable directory, a second
    concurrent trace, a backend without profiler support) logs a warning
    and runs the block unprofiled. No-op when gated off (module
    docstring) or when no directory is configured at all.
    """
    log_dir = _effective_trace_dir(log_dir)
    if log_dir is None:
        yield
        return
    started = False
    try:
        jax.profiler.start_trace(log_dir)
        started = True
    except Exception as e:  # noqa: BLE001 — degrade, don't abort the run
        logger.warning(
            "profiler trace to %s unavailable (%r); running unprofiled",
            log_dir,
            e,
        )
    try:
        yield
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
                logger.info("profile written to %s", log_dir)
            except Exception as e:  # noqa: BLE001
                logger.warning("profiler stop_trace failed: %r", e)
