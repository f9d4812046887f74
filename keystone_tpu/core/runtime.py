"""Process-level runtime setup: which backend a process runs on, and
where its compiled programs are kept.

Platform rule, the same for every entry point: an explicit
``JAX_PLATFORMS`` is obeyed and stated; unset means TPU, and a machine
with no TPU then fails at backend init with the backend's own error.
``JAX_PLATFORMS=cpu`` is the one way to ask for the CPU — jax's own
default (quietly hand out CPU devices when no accelerator initialises)
would let a CPU run pass for a chip run.

The reference amortizes JIT warmup inside one long-lived JVM; a CLI
framework on JAX pays XLA compilation on every fresh process instead.
The persistent compilation cache removes that: compiled executables are
keyed by HLO and reloaded across processes.
"""

from __future__ import annotations

import atexit
import json
import logging
import os

from keystone_tpu.core.logging import get_logger

logger = get_logger("keystone_tpu.runtime")

# the one in-code cache location: fixed (a directory that moves never
# hits), inside the checkout, git-ignored
_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".jax_cache")
)

_backend: dict | None = None


def select_platform() -> str:
    """Apply the platform rule before first device use; returns the
    platform string in force. The choice is exported so child processes
    (fleet replicas, supervised workers) run under the same one."""
    plat = os.environ.get("JAX_PLATFORMS") or "tpu"
    os.environ["JAX_PLATFORMS"] = plat
    import jax

    # backend init is lazy, so this wins even though jax is imported
    jax.config.update("jax_platforms", plat)
    return plat


def enable_compilation_cache() -> str | None:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself, no
    directory is set in code and child processes inherit the variable.
    Where it is not, the cache is ``.jax_cache/`` at the root of the
    checkout — resolved here and nowhere else, so every process of every
    entry point (fleet replicas, chaos children, multihost workers)
    shares one cache. Safe to call multiple times; must run before the
    first jit compilation to help that compilation.

    A relaunched or rejoining host warm-starts from already-compiled
    executables instead of recompiling for minutes — the
    elastic-multihost rejoin cost and the serving cold start are both a
    compilation-cache problem.
    """
    import jax

    # cache everything that took meaningful compile time; tiny programs
    # recompile faster than they deserialize
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    try:
        os.makedirs(_CACHE_DIR, exist_ok=True)
    except OSError:
        # best-effort optimization: a read-only checkout must not take
        # down the entry points
        return None
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    return _CACHE_DIR


def init_backend() -> dict:
    """Bring the backend up NOW under the platform rule and log what it
    is: one ``device {json}`` line (platform, device_kind, count, compile
    cache) that a supervising process can read from outside, one
    ``startup {json}`` line when the process's first unit of work has
    ended (where the time before it went: ``observe/spans.py``'s startup
    period, which opens here), and one ``compile {json}`` line at exit
    (backend compile seconds, persistent cache hits and misses). Raises
    the backend's own error when the selected platform cannot
    initialise. Idempotent."""
    global _backend
    if _backend is not None:
        return _backend
    from keystone_tpu.observe import spans

    up: dict = {}  # what the spans say when they close: the backend, once up
    process = spans.open_startup(
        attrs=lambda: {
            "platform": up.get("platform"),
            "chips": up.get("count"),
            "compile_cache": up.get("compile_cache"),
        },
        report=lambda summary: logger.info("startup %s", json.dumps(summary)),
    )
    with spans.span(
        "runtime.init_backend",
        parent=process,
        platform=lambda: up.get("platform"),
        device_kind=lambda: up.get("device_kind"),
        count=lambda: up.get("count"),
    ):
        select_platform()
        cache_dir = enable_compilation_cache()
        import jax

        devs = jax.devices()
        up.update(
            platform=devs[0].platform,
            device_kind=devs[0].device_kind,
            count=len(devs),
            compile_cache=cache_dir,
        )
    _backend = up
    logger.info("device %s", json.dumps(_backend))

    def report_compiles() -> None:
        # the process is ending: a log stream some harness captured and
        # closed already is not worth a traceback
        logging.raiseExceptions = False
        logger.info(
            "compile %s",
            json.dumps({k: round(v, 3) for k, v in spans.compile_counts().items()}),
        )

    atexit.register(report_compiles)
    return _backend
