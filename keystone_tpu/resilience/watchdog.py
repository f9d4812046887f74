"""Hang watchdogs: a step-time monitor thread for training loops and
the diagnostics it prints when a step stops completing.

A hung ``jax.distributed.initialize`` or a wedged device doesn't
raise — it just stops. The watchdog turns "stops" into evidence: when
no :meth:`Watchdog.pet` arrives within ``timeout_s``, it logs a WARNING
with every thread's current stack, emits a ``resilience`` event, bumps
the ``watchdog_stalls`` counter, and invokes the optional ``on_stall``
callback (which may escalate — e.g. abort the process — but the default
deliberately only diagnoses: killing a run that would have recovered is
the watchdog's own failure mode).

One stall fires once; the next pet re-arms it, so a recovered loop that
stalls again later is reported again.

``escalate_after=N`` upgrades diagnosis to action: after N consecutive
timeout periods with no pet, the watchdog dumps every thread stack one
final time and hard-aborts the process (``os._exit`` with
:data:`keystone_tpu.resilience.cluster.EXIT_WEDGED`). A wedged main
thread would otherwise keep the cluster heartbeat daemon alive forever
— the host looks healthy to the failure detector while contributing
nothing — so fast-failing is what lets the run supervisor relaunch it.

The multihost init hang is handled differently — JAX's coordinator
already owns a timeout, so :func:`keystone_tpu.parallel.multihost.
initialize` passes it through and wraps the failure with the
coordinator address; see that module.
"""

from __future__ import annotations

import os
import sys
import threading
import time
import traceback
from typing import Callable


def dump_stacks() -> str:
    """Every thread's current Python stack, formatted — the first thing
    a hang diagnosis needs."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for ident, frame in sys._current_frames().items():
        out.append(f"--- thread {names.get(ident, '?')} ({ident}) ---")
        out.extend(
            line.rstrip() for line in traceback.format_stack(frame)
        )
    return "\n".join(out)


class Watchdog:
    """Daemon thread that flags a loop whose heartbeat stops.

    Usage::

        with Watchdog(timeout_s=120, label="lm_train") as dog:
            for step in ...:
                run_step()
                dog.pet()

    ``clock`` is injectable for tests; the monitor polls at
    ``poll_s`` (default ``timeout_s / 4``, floored to 10 ms).
    """

    def __init__(
        self,
        timeout_s: float,
        label: str = "loop",
        on_stall: Callable[[], None] | None = None,
        poll_s: float | None = None,
        clock: Callable[[], float] = time.monotonic,
        escalate_after: int | None = None,
        abort: Callable[[int], None] | None = None,
    ):
        if timeout_s <= 0:
            raise ValueError(f"timeout_s={timeout_s}: must be > 0")
        if escalate_after is not None and escalate_after < 1:
            raise ValueError(
                f"escalate_after={escalate_after}: must be >= 1"
            )
        self.timeout_s = timeout_s
        self.label = label
        self.on_stall = on_stall
        self.escalate_after = escalate_after
        # injectable for tests; production default is os._exit — a
        # wedged interpreter may not run atexit/finally anyway, and the
        # point is to die fast enough to trip the failure detector
        self._abort = abort if abort is not None else os._exit
        self.poll_s = poll_s if poll_s is not None else max(timeout_s / 4, 0.01)
        self.clock = clock
        self.stalls = 0
        self._last_pet = clock()
        self._flagged = False
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._lock = threading.Lock()

    def pet(self) -> None:
        """Record a heartbeat; re-arms after a reported stall."""
        with self._lock:
            self._last_pet = self.clock()
            self._flagged = False

    @property
    def running(self) -> bool:
        return self._thread is not None

    def start(self) -> "Watchdog":
        with self._lock:
            self._last_pet = self.clock()  # the clock starts NOW, not
            self._flagged = False  # at construction (callers may defer
            # start past a compile/warmup phase)
        self._thread = threading.Thread(
            target=self._monitor, name=f"watchdog:{self.label}", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)

    def __enter__(self) -> "Watchdog":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _monitor(self) -> None:
        while not self._stop.wait(self.poll_s):
            escalate = False
            with self._lock:
                idle = self.clock() - self._last_pet
                stalled = idle > self.timeout_s and not self._flagged
                if stalled:
                    self._flagged = True
                    self.stalls += 1
                # "consecutive stalls" = full timeout periods since the
                # last pet; a single pet resets the count to zero
                if (
                    self.escalate_after is not None
                    and idle // self.timeout_s >= self.escalate_after
                ):
                    escalate = True
            if stalled:
                self._report(idle)
            if escalate:
                self._escalate(idle)
                return  # unreachable with the real os._exit abort;
                # injected test aborts must not re-fire every poll

    def _escalate(self, idle: float) -> None:
        from keystone_tpu.core.logging import get_logger
        from keystone_tpu.resilience.cluster import EXIT_WEDGED
        from keystone_tpu.resilience.emit import decision

        get_logger("keystone_tpu.resilience").critical(
            "%s: no progress for %.1fs (%d consecutive %.1fs timeouts) "
            "— this host is wedged; hard-aborting so the failure "
            "detector / supervisor can replace it. Thread stacks:\n%s",
            self.label,
            idle,
            self.escalate_after,
            self.timeout_s,
            dump_stacks(),
        )
        decision(
            "watchdog_abort",
            counter="watchdog_aborts",
            counter_labels={"label": self.label},
            label=self.label,
            idle_s=idle,
            timeout_s=self.timeout_s,
            escalate_after=self.escalate_after,
        )
        self._abort(EXIT_WEDGED)

    def _report(self, idle: float) -> None:
        from keystone_tpu.core.logging import get_logger
        from keystone_tpu.resilience.emit import decision

        get_logger("keystone_tpu.resilience").warning(
            "%s: no progress for %.1fs (timeout %.1fs); thread stacks:\n%s",
            self.label,
            idle,
            self.timeout_s,
            dump_stacks(),
        )
        decision(
            "watchdog_stall",
            counter="watchdog_stalls",
            counter_labels={"label": self.label},
            label=self.label,
            idle_s=idle,
            timeout_s=self.timeout_s,
        )
        if self.on_stall is not None:
            try:
                self.on_stall()
            except Exception:  # noqa: BLE001 — a broken escalation hook
                # must not kill the monitor thread; the stall is already
                # logged above
                get_logger("keystone_tpu.resilience").exception(
                    "%s: on_stall callback failed", self.label
                )
