"""The measured window: when set-up ended, what compiled inside it,
the peak of device memory at its end, and the traced span inside it.
One ``Window`` per run; the traffic kind drives it. What only one kind
of traffic wants (counters, spans of a server) that kind gathers itself
and hands to the readers through its ``facts``."""

from __future__ import annotations

import json
import shutil
import tempfile
import time

from harness import device, xplane

_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "traces",
    "/jax/core/compile/backend_compile_duration": "backend_compiles",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
}


def say(**line) -> None:
    """An earlier line of standard output: facts beside the result."""
    print(json.dumps(line), flush=True)


class Window:
    def __init__(self, *, t_process: float, seed: int, seconds: float,
                 trace: bool, chips: int):
        self.t_process = t_process
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.chips = chips
        self.setup_s: float | None = None
        self.t_begin = 0.0
        self.elapsed_s = 0.0
        self.compiles = dict.fromkeys(
            [*_COMPILE_EVENTS.values(), *_CACHE_EVENTS.values()], 0
        )
        self._open = False
        self.traced: dict | None = None
        self.traced_s = 0.0
        self.memory_peak_bytes: int | None = None
        self._trace_dir: str | None = None
        self._t_trace = 0.0
        import jax

        def on_duration(event, _duration, **_kw):
            if self._open and event in _COMPILE_EVENTS:
                self.compiles[_COMPILE_EVENTS[event]] += 1

        def on_event(event, **_kw):
            if self._open and event in _CACHE_EVENTS:
                self.compiles[_CACHE_EVENTS[event]] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def begin(self) -> float:
        """Set-up is over: everything from process start to here is
        ``setup_s``."""
        self._open = True
        self.t_begin = time.perf_counter()
        self.setup_s = self.t_begin - self.t_process
        return self.t_begin

    def end(self) -> None:
        self.elapsed_s = time.perf_counter() - self.t_begin
        self._open = False
        self.memory_peak_bytes = device.memory_peak_bytes()
        if self._trace_dir is not None:
            # reduced here and not in trace_stop: the reduction is
            # seconds of Python that belong to no fit
            try:
                self.traced, layout = xplane.reduce_dir(
                    self._trace_dir, self.traced_s
                )
                say(trace_layout=layout)
            finally:
                shutil.rmtree(self._trace_dir, ignore_errors=True)

    def trace_start(self) -> None:
        import jax

        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self._trace_dir, profiler_options=options)
        self._t_trace = time.perf_counter()

    def trace_stop(self) -> None:
        import jax

        self.traced_s = time.perf_counter() - self._t_trace
        jax.profiler.stop_trace()
