"""Explicit pipeline instrumentation: ``instrument(pipeline)``.

The pipeline DSL already emits per-node events through lightweight hooks
in :mod:`keystone_tpu.core.pipeline` whenever an event sink is active.
:func:`instrument` is the stronger, opt-in form: it wraps every node so

- each call is recorded to the metrics registry (call counter + timer
  per node) regardless of whether an event sink is active,
- ``sync=True`` blocks on each node's output before stopping the clock,
  so per-node wall time attributes device work to the node that launched
  it instead of to whichever later node forces the value (JAX dispatch
  is async),
- outputs are bit-exact: the wrapper calls the node and returns its
  result untouched (``block_until_ready`` does not change values).

Wrapped nodes are still treenodes, so an instrumented pipeline remains a
jittable pytree — under tracing each wrapper records once with
``phase="compile"``.
"""

from __future__ import annotations

import dataclasses
import time

import jax

from keystone_tpu.core.pipeline import Pipeline, Transformer, is_tracing
from keystone_tpu.core.treenode import static_field, treenode
from keystone_tpu.observe import events as _events
from keystone_tpu.observe import metrics as _metrics


@treenode
class InstrumentedNode(Transformer):
    """One wrapped pipeline node; see module docstring."""

    inner: Transformer
    label: str = static_field(default="")
    sync: bool = static_field(default=False)

    # core.pipeline's per-node hook skips nodes carrying this marker so
    # an instrumented pipeline under an active sink records once, not twice
    _observe_instrumented = True

    def __call__(self, batch):
        reg = _metrics.get_registry()
        log = _events.active()
        tracing = is_tracing(batch)
        phase = "compile" if tracing else "apply"
        t0 = time.perf_counter()
        try:
            out = self.inner(batch)
            if self.sync and not tracing:
                jax.block_until_ready(out)
        except BaseException as e:
            wall = time.perf_counter() - t0
            reg.counter("node_errors", node=self.label).inc()
            if log is not None:
                log.emit(
                    "node",
                    node=self.label,
                    phase=phase,
                    wall_s=wall,
                    status="failed",
                    error=repr(e),
                )
            raise
        wall = time.perf_counter() - t0
        if tracing:
            # trace time is not apply time: a 100x-slower compile sample
            # would dominate the timer's mean/max — keep it in its own
            # series so the apply metrics stay honest
            reg.counter("node_traces", node=self.label).inc()
            reg.timer("node_trace_seconds", node=self.label).observe(wall)
        else:
            reg.counter("node_calls", node=self.label).inc()
            reg.timer("node_seconds", node=self.label).observe(wall)
        if log is not None:
            log.emit(
                "node", node=self.label, phase=phase, wall_s=wall, status="ok"
            )
        return out

    def __repr__(self):
        return f"InstrumentedNode({self.label})"


def _wrap(node: Transformer, label: str, sync: bool) -> InstrumentedNode:
    if isinstance(node, InstrumentedNode):
        # no double wrapping, but honor a CHANGED sync request — silently
        # keeping the old setting would mis-attribute async device work
        # the caller just asked to pin down
        if node.sync == sync:
            return node
        return dataclasses.replace(node, sync=sync)
    return InstrumentedNode(inner=node, label=label, sync=sync)


def instrument(pipe: Transformer, sync: bool = False) -> Transformer:
    """Wrap every node of ``pipe`` (or a single transformer) so calls are
    recorded per node. Idempotent: already-wrapped nodes are not wrapped
    again (their ``sync`` is updated if the request differs)."""
    if isinstance(pipe, Pipeline):
        return Pipeline(
            nodes=tuple(
                _wrap(node, _events.node_label(node, i), sync)
                for i, node in enumerate(pipe.nodes)
            )
        )
    return _wrap(pipe, _events.node_label(pipe), sync)
