"""What the span readers share: the host's time in the traced fit, by
layer, from the program's own spans.

This is the second place where the benchmark touches the program (the
configuration's adapter is the first): ``keystone_tpu/observe/spans.py``
records spans while a ``jax.profiler`` session is on, which the traced
run's is, and ``profiled_spans()`` hands back the records of the newest
session. A program without that function, or without a ``fit`` root
span, gives None, and so does a run with no trace.

A record holds ``name``, ``span``, ``parent``, ``trace`` and ``t0_ns`` /
``t1_ns`` on one host clock. Every instant of the root's wall goes to
the innermost span over it (deepest in the tree, then the one that
started last), so a span keeps its duration less what its children
cover, overlapping children are not counted twice, and the layers add
up to the wall. A span belongs to the layer its name starts with, else
to its parent's; what only the root covers is ``uncovered``.
"""

from __future__ import annotations

ROOT = "fit"
# name prefix -> layer, first match wins
LAYERS = (
    ("jit.", "compile"),
    ("fit.load", "load"),
    ("fit.h2d", "load"),
    ("fit.featurize", "featurize"),
    ("featurize.", "featurize"),
    ("fit.labels", "featurize"),
    ("fit.solve", "solve"),
    ("fit.score", "score"),
    ("score.", "score"),
)
COMPILES = ("jit.backend_compile", "jit.cache_read")


def records(m) -> list[dict] | None:
    """The records of the last ``fit`` root of the profiled session and
    of everything under it, root first; None if there is none."""
    if not m.get("trace"):
        return None
    try:
        from keystone_tpu.observe import spans
    except ImportError:
        return None
    read = getattr(spans, "profiled_spans", None)
    recs = [r for r in (read() if read else []) if "t0_ns" in r]
    roots = [r for r in recs if r["name"] == ROOT and not r.get("parent")]
    if not roots:
        return None
    root = roots[-1]
    by_id = {r["span"]: r for r in recs if r["trace"] == root["trace"]}

    def under_root(r) -> bool:
        seen = set()
        while r is not root and r["span"] not in seen:
            seen.add(r["span"])
            r = by_id.get(r.get("parent"))
            if r is None:
                return False
        return r is root

    return [root, *(r for r in by_id.values() if r is not root and under_root(r))]


def layer_ms(recs: list[dict]) -> dict[str, float]:
    """layer -> ms of the root's wall (``wall`` and ``uncovered`` too)."""
    root = recs[0]
    by_id = {r["span"]: r for r in recs}
    depth, layer = {root["span"]: 0}, {root["span"]: "uncovered"}

    def place(r) -> None:
        if r["span"] in depth:
            return
        parent = by_id[r["parent"]]
        place(parent)
        depth[r["span"]] = depth[parent["span"]] + 1
        layer[r["span"]] = next(
            (to for prefix, to in LAYERS if r["name"].startswith(prefix)),
            layer[parent["span"]],
        )

    for r in recs:
        place(r)
    lo, hi = root["t0_ns"], root["t1_ns"]
    cuts = sorted({min(max(t, lo), hi) for r in recs for t in (r["t0_ns"], r["t1_ns"])})
    out = {"wall": (hi - lo) / 1e6, "uncovered": 0.0}
    for a, b in zip(cuts, cuts[1:]):
        over = [r for r in recs if r["t0_ns"] <= a and r["t1_ns"] >= b]
        inner = max(over, key=lambda r: (depth[r["span"]], r["t0_ns"]))
        key = layer[inner["span"]]
        out[key] = out.get(key, 0.0) + (b - a) / 1e6
    return out


def host_ms(m, layer: str) -> float | None:
    recs = records(m)
    if recs is None:
        return None
    return layer_ms(recs).get(layer, 0.0)
