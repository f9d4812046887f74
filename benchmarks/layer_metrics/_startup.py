"""What the ``setup_*`` readers share: where the time before the first
window went, from the program's own startup record.

``keystone_tpu/observe/spans.py`` records, from ``init_backend`` to the
end of the process's first fit (the warm-up fit of ``fit_loop``), a
``process`` root back-dated to the process's start, with
``runtime.init_backend``, the fit's own spans and every ``jit.trace`` /
``jit.lower`` / ``jit.cache_read`` / ``jit.backend_compile`` under it;
``startup_spans()`` hands the records back. A program without that
function, a period that is still open or never opened (no ``process``
root) and a run with no trace give None.

Every instant of the root's wall goes to the innermost span over it
(deepest in the tree, then the one that started last: the rule of
``_spans.layer_ms``; the sweep itself is the program's ``spans.self_ns``,
the one its ``startup {json}`` line is made from too), keyed here by
what that span is: ``backend``
(``runtime.init_backend``), ``trace`` / ``lower`` / ``cache_read`` /
``compile`` (the ``jit.*`` records, wherever they lie), ``first_run``
(any other span: the first fit less its ``jit.*`` time). What only the
root covers is ``import`` up to the backend's start (interpreter,
``import jax``, the harness's and the program's imports) and
``uncovered`` after it (the adapter's Python between the backend and the
fit). The eight parts add up to the root's wall.
"""

from __future__ import annotations

ROOT = "process"
KEYS = {
    "runtime.init_backend": "backend",
    "jit.trace": "trace",
    "jit.lower": "lower",
    "jit.cache_read": "cache_read",
    "jit.backend_compile": "compile",
}
PROGRAMS = ("jit.cache_read", "jit.backend_compile")


def records(m) -> list[dict] | None:
    """The ``process`` root of the startup period first, then every
    other record of it; None if there is no root."""
    if not m.get("trace"):
        return None
    try:
        from keystone_tpu.observe import spans
    except ImportError:
        return None
    read = getattr(spans, "startup_spans", None)
    recs = [r for r in (read() if read else []) if "t0_ns" in r]
    roots = [r for r in recs if r["name"] == ROOT and not r.get("parent")]
    if not roots:
        return None
    return [roots[-1], *(r for r in recs if r is not roots[-1])]


def parts(recs: list[dict]) -> dict[str, float]:
    """part -> seconds of the root's wall; ``wall`` and ``programs`` too."""
    from keystone_tpu.observe import spans

    root = recs[0]
    by_id = {r["span"]: r for r in recs}
    # whole nanoseconds until the end, so that the parts add up exactly
    mine = spans.self_ns(recs, root)
    alone = mine.pop(root["span"])  # what only the root covers
    ns = dict.fromkeys(["import", *KEYS.values(), "first_run", "uncovered"], 0)
    for sid, n in mine.items():
        ns[KEYS.get(by_id[sid]["name"], "first_run")] += n
    lo, hi = root["t0_ns"], root["t1_ns"]
    backend = [by_id[i]["t0_ns"] for i in mine if KEYS.get(by_id[i]["name"]) == "backend"]
    if backend:
        ns["import"] = min(min(max(min(backend), lo), hi) - lo, alone)
    ns["uncovered"] = alone - ns["import"]
    out = {k: v / 1e9 for k, v in ns.items()}
    out["wall"] = (hi - lo) / 1e9
    out["programs"] = sum(by_id[i]["name"] in PROGRAMS for i in mine)
    return out


def part(m, key: str) -> float | None:
    recs = records(m)
    if recs is None:
        return None
    return parts(recs)[key]
