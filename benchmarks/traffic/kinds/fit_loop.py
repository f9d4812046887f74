"""Whole fits back to back. One fit is one call of the configuration
adapter's ``one_fit(seed, sizes)``, which calls the program's own entry.
No new fit starts once ``seconds`` have passed; the one in flight
finishes, and the rate is taken over the real elapsed time."""

from __future__ import annotations

import time

from harness.window import say


def run(adapter, sizes: dict, mix: dict, win) -> dict:
    # set-up: one whole fit warms every program the window will run
    t = time.perf_counter()
    warm = adapter.one_fit(win.seed, sizes)
    say(warm_up_fit={"wall_s": time.perf_counter() - t, **warm})

    fits, failed, walls = [], 0, []
    t_begin = win.begin()
    while time.perf_counter() - t_begin < win.seconds:
        tracing = win.trace and len(walls) == mix["traced_fit"]
        if tracing:
            win.trace_start()
        t = time.perf_counter()
        try:
            fits.append(adapter.one_fit(win.seed, sizes))
        except Exception as e:  # noqa: BLE001 - a failed fit is a result
            failed += 1
            say(fit_failed=repr(e))
        walls.append(time.perf_counter() - t)
        if tracing:
            win.trace_stop()
        if failed >= mix["stop_after_failures"]:
            break
    win.end()

    rows = sizes["train_rows"]
    say(fits={"walls_s": walls, "rows_per_fit": rows, "results": fits})
    correct, detail = adapter.check_fits(win.seed, sizes, fits)
    say(check=detail)
    return {
        "attempted": len(fits) + failed,
        "failed": failed,
        "correct": bool(correct and fits and not failed),
        "metrics": {
            "fit_rows_per_s_per_chip": rows * len(fits)
            / win.elapsed_s
            / win.chips,
        },
        "facts": {"traced_fits": 1},
    }
