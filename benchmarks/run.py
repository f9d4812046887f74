"""One run of one cell:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell, its configuration, its traffic mix and its per-layer
metrics by name (``harness/find.py``), brings the backend up, lets the
mix's kind drive the window, and prints the result as the last line.
``--rehearse-cpu`` runs the cell's ``toy`` sizes on an asked-for CPU and
prints every metric's value as null: a CPU clock is no device metric.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
# the program under test lies beside benchmarks/, or on PYTHONPATH
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]

from harness import device, find, peaks, xplane  # noqa: E402
from harness.window import Window, say  # noqa: E402


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument(
        "--rehearse-cpu",
        action="store_true",
        help="toy sizes on JAX_PLATFORMS=cpu; prints no metric value",
    )
    return p.parse_args(argv)


def sizes_of(cfg: dict, cell: dict, adapter, rehearse: bool) -> dict:
    """The configuration's sizes as this cell runs them: the file's
    top-level scalars, then the group the cell's ``sizes_group`` names
    (the fit cut), then ``toy`` in a rehearsal, then whatever the
    adapter's ``cell_sizes`` derives from those and the cell's chips."""
    sizes = {k: v for k, v in cfg.items() if not isinstance(v, (dict, list))}
    sizes.update(cfg.get(cell.get("sizes_group", ""), {}))
    if rehearse:
        sizes.update(cfg["toy"])
    sizes["chips"] = cell["chips"]
    return getattr(adapter, "cell_sizes", dict)(sizes)


def per_layer(man: dict, cell_name: str, measured: dict) -> dict:
    out = {}
    for m in find.metrics_of(man, "per_layer", cell_name):
        value = find.layer_metric(m["name"]).read(measured)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    man = find.manifest()
    cell = find.cell(args.workload)
    cfg, adapter = find.config(cell["config"])
    mix, kind = find.traffic(cell["traffic"])
    dev = device.bring_up(cell["chips"], args.rehearse_cpu)
    sizes = sizes_of(cfg, cell, adapter, args.rehearse_cpu)
    say(cell=args.workload, seed=args.seed, sizes=sizes, device=dev)

    win = Window(
        t_process=T_PROCESS,
        seed=args.seed,
        seconds=args.seconds,
        trace=bool(args.trace),
        chips=cell["chips"],
    )
    result = kind.run(adapter, sizes, mix, win)
    say(window={"elapsed_s": win.elapsed_s, "compiles_inside": win.compiles})

    if win.memory_peak_bytes is not None:
        dev["memory_peak_bytes"] = win.memory_peak_bytes
    line = {
        "correct": result["correct"],
        "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": {},
        "device": dev,
    }
    if args.trace:
        measured = {
            "trace": win.traced,
            "facts": result["facts"],
            "sizes": sizes,
            "work": adapter.ops_and_bytes(sizes),
            "programs": cfg.get("programs", {}),
            "peaks": None if args.rehearse_cpu else peaks.peaks_for(dev["kind"]),
        }
        line["metrics"] = per_layer(man, args.workload, measured)
        if win.traced is not None:
            dev["busy_s"] = win.traced["busy_s"]
            dev["window_s"] = win.traced["window_s"]
            line["breakdown"] = {
                "device_ops": xplane.top(win.traced["ops_s"]),
                "idle_gaps": xplane.top(win.traced["gaps_s"]),
            }
            say(trace={"programs_s": xplane.top(win.traced["programs_s"]),
                       "program_runs": win.traced["program_runs"]})
    else:
        values = {"setup_s": win.setup_s, **result["metrics"]}
        for m in find.metrics_of(man, "end_to_end", args.workload):
            if m["name"] in values:
                line["metrics"][m["name"]] = {
                    "value": values[m["name"]],
                    "unit": m["unit"],
                }
    if args.rehearse_cpu:
        say(rehearsal_cpu_clock_not_device_metrics={
            k: v["value"] for k, v in line["metrics"].items()})
        for v in line["metrics"].values():
            v["value"] = None
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
