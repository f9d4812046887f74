"""Flagship LM train-step MFU push (VERDICT r4 #2: 0.19 → ≥0.35).

Sweeps the levers the round-4 review names — per-step token count
(batch), dense- vs blockwise-attention backward, chunked CE — on the
bench shape (dim 1024 × 8 layers, S=2048, bf16 policy). Each config
runs in a fresh subprocess (a same-shape jit cache would otherwise
serve config A's program to config B; the KST_FLASH_* knobs are
per-call reads but the compiled step is cached by shape).

Writes LM_MFU_PUSH.json (every measurement + the winner) and, when the
winner beats the current bench default by >3%, LM_BENCH_TUNED.json —
which bench.bench_lm_train picks up automatically, so the chip
session's closing bench.py run records the tuned number without a
human in the loop.

Run ON CHIP, in one call of the chip tool (the parent stays off jax so
each child owns the chip; unset JAX_PLATFORMS means TPU). ~1-3 min/config, grid of 9.
"""

from __future__ import annotations

import datetime
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (batch, dense_bwd, logit_chunk, remat) — baseline first, then
# single-lever moves, then the combined candidates. dense_bwd=False
# forces the blockwise flash backward (KST_FLASH_DENSE_BWD_MAX=0):
# at S=2048 the dense path's transient (S,S) f32 tensors are ~2.1 GB of
# HBM traffic per (B,H) slice class — whether recompute beats that
# traffic is exactly what the chip must answer. logit_chunk must divide
# the S=2048 trained positions (the r5 session failed 8192 on exactly
# that check — fixed to 1024).
CONFIGS = [
    (8, True, 0, False),
    (8, False, 0, False),
    (8, True, 1024, False),
    (16, True, 0, False),
    (16, False, 0, False),
    (32, True, 0, False),
    (32, False, 0, False),
    (32, True, 1024, False),
    (32, True, 0, "dots"),  # memory headroom fallback for the big batch
]

_CHILD = r"""
import sys, json
sys.path.insert(0, {repo!r})
import bench
from keystone_tpu.core.runtime import init_backend
init_backend()  # the platform rule: unset JAX_PLATFORMS means TPU
r = bench._lm_train_step_rate(
    seq=bench.LM_SEQ, dim=bench.LM_DIM, depth=bench.LM_DEPTH,
    heads=bench.LM_HEADS, batch={batch}, iters=3,
    logit_chunk={logit_chunk}, remat={remat!r},
)
print("RESULT " + json.dumps(r))
"""


def _tag(batch, dense_bwd, lc, remat) -> str:
    return (
        f"b{batch}_{'dense' if dense_bwd else 'blockwise'}_lc{lc}"
        + (f"_remat{remat}" if remat else "")
    )


def _write(results) -> dict:
    ok = [r for r in results if "tokens_per_s" in r]
    best = (
        max(ok, key=lambda r: (r["tflops_per_s"], r["tokens_per_s"]))
        if ok
        else None
    )
    base_tag = _tag(*CONFIGS[0])  # first config IS the bench default
    base = next((r for r in ok if r["config"] == base_tag), None)
    art = {
        "workload": "flagship LM train step (bench shape, bf16 policy)",
        "results": results,
        "configs_total": len(CONFIGS),
        "configs_run": len(results),
        "truncated": len(results) < len(CONFIGS),
        "best": best,
        "baseline": base,
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc
        ).isoformat(),
    }
    with open(os.path.join(REPO, "LM_MFU_PUSH.json"), "w") as f:
        json.dump(art, f, indent=1)
    # hand the winner to bench.py only when it actually wins
    if best and base and best["tflops_per_s"] > 1.03 * base["tflops_per_s"]:
        with open(os.path.join(REPO, "LM_BENCH_TUNED.json"), "w") as f:
            json.dump(
                {
                    "shape": "dim1024_depth8_s2048",
                    "batch": best["batch"],
                    "logit_chunk": best["logit_chunk"],
                    "dense_bwd": best["dense_bwd"],
                    "remat": best["remat"],
                    "measured_tflops_per_s": best["tflops_per_s"],
                    "from": "tools/lm_mfu_push.py",
                    "timestamp": art["timestamp"],
                },
                f,
                indent=1,
            )
    return art


def main() -> None:
    results = []
    for batch, dense_bwd, lc, remat in CONFIGS:
        env = dict(os.environ)
        if not dense_bwd:
            env["KST_FLASH_DENSE_BWD_MAX"] = "0"
        tag = _tag(batch, dense_bwd, lc, remat)
        try:
            out = subprocess.run(
                [
                    sys.executable,
                    "-c",
                    _CHILD.format(
                        repo=REPO, batch=batch, logit_chunk=lc, remat=remat
                    ),
                ],
                env=env,
                capture_output=True,
                text=True,
                timeout=600,
            )
            line = next(
                (
                    l
                    for l in out.stdout.splitlines()
                    if l.startswith("RESULT ")
                ),
                None,
            )
            if out.returncode or line is None:
                results.append(
                    {"config": tag, "error": out.stderr.strip()[-300:]}
                )
                print(f"# {tag}: FAILED", file=sys.stderr)
            else:
                r = json.loads(line[len("RESULT "):])
                results.append(
                    {
                        "config": tag,
                        "batch": batch,
                        "dense_bwd": dense_bwd,
                        "logit_chunk": lc,
                        "remat": remat,
                        "tokens_per_s": round(r["tokens_per_s"], 1),
                        "tflops_per_s": round(r["tflops_per_s"], 2),
                    }
                )
                print(
                    f"# {tag}: {r['tokens_per_s']:.0f} tok/s "
                    f"{r['tflops_per_s']:.1f} TF/s",
                    file=sys.stderr,
                )
        except subprocess.TimeoutExpired:
            results.append({"config": tag, "error": "timeout"})
            print(f"# {tag}: TIMEOUT", file=sys.stderr)
        _write(results)

    print(json.dumps(_write(results)))


if __name__ == "__main__":
    main()
