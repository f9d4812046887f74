"""Flash-attention block-size sweep for the long-context train step.

VERDICT r3 #2 names attention block sizes as an MFU lever; the forward
kernels' tunables are env knobs (`KST_FLASH_*`, ops/flash_attention.py,
all read per call) — each configuration still runs in a FRESH
subprocess so the shape-keyed jit cache can't serve config A's
compiled program to config B. This
harness times one 16k-token causal train step per
configuration (the workload whose S² term the blocks govern —
bench.bench_lm_longctx's shape) and writes FLASH_SWEEP.json with
tokens/s per config and the winner. The backward kernel chooses its
blocks from the shape (``_bwd_blocks``) and has no knob to sweep.

Run ON CHIP, in one call of the chip tool (the parent stays off jax so
each child owns the chip; unset JAX_PLATFORMS means TPU). ~1-2 min/config, default grid 4.
"""

from __future__ import annotations

import datetime
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (block_q, block_k): the defaults first, then three moves — enough to
# read which direction helps without paying the full grid (each extra
# point is a subprocess-minute or two)
CONFIGS = [
    (512, 512),
    (256, 512),
    (1024, 1024),
    (512, 1024),
]

_CHILD = r"""
import sys, json
sys.path.insert(0, {repo!r})
import bench
from keystone_tpu.core.runtime import init_backend
init_backend()  # the platform rule: unset JAX_PLATFORMS means TPU
r = bench._lm_train_step_rate(
    seq=bench.LM_LONG_SEQ, dim=bench.LM_LONG_DIM,
    depth=bench.LM_LONG_DEPTH, heads=8, batch=1, pos_encoding="rope",
    use_mesh=False, iters=2, logit_chunk=4096,
)
print("RESULT " + json.dumps(r))
"""


def _write(results) -> dict:
    """Write the artifact NOW (called after every config): a killed or
    timed-out sweep keeps every completed measurement."""
    ok = [r for r in results if "tokens_per_s" in r]
    best = max(ok, key=lambda r: r["tokens_per_s"]) if ok else None
    art = {
        "workload": "lm_longctx16k train step (bench shapes)",
        "results": results,
        "configs_total": len(CONFIGS),
        "configs_run": len(results),
        "truncated": len(results) < len(CONFIGS),
        "best": best,
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc
        ).isoformat(),
    }
    with open(os.path.join(REPO, "FLASH_SWEEP.json"), "w") as f:
        json.dump(art, f, indent=1)
    return art


def main() -> None:
    results = []
    for bq, bk in CONFIGS:
        env = dict(
            os.environ,
            KST_FLASH_BLOCK_Q=str(bq),
            KST_FLASH_BLOCK_K=str(bk),
        )
        tag = f"q{bq}_k{bk}"
        try:
            out = subprocess.run(
                [sys.executable, "-c", _CHILD.format(repo=REPO)],
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
                        "tokens_per_s": round(r["tokens_per_s"], 1),
                        "tflops_per_s": round(r["tflops_per_s"], 2),
                    }
                )
                print(
                    f"# {tag}: {r['tokens_per_s']:.0f} tok/s",
                    file=sys.stderr,
                )
        except subprocess.TimeoutExpired:
            results.append({"config": tag, "error": "timeout"})
            print(f"# {tag}: TIMEOUT", file=sys.stderr)
        _write(results)

    print(json.dumps(_write(results)))


if __name__ == "__main__":
    main()
