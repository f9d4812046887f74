"""Stage-2 flagship LM MFU push: cross the stage-1 winner with the
attention-implementation axis.

Stage 1 (tools/lm_mfu_push.py) sweeps batch / backward / chunked-CE /
remat with the attention implementation fixed at the auto-selected
Pallas flash kernel. But TPU_VALIDATION records flash at only
0.98-1.27x dense in the <=8k regime, so at the S=2048 bench shape the
attention impl itself is an untested lever. This harness takes the
stage-1 winner's knobs and sweeps:

- dense XLA attention (KST_LOCAL_ATTN=dense, models/lm/model.py)
- flash at non-default block sizes (KST_FLASH_BLOCK_Q/K)
- one batch step beyond the stage-1 winner (if it won at the grid edge)

Each config runs in a fresh subprocess (shape-keyed jit cache). Writes
LM_MFU_PUSH2.json and refreshes LM_BENCH_TUNED.json (with the winning
``env`` knobs — bench.bench_lm_train applies them) when a config beats
the stage-1 winner by >3%.

Run ON CHIP after tools/lm_mfu_push.py. ~1-3 min/config.
"""

from __future__ import annotations

import datetime
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

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


def _stage1_winner() -> dict:
    """The stage-1 winner's knobs, falling back to the bench default when
    no stage-1 artifact exists (so the harness still runs standalone)."""
    try:
        with open(os.path.join(REPO, "LM_MFU_PUSH.json")) as f:
            art = json.load(f)
        best = art.get("best")
        if best:
            return {
                "batch": int(best["batch"]),
                "dense_bwd": bool(best["dense_bwd"]),
                "logit_chunk": int(best["logit_chunk"]),
                "remat": best["remat"] or False,
            }
    except (OSError, ValueError, KeyError):
        pass
    return {"batch": 8, "dense_bwd": True, "logit_chunk": 0,
            "remat": False}


def _configs(base: dict) -> list[dict]:
    """The stage-2 grid, informed by the stage-1 chip results
    (LM_MFU_PUSH.json r5): b8 dense/blockwise tied at ~76 TF/s, b16
    SLOWER, b32 OOM'd — but every chunked-CE config failed on the
    divisor check (8192 does not divide the 2048 trained positions), and
    chunked CE is exactly what removes the (B·S, V) f32 logits that OOM
    b32 (8.6 GB at b32). So stage 2 re-anchors the winner, sweeps the
    attention impl (the other untested axis), and retries the big-batch
    configs WITH a valid logit_chunk."""
    cfgs = [dict(base, attn="auto", tag="s1winner")]
    cfgs.append(dict(base, attn="dense", tag="dense_attn"))
    for bq, bk in ((256, 512), (512, 1024), (1024, 1024), (1024, 2048)):
        cfgs.append(
            dict(base, attn="flash", block_q=bq, block_k=bk,
                 tag=f"flash_q{bq}_k{bk}")
        )
    # chunked CE at the winner's batch (HBM saving alone may help)...
    cfgs.append(dict(base, logit_chunk=1024, attn="auto", tag="lc1024"))
    # ...and the big-batch retry it should unlock (stage-1 b32 OOM was
    # the logits tensor; blockwise bwd keeps attention transients small)
    for b, lc, dense in ((16, 1024, True), (32, 1024, False),
                         (32, 1024, True), (32, 512, False)):
        cfgs.append(
            dict(base, batch=b, logit_chunk=lc, dense_bwd=dense,
                 attn="auto",
                 tag=f"b{b}_lc{lc}_{'dense' if dense else 'blockwise'}")
        )
    return cfgs


def _env_for(cfg: dict) -> dict:
    env = dict(os.environ)
    # scrub every knob this sweep owns, then set the config's —
    # inherited exports must not contaminate a config's measurement
    for k in ("KST_LOCAL_ATTN", "KST_FLASH_BLOCK_Q",
              "KST_FLASH_BLOCK_K", "KST_FLASH_DENSE_BWD_MAX"):
        env.pop(k, None)
    if not cfg["dense_bwd"]:
        env["KST_FLASH_DENSE_BWD_MAX"] = "0"
    if cfg["attn"] != "auto":
        env["KST_LOCAL_ATTN"] = cfg["attn"]
    if cfg.get("block_q"):
        env["KST_FLASH_BLOCK_Q"] = str(cfg["block_q"])
        env["KST_FLASH_BLOCK_K"] = str(cfg["block_k"])
    return env


def _knob_env(cfg: dict) -> dict:
    """The per-call env knobs a winning config needs at bench time
    (bench_lm_train merges these on top of its dense_bwd handling)."""
    out = {}
    if cfg["attn"] != "auto":
        out["KST_LOCAL_ATTN"] = cfg["attn"]
    if cfg.get("block_q"):
        out["KST_FLASH_BLOCK_Q"] = str(cfg["block_q"])
        out["KST_FLASH_BLOCK_K"] = str(cfg["block_k"])
    return out


def _write(results, base) -> dict:
    ok = [r for r in results if "tokens_per_s" in r]
    best = (
        max(ok, key=lambda r: (r["tflops_per_s"], r["tokens_per_s"]))
        if ok
        else None
    )
    anchor = next((r for r in ok if r["config"] == "s1winner"), None)
    art = {
        "workload": "flagship LM train step, stage-2 attention-impl "
                    "cross (bench shape, bf16 policy)",
        "stage1_winner_knobs": base,
        "results": results,
        "best": best,
        "anchor": anchor,
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc
        ).isoformat(),
    }
    with open(os.path.join(REPO, "LM_MFU_PUSH2.json"), "w") as f:
        json.dump(art, f, indent=1)
    if best and anchor and (
        best["tflops_per_s"] > 1.03 * anchor["tflops_per_s"]
    ):
        with open(os.path.join(REPO, "LM_BENCH_TUNED.json"), "w") as f:
            json.dump(
                {
                    "shape": "dim1024_depth8_s2048",
                    "batch": best["cfg"]["batch"],
                    "logit_chunk": best["cfg"]["logit_chunk"],
                    "dense_bwd": best["cfg"]["dense_bwd"],
                    "remat": best["cfg"]["remat"],
                    "env": _knob_env(best["cfg"]),
                    "measured_tflops_per_s": best["tflops_per_s"],
                    "from": "tools/lm_mfu_push2.py",
                    "timestamp": art["timestamp"],
                },
                f,
                indent=1,
            )
    return art


def main() -> None:
    base = _stage1_winner()
    print(f"# stage-1 winner knobs: {base}", file=sys.stderr)
    results = []
    for cfg in _configs(base):
        tag = cfg["tag"]
        try:
            out = subprocess.run(
                [
                    sys.executable,
                    "-c",
                    _CHILD.format(
                        repo=REPO, batch=cfg["batch"],
                        logit_chunk=cfg["logit_chunk"],
                        remat=cfg["remat"],
                    ),
                ],
                env=_env_for(cfg),
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
                        "cfg": cfg,
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
        _write(results, base)

    print(json.dumps(_write(results, base)))


if __name__ == "__main__":
    main()
