#!/usr/bin/env python3
"""Chip smoke: the fit -> serve main path, once, on the TPU, through the
entry points a user calls — the quickest proof that the system still
starts on the chip. ``python3 chip_smoke.py`` from the repo root; exit 0
and two stdout lines only when every phase passed on platform ``tpu``:
``{"report": {versions, total and per-phase wall and compile seconds,
pass/fail per phase}}``, then last, with exactly these keys,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

Phases, each a child process that owns the chip alone (this parent is
stdlib-only and never imports jax or keystone_tpu — a parent that has
touched jax holds the chip, and a child that needs it then fails or
hangs):

  fit      python -m keystone_tpu mnist-random-fft, MnistRandomFFT at the
           BASELINE.md width (60 000 x 784, 4 FFTs, block 2048); test
           error on the separable synthetic corpus under a bound
  serve    python -m keystone_tpu serve mnist: /predict over every
           compiled bucket, a pad-and-trim size and an oversized batch,
           answers checked against labels regenerated from the demo's
           seeds; /metrics; SIGTERM -> "draining": true -> exit 0
  train    python -m keystone_tpu lm-transformer at the flagship width
           (the Pallas flash path on a TPU); loss finite at every step
  kernels  the Pallas kernels with interpret=False written out, each
           against its XLA reference
  mesh     (more than one device only) rows split evenly over the data
           axis, mesh fit == one-device fit
  warm     the fit phase again in a new process: every program comes
           from the persistent compile cache

The times it prints are set-up facts (compile included), not metrics.
No accelerator, a platform other than ``tpu``, a failed phase or a
child that outlives its bound: non-zero exit and no result line.
"""

from __future__ import annotations

import atexit
import json
import math
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
TOTAL_BOUND_S = 1150.0  # the contract's 1200 s, less the time to report

FIT = {"rows": 60_000, "num_ffts": 4, "block_size": 2048}
# MnistRandomFFT on the separable synthetic corpus: the CPU run at 2000
# rows gives 0.30 % test error
MAX_TEST_ERROR = 0.02
# served answers vs regenerated labels, over all rows sent
MAX_SERVE_ERROR = 0.05
LM = {
    "steps": 3, "dim": 1024, "depth": 8, "num_heads": 16, "seq": 2048,
    "batch": 8, "vocab": 32768,
}
KERNELS = {
    # mm_fused: (M, K=N); M <= int8_matmul._MAX_M
    "mm_shapes": [[8, 1024], [256, 1024], [8, 4096], [256, 4096]],
    # ata_int8_pallas: one 60 000-row chunk at D = 2048
    "gram_shape": [60_000, 2048],
    # flash_attention at the LM phase's head shape: (B, H, S, D)
    "flash_shape": [1, 16, 2048, 64],
    "interpret": False,
}

_DEVICE_RE = re.compile(r" device (\{.*\})\s*$")
_COMPILE_RE = re.compile(r" compile (\{.*\})\s*$")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


# children still running: stopped when this process exits or is told to
# stop, so the smoke leaves no process behind (each child is its own
# session, which a signal to this process alone would not reach)
_LIVE: set = set()


def _stop_all(signum=None, frame=None) -> None:
    for child in list(_LIVE):
        child.stop()
    if signum is not None:
        sys.exit(128 + signum)


class Child:
    """One child process, its merged output parsed as it streams: the
    launcher's ``device {json}`` and ``compile {json}`` lines
    (keystone_tpu/core/runtime.py) are picked out, the tail is kept for
    the failure report. Runs in its own session so the whole group can
    be stopped. ``echo`` repeats every line on stderr, for a child whose
    own parent wants to read the same lines."""

    def __init__(self, cmd: list[str], echo: bool = False):
        self.cmd = cmd
        self.echo = echo
        self.device: dict | None = None
        self.compile: dict | None = None
        self.lines: list[str] = []
        self._seen = threading.Condition()
        self.t0 = time.perf_counter()
        self.proc = subprocess.Popen(
            cmd,
            cwd=ROOT,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            start_new_session=True,
        )
        _LIVE.add(self)
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for raw in self.proc.stdout:
            line = raw.rstrip("\n")
            if self.echo:
                print(line, file=sys.stderr, flush=True)
            with self._seen:
                self.lines.append(line)
                for attr, rx in (("device", _DEVICE_RE), ("compile", _COMPILE_RE)):
                    m = rx.search(line)
                    if m:
                        try:
                            setattr(self, attr, json.loads(m.group(1)))
                        except ValueError:
                            pass
                self._seen.notify_all()

    def wait_for(self, pred, timeout: float) -> bool:
        """Block until ``pred(self)`` holds, the child exits, or
        ``timeout`` seconds pass."""
        deadline = time.monotonic() + timeout
        with self._seen:
            while not pred(self):
                left = deadline - time.monotonic()
                if left <= 0 or (
                    self.proc.poll() is not None and not self._reader.is_alive()
                ):
                    return pred(self)
                self._seen.wait(min(left, 0.2))
        return True

    def stop(self) -> None:
        """Stop the child and everything it started."""
        if self.proc.poll() is None:
            for sig in (signal.SIGTERM, signal.SIGKILL):
                try:
                    os.killpg(self.proc.pid, sig)
                except ProcessLookupError:
                    break
                try:
                    self.proc.wait(timeout=10)
                    break
                except subprocess.TimeoutExpired:
                    continue
        _LIVE.discard(self)
        self._reader.join(timeout=5)

    def finish(self, bound_s: float) -> int | None:
        """Wait for exit within the bound; None means it outlived it
        (and was stopped)."""
        try:
            rc = self.proc.wait(timeout=max(bound_s, 0.0))
        except subprocess.TimeoutExpired:
            rc = None
        self.stop()
        return rc

    @property
    def wall_s(self) -> float:
        return round(time.perf_counter() - self.t0, 2)

    def tail(self, n: int = 25) -> str:
        return "\n".join(self.lines[-n:])


def _result(child: Child, ok: bool, why: str = "", **fields) -> dict:
    out = {
        "ok": bool(ok),
        "wall_s": child.wall_s,
        "compile_s": (child.compile or {}).get("backend_compile_s"),
        "cache_hits": (child.compile or {}).get("cache_hits"),
        "cache_misses": (child.compile or {}).get("cache_misses"),
        "device": child.device,
        **fields,
    }
    if not ok:
        out["why"] = why
        log(
            f"FAILED: {why}\n--- last output of "
            f"{' '.join(child.cmd[:6])} ...\n{child.tail()}"
        )
    return out


def _platform_ok(child: Child, require_platform: str | None) -> str | None:
    """None when the child's device line names the required platform,
    else the reason it does not."""
    if child.device is None:
        return "the child never logged its device line"
    if require_platform and child.device.get("platform") != require_platform:
        return (
            f"platform is {child.device.get('platform')!r}, not "
            f"{require_platform!r}"
        )
    return None


def run_to_end(
    cmd: list[str], bound_s: float, require_platform: str | None
) -> tuple[Child, str | None]:
    """Run a child to its end. Fails fast — child stopped — when its
    device line names the wrong platform, so a CPU machine is refused in
    seconds, not after a CPU-speed run. Returns (child, why-not-ok)."""
    child = Child(cmd)
    child.wait_for(lambda c: c.device is not None, bound_s)
    why = _platform_ok(child, require_platform)
    if why is not None and child.proc.poll() is None:
        child.stop()
        return child, why
    rc = child.finish(bound_s - child.wall_s)
    if rc is None:
        return child, f"outlived its bound of {bound_s:.0f}s"
    if rc != 0:
        return child, f"exit code {rc}"
    return child, why


# ------------------------------------------------------------------ phases


def phase_fit(
    *,
    rows: int = FIT["rows"],
    num_ffts: int = FIT["num_ffts"],
    block_size: int = FIT["block_size"],
    max_test_error: float = MAX_TEST_ERROR,
    bound_s: float,
    require_platform: str | None = "tpu",
) -> dict:
    cmd = [
        sys.executable, "-m", "keystone_tpu", "mnist-random-fft",
        "--synthetic", str(rows), "--num-ffts", str(num_ffts),
        "--block-size", str(block_size),
    ]
    child, why = run_to_end(cmd, bound_s, require_platform)
    if why:
        return _result(child, False, why)
    result = re.compile(r"MnistRandomFFT: train err ([\d.]+)%, test err ([\d.]+)%")
    m = next(filter(None, map(result.search, reversed(child.lines))), None)
    if m is None:
        return _result(child, False, "no MnistRandomFFT result line")
    train_err, test_err = float(m.group(1)) / 100, float(m.group(2)) / 100
    ok = test_err <= max_test_error
    return _result(
        child, ok,
        f"test error {test_err:.4f} > bound {max_test_error}",
        rows=rows, train_error=train_err, test_error=test_err,
        max_test_error=max_test_error,
    )


def phase_train(
    *,
    lm: dict | None = None,
    compute_dtype: str = "bfloat16",
    bound_s: float,
    require_platform: str | None = "tpu",
) -> dict:
    lm = {**LM, **(lm or {})}
    cmd = [sys.executable, "-m", "keystone_tpu", "lm-transformer"]
    for key, val in lm.items():
        cmd += ["--" + key.replace("_", "-"), str(val)]
    cmd += ["--compute-dtype", compute_dtype]
    child, why = run_to_end(cmd, bound_s, require_platform)
    if why:
        return _result(child, False, why)
    losses: dict[int, float] = {}
    for line in child.lines:
        m = re.search(r"step (\d+) loss (\S+)", line)
        if m:
            try:
                losses[int(m.group(1))] = float(m.group(2))
            except ValueError:
                losses[int(m.group(1))] = float("nan")
    want = list(range(1, lm["steps"] + 1))
    ok = sorted(losses) == want and all(math.isfinite(v) for v in losses.values())
    return _result(
        child, ok,
        f"want a finite loss at steps {want}, got {losses}",
        losses=[losses.get(i) for i in want], **lm,
    )


def _json_line(child: Child) -> dict | None:
    """The result a child of this file printed: its last line that is a
    JSON object (log lines written at exit may follow it)."""
    for line in reversed(child.lines):
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                return None
    return None


def _self_child(phase: str, args: dict) -> list[str]:
    return [
        sys.executable, os.path.abspath(__file__), "--phase", phase,
        json.dumps(args),
    ]


def _json_phase(
    phase: str, args: dict, bound_s: float, require_platform: str | None
) -> dict:
    """A phase whose work needs numpy or jax runs as a child of this
    same file; its last line is its result as JSON."""
    child, why = run_to_end(_self_child(phase, args), bound_s, require_platform)
    res = _json_line(child)
    if res is None:
        return _result(child, False, why or "no JSON result line")
    # the child's exit code mirrors its "ok", so ``why`` is set on failure;
    # its own reason is the better one
    ok = bool(res.pop("ok", False)) and why is None
    return _result(child, ok, res.pop("why", None) or why or "failed", **res)


def phase_kernels(
    *,
    kernels: dict | None = None,
    bound_s: float,
    require_platform: str | None = "tpu",
) -> dict:
    return _json_phase(
        "kernels", {**KERNELS, **(kernels or {})}, bound_s, require_platform
    )


def phase_mesh(
    *,
    rows: int = FIT["rows"],
    num_ffts: int = FIT["num_ffts"],
    block_size: int = FIT["block_size"],
    bound_s: float,
    require_platform: str | None = "tpu",
) -> dict:
    return _json_phase(
        "mesh",
        {"rows": rows, "num_ffts": num_ffts, "block_size": block_size},
        bound_s,
        require_platform,
    )


def phase_serve(
    *,
    rows: int = FIT["rows"],
    num_ffts: int = FIT["num_ffts"],
    max_error: float = MAX_SERVE_ERROR,
    bound_s: float,
    require_platform: str | None = "tpu",
) -> dict:
    """The serve driver needs numpy (to regenerate rows from the demo's
    seeds), so it runs as a child too; it starts the server — which owns
    the chip — as ITS child, never touches jax itself, and repeats the
    server's output, so the device read here is the server's."""
    return _json_phase(
        "serve",
        {
            "rows": rows, "num_ffts": num_ffts, "max_error": max_error,
            "bound_s": bound_s - 15.0,
        },
        bound_s,
        require_platform,
    )


def phase_warm(
    cold: dict, *, require_platform: str | None = "tpu", **fit_kw
) -> dict:
    """The fit phase a second time in a new process. Warm means what the
    first fit wrote to the persistent cache is read back; where the first
    fit really was cold (it wrote entries), the warm wall and compile
    seconds must be clearly below the cold ones."""
    warm = phase_fit(require_platform=require_platform, **fit_kw)
    out = {
        **warm,
        "cold_wall_s": cold.get("wall_s"),
        "warm_wall_s": warm.get("wall_s"),
        "cold_compile_s": cold.get("compile_s"),
        "warm_compile_s": warm.get("compile_s"),
        "compile_cache": (warm.get("device") or {}).get("compile_cache"),
        # the first fit already read from the cache, or wrote nothing to
        # it: the machine came with a cache that held these programs, and
        # cold vs warm is then not a comparison
        "prewarmed": bool(cold.get("cache_hits")) or not cold.get("cache_misses"),
    }
    if not warm["ok"]:
        return out
    why = None
    # every entry the cold fit wrote must be read back. (Not "no misses":
    # a program that compiles in about the cache's 0.5 s admission
    # threshold may be written by either run — seen on the four-chip
    # host: cold 5 written, warm 5 read + 2 written.)
    if not warm.get("cache_hits") or warm["cache_hits"] < (
        cold.get("cache_misses") or 0
    ):
        why = (
            f"warm fit was not served from the cache: it read "
            f"{warm.get('cache_hits')} entries, the cold fit wrote "
            f"{cold.get('cache_misses')}"
        )
    # "clearly" is judged on compile seconds, which is what the cache
    # changes; the wall (process start, data, transfers — and their
    # jitter — included) only has to come out below
    elif not out["prewarmed"] and not (
        out["warm_wall_s"] < out["cold_wall_s"]
        and out["warm_compile_s"] < 0.6 * out["cold_compile_s"]
    ):
        why = (
            f"warm fit not clearly below cold: wall {out['warm_wall_s']}s vs "
            f"{out['cold_wall_s']}s, compile {out['warm_compile_s']}s vs "
            f"{out['cold_compile_s']}s"
        )
    if why:
        out.update(ok=False, why=why)
        log(f"FAILED: {why}")
    return out


# ------------------------------------------------- children of this file


def _http(method: str, url: str, body=None, timeout: float = 120.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read().decode()


def _drain_seen(host: str, port: int, pid: int) -> bool:
    """SIGTERM the server and read /healthz during the drain. The
    connections are opened BEFORE the signal: once drain begins the
    accept loop stops, but a handler thread that already holds a
    connection still answers until the process exits."""
    socks = []
    for _ in range(16):
        s = socket.create_connection((host, port), timeout=5)
        socks.append(s)
    time.sleep(0.3)  # let the accept loop hand each one to a thread
    os.kill(pid, signal.SIGTERM)
    seen = False
    for s in socks:
        if not seen:
            try:
                s.sendall(b"GET /healthz HTTP/1.0\r\n\r\n")
                raw = b""
                while chunk := s.recv(65536):
                    raw += chunk
                body = raw.split(b"\r\n\r\n", 1)[1]
                seen = json.loads(body).get("draining") is True
            except (OSError, IndexError, ValueError):
                pass
            time.sleep(0.002)
        s.close()
    return seen


def _child_serve(args: dict) -> dict:
    import numpy as np  # this child never imports jax: the server owns the chip

    rows, num_ffts = int(args["rows"]), int(args["num_ffts"])
    bound_s = float(args["bound_s"])
    cmd = [
        sys.executable, "-m", "keystone_tpu", "serve", "mnist",
        "--synthetic", str(rows), "--num-ffts", str(num_ffts), "--port", "0",
    ]
    server = Child(cmd, echo=True)
    out: dict = {"ok": False}

    def fail(why: str) -> dict:
        out["why"] = why
        return out

    try:
        serving = re.compile(
            r"serving .* on http://([\d.]+):(\d+) \(cold start ([\d.]+)s\)"
        )

        def up(c):
            return any(serving.search(ln) for ln in c.lines)

        if not server.wait_for(up, bound_s):
            return fail("no 'serving ...' line within the bound")
        m = next(filter(None, map(serving.search, server.lines)))
        host, port = m.group(1), int(m.group(2))
        out["cold_start_s"] = float(m.group(3))
        url = f"http://{host}:{port}"

        _, body = _http("GET", url + "/healthz")
        health = json.loads(body)
        out["healthz_device"] = {
            k: health.get(k) for k in ("platform", "device_kind", "device_count")
        }
        want = {
            "platform": server.device["platform"],
            "device_kind": server.device["device_kind"],
            "device_count": server.device["count"],
        }
        if out["healthz_device"] != want:
            return fail(f"/healthz device {out['healthz_device']} != log {want}")

        # held-out rows from the demo's own class centers
        # (serve/server.py::_fit_mnist_demo: centers from seed 42)
        centers = (
            np.random.default_rng(42).normal(size=(10, 784)).astype(np.float32)
        )
        rng = np.random.default_rng(7)
        _, body = _http("GET", url + "/metrics")
        n_buckets = _prom_value(body, "serve_aot_compiled_total")
        # the default buckets (serve/queue.py): every compiled size, one
        # between buckets (pad and trim), one above the largest (stream)
        sizes = [1, 8, 32, 5, 40]
        sent = wrong = 0
        for n in sizes:
            labels = rng.integers(0, 10, size=n)
            data = centers[labels] + rng.normal(size=(n, 784)).astype(np.float32)
            status, body = _http("POST", url + "/predict", {"rows": data.tolist()})
            pred = json.loads(body)["predictions"]
            if status != 200 or len(pred) != n:
                return fail(f"/predict n={n}: status {status}, {len(pred)} answers")
            sent += n
            wrong += int(np.sum(np.asarray(pred) != labels))
        out.update(request_sizes=sizes, rows_sent=sent, rows_wrong=wrong)
        if wrong > args["max_error"] * sent:
            return fail(f"{wrong}/{sent} answers differ from the labels")

        _, body = _http("GET", url + "/metrics")
        out["aot_compiled"] = _prom_value(body, "serve_aot_compiled_total")
        if "serve_aot_fallback" in body:
            return fail("/metrics shows serve_aot_fallback")
        if out["aot_compiled"] != 3 or n_buckets != 3:
            return fail(f"serve_aot_compiled_total={out['aot_compiled']}, want 3")
        # the served model sits on device 0 whatever the device count
        # (_fit_mnist_demo passes no mesh)
        out["placement"] = "device 0"

        out["draining_seen"] = _drain_seen(host, port, server.proc.pid)
        rc = server.finish(60.0)
        out["exit_code"] = rc
        if not out["draining_seen"]:
            return fail('/healthz never showed "draining": true after SIGTERM')
        if rc != 0:
            return fail(f"server exit code {rc} after SIGTERM")
        out["ok"] = True
        return out
    except (OSError, ValueError, KeyError, urllib.error.URLError) as e:
        return fail(f"{type(e).__name__}: {e}")
    finally:
        server.stop()


def _prom_value(text: str, name: str) -> float | None:
    total = None
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            total = (total or 0.0) + float(line.rsplit(None, 1)[1])
    return total


def _child_kernels(args: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.core.runtime import init_backend

    init_backend()
    from keystone_tpu.ops.attention import dense_attention
    from keystone_tpu.ops.flash_attention import flash_attention
    from keystone_tpu.ops.gram import ata_int8_pallas, ata_int8_xla
    from keystone_tpu.ops.int8_matmul import mm_fused
    from keystone_tpu.ops.quantization import mm, quantize_int8

    interpret = bool(args["interpret"])
    checks: dict = {}
    key = jax.random.key(0)

    def close(got, want, rtol, atol) -> dict:
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = np.abs(got - want)
        return {
            "ok": bool(np.all(err <= atol + rtol * np.abs(want)))
            and bool(np.all(np.isfinite(got))),
            "max_abs_err": float(err.max()),
            "max_abs_ref": float(np.abs(want).max()),
            "rtol": rtol, "atol": atol,
        }

    # mm_fused vs quantization.mm — the XLA path it replaces, like for
    # like as in tests/test_int8_matmul.py (same operand dtype, f32
    # accumulate; only tile padding and op order differ) and with its
    # tolerances: bf16 as there; f32's absolute part scaled to the
    # output's magnitude, which grows with sqrt(K) past the test's sizes
    for m_rows, kn in args["mm_shapes"]:
        kw, ky = jax.random.split(jax.random.fold_in(key, m_rows * 100003 + kn))
        qt = quantize_int8(jax.random.normal(kw, (kn, kn), jnp.float32))
        y = jax.random.normal(ky, (m_rows, kn), jnp.float32)
        for dt, tol in ((jnp.bfloat16, 2e-2), (jnp.float32, 1e-4)):
            yd = y.astype(dt)
            want = mm(yd, qt, dt)
            got = mm_fused(yd, qt, interpret=interpret)
            name = f"mm_fused_m{m_rows}_k{kn}_{jnp.dtype(dt).name}"
            scale = float(jnp.abs(want.astype(jnp.float32)).max())
            atol = tol if dt == jnp.bfloat16 else tol * scale
            checks[name] = close(got, want, tol, atol)
            if dt == jnp.float32:
                # for the record, not gating: both paths multiply on the
                # MXU at its default precision, which is not f32
                with jax.default_matmul_precision("highest"):
                    exact = mm(yd, qt, dt)
                checks[name]["max_abs_err_vs_highest_precision"] = float(
                    jnp.abs(got - exact).max()
                )

    # ata_int8_pallas vs ata_int8_xla, tolerance of
    # tests/test_streaming_fit.py::test_int8_gram_pallas_matches_xla
    n, d = args["gram_shape"]
    a = jax.random.normal(jax.random.fold_in(key, 1), (n, d), jnp.float32)
    want = ata_int8_xla(a)
    got = ata_int8_pallas(a, interpret=interpret)
    checks[f"ata_int8_pallas_n{n}_d{d}"] = close(got, want, 1e-5, 1e-4 * n / 300)

    # flash_attention (bf16 in, as the LM phase feeds it) vs dense
    # attention on the same values at full precision; tolerance: bf16
    # output rounding (2^-8 relative on O(1) values) with margin
    b, h, s, hd = args["flash_shape"]
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, 10 + i), (b, h, s, hd), jnp.bfloat16)
        for i in range(3)
    )
    with jax.default_matmul_precision("highest"):
        want = dense_attention(
            *(x.astype(jnp.float32) for x in (q, k, v)), causal=True
        )
    got = flash_attention(q, k, v, causal=True, interpret=interpret)
    checks[f"flash_attention_b{b}_h{h}_s{s}_d{hd}_bfloat16"] = close(
        got, want, 2e-2, 2e-2
    )

    bad = [name for name, c in checks.items() if not c["ok"]]
    out = {"ok": not bad, "interpret": interpret, "checks": checks}
    if bad:
        out["why"] = f"kernels off their reference: {bad}"
    return out


def _child_mesh(args: dict) -> dict:
    import jax
    import numpy as np

    from keystone_tpu.core.runtime import init_backend

    init_backend()
    from keystone_tpu.models.mnist_random_fft import (
        IMAGE_SIZE,
        NUM_CLASSES,
        build_batch_featurizers,
        featurize,
    )
    from keystone_tpu.ops.linear import BlockLeastSquaresEstimator
    from keystone_tpu.ops.util import ClassLabelIndicators
    from keystone_tpu.parallel.mesh import create_mesh, shard_batch

    rows = int(args["rows"])
    rng = np.random.default_rng(0)
    labels = rng.integers(0, NUM_CLASSES, size=rows).astype(np.int32)
    centers = (
        np.random.default_rng(42)
        .normal(size=(NUM_CLASSES, IMAGE_SIZE))
        .astype(np.float32)
    )
    data = centers[labels] + rng.normal(size=(rows, IMAGE_SIZE)).astype(np.float32)
    groups = build_batch_featurizers(
        int(args["num_ffts"]), int(args["block_size"]), seed=0
    )
    est = BlockLeastSquaresEstimator(block_size=int(args["block_size"]), num_iter=1)

    def fit(x):
        y = np.zeros(x.shape[0], np.int32)
        y[:rows] = labels
        blocks = featurize(groups, x)
        model = est.fit(
            blocks, ClassLabelIndicators(num_classes=NUM_CLASSES)(y), n_valid=rows
        )
        pred = np.asarray(jax.numpy.argmax(model(blocks), axis=-1))[:rows]
        return jax.block_until_ready(model), pred

    devices = jax.devices()
    mesh = create_mesh()
    x_mesh = shard_batch(data, mesh)
    shard_rows = sorted(s.data.shape[0] for s in x_mesh.addressable_shards)
    model_mesh, pred_mesh = fit(x_mesh)
    model_one, pred_one = fit(jax.device_put(data, devices[0]))
    w_mesh, w_one = (
        np.concatenate(
            [np.asarray(leaf).ravel() for leaf in jax.tree_util.tree_leaves(m)]
        )
        for m in (model_mesh, model_one)
    )
    rel = float(np.linalg.norm(w_mesh - w_one) / np.linalg.norm(w_one))
    agree = float(np.mean(pred_mesh == pred_one))
    # the planner-selectable int8 Gram on a row-sharded chunk (what a
    # streamed fit over the mesh hands it) vs its one-device XLA form
    from keystone_tpu.ops.gram import ata_int8, ata_int8_xla

    feats = featurize(groups, x_mesh)[0]
    g_mesh = np.asarray(jax.jit(ata_int8)(feats))
    g_one = np.asarray(ata_int8_xla(jax.device_put(feats, devices[0])))
    gram_rel = float(np.linalg.norm(g_mesh - g_one) / np.linalg.norm(g_one))
    out = {
        "mesh": dict(mesh.shape),
        "rows": rows,
        "shard_rows": shard_rows,
        "weights_rel_l2_diff": rel,
        "prediction_agreement": agree,
        "int8_gram_rel_l2_diff": gram_rel,
    }
    even = len(shard_rows) == len(devices) and set(shard_rows) == {
        x_mesh.shape[0] // len(devices)
    }
    out["ok"] = even and rel <= 1e-2 and agree >= 0.995 and gram_rel <= 1e-5
    if not out["ok"]:
        out["why"] = (
            f"mesh vs one device: shards {shard_rows}, weights rel diff "
            f"{rel:.2e}, predictions agree {agree:.4f}, int8 Gram rel diff "
            f"{gram_rel:.2e}"
        )
    return out


# -------------------------------------------------------------------- main


def main() -> int:
    t0 = time.monotonic()
    phases: dict[str, dict] = {}

    def run(name: str, fn, bound_s: float) -> None:
        left = TOTAL_BOUND_S - (time.monotonic() - t0)
        if left < 30:
            phases[name] = {"ok": False, "why": "no time left in the total bound"}
        else:
            log(f"{name}: start")
            phases[name] = fn(bound_s=min(bound_s, left))
        res = phases[name]
        log(f"{name}: {'ok' if res['ok'] else 'FAILED: ' + res['why']} "
            f"({res.get('wall_s')}s)")

    run("fit", phase_fit, 420.0)
    device = phases["fit"].get("device")
    # a machine with no TPU is refused at the first phase, not five times
    if device is not None and device["platform"] == "tpu":
        run("serve", phase_serve, 480.0)
        run("train", phase_train, 600.0)
        run("kernels", phase_kernels, 420.0)
        if device["count"] > 1:
            run("mesh", phase_mesh, 420.0)
        run("warm", lambda **kw: phase_warm(phases["fit"], **kw), 300.0)
    for res in phases.values():
        if res["ok"] and res["device"] != device:
            res.update(
                ok=False,
                why=f"device {res['device']} differs from the fit phase's",
            )
    ok = all(res["ok"] for res in phases.values())
    report = {
        "versions": _versions(),
        "total_wall_s": round(time.monotonic() - t0, 1),
        "phases": phases,
    }
    if not ok:
        # nothing on stdout for a run that did not pass
        log("FAILED: " + json.dumps({"ok": False, "device": device, **report}))
        return 1
    # the per-phase record first, the verdict last: the last line of
    # stdout holds the keys of ``verdict`` and no others
    print(json.dumps({"report": report}), flush=True)
    print(json.dumps(verdict(ok, device)), flush=True)
    return 0


def verdict(ok: bool, device: dict) -> dict:
    """The last line of stdout, from a launcher ``device`` line: ``ok``
    and the device as jax reports it (``jax.devices()[0].platform``,
    ``.device_kind``, ``len(jax.devices())``), nothing else."""
    return {
        "ok": bool(ok),
        "device": {
            "platform": str(device["platform"]),
            "kind": str(device["device_kind"]),
            "count": int(device["count"]),
        },
    }


def _versions() -> dict:
    from importlib import metadata

    out = {"python": sys.version.split()[0]}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


if __name__ == "__main__":
    atexit.register(_stop_all)
    signal.signal(signal.SIGTERM, _stop_all)
    signal.signal(signal.SIGINT, _stop_all)
    if len(sys.argv) == 4 and sys.argv[1] == "--phase":
        # a child of this file (see _self_child): result as the last line
        fn = {"serve": _child_serve, "kernels": _child_kernels, "mesh": _child_mesh}
        res = fn[sys.argv[2]](json.loads(sys.argv[3]))
        print(json.dumps(res), flush=True)
        sys.exit(0 if res["ok"] else 1)
    sys.exit(main())
