"""Stdlib-only stub replica for chaos game days.

The fleet game-day campaign drills the ROUTER's composed-failure
behavior — failover, breakers, relaunch, the burst's client-visible
outcome — none of which depends on what the replica computes. This
worker implements exactly the slice of the ``serve`` HTTP contract the
router consumes (``POST /predict`` echoing rows doubled, ``GET
/healthz`` with the ``draining`` flag, SIGTERM drain-then-exit-0) with
zero jax/model boot cost, so a full campaign runs in seconds. The canned
campaign can swap in real ``serve mnist`` replicas with
``"replica": "mnist"`` when the game day should cover the model path
too (``tests/test_fleet.py`` already drills that stack).

This is the ONE copy of the stub-replica contract: the fleet and
collector process drills spawn it through the thin
``tests/fleet_replica_worker.py`` shim, so the tests and the chaos
campaigns can never drift apart on what a replica looks like.

Env knobs: ``STUB_SLOW_MS`` delays every /predict, ``STUB_DRAIN_S``
holds the draining state before exit, ``STUB_FAIL_PREDICT=1`` answers
500 (breaker rigs).
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

STATE = {"draining": False, "requests": 0}


class Handler(BaseHTTPRequestHandler):
    def log_message(self, fmt, *args):  # noqa: D102 — keep drill logs clean
        pass

    def _send(self, code, payload):
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):  # noqa: N802 — stdlib API
        if self.path == "/healthz":
            return self._send(
                200,
                {
                    "status": "draining" if STATE["draining"] else "ok",
                    "draining": STATE["draining"],
                    "queue_depth": float(os.environ.get("STUB_QUEUE_DEPTH", 0)),
                    "queue_p95_ms": float(os.environ.get("STUB_P95_MS", 1.0)),
                    "requests": STATE["requests"],
                    "pid": os.getpid(),
                },
            )
        return self._send(404, {"error": self.path})

    def do_POST(self):  # noqa: N802 — stdlib API
        n = int(self.headers.get("Content-Length") or 0)
        body = json.loads(self.rfile.read(n) or b"{}")
        if self.path != "/predict":
            return self._send(404, {"error": self.path})
        if os.environ.get("STUB_FAIL_PREDICT") == "1":
            return self._send(500, {"error": "injected stub failure"})
        slow_ms = float(os.environ.get("STUB_SLOW_MS", 0) or 0)
        if slow_ms:
            time.sleep(slow_ms / 1e3)
        STATE["requests"] += 1
        rows = body.get("rows") or []
        return self._send(
            200,
            {
                "predictions": [[2.0 * v for v in row] for row in rows],
                "pid": os.getpid(),
                "trace": self.headers.get("X-Keystone-Trace"),
            },
        )


def main(argv: list[str] | None = None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    port = 0
    if "--port" in argv:
        port = int(argv[argv.index("--port") + 1])
    httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)

    def term(signum, frame):
        # the PR-7 drain contract in miniature: flag draining (visible
        # in /healthz immediately), keep answering briefly so pollers
        # can see it, then exit 0
        STATE["draining"] = True

        def stop():
            time.sleep(float(os.environ.get("STUB_DRAIN_S", 0.2)))
            httpd.shutdown()

        threading.Thread(target=stop, daemon=True).start()

    signal.signal(signal.SIGTERM, term)
    print(f"stub replica on {httpd.server_address[1]}", flush=True)
    try:
        httpd.serve_forever(poll_interval=0.05)
    finally:
        httpd.server_close()


if __name__ == "__main__":
    main()
