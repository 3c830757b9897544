"""The one child that holds the chip: the program's own server entry point,
``distributed_llama_tpu.server.api.main``, with a control thread beside it.

The control thread is the benchmark's only way to device numbers without
editing the program: it reports what ``jax.devices()`` says, counts programs
built (``jax.monitoring`` backend-compile events, which fire for a real
compile and for a load from the persistent cache alike), starts and stops
``jax.profiler``, and reads the allocator's peak. Usage:

    python -m benchmark.harness.server_child <require_platform> <chips> \
        <control_port> <server argv...>
"""

from __future__ import annotations

import http.server
import json
import os
import sys
import threading
import time


class Control:
    """State the control thread serves. Listener callbacks run on whichever
    thread compiles, so the list is guarded."""

    def __init__(self, device: dict):
        self.device = device
        self._lock = threading.Lock()
        self._compiles: list[dict] = []
        self._cache_hits = 0
        self._tracing = False

    def on_duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self._compiles.append({"at": time.monotonic(), "fun": str(kw.get("fun_name", "")),
                                       "seconds": float(duration)})

    def on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self._cache_hits += 1

    def compiles(self) -> dict:
        with self._lock:
            return {"count": len(self._compiles),
                    "cache_hits": self._cache_hits, "events": list(self._compiles)}

    def memory(self) -> dict:
        import jax

        peaks = []
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        return {"peak_bytes": peaks}

    def profile(self, action: str, directory: str | None) -> dict:
        import jax

        with self._lock:
            if action == "start" and not self._tracing:
                jax.profiler.start_trace(directory)
                self._tracing = True
                return {"started": time.time()}
            if action == "stop" and self._tracing:
                jax.profiler.stop_trace()
                self._tracing = False
                return {"stopped": time.time()}
        return {"ignored": action}


def _handler(control: Control):
    class Handler(http.server.BaseHTTPRequestHandler):
        def log_message(self, fmt, *a):
            pass

        def do_POST(self):
            n = int(self.headers.get("Content-Length") or 0)
            body = json.loads(self.rfile.read(n) or b"{}")
            if self.path == "/device":
                out = control.device
            elif self.path == "/compiles":
                out = control.compiles()
            elif self.path == "/memory":
                out = control.memory()
            elif self.path == "/profile":
                out = control.profile(body.get("action", ""), body.get("dir"))
            else:
                self.send_error(404)
                return
            data = json.dumps(out).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

    return Handler


def device_or_exit(require_platform: str, chips: int) -> dict:
    """What JAX found; exits non-zero unless it is ``chips`` devices of
    ``require_platform``. There is no fallback."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if info["platform"] != require_platform or info["count"] < chips:
        print(f"benchmark: need {chips} device(s) of platform {require_platform!r}, "
              f"JAX reports {json.dumps(info)}", file=sys.stderr, flush=True)
        raise SystemExit(3)
    return info


def decode_chunk(server_argv: list[str]) -> int:
    """Steps per decode dispatch as the server will run it: its flag if the
    cell gives one, else the program's own default."""
    if "--decode-chunk" in server_argv:
        return int(server_argv[server_argv.index("--decode-chunk") + 1])
    from distributed_llama_tpu.apps.cli import build_parser

    return int(build_parser().get_default("decode_chunk"))


def main(argv: list[str]) -> None:
    require_platform, chips, port = argv[0], int(argv[1]), int(argv[2])
    device = device_or_exit(require_platform, chips)
    device["decode_chunk"] = decode_chunk(argv[3:])
    from jax import monitoring

    control = Control(device)
    monitoring.register_event_duration_secs_listener(control.on_duration)
    monitoring.register_event_listener(control.on_event)
    server = http.server.ThreadingHTTPServer(("127.0.0.1", port), _handler(control))
    threading.Thread(target=server.serve_forever, name="bench-control", daemon=True).start()

    from distributed_llama_tpu.server import api

    # api.main installs the SIGTERM drain and must own the main thread
    api.main(argv[3:])


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())
    main(sys.argv[1:])
