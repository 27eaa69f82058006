"""The worker as the product starts it, plus a control port for the harness.

Only the process that holds the chip can trace it or read its memory, and
the worker has no RPC op for either (ROADMAP B-I.1).  This entry runs
``hbbft_tpu.cryptoplane.proc_service.main(argv)`` unchanged on the main
thread and, beside it, one control thread on a localhost port that answers
three commands, one JSON object per line:

* ``{"op": "trace_start", "dir": ..., "host_only": bool}``: open a profiler
  session (device and host events, or the host's alone; Python tracer off)
  and write one anchor event
* ``{"op": "trace_stop"}``: close it and write ``<dir>/.../worker.xplane.pb``
* ``{"op": "memory"}``: ``peak_bytes_in_use`` of the fullest local device

Each reply carries ``wall_ns`` (``time.time_ns()`` when the command was
done), the stamp that ties the trace to the harness's flush stamps.  The
port is printed as ``{"control_port": N}`` before the worker's ready line.
A worker that never imported jax (``--backend eager``) answers ``memory``
with null and refuses to trace.
"""

from __future__ import annotations

import json
import os
import socket
import sys
import threading
import time
from typing import Any, Dict, Optional, Sequence

ANCHOR = "chipbench_anchor:"

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _memory_peak() -> Optional[int]:
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    peaks = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class Control:
    """The control thread's commands and the one profiler session it may
    hold open."""

    def __init__(self) -> None:
        self._session: Any = None
        self._dir: Optional[str] = None

    def handle(self, cmd: Dict[str, Any]) -> Dict[str, Any]:
        op = cmd.get("op")
        if op == "memory":
            return {"ok": True, "memory_peak_bytes": _memory_peak()}
        if op in ("trace_start", "trace_stop"):
            jax = sys.modules.get("jax")
            if jax is None:
                return {"ok": False, "error": "this worker holds no jax device"}
            if op == "trace_start":
                return self._trace_start(jax, cmd)
            return self._trace_stop()
        return {"ok": False, "error": f"unknown op {op!r}"}

    def _trace_start(self, jax: Any, cmd: Dict[str, Any]) -> Dict[str, Any]:
        """Open a profiler session: device events and the runtime's own host
        events, or with ``host_only`` the host's alone (``tpu_trace_mode``
        ``TRACE_ONLY_HOST``: no device event, and a stop of 0.3 s where a
        device window's first stop takes minutes, PERF.md).  The Python
        tracer stays off: it would record every call of the worker's
        pure-Python curve arithmetic."""
        from jax._src.lib import _profiler

        if self._session is not None:
            return {"ok": False, "error": "a trace is already open"}
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        if cmd.get("host_only"):
            options.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_HOST"}
        jax.devices()  # the backend before the session, or no device is traced
        self._session = _profiler.ProfilerSession(options)
        self._dir = cmd["dir"]
        # the trace has its own clock: one host event that carries the wall
        # clock in its name ties the two (reduce_trace.py)
        with jax.profiler.TraceAnnotation(f"{ANCHOR}{time.time_ns()}"):
            time.sleep(0.001)
        return {"ok": True}

    def _trace_stop(self) -> Dict[str, Any]:
        """Close the session and write its XSpace where ``jax.profiler``
        would (``<dir>/plugins/profile/<run>/<host>.xplane.pb``), without the
        ``trace.json.gz`` that ``stop_trace`` derives from it."""
        if self._session is None:
            return {"ok": False, "error": "no trace is open"}
        session, self._session = self._session, None
        t = time.perf_counter()
        xspace = session.stop()
        stop_s = time.perf_counter() - t
        run_dir = os.path.join(self._dir, "plugins", "profile", "run")
        os.makedirs(run_dir, exist_ok=True)
        path = os.path.join(run_dir, "worker.xplane.pb")
        with open(path, "wb") as f:
            f.write(xspace)
        return {"ok": True, "path": path, "bytes": len(xspace), "stop_s": stop_s}


def _serve(listener: socket.socket) -> None:
    control = Control()
    while True:
        try:
            conn, _ = listener.accept()
        except OSError:
            return
        with conn, conn.makefile("rw", encoding="utf-8") as stream:
            for line in stream:
                try:
                    reply = control.handle(json.loads(line))
                except Exception as e:  # the harness must see why, not a hang
                    reply = {"ok": False, "error": repr(e)}
                reply["wall_ns"] = time.time_ns()
                stream.write(json.dumps(reply) + "\n")
                stream.flush()


def main(argv: Optional[Sequence[str]] = None) -> int:
    sys.path.insert(0, REPO_ROOT)
    from hbbft_tpu.cryptoplane import proc_service

    listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    listener.bind(("127.0.0.1", 0))
    listener.listen(2)
    print(json.dumps({"control_port": listener.getsockname()[1]}), flush=True)
    threading.Thread(
        target=_serve, args=(listener,), name="chipbench-control", daemon=True
    ).start()
    try:
        return proc_service.main(argv)
    finally:
        listener.close()


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
