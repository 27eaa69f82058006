"""Parent-side handle of the one worker process that owns the chip.

The spawn protocol is ``ServiceProcess``'s (a pipe on stdin is the stop
channel, one ready JSON line on stdout, one summary line at the end).  It is
the benchmark's own copy because ``ServiceProcess`` cannot start this
worker: its command is fixed to ``-m hbbft_tpu.cryptoplane.proc_service``
(``python=`` replaces the interpreter, not the entry), its pump drops every
line but the ready and the summary one, so the entry's control port would
never arrive, and it can set variables in the worker's environment but not
take the ``HBBFT_TPU_*`` ones out.  The parent never imports jax.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
from typing import Any, Dict, List, Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(os.path.dirname(HERE))
DEFAULT_ENTRY = os.path.join(HERE, "worker_entry.py")


def worker_env(base: Dict[str, str]) -> Dict[str, str]:
    """The caller's environment without any ``HBBFT_TPU_*`` knob, so the
    worker runs on the program's defaults whatever shell started the run.
    ``JAX_COMPILATION_CACHE_DIR`` passes through where it is set; where it
    is not, the program keeps its cache at ``<checkout>/.jax_cache``."""
    env = {k: v for k, v in base.items() if not k.startswith("HBBFT_TPU_")}
    env["PYTHONPATH"] = REPO_ROOT
    return env


class Worker:
    def __init__(
        self,
        settings: Dict[str, Any],
        entry: str = DEFAULT_ENTRY,
        entry_args: Sequence[str] = (),
        ready_timeout_s: float = 600.0,
    ) -> None:
        self.settings = settings
        self.entry = entry
        self.entry_args = list(entry_args)
        self.ready_timeout_s = ready_timeout_s
        self.proc: Optional[subprocess.Popen] = None
        self.ready: Optional[Dict[str, Any]] = None
        self.summary: Optional[Dict[str, Any]] = None
        self.control_port: Optional[int] = None
        self._ready_evt = threading.Event()
        self._pump_thread: Optional[threading.Thread] = None
        self._control: Optional[socket.socket] = None
        self._control_stream: Any = None

    def argv(self) -> List[str]:
        s = self.settings
        return [
            sys.executable, self.entry, *self.entry_args,
            "--suite", str(s["suite"]),
            "--backend", str(s["backend"]),
            "--host", "127.0.0.1",
            "--port", "0",
            "--window-s", str(s["window_s"]),
            "--max-batch", str(s["max_batch"]),
        ]

    def start(self) -> None:
        """Spawn and return at once; :meth:`wait_ready` blocks."""
        self.proc = subprocess.Popen(
            self.argv(),
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            stderr=None,  # a compile error or a flush's traceback is there
            text=True,
            env=worker_env(dict(os.environ)),
            cwd=REPO_ROOT,
            start_new_session=True,  # its own process group: stop() ends all of it
        )
        self._pump_thread = threading.Thread(
            target=self._pump, name="chipbench-pump", daemon=True
        )
        self._pump_thread.start()

    def _pump(self) -> None:
        assert self.proc is not None and self.proc.stdout is not None
        for line in self.proc.stdout:
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if not isinstance(obj, dict):
                continue
            if "control_port" in obj:
                self.control_port = int(obj["control_port"])
            elif obj.get("ready"):
                self.ready = obj
                self._ready_evt.set()
            elif "done" in obj:
                self.summary = obj
        self._ready_evt.set()  # EOF: stop waiting for a ready line

    def wait_ready(self) -> Dict[str, Any]:
        self._ready_evt.wait(self.ready_timeout_s)
        if self.ready is None:
            rc = self.proc.poll() if self.proc else None
            raise RuntimeError(f"the worker printed no ready line (rc={rc})")
        return self.ready

    @property
    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    @property
    def addr(self):
        assert self.ready is not None
        return ("127.0.0.1", int(self.ready["port"]))

    def control(self, **cmd: Any) -> Dict[str, Any]:
        """One command to the entry's control thread; raises on refusal."""
        if self._control is None:
            if self.control_port is None:
                raise RuntimeError("the worker entry printed no control port")
            # the profiler's first stop in a process takes minutes for
            # programs of this size (PERF.md, section 7), so wait long
            self._control = socket.create_connection(
                ("127.0.0.1", self.control_port), timeout=3000.0
            )
            self._control_stream = self._control.makefile("rw", encoding="utf-8")
        self._control_stream.write(json.dumps(cmd) + "\n")
        self._control_stream.flush()
        line = self._control_stream.readline()
        if not line:
            raise RuntimeError(f"control channel closed during {cmd.get('op')}")
        reply = json.loads(line)
        if not reply.get("ok"):
            raise RuntimeError(f"control {cmd.get('op')}: {reply.get('error')}")
        return reply

    def stop(self, grace_s: float) -> Optional[int]:
        """Ask the worker to stop, wait ``grace_s`` for it to end, kill its
        process group (whatever is left of it, or all of it where it would
        not stop), and return its exit code."""
        proc = self.proc
        if proc is None:
            return None
        if self._control is not None:
            try:
                self._control_stream.close()
                self._control.close()
            except OSError:
                pass
            self._control = None
        if proc.poll() is None and proc.stdin:
            try:
                proc.stdin.write(json.dumps({"stop": True}) + "\n")
                proc.stdin.flush()
            except (OSError, ValueError):
                pass
        try:
            if proc.stdin:
                proc.stdin.close()
        except OSError:
            pass
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            pass
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:  # the group has ended
            pass
        proc.wait()
        if self._pump_thread is not None:
            self._pump_thread.join(timeout=10)
        return proc.returncode
