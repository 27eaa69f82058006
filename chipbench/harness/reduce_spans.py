"""From a traced run's profiler traces to the program's own spans.

The program writes its spans (``hbbft_tpu.utils.metrics.Metrics.span``:
``crypto.rpc.*`` in the RPC server, ``crypto.window`` and ``crypto.flush``
in the service, ``crypto.tpu.*`` in the backend) into whatever profiler
session is open in the worker, and names its two programs
``jit_hbbft_scan_<n1>_<n2>_<legs>`` and ``jit_hbbft_pair_<pairs>``.  The
harness leaves the traces of a run under ``chipbench/.trace/{device,host}``
(``reduce_trace.find_trace``); this module reads them a second time, in a
child process like ``reduce_trace`` (the parent never imports jax), and
keeps its answer beside them (``chipbench/.trace/spans.json``, keyed by the
traces' paths, mtimes and sizes), so the nine readers under
``layer_metrics/`` that use it pay for one parse.  A program without the
spans (an older commit) gives a reduction with no whole flush and no named
module: every reader then returns None.

How it reads:

* **Spans**: every event on a host plane whose name (before ``#``) starts
  with ``crypto.``, with its args (the event's stats).  A line is a thread;
  no line's name is relied on.  A span that was open when the window closed
  was never written, so every span read is whole.
* **Whole flushes**: the ``crypto.flush`` spans.  A flush's members are the
  spans it contains on its own line, the ``crypto.window`` that ended last
  before it on that line, and the ``crypto.rpc.*`` spans (another line)
  whose ``span`` id the flush lists in ``spans``.  ``spans_per_flush`` is
  each name's summed duration and count over the members, per flush.  It
  comes from the device's window where that holds a whole flush, else from
  the host-only window (a long flush overflows the device's buffer, so the
  harness closes that window inside the flush; ``TRACE_ONLY_HOST`` records
  annotations like any host event).
* **Programs**: the events of the device plane's ``XLA Modules`` line by
  name prefix: scan, pair, other.  Per flush: the events that start inside a
  whole flush of the device's window; where that window was cut, the mean
  over whole ``crypto.tpu.check`` spans of the device's window (those that
  hold a scan and a pair launch) times the checks per flush that the
  host-only window's flushes count.
* **Idle gaps**: the complement of the union of the device's ``XLA Ops``
  intervals (``reduce_trace.union``) from the harness's anchor event to the
  last device op or span (the harness does not hand a reader the window's
  stamps).  Each gap is cut at span boundaries and every piece put down to
  the innermost span that covers it on a line of the flush's thread, else
  to the innermost ``crypto.rpc.*`` span that covers it, else to
  ``outside_worker``: the client and the socket, the harness's process,
  which no profiler sees.  A piece whose innermost span is ``crypto.flush``
  or ``crypto.rpc.serve`` itself is ``unattributed``.  In a window that
  holds no whole flush the spans that would name the rest (``crypto.flush``,
  ``crypto.rpc.wait``) were never written: there the rest is
  ``unnamed_in_cut_window``.

The program's ``jax.named_scope``s (``scan_g1`` ... ``final_exp``) are not
read: ``ProfileData`` gives an op its HLO line as name and three timing
stats, and the ``op_name`` that holds the scope lies in the HLO protos of
the trace's ``/host:metadata`` plane, which it does not expose (PERF.md,
section 7).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

from chipbench.harness.reduce_trace import (
    ANCHOR,
    DEVICE_PLANE,
    MODULES_LINE,
    OPS_LINE,
    Interval,
    clip,
    find_trace,
    union,
)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(os.path.dirname(HERE))
TRACE_DIR = os.path.join(os.path.dirname(HERE), ".trace")
CACHE = os.path.join(TRACE_DIR, "spans.json")

PREFIX = "crypto."
RPC = "crypto.rpc."
FLUSH = "crypto.flush"
WINDOW = "crypto.window"
SERVE = "crypto.rpc.serve"
CHECK = "crypto.tpu.check"
MODULE_KINDS = (("scan", "jit_hbbft_scan_"), ("pair", "jit_hbbft_pair_"))


class Span(NamedTuple):
    line: Tuple[int, int]  # (plane, line) as the trace orders them
    name: str
    start: float           # ns on the trace's clock
    end: float
    args: Dict[str, Any]


class Module(NamedTuple):
    kind: str              # scan | pair | other
    start: float
    end: float


def read_spans(data: Any) -> List[Span]:
    """The program's spans on the host's planes, by start."""
    out: List[Span] = []
    for p, plane in enumerate(data.planes):
        if DEVICE_PLANE.match(plane.name):
            continue
        for l, line in enumerate(plane.lines):
            for ev in line.events:
                name = ev.name.split("#")[0]
                if name.startswith(PREFIX):
                    start = float(ev.start_ns)
                    out.append(Span(
                        (p, l), name, start, start + float(ev.duration_ns),
                        dict(ev.stats),
                    ))
    out.sort(key=lambda s: (s.start, -s.end))
    return out


def module_kind(name: str) -> str:
    for kind, prefix in MODULE_KINDS:
        if name.startswith(prefix):
            return kind
    return "other"


def read_device(data: Any) -> Tuple[List[Module], List[Interval]]:
    """The device planes' program launches and the intervals of their ops."""
    modules: List[Module] = []
    ops: List[Interval] = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == MODULES_LINE:
                for ev in line.events:
                    start = float(ev.start_ns)
                    modules.append(Module(
                        module_kind(ev.name), start, start + float(ev.duration_ns)
                    ))
            elif line.name == OPS_LINE:
                for ev in line.events:
                    start = float(ev.start_ns)
                    ops.append((start, start + float(ev.duration_ns)))
    modules.sort(key=lambda m: m.start)
    return modules, ops


def anchor_ns(data: Any) -> Optional[float]:
    """Where on the trace's clock the harness's anchor event starts."""
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(ANCHOR):
                    return float(ev.start_ns)
    return None


# -- flushes and their members ----------------------------------------------

def flush_members(spans: Sequence[Span]) -> List[Tuple[Span, List[Span]]]:
    """Every whole flush with the spans that belong to it (itself first)."""
    out = []
    for flush in (s for s in spans if s.name == FLUSH):
        ids = set(str(flush.args.get("spans", "")).split())
        members = [flush]
        window: Optional[Span] = None
        for s in spans:
            if s is flush:
                continue
            if s.line == flush.line:
                if flush.start <= s.start and s.end <= flush.end:
                    members.append(s)
                elif s.name == WINDOW and s.end <= flush.start and (
                    window is None or s.end > window.end
                ):
                    window = s
            elif s.name.startswith(RPC) and str(s.args.get("span")) in ids:
                members.append(s)
        if window is not None and not any(
            s.name == FLUSH and s.line == flush.line
            and window.end <= s.start < flush.start
            for s in spans
        ):
            members.append(window)
        out.append((flush, members))
    return out


def spans_per_flush(
    flushes: Sequence[Tuple[Span, List[Span]]]
) -> Dict[str, Dict[str, float]]:
    """``{name: {"ms", "count"}}``: summed duration and number of the spans
    of each name over the flushes' members, per flush."""
    n = len(flushes)
    out: Dict[str, Dict[str, float]] = {}
    for _, members in flushes:
        for s in members:
            entry = out.setdefault(s.name, {"ms": 0.0, "count": 0.0})
            entry["ms"] += (s.end - s.start) / 1e6 / n
            entry["count"] += 1.0 / n
    return out


# -- the two programs ----------------------------------------------------------

def modules_inside(
    modules: Sequence[Module], start: float, end: float
) -> Dict[str, Dict[str, float]]:
    """Seconds and launches by kind of the programs that start in
    ``[start, end]``."""
    out: Dict[str, Dict[str, float]] = {}
    for m in modules:
        if start <= m.start <= end:
            entry = out.setdefault(m.kind, {"seconds": 0.0, "launches": 0})
            entry["seconds"] += (m.end - m.start) / 1e9
            entry["launches"] += 1
    return out


def _mean(per: Sequence[Dict[str, Dict[str, float]]], times: float = 1.0):
    out: Dict[str, Dict[str, float]] = {}
    for one in per:
        for kind, entry in one.items():
            mine = out.setdefault(kind, {"seconds": 0.0, "launches": 0.0})
            mine["seconds"] += entry["seconds"] / len(per) * times
            mine["launches"] += entry["launches"] / len(per) * times
    return out


def modules_per_flush(
    modules: Sequence[Module], flushes: Sequence[Tuple[Span, List[Span]]]
) -> Optional[Dict[str, Dict[str, float]]]:
    """Per flush, over the whole flushes of the device's window."""
    if not flushes or not modules:
        return None
    return _mean([modules_inside(modules, f.start, f.end) for f, _ in flushes])


def modules_per_check(
    modules: Sequence[Module], spans: Sequence[Span]
) -> Tuple[Optional[Dict[str, Dict[str, float]]], int]:
    """Per aggregate check, over the whole checks of the device's window
    (those that hold a scan and a pair launch), and how many those were."""
    per = [
        inside for inside in (
            modules_inside(modules, s.start, s.end)
            for s in spans if s.name == CHECK
        )
        if "scan" in inside and "pair" in inside
    ]
    return (_mean(per), len(per)) if per else (None, 0)


# -- idle gaps by span ---------------------------------------------------------

def _innermost(spans: Sequence[Span], t: float) -> Optional[Span]:
    best: Optional[Span] = None
    for s in spans:
        if s.start <= t <= s.end and (
            best is None or (s.start, -s.end) >= (best.start, -best.end)
        ):
            best = s
    return best


def label_at(
    flush_side: Sequence[Span], rpc_side: Sequence[Span], t: float, rest: str
) -> str:
    inner = _innermost(flush_side, t)
    if inner is None:
        inner = _innermost(rpc_side, t)
    if inner is None:
        return rest
    return "unattributed" if inner.name in (FLUSH, SERVE) else inner.name


def idle_by_span(
    busy: Sequence[Interval], spans: Sequence[Span], lo: float, hi: float,
    rest: str,
) -> Dict[str, float]:
    """Seconds of ``[lo, hi]`` outside ``busy`` (the ops' union: sorted,
    disjoint), by the span each piece falls to.  The entries sum to the
    window's idle time."""
    flush_side = [s for s in spans if not s.name.startswith(RPC)]
    rpc_side = [s for s in spans if s.name.startswith(RPC)]
    edges = sorted(
        {lo, hi} | {t for s in spans for t in (s.start, s.end) if lo < t < hi}
    )
    labels = [
        label_at(flush_side, rpc_side, (a + b) / 2.0, rest)
        for a, b in zip(edges, edges[1:])
    ]
    out: Dict[str, float] = {}
    i = 0
    edge = lo
    for s, e in clip(busy, lo, hi) + [(hi, hi)]:
        # the gap [edge, s], cut at the edges it spans
        while edge < s:
            while edges[i + 1] <= edge:
                i += 1
            upto = min(s, edges[i + 1])
            out[labels[i]] = out.get(labels[i], 0.0) + (upto - edge) / 1e9
            edge = upto
        edge = max(edge, e)
    return out


# -- one run ------------------------------------------------------------------

def reduce_profiles(device: Any, host: Any) -> Dict[str, Any]:
    """Reduce the device window's profile and the host-only window's (either
    may be None)."""
    out: Dict[str, Any] = {
        "spans_from": None, "whole_flushes": 0, "spans_per_flush": None,
        "modules_per_flush": None, "modules_from": None,
        "idle_by_span_s": None,
    }
    dev_spans: List[Span] = []
    dev_flushes: List[Tuple[Span, List[Span]]] = []
    modules: List[Module] = []
    if device is not None:
        dev_spans = read_spans(device)
        dev_flushes = flush_members(dev_spans)
        modules, ops = read_device(device)
        if ops:
            busy = union(ops)
            del ops
            lo = anchor_ns(device)
            if lo is None:
                lo = busy[0][0]
            hi = max([busy[-1][1]] + [s.end for s in dev_spans])
            idle = idle_by_span(
                busy, dev_spans, lo, hi,
                "outside_worker" if dev_flushes else "unnamed_in_cut_window",
            )
            out["idle_by_span_s"] = idle
            out["idle_window_s"] = (hi - lo) / 1e9
            out["idle_s"] = sum(idle.values())
    host_flushes = flush_members(read_spans(host)) if host is not None else []
    flushes = dev_flushes or host_flushes
    if flushes:
        out["spans_from"] = "device_window" if dev_flushes else "host_only_window"
        out["whole_flushes"] = len(flushes)
        out["spans_per_flush"] = spans_per_flush(flushes)
    if dev_flushes:
        out["modules_per_flush"] = modules_per_flush(modules, dev_flushes)
        out["modules_from"] = "whole_flushes"
    elif flushes and CHECK in out["spans_per_flush"]:
        per_check, whole = modules_per_check(modules, dev_spans)
        if per_check is not None:
            checks = out["spans_per_flush"][CHECK]["count"]
            out["modules_per_flush"] = _mean([per_check], times=checks)
            out["modules_from"] = f"{whole}_whole_checks_x_{checks:g}_checks_per_flush"
    return out


def reduce_files(device_path: Optional[str], host_path: Optional[str]) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    t = time.perf_counter()
    out = reduce_profiles(
        ProfileData.from_file(device_path) if device_path else None,
        ProfileData.from_file(host_path) if host_path else None,
    )
    out["reduce_spans_s"] = time.perf_counter() - t
    return out


def _key(paths: Sequence[Optional[str]]) -> List[Any]:
    key = []
    for path in paths:
        st = os.stat(path) if path else None
        key.append([path, st.st_mtime_ns, st.st_size] if st else None)
    return key


def for_run(obs: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The reduction of the traces this run wrote (None where the worker
    held no jax device: it traced nothing, and what lies under the trace
    directory is another run's), from the cache where a reader asked before.  Writes the
    reduction's notes into the line's ``run``.  A reduction that fails is
    noted there (``reduce_spans_error``) and reads as None: a reader never
    raises."""
    if not obs.get("device_kind"):
        return None
    device = find_trace(os.path.join(TRACE_DIR, "device"))
    host = find_trace(os.path.join(TRACE_DIR, "host"))
    if not device and not host:
        return None
    key = _key([device, host])
    out = None
    try:
        with open(CACHE) as f:
            cached = json.load(f)
        if cached.get("key") == key:
            out = cached
    except (OSError, ValueError):
        pass
    if out is None:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # the child starts no backend
        env["PYTHONPATH"] = REPO_ROOT
        done = subprocess.run(
            [sys.executable, "-m", "chipbench.harness.reduce_spans"],
            input=json.dumps({"device": device, "host": host}),
            capture_output=True, text=True, env=env, cwd=REPO_ROOT, timeout=1800,
        )
        if done.returncode != 0:
            out = {"error": done.stderr[-2000:]}
        else:
            out = json.loads(done.stdout.strip().splitlines()[-1])
        out["key"] = key
        with open(CACHE, "w") as f:
            json.dump(out, f)
    notes = obs["notes"]
    if "error" in out:
        notes["reduce_spans_error"] = out["error"]
        return None
    for name in (
        "spans_per_flush", "idle_by_span_s", "spans_from", "modules_from", "idle_window_s", "reduce_spans_s",
    ):
        if out.get(name) is not None:
            notes[name] = out[name]
    return out


def span_ms(obs: Dict[str, Any], *names: str) -> Optional[float]:
    """Summed milliseconds per flush of the spans called ``names``; None
    without a whole flush that holds one of them."""
    out = for_run(obs)
    per = out and out["spans_per_flush"]
    if not per or not any(n in per for n in names):
        return None
    return sum(per[n]["ms"] for n in names if n in per)


def span_count(obs: Dict[str, Any], name: str) -> Optional[float]:
    out = for_run(obs)
    per = out and out["spans_per_flush"]
    return per[name]["count"] if per and name in per else None


def module_ms(obs: Dict[str, Any], kind: str) -> Optional[float]:
    """Device milliseconds per flush of the program of ``kind``."""
    out = for_run(obs)
    per = out and out["modules_per_flush"]
    if not per or kind not in per or not per[kind]["seconds"]:
        return None
    return per[kind]["seconds"] * 1e3


def main() -> int:
    """``python -m chipbench.harness.reduce_spans``: ``{"device": path or
    null, "host": path or null}`` on stdin, the reduction as one JSON line on
    stdout."""
    req = json.load(sys.stdin)
    print(json.dumps(reduce_files(req.get("device"), req.get("host"))))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
