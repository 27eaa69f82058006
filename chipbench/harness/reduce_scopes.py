"""From a traced run's device trace to device time by the program's named scope.

The program puts its stages under ``jax.named_scope``s (``scan_g1``,
``g2_stage``, ``miller_loop``, ``final_exp`` ...) and writes, as the
``scopes`` arg of its ``crypto.tpu.scan_dispatch`` and
``crypto.tpu.pair_dispatch`` spans, the op-to-scope map of the executable it
runs: which scope each instruction of the program's optimised HLO belongs to
(``hbbft_tpu/utils/op_scopes.py``, whose decoder and op-name rule this
module imports, only where it finds such an arg).  This module reads the
device window's trace once more, in a child process like ``reduce_spans``
(the parent never imports jax), and keeps its answer beside that one's
(``chipbench/.trace/scopes.json``, keyed by the traces' paths, mtimes and
sizes), so the readers under ``layer_metrics/`` that use it pay for one
parse.  A program that writes no map (an older commit) gives no
``device_by_scope_ms``: every reader then returns None.

How it reads:

* **Maps**: the decoded ``scopes`` args of the dispatch spans of the
  device's window, else of the host-only window, by module name.  A module
  launch on the device plane's ``XLA Modules`` line takes the map of the
  module it names (``jit_hbbft_pair_3`` for ``jit_hbbft_pair_3(79...)``).
* **Ops**: every event of the plane's ``XLA Ops`` line belongs to the launch
  that holds its start, and resolves through that launch's map to a scope
  path or to none (an op of a module without a map, such as the join
  program, resolves to none).
* **Time of a launch**: each scope path's seconds are the union of the
  intervals of its ops and of the ops of every path under it, so a
  ``conditional`` and the ``while`` inside it count once; ``<unscoped>`` is
  the union of all the launch's ops less what some scope claims;
  ``<module>`` is the launch's own duration.  The top-level paths plus
  ``<unscoped>`` add up to the union of the launch's ops, which is
  ``<module>`` where the ops cover the launch.
* **Per flush**: the launches that start inside a whole flush of the
  device's window, as ``reduce_spans.modules_per_flush`` takes them.  Where
  that window was cut inside a flush, the mean over its whole
  ``crypto.tpu.check`` spans (those that hold a scan and a pair launch, by
  ``reduce_spans.modules_inside``: ``modules_per_check``'s rule) times the
  checks per flush that the host-only window's flushes count.

``device_by_scope_ms`` is ``{module kind: {scope path, <unscoped> or
<module>: ms per flush}}``, kinds as ``reduce_spans.module_kind`` names
them (scan, pair, other).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from chipbench.harness import reduce_spans
from chipbench.harness.reduce_trace import (
    DEVICE_PLANE,
    MODULES_LINE,
    OPS_LINE,
    find_trace,
)

DISPATCHES = ("crypto.tpu.scan_dispatch", "crypto.tpu.pair_dispatch")
UNSCOPED = "<unscoped>"
MODULE = "<module>"

Op = Tuple[float, float, str]  # start, end (ns), name as the trace gives it
Launch = Tuple[float, str, Dict[str, float]]  # start, kind, seconds by key


def read_maps(spans: Sequence[reduce_spans.Span]) -> Dict[str, Dict[str, str]]:
    """``{module name: {instruction: scope path}}`` from the dispatch spans'
    ``scopes`` args."""
    texts = {
        str(s.args["scopes"]) for s in spans
        if s.name in DISPATCHES and s.args.get("scopes")
    }
    if not texts:
        return {}
    from hbbft_tpu.utils import op_scopes

    return dict(op_scopes.decode(text) for text in texts)


def read_planes(data: Any) -> List[Tuple[List[Op], List[Op]]]:
    """Each device plane's module launches and ops, by start."""
    out = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines: Dict[str, List[Op]] = {MODULES_LINE: [], OPS_LINE: []}
        for line in plane.lines:
            if line.name in lines:
                events = lines[line.name]
                for ev in line.events:
                    start = float(ev.start_ns)
                    events.append((start, start + float(ev.duration_ns), ev.name))
        out.append((sorted(lines[MODULES_LINE]), sorted(lines[OPS_LINE])))
    return out


def map_of(module: str, maps: Dict[str, Dict[str, str]]) -> Dict[str, str]:
    """The map of a launch's module: its name before the fingerprint in
    parentheses."""
    return maps.get(module.split("(", 1)[0], {})


class _Union:
    """The length of a union of intervals given in order of their starts."""

    def __init__(self) -> None:
        self.done = 0.0
        self.run: Optional[List[float]] = None  # the stretch still open

    def add(self, start: float, end: float) -> None:
        run = self.run
        if run is None or start > run[1]:
            if run is not None:
                self.done += run[1] - run[0]
            self.run = [start, end]
        elif end > run[1]:
            run[1] = end

    def seconds(self) -> float:
        run = self.run
        return (self.done + (run[1] - run[0] if run else 0.0)) / 1e9


def launch_seconds(
    module: Op, ops: Sequence[Op], paths_of: Callable[[str], Tuple[str, ...]]
) -> Dict[str, float]:
    """Seconds of one launch by scope path (each with the paths under it),
    ``<unscoped>`` and ``<module>``, from its ops in order of their starts;
    ``paths_of`` gives an op's scope path and every path above it."""
    claimed: Dict[str, _Union] = {}
    scoped, every = _Union(), _Union()
    for start, end, name in ops:
        every.add(start, end)
        paths = paths_of(name)
        if paths:
            scoped.add(start, end)
            for path in paths:
                if path not in claimed:
                    claimed[path] = _Union()
                claimed[path].add(start, end)
    out = {path: union.seconds() for path, union in claimed.items()}
    out[UNSCOPED] = every.seconds() - scoped.seconds()
    out[MODULE] = (module[1] - module[0]) / 1e9
    return out


def read_launches(
    planes: Sequence[Tuple[List[Op], List[Op]]], maps: Dict[str, Dict[str, str]]
) -> List[Launch]:
    """Every launch of the device planes with its seconds by key."""
    from hbbft_tpu.utils import op_scopes

    resolved: Dict[str, Dict[str, Tuple[str, ...]]] = {}  # by module, by op name

    def resolver(module: str) -> Callable[[str], Tuple[str, ...]]:
        scopes = map_of(module, maps)
        known = resolved.setdefault(module.split("(", 1)[0], {})

        def paths_of(name: str) -> Tuple[str, ...]:
            paths = known.get(name)
            if paths is None:
                parts = scopes.get(op_scopes.op_name(name), "").split("/")
                paths = known[name] = tuple(
                    "/".join(parts[:i]) for i in range(1, len(parts) + 1) if parts[0]
                )
            return paths

        return paths_of

    out: List[Launch] = []
    for modules, ops in planes:
        i = 0
        for module in modules:
            while i < len(ops) and ops[i][0] < module[0]:
                i += 1
            mine: List[Op] = []
            while i < len(ops) and ops[i][0] < module[1]:
                mine.append(ops[i])
                i += 1
            out.append((
                module[0], reduce_spans.module_kind(module[2]),
                launch_seconds(module, mine, resolver(module[2])),
            ))
    out.sort(key=lambda launch: launch[0])
    return out


def _inside(launches: Sequence[Launch], start: float, end: float):
    out: Dict[str, Dict[str, float]] = {}
    for at, kind, seconds in launches:
        if start <= at <= end:
            mine = out.setdefault(kind, {})
            for key, s in seconds.items():
                mine[key] = mine.get(key, 0.0) + s
    return out


def _mean_ms(per: Sequence[Dict[str, Dict[str, float]]], times: float = 1.0):
    out: Dict[str, Dict[str, float]] = {}
    for one in per:
        for kind, seconds in one.items():
            mine = out.setdefault(kind, {})
            for key, s in seconds.items():
                mine[key] = mine.get(key, 0.0) + s * 1e3 / len(per) * times
    return out


def reduce_profiles(device: Any, host: Any) -> Dict[str, Any]:
    """Reduce the device window's profile and the host-only window's (either
    may be None)."""
    out: Dict[str, Any] = {"device_by_scope_ms": None, "scopes_from": None}
    if device is None:
        return out
    spans = reduce_spans.read_spans(device)
    host_spans = reduce_spans.read_spans(host) if host is not None else []
    maps = read_maps(spans) or read_maps(host_spans)
    if not maps:
        return out
    planes = read_planes(device)
    launches = read_launches(planes, maps)
    flushes = reduce_spans.flush_members(spans)
    if flushes:
        out["device_by_scope_ms"] = _mean_ms(
            [_inside(launches, f.start, f.end) for f, _ in flushes]
        )
        out["scopes_from"] = f"{len(flushes)}_whole_flushes"
        return out
    host_flushes = reduce_spans.flush_members(host_spans)
    per_flush = reduce_spans.spans_per_flush(host_flushes) if host_flushes else {}
    if reduce_spans.CHECK not in per_flush:
        return out
    # reduce_spans.modules_per_check's whole checks: those that hold a scan
    # and a pair launch
    modules = [
        reduce_spans.Module(reduce_spans.module_kind(name), start, end)
        for launched, _ in planes for start, end, name in launched
    ]
    checks = [
        s for s in spans if s.name == reduce_spans.CHECK
        and {"scan", "pair"} <= set(reduce_spans.modules_inside(modules, s.start, s.end))
    ]
    if not checks:
        return out
    times = per_flush[reduce_spans.CHECK]["count"]
    out["device_by_scope_ms"] = _mean_ms(
        [_inside(launches, c.start, c.end) for c in checks], times=times
    )
    out["scopes_from"] = f"{len(checks)}_whole_checks_x_{times:g}_checks_per_flush"
    return out


def reduce_files(device_path: Optional[str], host_path: Optional[str]) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    t = time.perf_counter()
    out = reduce_profiles(
        ProfileData.from_file(device_path) if device_path else None,
        ProfileData.from_file(host_path) if host_path else None,
    )
    out["reduce_scopes_s"] = time.perf_counter() - t
    return out


def for_run(obs: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The reduction of the traces this run wrote, from the cache where a
    reader asked before; None where the worker held no jax device.  Writes
    ``device_by_scope_ms``, ``scopes_from`` and ``reduce_scopes_s`` into the
    line's ``run``.  A reduction that fails is noted there
    (``reduce_scopes_error``) and reads as None: a reader never raises."""
    if not obs.get("device_kind"):
        return None
    device = find_trace(os.path.join(reduce_spans.TRACE_DIR, "device"))
    host = find_trace(os.path.join(reduce_spans.TRACE_DIR, "host"))
    if not device:
        return None
    key = reduce_spans._key([device, host])
    cache = os.path.join(os.path.dirname(reduce_spans.CACHE), "scopes.json")
    out = None
    try:
        with open(cache) as f:
            cached = json.load(f)
        if cached.get("key") == key:
            out = cached
    except (OSError, ValueError):
        pass
    if out is None:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # the child starts no backend
        env["PYTHONPATH"] = reduce_spans.REPO_ROOT
        done = subprocess.run(
            [sys.executable, "-m", "chipbench.harness.reduce_scopes"],
            input=json.dumps({"device": device, "host": host}),
            capture_output=True, text=True, env=env, cwd=reduce_spans.REPO_ROOT,
            timeout=1800,
        )
        if done.returncode != 0:
            out = {"error": done.stderr[-2000:]}
        else:
            out = json.loads(done.stdout.strip().splitlines()[-1])
        out["key"] = key
        with open(cache, "w") as f:
            json.dump(out, f)
    notes = obs["notes"]
    if "error" in out:
        notes["reduce_scopes_error"] = out["error"]
        return None
    for name in ("device_by_scope_ms", "scopes_from", "reduce_scopes_s"):
        if out.get(name) is not None:
            notes[name] = out[name]
    return out


def scope_ms(obs: Dict[str, Any], *paths: str) -> Optional[float]:
    """Device milliseconds per flush under any of the scope ``paths``,
    summed over the programs; None where no program's ops resolve to one."""
    out = for_run(obs)
    per = out and out["device_by_scope_ms"]
    if not per:
        return None
    found = [kind[p] for kind in per.values() for p in paths if p in kind]
    return sum(found) if found else None


def unscoped_ms(obs: Dict[str, Any]) -> Optional[float]:
    """Device milliseconds per flush of ops that no scope claims, every
    program's."""
    out = for_run(obs)
    per = out and out["device_by_scope_ms"]
    if not per:
        return None
    return sum(kind.get(UNSCOPED, 0.0) for kind in per.values())


def main() -> int:
    """``python -m chipbench.harness.reduce_scopes``: ``{"device": path or
    null, "host": path or null}`` on stdin, the reduction as one JSON line on
    stdout."""
    req = json.load(sys.stdin)
    print(json.dumps(reduce_files(req.get("device"), req.get("host"))))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
