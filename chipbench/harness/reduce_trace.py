"""From a profiler trace (``.xplane.pb``) to device busy time and launches.

Read with ``jax.profiler.ProfileData`` alone (importing it starts no
backend, so the parent stays off the chip).  Nothing here relies on a
module's or an op's name: both flush stages are ``jit_run`` today.

* device plane: a plane named ``/device:<KIND>:<n>``;
* launches: the events on its ``XLA Modules`` line, one per program run;
* busy: the union of the intervals of its ``XLA Ops`` line (of the modules
  line where a trace has no ops line), so overlapping events count once;
* idle gaps: the stretches of the traced window that the union leaves;
* launches as the host saw them: the runtime's ``tpu::System::Execute=>Done``
  events on the host's planes, one per program run.  A window that records
  the host alone holds them for a flush of any length (the device's buffer
  does not), and in a device window they stand beside the modules line, so
  every traced run checks the one count against the other.

The trace has its own clock.  The worker entry writes one host event named
``chipbench_anchor:<time.time_ns()>`` right after the trace starts; its
position on the trace's clock gives the offset to the wall clock on which
the harness stamps its flushes, so a gap can be labelled ``inside_flush`` or
``between_flushes``.  Without the anchor gaps are ``unlabelled`` and the
window is the span from the first to the last device event.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
ANCHOR = "chipbench_anchor:"
HOST_LAUNCH = "tpu::System::Execute=>Done"
TOP = 10
NAME_CHARS = 80

Interval = Tuple[float, float]


def find_trace(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` that a profiler session left under
    ``trace_dir``."""
    found = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    return max(found, key=os.path.getmtime) if found else None


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [
        (max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi
    ]


def _anchor_offset_ns(data: Any) -> Optional[float]:
    """wall clock minus trace clock, from the entry's anchor event."""
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(ANCHOR):
                    wall = ev.name[len(ANCHOR):].split("#")[0]
                    return float(int(wall)) - float(ev.start_ns)
    return None


def host_launches(data: Any, window_wall_ns: Optional[Interval] = None) -> int:
    """The runtime's launch events on the host's planes; inside the window
    where the trace has its anchor and a window is given."""
    offset = _anchor_offset_ns(data)
    lo = hi = None
    if offset is not None and window_wall_ns is not None:
        lo, hi = window_wall_ns[0] - offset, window_wall_ns[1] - offset
    count = 0
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == HOST_LAUNCH and (
                    lo is None or lo <= float(ev.start_ns) <= hi
                ):
                    count += 1
    return count


def whole_periods(names: Sequence[str]) -> int:
    """How many of ``names``, from the first, make up whole repeats of the
    shortest pattern that the sequence repeats at least twice; all of them
    where it repeats none.  A window closed inside a flush ends inside one
    aggregate check, and a mean over whole checks is not skewed by it."""
    n = len(names)
    for p in range(1, n // 2 + 1):
        if all(names[i] == names[i + p] for i in range(n - p)):
            return n // p * p
    return n


def short_name(name: str) -> str:
    """An op's name as the trace gives it is its whole HLO line; what comes
    before `` = `` names it."""
    return name.split(" = ", 1)[0][:NAME_CHARS]


def _label(gap: Interval, flushes: Sequence[Interval]) -> str:
    mid = (gap[0] + gap[1]) / 2.0
    for start, end in flushes:
        if start <= mid <= end:
            return "inside_flush"
    return "between_flushes"


def reduce_profile(
    data: Any,
    window_wall_ns: Optional[Interval] = None,
    flushes_wall_ns: Sequence[Interval] = (),
) -> Optional[Dict[str, Any]]:
    """Reduce one profile.  ``window_wall_ns`` is the traced window and
    ``flushes_wall_ns`` the flushes in it, both on the wall clock
    (``time.time_ns()``).  Returns None where no operation ran on a device
    plane: there is nothing to read, which is not the same as 0."""
    offset = _anchor_offset_ns(data)
    planes = [p for p in data.planes if DEVICE_PLANE.match(p.name)]
    per_plane = []
    all_modules: List[Tuple[float, float, str]] = []
    op_seconds: Dict[str, float] = {}
    module_seconds: Dict[str, float] = {}
    launches = 0
    for plane in planes:
        lines = {line.name: line for line in plane.lines}
        modules = [
            (float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
            for e in (lines[MODULES_LINE].events if MODULES_LINE in lines else ())
        ]
        ops = [
            (float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
            for e in (lines[OPS_LINE].events if OPS_LINE in lines else ())
        ]
        busy_src = ops or modules
        if not busy_src:
            continue
        launches += len(modules)
        all_modules += modules
        for s, e, name in modules:
            module_seconds[name] = module_seconds.get(name, 0.0) + (e - s) / 1e9
        for s, e, name in ops:
            name = short_name(name)
            op_seconds[name] = op_seconds.get(name, 0.0) + (e - s) / 1e9
        per_plane.append(union([(s, e) for s, e, _ in busy_src]))
    if not per_plane:
        return None

    if offset is not None and window_wall_ns is not None:
        lo, hi = window_wall_ns[0] - offset, window_wall_ns[1] - offset
        flushes = [(s - offset, e - offset) for s, e in flushes_wall_ns]
    else:
        lo = min(iv[0][0] for iv in per_plane)
        hi = max(iv[-1][1] for iv in per_plane)
        flushes = None
    busy_ns = 0.0
    gaps: List[Interval] = []
    for merged in per_plane:
        inside = clip(merged, lo, hi)
        busy_ns += sum(e - s for s, e in inside)
        edge = lo
        for s, e in inside:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
        if hi > edge:
            gaps.append((edge, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    idle_gaps = [
        [_label(g, flushes) if flushes is not None else "unlabelled",
         (g[1] - g[0]) / 1e9]
        for g in gaps[:TOP]
    ]
    idle_by_label: Dict[str, float] = {}
    for g in gaps:
        label = _label(g, flushes) if flushes is not None else "unlabelled"
        idle_by_label[label] = idle_by_label.get(label, 0.0) + (g[1] - g[0]) / 1e9
    top_modules = sorted(module_seconds.items(), key=lambda kv: -kv[1])
    top_ops = sorted(op_seconds.items(), key=lambda kv: -kv[1])
    device_ops = [["module:" + n, s] for n, s in top_modules[:3]]
    device_ops += [[n, s] for n, s in top_ops[: TOP - len(device_ops)]]
    all_modules.sort()
    whole = whole_periods([name for _, _, name in all_modules])
    return {
        "device_planes": len(per_plane),
        "busy_s": busy_ns / 1e9 / len(per_plane),
        "window_s": (hi - lo) / 1e9,
        "launches": launches,
        "launches_in_whole_periods": whole,
        "module_s_per_launch": (
            sum(e - s for s, e, _ in all_modules[:whole]) / 1e9 / whole
            if whole else None
        ),
        "host_launch_events": host_launches(data, window_wall_ns),
        "anchored": offset is not None and window_wall_ns is not None,
        "device_ops": device_ops,
        "idle_gaps": idle_gaps,
        "idle_by_label_s": idle_by_label,
        "modules": {
            n: {"seconds": s} for n, s in top_modules[:TOP]
        },
    }


def describe(data: Any, first: int = 3) -> List[Dict[str, Any]]:
    """The planes and lines of a profile with their event counts and first
    events: what to look at by hand before trusting a reduction."""
    return [
        {
            "plane": plane.name,
            "lines": [
                {
                    "name": line.name,
                    "events": sum(1 for _ in line.events),
                    "first": [
                        (short_name(e.name), e.start_ns, e.duration_ns)
                        for _, e in zip(range(first), line.events)
                    ],
                }
                for line in plane.lines
            ],
        }
        for plane in data.planes
    ]


def reduce_file(
    path: str,
    window_wall_ns: Optional[Interval] = None,
    flushes_wall_ns: Sequence[Interval] = (),
) -> Optional[Dict[str, Any]]:
    from jax.profiler import ProfileData

    return reduce_profile(
        ProfileData.from_file(path), window_wall_ns, flushes_wall_ns
    )


def main() -> int:
    """``python -m chipbench.harness.reduce_trace``: one JSON request on
    stdin, one JSON line on stdout.  ``path``, ``window_wall_ns`` and
    ``flushes_wall_ns`` name the device window's trace, whose reduction comes
    back as ``device`` (``null`` where no device event was found);
    ``host_path`` and ``host_window_wall_ns`` the host-only window's, whose
    launch count comes back as ``host_launches``.  With ``"describe": true``
    the line is :func:`describe`'s list of ``path`` instead."""
    import json
    import sys

    from jax.profiler import ProfileData

    req = json.load(sys.stdin)
    if req.get("describe"):
        print(json.dumps(describe(ProfileData.from_file(req["path"]))))
        return 0
    out: Dict[str, Any] = {}
    if req.get("path"):
        window = req.get("window_wall_ns")
        out["device"] = reduce_file(
            req["path"],
            tuple(window) if window else None,
            [tuple(f) for f in req.get("flushes_wall_ns", ())],
        )
    if req.get("host_path"):
        window = req.get("host_window_wall_ns")
        out["host_launches"] = host_launches(
            ProfileData.from_file(req["host_path"]),
            tuple(window) if window else None,
        )
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
