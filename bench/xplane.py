"""From a ``jax.profiler`` trace (``*.xplane.pb``) to busy intervals,
per-program device time and the longest idle gaps.

``read`` turns the file into plain lists, so that every reduction below
works on hand-built data (tests/bench/test_xplane.py) and on a trace alike.
Times inside are nanoseconds from the profile's start; ``start_unix_ns`` is
the profiler's own wall-clock anchor for that zero.
"""

from __future__ import annotations

import glob
import os
import re
import sys

#: lines of a device plane whose events are operations running on the
#: device (one event each), and the line of whole-program executions
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def find(trace_dir: str) -> "str | None":
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def read(path: str) -> dict:
    """``{"start_unix_ns", "stop_unix_ns", "devices": {plane: {line:
    [(name, start_ns, duration_ns)]}}}`` for the planes that are devices."""
    from jax.profiler import ProfileData    # reads the file; no backend
    pd = ProfileData.from_file(path)
    out = {"start_unix_ns": None, "stop_unix_ns": None, "devices": {}}
    for plane in pd.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            out["start_unix_ns"] = stats.get("profile_start_time")
            out["stop_unix_ns"] = stats.get("profile_stop_time")
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {}
        for line in plane.lines:
            lines[line.name] = [(e.name, float(e.start_ns),
                                 float(e.duration_ns)) for e in line.events]
        out["devices"][plane.name] = lines
    return out


def clip(events: "list[tuple]", lo: float, hi: float) -> "list[tuple]":
    """The parts of the events inside ``[lo, hi]``."""
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events: "list[tuple]") -> "list[tuple[float, float]]":
    """Merged ``(start, end)`` intervals of possibly overlapping events."""
    merged: "list[list[float]]" = []
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return [(a, b) for a, b in merged]


def busy_ns(merged: "list[tuple[float, float]]") -> float:
    return sum(b - a for a, b in merged)


def device_ops(trace: dict, lo: float, hi: float) -> "dict[str, list]":
    """Per device plane, the operation events inside the window (the
    modules' where a plane has no operation line)."""
    out = {}
    for plane, lines in trace["devices"].items():
        for names in (OP_LINES, MODULE_LINES):
            events = [e for n in names for e in lines.get(n, [])]
            if events:
                break
        out[plane] = clip(events, lo, hi)
    return out


def busy_seconds(trace: dict, lo: float, hi: float) -> "float | None":
    """Seconds in which an operation ran on the device, averaged over the
    device planes; None where the trace has no device plane."""
    per = [busy_ns(union(ev)) for ev in device_ops(trace, lo, hi).values()]
    return sum(per) / len(per) / 1e9 if per else None


def program_runs(trace: dict, pattern: str, lo: float, hi: float
                 ) -> "list[float]":
    """Device seconds of each execution, inside the window, of the programs
    whose name matches ``pattern`` (all device planes)."""
    rx = re.compile(pattern)
    runs = []
    for lines in trace["devices"].values():
        for n in MODULE_LINES:
            for name, start, dur in lines.get(n, []):
                if lo <= start and start + dur <= hi and rx.search(name):
                    runs.append(dur / 1e9)
    return runs


def top_ops(trace: dict, lo: float, hi: float, n: int = 10) -> "list[list]":
    """``[name, seconds]`` of the operations that took most device time."""
    total: "dict[str, float]" = {}
    for events in device_ops(trace, lo, hi).values():
        for name, _start, dur in events:
            name = name[:96]    # an operation's name is its whole HLO line
            total[name] = total.get(name, 0.0) + dur / 1e9
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def longest_gaps(merged: "list[tuple[float, float]]", lo: float, hi: float,
                 n: int = 10) -> "list[tuple[float, float]]":
    """The ``n`` longest idle ``(start, end)`` stretches of the window."""
    gaps, at = [], lo
    for a, b in merged:
        if a > at:
            gaps.append((at, min(a, hi)))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])[:n]


def label_gap(gap: "tuple[float, float]", spans: "list[tuple]") -> str:
    """The name of the span (``(name, start, end)``, same clock as the
    gap) that covers most of the gap; ``none`` if none covers any."""
    cover: "dict[str, float]" = {}
    for name, start, end in spans:
        c = min(end, gap[1]) - max(start, gap[0])
        if c > 0:
            cover[name] = cover.get(name, 0.0) + c
    return max(cover, key=cover.get) if cover else "none"


def dump(path: str) -> None:
    """Look at a trace by hand: planes, lines, event counts and names."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print("PLANE", plane.name, dict(list(plane.stats)[:8]))
        for line in plane.lines:
            events = list(line.events)
            names: "dict[str, list]" = {}
            for e in events:
                s = names.setdefault(e.name, [0, 0.0])
                s[0] += 1
                s[1] += e.duration_ns
            print(f"  LINE {line.name!r}: {len(events)} events")
            for k, (c, ns) in sorted(names.items(),
                                     key=lambda kv: -kv[1][1])[:12]:
                print(f"      {c:7d} x {ns / 1e9:10.6f} s  {k[:100]}")
            if events:
                e = events[0]
                print("      first:", e.name[:60], e.start_ns, e.duration_ns,
                      list(e.stats)[:6])


if __name__ == "__main__":
    dump(find(sys.argv[1]) if os.path.isdir(sys.argv[1]) else sys.argv[1])
