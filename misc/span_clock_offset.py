#!/usr/bin/env python3
"""How far the program's wall-clock spans lie from the profiler's clock.

    python misc/span_clock_offset.py <trace dir> <history dir> [pattern=name ...]

In a process that has imported ``jax``, an ambient span of a traced job is
mirrored as a ``TraceAnnotation`` with its ``span_id`` (core/tracing.py).
This joins the annotations of a ``jax.profiler`` trace (``*.xplane.pb``
under ``<trace dir>``) to the spans in ``<history dir>/trace-*.jsonl`` on
that id and prints, per span name, the median and the worst of

    (span.start - profile_start_time) - annotation.start

which is what labelling a gap of the device trace from wall-clock spans
can be trusted to. Each ``pattern=name`` (say ``_argsort=dshuffle:device``)
also counts how many executions of the device programs matching ``pattern``
lie inside an annotation called ``name``, and where each starts against the
start of the annotation nearest to it.
"""

import glob
import json
import os
import re
import statistics
import sys


def read_spans(history: str) -> "dict[str, dict]":
    spans = {}
    for path in glob.glob(os.path.join(history, "trace-*.jsonl")):
        with open(path) as f:
            for line in f:
                try:
                    s = json.loads(line)
                except ValueError:
                    continue
                spans[s["span_id"]] = s
    return spans


def read_profile(trace_dir: str):
    """(profile start in unix ns, annotations [(name, span_id, start_ns,
    duration_ns)], device programs [(name, start_ns, duration_ns)])."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise SystemExit(f"no *.xplane.pb under {trace_dir}")
    zero, notes, programs = None, [], []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name == "Task Environment":
            zero = dict(plane.stats).get("profile_start_time")
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != "XLA Modules":
                continue
            for e in line.events:
                if device:
                    programs.append((e.name, e.start_ns, e.duration_ns))
                    continue
                span_id = dict(e.stats).get("span_id")
                if span_id:
                    notes.append((e.name, span_id, e.start_ns,
                                  e.duration_ns))
    return zero, notes, programs


def main(argv: "list[str]") -> int:
    if len(argv) < 2:
        print(__doc__, file=sys.stderr)
        return 2
    zero, notes, programs = read_profile(argv[0])
    spans = read_spans(argv[1])
    print(f"profile_start_time {zero}; {len(notes)} annotations with a "
          f"span id, {len(spans)} spans, {len(programs)} program runs")
    if zero is None:
        print("the profile has no profile_start_time")
        return 1
    per: "dict[str, list[float]]" = {}
    length: "dict[str, list[float]]" = {}
    for name, span_id, start_ns, dur_ns in notes:
        s = spans.get(span_id)
        if s is None or not s.get("end"):
            continue
        per.setdefault(name, []).append(
            (s["start"] * 1e9 - zero - start_ns) / 1e9)
        length.setdefault(name, []).append(
            (s["end"] - s["start"]) - dur_ns / 1e9)
    everything = [x for xs in per.values() for x in xs]
    for name, xs in sorted(per.items()) + [("ALL", everything)]:
        if not xs:
            continue
        worst = max(xs, key=abs)
        line = (f"{name}: n {len(xs)} offset_s median "
                f"{statistics.median(xs):.9f} worst {worst:.9f}")
        if name in length:
            line += (" | span minus annotation length_s median "
                     f"{statistics.median(length[name]):.9f} worst "
                     f"{max(length[name], key=abs):.9f}")
        print(line)
    for arg in argv[2:]:
        pattern, _, name = arg.partition("=")
        rx = re.compile(pattern)
        boxes = [(a, a + d) for n, _sid, a, d in notes if n == name]
        runs = [(a, a + d) for n, a, d in programs if rx.search(n)]
        inside = sum(1 for a, b in runs
                     if any(lo <= a and b <= hi for lo, hi in boxes))
        print(f"{pattern}: {inside} of {len(runs)} program runs lie inside "
              f"one of {len(boxes)} {name} annotations")
        if not boxes or not runs:
            continue
        # where a run starts against the annotation nearest to it: the
        # device line's own clock against the host line's
        lead = []
        for a, b in runs:
            lo, hi = min(boxes, key=lambda box: min(abs(a - box[0]),
                                                    abs(a - box[1])))
            lead.append((a - lo) / 1e9)
            if not (lo <= a and b <= hi):
                under = [(n, (a - x) / 1e9) for n, _sid, x, d in notes
                         if x <= a < x + d]
                print(f"  outside: run at {a / 1e9:.6f} s for "
                      f"{(b - a) / 1e9:.6f} s; nearest {name} "
                      f"{lo / 1e9:.6f} to {hi / 1e9:.6f} s; annotations "
                      f"open at its start (name, seconds in): {under}")
        print(f"{pattern}: run start minus {name} start, s: min "
              f"{min(lead):.6f} median {statistics.median(lead):.6f} max "
              f"{max(lead):.6f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
