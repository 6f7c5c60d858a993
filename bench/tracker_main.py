#!/usr/bin/env python3
"""The tracker process, with the benchmark's own window into it.

Only the process that holds the chip can trace it or read its memory, and
the program has no profiler call. This starts a small thread that watches a
control directory, then calls ``tpumr.cli.main`` in this same process, so
the tracker is the program's own, unchanged. Requests are empty files the
benchmark touches; each is answered by ``<name>.done`` (one JSON line):

- ``trace_start``: ``jax.profiler.start_trace(<control>/trace)``, with the
  wall-clock anchors around the call;
- ``trace_stop``: ``jax.profiler.stop_trace()``;
- ``memory``: ``memory_stats()`` of every local device.

Usage: ``tracker_main.py <control-dir> <tpumr arguments...>``.
"""

import json
import os
import sys
import threading
import time


def _answer(control: str, name: str, payload: dict) -> None:
    tmp = os.path.join(control, f"{name}.tmp")
    with open(tmp, "w") as f:
        f.write(json.dumps(payload) + "\n")
    os.replace(tmp, os.path.join(control, f"{name}.done"))


def _handle(control: str, name: str) -> dict:
    import jax
    if name == "trace_start":
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # the host's Python is not read,
        opts.host_tracer_level = 1      # and would make the file huge
        t0 = time.time()
        jax.profiler.start_trace(os.path.join(control, "trace"),
                                 profiler_options=opts)
        return {"wall_before": t0, "wall_after": time.time()}
    if name == "trace_stop":
        t0 = time.time()
        jax.profiler.stop_trace()
        return {"wall_before": t0, "wall_after": time.time()}
    if name == "memory":
        return {"devices": [
            {"id": d.id, "platform": d.platform, "kind": d.device_kind,
             "stats": {k: int(v) for k, v in (d.memory_stats() or {}).items()
                       if isinstance(v, (int, float))}}
            for d in jax.local_devices()]}
    return {"error": f"unknown request {name}"}


def watch(control: str, stop: threading.Event) -> None:
    while not stop.is_set():
        for entry in sorted(os.listdir(control)):
            if not entry.endswith(".request"):
                continue
            name = entry[:-len(".request")]
            os.remove(os.path.join(control, entry))
            try:
                payload = _handle(control, name)
            except Exception as e:  # noqa: BLE001 - reported to the asker
                payload = {"error": f"{type(e).__name__}: {e}"}
            _answer(control, name, payload)
        stop.wait(0.05)


def main(argv: "list[str]") -> int:
    control, rest = argv[0], argv[1:]
    os.makedirs(control, exist_ok=True)
    stop = threading.Event()
    t = threading.Thread(target=watch, args=(control, stop),
                         name="bench-control", daemon=True)
    t.start()
    from tpumr.cli import main as tpumr_main
    try:
        return tpumr_main(rest)
    finally:
        stop.set()
        t.join(timeout=5)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
