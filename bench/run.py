#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Every run is a new process: it makes the cell's input from the seed (or
finds this seed's input from an earlier run of the cell in this checkout),
starts a jobtracker and a tasktracker that owns the chip, runs the cold job
and the traffic mix's warm-up jobs (all of that is ``setup_s``), drives the
mix for ``--seconds`` from one closed-loop client, stops the daemons,
compares what the window's jobs wrote with the plain reference, and prints
one JSON line. Everything that belongs to one cell is found BY NAME from
its entry in BENCHMARK.json (bench/README.md): ``configs/<config>.json``,
``traffic/<traffic>.json``, ``families/<family>.py``,
``layer_metrics/<metric>.json`` and the reader each names.

This process never imports JAX while the tracker lives; the device is what
the tracker says it holds. Without a TPU under the tracker (or with fewer
chips than the cell asks for) it prints no result and exits non-zero.
``--rehearse`` runs the same control flow at toy sizes on CPU devices: its
line names platform ``cpu``, carries no device metric, and it exits 1.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time

T_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import xplane  # noqa: E402
from bench.cluster import BenchFailure, Cluster  # noqa: E402

WORK = os.path.join(HERE, ".work")
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


# ------------------------------------------------------- found by name


def _load_json(*parts: str) -> dict:
    path = os.path.join(HERE, *parts)
    if not os.path.isfile(path):
        raise KeyError(f"no {os.path.join('bench', *parts)}")
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(bm: dict, name: str) -> dict:
    """The cell's entry with its configuration, traffic mix and family
    module, each found by the name the entry gives."""
    entry = next((w for w in bm["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}")
    config = _load_json("configs", entry["config"] + ".json")
    traffic = _load_json("traffic", entry["traffic"] + ".json")
    family = importlib.import_module(f"bench.families.{config['family']}")
    return {"name": name, "chips": entry["chips"], "config": config,
            "traffic": traffic, "family": family}


def find_reducer(name: str):
    """The reader function of this name in ``bench/reducers.py`` or any
    ``bench/reducers_*.py``."""
    mods = ["reducers"] + sorted(
        f[:-3] for f in os.listdir(HERE)
        if f.startswith("reducers_") and f.endswith(".py"))
    for mod in mods:
        fn = getattr(importlib.import_module(f"bench.{mod}"), name, None)
        if callable(fn):
            return fn
    raise KeyError(f"no reader {name!r} in bench/reducers*.py")


def cell_metrics(bm: dict, cell: str, kind: str) -> "list[dict]":
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bm[kind]
            if "workloads" not in m or cell in m["workloads"]]


def layer_readers(bm: dict, cell: str) -> "list[tuple[dict, object]]":
    out = []
    for m in cell_metrics(bm, cell, "per_layer"):
        spec = _load_json("layer_metrics", m["name"] + ".json")
        out.append((m, find_reducer(spec["reducer"])))
    return out


def load_peak(kind: str) -> dict:
    peaks = _load_json("peaks.json")
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


# ------------------------------------------------- the window's arithmetic


def window_metrics(jobs: "list[dict]", rows_per_job: int,
                   window_s: float) -> dict:
    """``rows_per_s``: the input rows of every job that completed sound in
    the window over the window's true length (stalls included).
    ``job_max_s``: the slowest job, submit to complete, client's side."""
    done = [j for j in jobs if not j.get("failure")]
    return {"rows_per_s": rows_per_job * len(done) / window_s,
            "job_max_s": max(j["client_s"] for j in jobs)}


def result_line(correct: bool, jobs: "list[dict]", metrics: dict,
                units: dict, device: dict, checks: dict,
                breakdown: "dict | None" = None) -> dict:
    line = {"correct": bool(correct), "attempted": len(jobs),
            "failed": sum(1 for j in jobs if j.get("failure")),
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in metrics.items()},
            "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    line["checks"] = checks     # each number compared, beside its limit
    return line


def compare(cell: dict, jobs: "list[dict]", sizes: dict, seed: int,
            inputs: dict) -> dict:
    """Each number compared beside its limit: what the window's jobs
    wrote against the plain reference (the family's), and the jobs that
    broke a guarantee the configuration states (limit 0)."""
    checks = cell["family"].check([j for j in jobs if j["ok"]], sizes, seed,
                                  inputs, cell["config"]["limits"])
    checks["jobs_unsound"] = {
        "value": sum(1 for j in jobs if j.get("failure")), "limit": 0}
    return checks


def judge(checks: dict) -> bool:
    """True where every compared number is within its limit and at least
    one was compared."""
    held = [c for c in checks.values() if c["limit"] is not None]
    return bool(held) and all(c["value"] <= c["limit"] for c in held)


# ------------------------------------------------------------------ data


def prepare_input(cell: dict, sizes: dict, seed: int, rehearse: bool
                  ) -> dict:
    """This seed's input, made now or found from an earlier run of this
    cell in this checkout. Another seed's input is deleted first, so the
    disk holds one set."""
    base = os.path.join(WORK, "data", cell["config"]["name"]
                        + ("-rehearse" if rehearse else ""))
    data_dir = os.path.join(base, f"seed-{seed}")
    ready = os.path.join(data_dir, "ready.json")
    if os.path.isfile(ready):
        with open(ready) as f:
            return json.load(f)
    shutil.rmtree(base, ignore_errors=True)
    os.makedirs(data_dir)
    inputs = cell["family"].make_input(sizes, seed, data_dir)
    with open(ready, "w") as f:
        json.dump(inputs, f)
    return inputs


# --------------------------------------------------------------- the run


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _defs(keys: "list[str]") -> "list[str]":
    return [x for d in keys for x in ("-D", d)]


def submit(cluster: Cluster, session, cell: dict, sizes: dict,
           on_chip: bool) -> dict:
    """One job through the client, with the master's rollup and the reason
    it counts under ``failed``, if any."""
    job = session.submit()
    job["rollup"] = cluster.rollup(job["name"]) if job["ok"] else None
    if not job["ok"]:
        job["failure"] = "the client reported failure"
    elif job["rollup"] is None:
        job["failure"] = "the master wrote no rollup"
    else:
        job["failure"] = cell["family"].job_failure(job["rollup"], sizes,
                                                    on_chip)
    if job["failure"]:
        log(f"job {job['name']} counts as failed: {job['failure']}"
            + job.get("stderr", ""))
    return job


def measure(cell: dict, bm: dict, seed: int, seconds: float, trace: bool,
            rehearse: bool) -> "tuple[dict, bool]":
    """Returns (the result line, whether it ran on the chips asked for)."""
    cfg, family = cell["config"], cell["family"]
    sizes = dict(cfg["sizes"], **(cfg["rehearse"] if rehearse else {}))
    run_dir = os.path.join(WORK, cell["name"], "run")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    inputs = prepare_input(cell, sizes, seed, rehearse)
    log(f"input ready: {inputs}")

    daemon_defs = _defs(cfg["cluster"]["daemon_defs"] + (
        ["tpumr.heartbeat.interval.ms=100"] if rehearse else []))
    cluster = Cluster(run_dir, daemon_defs,
                      _defs(cfg["cluster"]["tracker_defs"]))
    session = None
    try:
        cluster.start()
        dev = cluster.device
        on_chip = dev["platform"] == "tpu" and dev["count"] >= cell["chips"]
        log(f"cluster up; tracker devices: {dev}")
        if not on_chip and not rehearse:
            raise BenchFailure(
                f"cell {cell['name']} needs {cell['chips']} TPU chip(s); "
                f"the tracker has {dev}")
        job_defs = cfg["cluster"]["job_defs"] + (
            ["tpumr.trace.enabled=true"] if trace else [])
        session = family.Session(cluster, sizes, cell["traffic"], inputs,
                                 run_dir, job_defs)

        # the cold job: the first job on a fresh tracker with the compile
        # cache warm. Where the tracker compiled for it (a first run in
        # this checkout), the job is run again on a fresh tracker.
        log_at = cluster.tt.log_size()
        cold = submit(cluster, session, cell, sizes, on_chip)
        compile_s, _ = cluster.tt.compiles(log_at)
        log(f"first job {cold['client_s']:.3f}s, tracker compile "
            f"{compile_s:.3f}s")
        if compile_s > 1.0 and not rehearse:
            session.restart()
            cluster.restart_tracker()
            cold = submit(cluster, session, cell, sizes, on_chip)
            log(f"cold job again on a fresh tracker {cold['client_s']:.3f}s")
        if cold["failure"]:
            raise BenchFailure(f"the cold job failed: {cold['failure']}")
        for _ in range(int(cell["traffic"]["warmup_jobs"])):
            submit(cluster, session, cell, sizes, on_chip)

        # the window: one closed-loop client; the next job is submitted
        # only while the deadline has not passed, the last one finishes
        anchor = cluster.request("trace_start") if trace else None
        log_at = cluster.tt.log_size()
        jobs = []
        wall0, t0 = time.time_ns(), time.monotonic()
        setup_s = t0 - T_START
        while time.monotonic() - t0 < seconds:
            jobs.append(submit(cluster, session, cell, sizes, on_chip))
        window_s = time.monotonic() - t0
        wall1 = time.time_ns()
        if trace:
            cluster.request("trace_stop", timeout=240)
        memory = cluster.request("memory")
        _, window_compiles = cluster.tt.compiles(log_at)
        spans = cluster.spans([j["rollup"]["job_id"] for j in jobs
                               if j["rollup"]]) if trace else None
        log(f"window {window_s:.3f}s, {len(jobs)} job(s): "
            f"{[round(j['client_s'], 3) for j in jobs]}; trace, memory "
            f"and spans read")
    finally:
        if session is not None:
            session.close()
        rcs = cluster.stop()
        shutil.rmtree(os.path.join(run_dir, "local"), ignore_errors=True)
    if any(rcs.values()):
        raise BenchFailure(f"daemons did not stop cleanly: {rcs}")
    log("daemons stopped")
    for j in jobs:      # which maps ran on a chip: the master's log is whole
        j["chip_maps"] = [] if not on_chip else (
            cluster.tpu_maps(j["rollup"]["job_id"]) if j["rollup"] else None)
    t_ref = time.monotonic()

    # the daemons are gone and the device's memory is read: the reference
    with open(os.path.join(run_dir, "jobs.json"), "w") as f:
        json.dump(jobs, f)
    checks = compare(cell, jobs, sizes, seed, inputs)
    correct = judge(checks)
    if not rehearse:
        for j in jobs:      # a sort's output is 1 GB a job
            shutil.rmtree(j["out"], ignore_errors=True)
    log(f"compared in {time.monotonic() - t_ref:.1f}s")

    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": max(
                  [d["stats"].get("peak_bytes_in_use", 0)
                   for d in memory.get("devices", [])] or [0])}
    e2e = dict(window_metrics(jobs, family.rows_per_job(sizes), window_s),
               cold_job_s=cold["client_s"], setup_s=setup_s)
    if not trace:
        wanted = cell_metrics(bm, cell["name"], "end_to_end")
        metrics = {m["name"]: e2e[m["name"]] for m in wanted}
        units = {m["name"]: m["unit"] for m in wanted}
        return result_line(correct, jobs, metrics, units, device,
                           checks), on_chip

    obs = {"jobs": jobs, "window_s": window_s, "spans": spans,
           "sizes": sizes, "window_compiles": window_compiles,
           "trace": None, "peak": None}
    breakdown = None
    path = xplane.find(os.path.join(cluster.control, "trace"))
    if path and on_chip:
        tr = xplane.read(path)
        zero = tr["start_unix_ns"] or int(anchor["wall_after"] * 1e9)
        tr["lo"], tr["hi"] = float(wall0 - zero), float(wall1 - zero)
        obs["trace"], obs["peak"] = tr, load_peak(dev["kind"])
        busy = xplane.busy_seconds(tr, tr["lo"], tr["hi"])
        device["busy_s"] = busy if busy is not None else 0.0
        device["window_s"] = (tr["hi"] - tr["lo"]) / 1e9
        breakdown = make_breakdown(tr, spans or [], zero)
    metrics, units = {}, {}
    for m, reader in layer_readers(bm, cell["name"]):
        value = reader(obs)
        if value is not None:
            metrics[m["name"]], units[m["name"]] = value, m["unit"]
    return result_line(correct, jobs, metrics, units, device, checks,
                       breakdown), on_chip


def make_breakdown(tr: dict, spans: "list[dict]", zero: int) -> dict:
    """The device operations that took most time, and the longest idle
    gaps of the first device, each named by the program's span that
    covers most of it."""
    ops = xplane.device_ops(tr, tr["lo"], tr["hi"])
    named = [(s["name"], s["start"] * 1e9 - zero, s["end"] * 1e9 - zero)
             for s in spans if s.get("end") and s["name"] != "job"]
    gaps = []
    if ops:
        merged = xplane.union(ops[sorted(ops)[0]])
        for g in xplane.longest_gaps(merged, tr["lo"], tr["hi"]):
            gaps.append([xplane.label_gap(g, named), (g[1] - g[0]) / 1e9])
    return {"device_ops": xplane.top_ops(tr, tr["lo"], tr["hi"]),
            "idle_gaps": gaps}


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes, CPU devices, a 100 ms beat; exits 1")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "tpumr")):
        print("bench/run.py: no tpumr package beside bench/; run it from a "
              "checkout", file=sys.stderr)
        return 2
    try:
        bm = load_benchmark()
        cell = load_cell(bm, args.workload)
        line, on_chip = measure(cell, bm, args.seed, args.seconds,
                                bool(args.trace), args.rehearse)
    except (BenchFailure, KeyError) as e:
        log(f"no result: {e}")
        return 3
    for name, c in line["checks"].items():
        print(f"check {name}: value {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0 if on_chip else 1


if __name__ == "__main__":
    sys.exit(main())
