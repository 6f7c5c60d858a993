"""TeraSort at scale through the distributed shuffle copier.

BASELINE workload 5 (10 GB, 100M x 100B) end-to-end on a real
mini-cluster: map spills (tlz-compressed), tasktracker chunked serving,
the parallel RAM-budgeted reduce copier (segments in RAM or spilled,
counted), streamed merge, and a full teravalidate. Round 2's 772 s scale
proof predates the copier (it ran the serial LocalJobRunner shuffle);
this is the path `ReduceTask.java:659,1080` describes.

Host-only (no TPU needed). Run:  python misc/bench_terasort_scale.py
[records] [reduces]; prints one JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    records = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000_000
    reduces = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    #: reuse an existing teragen dir (skip the 3-min gen) and/or raise
    #: the copier RAM budget: TERASORT_GEN_DIR=..., TERASORT_RAM_MB=...
    #: TERASORT_DEVICE=1 runs the dense/gang-reduce shuffle instead of
    #: the per-record host path (vectorized end-to-end; sorts on the
    #: accelerator devices, or on the CPU backend under JAX_PLATFORMS=cpu)
    gen_dir = os.environ.get("TERASORT_GEN_DIR")
    ram_mb = float(os.environ.get("TERASORT_RAM_MB", 0) or 0)
    device = os.environ.get("TERASORT_DEVICE") == "1"

    from tpumr.cli import main as cli_main
    from tpumr.core.counters import TaskCounter
    from tpumr.examples.terasort import make_terasort_conf
    from tpumr.mapred.job_client import JobClient
    from tpumr.mapred.jobconf import JobConf
    from tpumr.mapred.mini_cluster import MiniMRCluster

    work = tempfile.mkdtemp(prefix="tpumr-terasort-scale-")
    rows: dict = {"records": records, "gb": records * 100 / 1e9,
                  "reduces": reduces}

    if gen_dir:
        gen_uri = gen_dir if "://" in gen_dir else f"file://{gen_dir}"
        rows["teragen_s"] = 0.0
    else:
        gen_uri = f"file://{work}/gen"
        t0 = time.time()
        assert cli_main(["examples", "teragen", str(records),
                         gen_uri, "-m", "8"]) == 0
        rows["teragen_s"] = round(time.time() - t0, 1)
        print(f"[teragen] {records:,} records: {rows['teragen_s']}s",
              file=sys.stderr, flush=True)

    base = JobConf()
    with MiniMRCluster(num_trackers=2, cpu_slots=2, tpu_slots=0,
                       conf=base) as c:
        conf = c.create_job_conf()
        ts = make_terasort_conf(gen_uri, f"file://{work}/out", reduces,
                                device_shuffle=device)
        rows["device_shuffle"] = device
        for k, v in ts:
            conf.set(k, v)
        # production shuffle config: tlz-compressed map outputs through
        # the parallel RAM-budgeted copier
        conf.set("mapred.compress.map.output", True)
        conf.set("mapred.map.output.compression.codec", "tlz")
        if ram_mb:
            conf.set("tpumr.shuffle.ram.mb", ram_mb)
            rows["shuffle_ram_mb"] = ram_mb
        t0 = time.time()
        result = JobClient(conf).run_job(conf)
        rows["terasort_s"] = round(time.time() - t0, 1)
        assert result.successful, result.error
        cv = result.counters.value
        if device:
            # which backend ACTUALLY sorted (the gang reduce stamps a
            # counter when jax resolved to a real accelerator) — the
            # artifact must say "backend: tpu" only when it was
            from tpumr.core.counters import BackendCounter
            rows["backend"] = ("tpu" if cv(
                BackendCounter.GROUP,
                BackendCounter.DEVICE_SORT_ON_ACCEL) else "cpu")
        rows["shuffle_bytes"] = cv(TaskCounter.FRAMEWORK_GROUP,
                                   TaskCounter.REDUCE_SHUFFLE_BYTES)
        rows["segments_mem"] = cv(
            TaskCounter.FRAMEWORK_GROUP,
            TaskCounter.REDUCE_SHUFFLE_SEGMENTS_MEM)
        rows["segments_disk"] = cv(
            TaskCounter.FRAMEWORK_GROUP,
            TaskCounter.REDUCE_SHUFFLE_SEGMENTS_DISK)

    t0 = time.time()
    import contextlib
    with contextlib.redirect_stdout(sys.stderr):   # keep stdout pure JSON
        assert cli_main(["examples", "teravalidate", f"file://{work}/out",
                         f"file://{work}/validate"]) == 0
    rows["teravalidate_s"] = round(time.time() - t0, 1)
    rows["mb_per_s"] = round(records * 100 / 1e6 / rows["terasort_s"], 1)
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
