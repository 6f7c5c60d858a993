#!/usr/bin/env python3
"""The PageRank round driver: a user's iterative driver program, and the
traffic generator of the ``rounds`` mix for the ``pagerank`` family.

The loop of ``tpumr examples pagerank`` (the same ``JobConf`` keys: the
graph's split files through ``DenseInputFormat``, one split a file, the
``pagerank`` kernel, its reducer, one state directory a round), which runs
a fixed number of iterations and cannot stop at a deadline. This one runs
one iteration each time its standard input says ``round`` and answers with
one JSON line: the job's name, whether it succeeded, and the directories
of the state it was given and of the state it wrote. Round 0's state
(``PR_0 = 1 / |V|``) is written before the first job.

One departure from the example: it asks the master whether the job has
ended every 50 ms, not every 200 ms (``run_job``'s poll). A round is about
4 s, so the 200 ms step is 5 % of it, more than half of ``job_max_s``'s
9 % bound: runs whose rounds end a few ms either side of a step read a
whole step apart (PERF.md, section 6).

It names the program's reducer by its dotted name and imports nothing of
the PageRank module, so a program without it fails the job, not this
process. It never initialises a JAX backend (the tracker holds the chip):
the benchmark starts it with ``JAX_PLATFORMS=cpu``.
"""

import argparse
import io
import json
import sys
import time

import numpy as np

POLL_S = 0.05       # seconds between two asks whether the job has ended


def main(argv: "list[str]") -> int:
    ap = argparse.ArgumentParser(prog="pagerank_client.py")
    ap.add_argument("jobtracker", help="HOST:PORT")
    ap.add_argument("graph", help="directory with arcs/ and outdeg.npy")
    ap.add_argument("output", help="output directory URI")
    ap.add_argument("-D", dest="defs", action="append", default=[],
                    metavar="k=v")
    args = ap.parse_args(argv)

    from tpumr.core.configuration import Configuration
    from tpumr.fs import get_filesystem
    from tpumr.mapred.input_formats import DenseInputFormat, load_dense
    from tpumr.mapred.job_client import JobClient
    from tpumr.mapred.jobconf import JobConf

    Configuration.add_default_resource(
        {"mapred.job.tracker": args.jobtracker})
    graph = args.graph.rstrip("/")
    out = args.output.rstrip("/")
    fs = get_filesystem(out)
    outdeg = load_dense(get_filesystem(graph), f"{graph}/outdeg.npy")
    ranks = np.full(len(outdeg), 1.0 / len(outdeg), np.float32)
    contrib = np.where(outdeg > 0, ranks / np.maximum(outdeg, 1),
                       0.0).astype(np.float32)
    fs.mkdirs(f"{out}/r0")
    for name, arr in (("ranks.npy", ranks), ("contrib.npy", contrib)):
        buf = io.BytesIO()
        np.save(buf, arr)
        fs.write_bytes(f"{out}/r0/{name}", buf.getvalue())
    print(json.dumps({"ready": True}), flush=True)
    state, it = f"{out}/r0", 0
    for line in sys.stdin:
        if line.strip() != "round":
            break
        it += 1
        conf = JobConf()
        conf.set_job_name(f"pagerank-iter-{it}")
        conf.set_input_paths(f"{graph}/arcs")
        conf.set_output_path(f"{out}/out{it}")
        conf.set_input_format(DenseInputFormat)
        conf.set("tpumr.dense.split.rows", 1 << 40)
        conf.set_map_kernel("pagerank")
        conf.set("mapred.reducer.class", "tpumr.ops.pagerank.PageRankReducer")
        conf.set_num_reduce_tasks(1)
        conf.set("tpumr.pagerank.state", state)
        conf.set("tpumr.pagerank.state.out", f"{out}/r{it}")
        for kv in args.defs:
            k, _, v = kv.partition("=")
            conf.set(k.strip(), v.strip())
        t0 = time.monotonic()
        try:
            st = JobClient(conf).submit_job(conf).wait_for_completion(
                poll_s=POLL_S)
            ok = st["state"] == "SUCCEEDED"
        except (OSError, RuntimeError):
            ok = False
        wall = time.monotonic() - t0
        print(json.dumps({"round": it, "job_name": f"pagerank-iter-{it}",
                          "ok": ok, "job_s": wall,
                          "given": state.split("://", 1)[-1],
                          "state": f"{out}/r{it}".split("://", 1)[-1],
                          "out": f"{out}/out{it}".split("://", 1)[-1]}),
              flush=True)
        if ok:
            state = f"{out}/r{it}"
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
