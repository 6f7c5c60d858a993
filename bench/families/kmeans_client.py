#!/usr/bin/env python3
"""The K-Means round driver: a user's iterative driver program, and the
traffic generator of the ``rounds`` mix.

A copy of the loop of ``tpumr examples kmeans`` (tpumr/examples/basic.py:
same ``JobConf`` keys, ``DenseInputFormat``, ``kmeans-assign`` kernel,
``CentroidReducer``, centroids carried from round to round), which runs a
fixed number of rounds and cannot stop at a deadline. This one runs a round
each time its standard input says ``round`` and answers with one JSON line:
the job's name and its submit-to-complete seconds on this process's clock.

One departure from the example: each round's centroids go to a file of
their own (``iter<N>.in.npy``), as the pipeline's loop nodes version
theirs. The tracker caches centroids by PATH (ops/kmeans.py), and the
example's ``clear_centroid_cache()`` clears only the client's own process,
so on a cluster a rewritten file is never read again and every round
computes round 1 (PERF.md, Open questions). The files stay beside the
outputs: they are the requests the answers are checked against.

It never initialises a JAX backend (the tracker holds the chip): the
benchmark starts it with ``JAX_PLATFORMS=cpu``.
"""

import argparse
import ast
import json
import sys
import time

import numpy as np


def main(argv: "list[str]") -> int:
    ap = argparse.ArgumentParser(prog="kmeans_client.py")
    ap.add_argument("jobtracker", help="HOST:PORT")
    ap.add_argument("points", help=".npy of shape (n, d)")
    ap.add_argument("output", help="output directory URI")
    ap.add_argument("-k", type=int, required=True)
    ap.add_argument("--split-rows", type=int, required=True)
    ap.add_argument("-D", dest="defs", action="append", default=[],
                    metavar="k=v")
    args = ap.parse_args(argv)

    from tpumr.core.configuration import Configuration
    from tpumr.examples.basic import (CentroidReducer, _read_pairs,
                                      load_npy_rows, save_npy)
    from tpumr.fs import get_filesystem
    from tpumr.mapred.input_formats import DenseInputFormat
    from tpumr.mapred.job_client import run_job
    from tpumr.mapred.jobconf import JobConf
    from tpumr.ops.kmeans import KMeansCpuMapper, clear_centroid_cache

    # what ``tpumr -D mapred.job.tracker=ADDR`` does for its subcommand
    Configuration.add_default_resource(
        {"mapred.job.tracker": args.jobtracker})
    fs = get_filesystem(args.output)
    out = args.output.rstrip("/")
    centroids = load_npy_rows(get_filesystem(args.points), args.points,
                              args.k).astype(np.float32)
    print(json.dumps({"ready": True}), flush=True)
    it = 0
    for line in sys.stdin:
        if line.strip() != "round":
            break
        clear_centroid_cache()
        cent_path = f"{out}/iter{it}.in.npy"
        save_npy(fs, cent_path, centroids)
        conf = JobConf()
        conf.set_job_name(f"kmeans-iter-{it}")
        conf.set_input_paths(args.points)
        conf.set_output_path(f"{out}/iter{it}")
        conf.set_input_format(DenseInputFormat)
        conf.set("tpumr.dense.split.rows", args.split_rows)
        conf.set("tpumr.kmeans.centroids", cent_path)
        conf.set_map_kernel("kmeans-assign")
        conf.set_mapper_class(KMeansCpuMapper)
        conf.set_reducer_class(CentroidReducer)
        conf.set_num_reduce_tasks(1)
        for kv in args.defs:
            k, _, v = kv.partition("=")
            conf.set(k.strip(), v.strip())
        conf.set("tpumr.local.run.on.tpu", True)
        t0 = time.monotonic()
        ok = bool(run_job(conf).successful)
        wall = time.monotonic() - t0
        if ok:
            centroids = centroids.copy()
            for key, val in _read_pairs(fs, f"{out}/iter{it}"):
                centroids[int(key)] = np.asarray(ast.literal_eval(val),
                                                 dtype=np.float32)
        print(json.dumps({"round": it, "job_name": f"kmeans-iter-{it}",
                          "ok": ok, "job_s": wall}), flush=True)
        it += 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
