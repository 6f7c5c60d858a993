"""PageRank, one iteration a job: ``tpumr examples pagerank``.

The graph is a directory in the layout of ``tpumr/ops/pagerank.py``
(``arcs/range-<lo>-<hi>.npy`` split files by destination range and
``outdeg.npy``; ``ops.pagerank.write_graph`` writes it from arcs held in
memory). Each iteration is one job: the ``pagerank`` kernel maps every
split (on a TPU slot, or its numpy twin on a CPU slot, as the hybrid
scheduler decides) and one reduce writes the round's ranks and the next
round's contributions as a state directory, ``<work>/r<i>``.
"""

from __future__ import annotations

import argparse
import io

import numpy as np

from tpumr.examples import register
from tpumr.fs import get_filesystem
from tpumr.mapred.input_formats import DenseInputFormat, load_dense
from tpumr.mapred.job_client import run_job
from tpumr.mapred.jobconf import JobConf


def round_conf(graph: str, work: str, it: int) -> JobConf:
    """The job of iteration ``it``: the state ``r<it - 1>`` in,
    ``r<it>`` out."""
    from tpumr.ops.pagerank import PageRankReducer
    conf = JobConf()
    conf.set_job_name(f"pagerank-iter-{it}")
    conf.set_input_paths(f"{graph}/arcs")
    conf.set_output_path(f"{work}/out{it}")
    conf.set_input_format(DenseInputFormat)
    conf.set("tpumr.dense.split.rows", 1 << 40)      # one split a file
    conf.set_map_kernel("pagerank")
    conf.set_reducer_class(PageRankReducer)
    conf.set_num_reduce_tasks(1)
    conf.set("tpumr.pagerank.state", f"{work}/r{it - 1}")
    conf.set("tpumr.pagerank.state.out", f"{work}/r{it}")
    return conf


@register("pagerank", "PageRank over a graph by destination range, one "
                      "iteration a job")
def pagerank(argv: "list[str]") -> int:
    ap = argparse.ArgumentParser(prog="tpumr examples pagerank")
    ap.add_argument("graph", help="directory with arcs/ and outdeg.npy")
    ap.add_argument("work", help="directory for the rounds' state and "
                                 "the jobs' output")
    ap.add_argument("-i", "--iterations", type=int, default=10)
    ap.add_argument("-D", dest="defs", action="append", default=[],
                    metavar="k=v")
    args = ap.parse_args(argv)
    from tpumr.ops.pagerank import write_state
    graph, work = args.graph.rstrip("/"), args.work.rstrip("/")
    fs = get_filesystem(work)
    outdeg = load_dense(get_filesystem(graph), f"{graph}/outdeg.npy")
    write_state(fs, f"{work}/r0", np.full(len(outdeg), 1.0 / len(outdeg)),
                outdeg)
    for it in range(1, args.iterations + 1):
        conf = round_conf(graph, work, it)
        for kv in args.defs:
            k, _, v = kv.partition("=")
            conf.set(k.strip(), v.strip())
        if not run_job(conf).successful:
            return 1
    ranks = np.load(io.BytesIO(fs.read_bytes(
        f"{work}/r{args.iterations}/ranks.npy")))
    top = np.argsort(-ranks, kind="stable")[:10]
    print(f"ranks of {len(ranks)} vertices after {args.iterations} "
          f"iteration(s) in {work}/r{args.iterations}/ranks.npy")
    for v in top:
        print(f"{v}\t{ranks[v]:.6e}")
    return 0
