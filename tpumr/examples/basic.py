"""Core example programs: wordcount, grep, pi, kmeans, matmul.

≈ WordCount.java (69 LoC), Grep.java, PiEstimator.java (353 LoC) in the
reference's ``src/examples``, plus the K-Means / matrix-multiply GPU jobs
the Shirahata work ran through pipes (not in the reference tree —
SURVEY.md §2.1 end note). Every program is TPU-wired by default (a device
kernel + a CPU fallback mapper, so the hybrid scheduler has both backends
to profile) — unlike the reference, where only pipes jobs could use the
accelerator.
"""

from __future__ import annotations

import argparse
import io
import sys

import numpy as np

from tpumr.examples import register
from tpumr.fs import get_filesystem
from tpumr.mapred.api import Reducer
from tpumr.mapred.input_formats import (DenseInputFormat, NLineInputFormat,
                                        TextInputFormat)
from tpumr.mapred.job_client import run_job
from tpumr.mapred.jobconf import JobConf


class LongSumReducer(Reducer):
    """≈ mapred/lib/LongSumReducer.java."""

    def reduce(self, key, values, output, reporter):
        output.collect(key, sum(values))


class CentroidReducer(Reducer):
    """Averages (partial_sum, count) pairs into the new centroid."""

    def reduce(self, key, values, output, reporter):
        total, n = None, 0
        for s, c in values:
            s = np.asarray(s, dtype=np.float64)
            total = s if total is None else total + s
            n += int(c)
        output.collect(key, (total / max(1, n)).tolist())


def _common(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("-r", "--reduces", type=int, default=1)
    ap.add_argument("--cpu-only", action="store_true",
                    help="drop the device kernel (CPU mapper only)")
    ap.add_argument("-D", dest="defs", action="append", default=[],
                    metavar="k=v")


def _apply(conf: JobConf, args: argparse.Namespace) -> None:
    conf.set_num_reduce_tasks(args.reduces)
    for kv in args.defs:
        k, _, v = kv.partition("=")
        conf.set(k.strip(), v.strip())
    if not args.cpu_only:
        conf.set("tpumr.local.run.on.tpu", True)


def save_npy(fs, path: str, arr: np.ndarray) -> None:
    buf = io.BytesIO()
    np.save(buf, arr)
    fs.write_bytes(path, buf.getvalue())


def load_npy(fs, path: str) -> np.ndarray:
    return np.load(io.BytesIO(fs.read_bytes(path)))


def load_npy_rows(fs, path: str, k: int) -> np.ndarray:
    """First ``k`` rows via a ranged read — the driver must not pull the
    full (possibly 100M-point) array just to seed centroids."""
    from tpumr.mapred.input_formats import read_npy_header
    with fs.open(path) as f:
        shape, dtype, data_start = read_npy_header(f)
        n_rows = min(k, shape[0])
        row_bytes = int(np.prod(shape[1:], dtype=np.int64)) * dtype.itemsize
        f.seek(data_start)
        raw = f.read(n_rows * row_bytes)
    return np.frombuffer(raw, dtype=dtype).reshape((n_rows,) + shape[1:])


@register("wordcount", "count words in the input files")
def wordcount(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(prog="tpumr examples wordcount")
    ap.add_argument("input")
    ap.add_argument("output")
    _common(ap)
    args = ap.parse_args(argv)
    conf = JobConf()
    conf.set_job_name("wordcount")
    conf.set_input_paths(*args.input.split(","))
    conf.set_output_path(args.output)
    from tpumr.ops.wordcount import WordCountCpuMapper
    if args.cpu_only:
        conf.set_input_format(TextInputFormat)
        conf.set_mapper_class(WordCountCpuMapper)
    else:
        # whitespace tokenization doesn't need per-line records — the
        # raw-buffer format skips the line machinery entirely
        from tpumr.mapred.input_formats import RawTextInputFormat
        conf.set_input_format(RawTextInputFormat)
        conf.set_map_kernel("wordcount")
    conf.set_reducer_class(LongSumReducer)
    conf.set_combiner_class(LongSumReducer)
    _apply(conf, args)
    return 0 if run_job(conf).successful else 1


@register("grep", "count matches of a regex in the input files")
def grep(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(prog="tpumr examples grep")
    ap.add_argument("input")
    ap.add_argument("output")
    ap.add_argument("regex")
    ap.add_argument("group", nargs="?", type=int, default=0)
    _common(ap)
    args = ap.parse_args(argv)
    conf = JobConf()
    conf.set_job_name("grep")
    conf.set_input_paths(*args.input.split(","))
    conf.set_output_path(args.output)
    conf.set_input_format(TextInputFormat)
    conf.set("tpumr.grep.pattern", args.regex)
    conf.set("tpumr.grep.group", args.group)
    from tpumr.ops.grep import GrepCpuMapper
    if args.cpu_only:
        conf.set_mapper_class(GrepCpuMapper)
    else:
        conf.set_map_kernel("grep")
    conf.set_reducer_class(LongSumReducer)
    conf.set_combiner_class(LongSumReducer)
    _apply(conf, args)
    return 0 if run_job(conf).successful else 1


@register("pi", "estimate pi by Monte-Carlo sampling on device")
def pi(argv: list[str]) -> int:
    """≈ PiEstimator.java: one map per sample block; here each map's whole
    block is drawn and reduced on device (pi-sampler kernel)."""
    ap = argparse.ArgumentParser(prog="tpumr examples pi")
    ap.add_argument("n_maps", type=int)
    ap.add_argument("n_samples", type=int, help="samples per map")
    ap.add_argument("--work", default="mem:///tmp/pi",
                    help="scratch URI for job input/output")
    _common(ap)
    args = ap.parse_args(argv)
    fs = get_filesystem(args.work)
    inp = f"{args.work.rstrip('/')}/in.txt"
    out = f"{args.work.rstrip('/')}/out"
    lines = "".join(f"{1000 + i} {args.n_samples}\n"
                    for i in range(args.n_maps))
    fs.write_bytes(inp, lines.encode())
    conf = JobConf()
    conf.set_job_name("pi")
    conf.set_input_paths(inp)
    conf.set_output_path(out)
    conf.set_input_format(NLineInputFormat)
    conf.set("mapred.line.input.format.linespermap", 1)
    from tpumr.ops.pi import PiCpuMapper
    if args.cpu_only:
        conf.set_mapper_class(PiCpuMapper)
    else:
        conf.set_map_kernel("pi-sampler")
    conf.set_reducer_class(LongSumReducer)
    _apply(conf, args)
    result = run_job(conf)
    if not result.successful:
        return 1
    counts = dict(_read_pairs(fs, out))
    inside, total = int(counts["inside"]), int(counts["total"])
    est = 4.0 * inside / max(1, total)
    print(f"Estimated value of Pi is {est}")
    return 0


def _read_pairs(fs, out_dir: str):
    for st in fs.list_files(out_dir):
        if st.path.name.startswith("part"):
            for line in fs.read_bytes(st.path).decode().splitlines():
                k, _, v = line.partition("\t")
                yield k, v


@register("kmeans", "iterative K-Means clustering (the north-star job)")
def kmeans(argv: list[str]) -> int:
    """Iterative driver: each round is one MapReduce job (assign on device,
    centroid average in reduce), rewriting the centroid file — the workload
    of the Shirahata hybrid-scheduling evaluation."""
    ap = argparse.ArgumentParser(prog="tpumr examples kmeans")
    ap.add_argument("points", help=".npy of shape (n, d)")
    ap.add_argument("output", help="output directory URI")
    ap.add_argument("-k", type=int, default=8)
    ap.add_argument("-i", "--iterations", type=int, default=5)
    ap.add_argument("--split-rows", type=int, default=1 << 17)
    _common(ap)
    args = ap.parse_args(argv)
    fs = get_filesystem(args.output)
    out = args.output.rstrip("/")
    cent_path = f"{out}/centroids.npy"
    seeds = load_npy_rows(get_filesystem(args.points), args.points, args.k)
    save_npy(fs, cent_path, seeds.astype(np.float32))
    centroids = None
    for it in range(args.iterations):
        # the file is rewritten in place each round: every tracker keys
        # its copies by the file's length and mtime (ops/devcache.py)
        conf = JobConf()
        conf.set_job_name(f"kmeans-iter-{it}")
        conf.set_input_paths(args.points)
        conf.set_output_path(f"{out}/iter{it}")
        conf.set_input_format(DenseInputFormat)
        conf.set("tpumr.dense.split.rows", args.split_rows)
        conf.set("tpumr.kmeans.centroids", cent_path)
        from tpumr.ops.kmeans import KMeansCpuMapper
        # the kernel is set in BOTH modes: CPU slots run its vectorized
        # map_batch_cpu (CpuBatchMapRunner); --cpu-only just withholds the
        # device. The per-record mapper stays as the opt-out fallback
        # (-D tpumr.cpu.batch.map=false).
        conf.set_map_kernel("kmeans-assign")
        conf.set_mapper_class(KMeansCpuMapper)
        conf.set_reducer_class(CentroidReducer)
        _apply(conf, args)
        if not run_job(conf).successful:
            return 1
        import ast
        centroids = load_npy(fs, cent_path).copy()
        for key, val in _read_pairs(fs, f"{out}/iter{it}"):
            centroids[int(key)] = np.asarray(ast.literal_eval(val),
                                             dtype=np.float32)
        save_npy(fs, cent_path, centroids)
    print(f"Final centroids written to {cent_path}")
    if centroids is not None:
        np.savetxt(sys.stdout, centroids, fmt="%.4f")
    return 0


@register("matmul", "blocked dense matrix multiply A @ B")
def matmul(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(prog="tpumr examples matmul")
    ap.add_argument("a", help=".npy for A (n, k)")
    ap.add_argument("b", help=".npy for B (k, m)")
    ap.add_argument("output")
    ap.add_argument("--split-rows", type=int, default=1 << 14)
    _common(ap)
    args = ap.parse_args(argv)
    conf = JobConf()
    conf.set_job_name("matmul")
    conf.set_input_paths(args.a)
    conf.set_output_path(args.output)
    conf.set_input_format(DenseInputFormat)
    conf.set("tpumr.dense.split.rows", args.split_rows)
    conf.set("tpumr.matmul.b", args.b)
    from tpumr.ops.matmul import MatmulCpuMapper
    if args.cpu_only:
        conf.set_mapper_class(MatmulCpuMapper)
    else:
        conf.set_map_kernel("matmul-block")
    conf.set_num_reduce_tasks(0)
    _apply(conf, args)
    return 0 if run_job(conf).successful else 1
