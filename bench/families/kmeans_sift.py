"""K-Means that trains an inverted file's coarse quantizer over SIFT-like
descriptors: the ``kmeans`` family's round driver, guarantees
(``job_failure``), comparison (``centroid_gap`` of every round of the
window, each split in the precision of the slot it ran on), control and
planted faults, with points of its own and the same plain numpy reference
computed in pieces that this size allows.

**The points.** Real SIFT vectors are not in the repository and are not
fetched. What is kept of them is what the program's arithmetic sees: 128
components, whole numbers 0 to 255 held as float32, non-negative, a norm
near 512, and points that gather around many more centres than the ``k``
the job looks for. A point is a component's centre (``components`` of
them, each coordinate an exponential draw of mean ``centre_scale``, from
the seed) plus a normal draw of ``spread``, plus the family's sawtooth
drift in the row index (``kmeans.drift_periods``: no half of the rows, of
a split or of the splits stands for the whole), rounded to a whole number
and held to 0..255. The first ``k`` rows, which the round driver takes as
the initial centroids, are drawn one from each of the first ``k``
components: a seeding that puts no two centroids into one component, so
no cluster starts as a sliver of another's (a sliver of a few rows turns
one row assigned the other way into a centroid gap of whole units).

Whole numbers up to 255 are exact in bfloat16, so the chip's one-pass dots
see the POINTS as float32 does; what the pass rounds is the centroids. And
a split's per-cluster sums are whole numbers under 2^24, exact in float32
in any order: program and reference differ by the rows they assign
differently and by nothing else.

**Why the reference is written again here.** ``kmeans.reference_rounds``
takes 65,536 rows at a time, which at k = 1024 makes every temporary a
fresh 268 MB mapping (a distance block, its double, the one-hot); the
machine that holds the chip counts memory that a dozen workers map and
unmap at that rate faster than it takes it back, and ends the run at
40 GiB (PERF.md, PR 30: making the input alone did). So here every array
as large as a block is allocated once a worker and written in place
(``out=``), in blocks of 16,384 rows, and the per-cluster sums come from a
sort and ``np.add.reduceat``, not from a one-hot product. The formulas,
the modes (``f32``, ``chip``, ``bf16``) and their order of operations are
``kmeans._assign``'s, so the two references agree bit for bit
(tests/bench/test_rehearse_sift.py). ``control`` and ``faults`` are the
``kmeans`` family's by import, over ITS loop: they are read in the
sandbox, never on the chip's host. Nothing here imports the program.
"""

from __future__ import annotations

import os

import numpy as np

from bench.cluster import BenchFailure
from bench.families import kmeans
from bench.families.kmeans import (KEEP, Session, bf16,  # noqa: F401
                                   centroid_gap, control, fault_args, faults,
                                   job_failure, read_centroids, rows_per_job)
from bench.pool import worker_pool

BLOCK = 1 << 14     # rows a piece: a float64 piece of 128 columns is 16 MB


# ------------------------------------------------------------------ data


def component_centres(sizes: dict, seed: int) -> np.ndarray:
    """``components x d`` float32 centres, a function of the seed alone
    (the stream one past the last chunk's)."""
    n_chunks = -(-sizes["rows"] // sizes["split_rows"])
    rng = np.random.default_rng([seed, n_chunks])
    centres = rng.exponential(sizes["centre_scale"],
                              (sizes["components"], sizes["d"]))
    return np.minimum(centres, 255.0).astype(np.float32)


def _gen_chunk(job: tuple) -> int:
    """Pool worker: chunk ``index`` of the points file, a function of
    (seed, index) and the rows' place in the set, written in place piece
    by piece into buffers made once."""
    path, data_start, seed, index, lo, rows, sizes, periods = job
    chunk, d = sizes["split_rows"], len(periods)
    centres = component_centres(sizes, seed)
    rng = np.random.default_rng([seed, index])
    first = np.where(periods < chunk, 0, lo)    # where a period counts from
    inv = 1.0 / periods
    x, own = np.empty((BLOCK, d), np.float32), np.empty((BLOCK, d),
                                                        np.float32)
    rows_at, turns = (np.empty((BLOCK, d), np.int64),
                      np.empty((BLOCK, d), np.float64))
    whole = np.empty((BLOCK, d), np.float64)
    with open(path, "r+b") as f:
        f.seek(data_start + lo * d * 4)
        for a in range(0, rows, BLOCK):
            n = min(BLOCK, rows - a)
            xs, ts = x[:n], turns[:n]
            rng.standard_normal(dtype=np.float32, out=xs)
            xs *= np.float32(sizes["spread"])
            comp = rng.integers(0, len(centres), n)
            head = max(0, min(n, sizes["k"] - (lo + a)))
            comp[:head] = np.arange(lo + a, lo + a + head)
            np.take(centres, comp, axis=0, out=own[:n])
            xs += own[:n]
            # the sawtooth, centred, as the kmeans family draws it:
            # drift * (2 * phase + 1 / period - 1)
            np.add(first[None, :], np.arange(a, a + n)[:, None],
                   out=rows_at[:n])
            np.multiply(rows_at[:n], inv, out=ts)
            ts -= np.floor(ts, out=whole[:n])
            ts *= 2.0
            ts += inv
            ts -= 1.0
            ts *= sizes["drift"]
            own[:n] = ts
            xs += own[:n]
            np.rint(xs, out=xs)
            np.clip(xs, 0.0, 255.0, out=xs)
            f.write(memoryview(xs).cast("B"))
    return rows


def make_input(sizes: dict, seed: int, data_dir: str) -> dict:
    """``rows x d`` float32 points with whole-number values 0..255 as one
    ``.npy``, made in bulk by a pool of processes, one split-sized chunk
    each."""
    rows, d, chunk = sizes["rows"], sizes["d"], sizes["split_rows"]
    if sizes["components"] < sizes["k"] or chunk < sizes["k"]:
        raise BenchFailure("the first k rows need k components and one "
                           "chunk")
    path = os.path.join(data_dir, "points.npy")
    header = np.lib.format.header_data_from_array_1_0(
        np.empty((0, d), np.float32))
    header["shape"] = (rows, d)
    with open(path, "wb") as f:
        np.lib.format.write_array_header_1_0(f, header)
        data_start = f.tell()
        f.truncate(data_start + rows * d * 4)
    periods = kmeans.drift_periods(rows, chunk, d)
    jobs = [(path, data_start, seed, i, lo, min(chunk, rows - lo), sizes,
             periods) for i, lo in enumerate(range(0, rows, chunk))]
    with worker_pool(len(jobs)) as p:
        written = sum(p.map(_gen_chunk, jobs, chunksize=1))
    if written != rows:
        raise BenchFailure(f"wrote {written} of {rows} rows")
    return {"points": path}


# ------------------------------------------------------------- reference


def _assign(x: np.ndarray, xb: "np.ndarray | None", cents: np.ndarray,
            mode: str, d2: np.ndarray):
    """``kmeans._assign`` with the distance block written into ``d2``
    (``BLOCK x k`` float32, made once a worker): the nearest centroid of
    each row and the rows as the sums see them. ``f32``: ``|c|^2 - 2
    x.c``; ``chip``: ``|x|^2 - 2 xb.cb + |c|^2``, the dot's inputs rounded
    to bfloat16, float32 accumulation. The control (``bf16``) is the
    family's own, temporaries and all: no run on the chip's host makes
    it."""
    if mode == "bf16":
        return kmeans._assign(x, xb, cents, mode)
    out = d2[:len(x)]
    c2 = np.sum(cents * cents, axis=1)
    if mode == "f32":
        np.matmul(x, cents.T, out=out)
        out *= -2.0
        out += c2[None, :]
        return np.argmin(out, axis=1), x
    np.matmul(xb, bf16(cents).T, out=out)
    out *= -2.0
    out += np.sum(x * x, axis=1, keepdims=True)
    out += c2[None, :]
    return np.argmin(out, axis=1), xb


def _add_sums(sums: np.ndarray, counts: np.ndarray, assign: np.ndarray,
              seen: np.ndarray) -> None:
    """Per-cluster sums and counts of one piece, in float64: the rows in
    cluster order, summed run by run (what ``onehot.T @ seen`` gives, with
    no rows-by-k array)."""
    order = np.argsort(assign, kind="stable")
    by_cluster = assign[order]
    starts = np.flatnonzero(np.r_[True, by_cluster[1:] != by_cluster[:-1]])
    sums[by_cluster[starts]] += np.add.reduceat(
        seen[order].astype(np.float64), starts, axis=0)
    counts += np.bincount(assign, minlength=len(counts))


def _ref_chunk(job: tuple):
    """Pool worker: per-cluster sums and counts of one split's rows, for
    each round's given centroids in that round's precision here."""
    path, lo, hi, rounds, keep = job
    points = np.load(path, mmap_mode="r")
    k, d = rounds[0][0].shape
    out = [(np.zeros((k, d), np.float64), np.zeros(k, np.int64))
           for _ in rounds]
    d2 = np.empty((BLOCK, k), np.float32)
    for a in range(lo, hi, BLOCK):
        x = np.asarray(points[a:min(a + BLOCK, hi)])
        if keep:
            x = x[KEEP[keep](np.arange(a, a + len(x)), lo, hi)]
        if not len(x):
            continue
        xb = bf16(x) if any(m != "f32" for _, m in rounds) else None
        for (cents, mode), (sums, counts) in zip(rounds, out):
            assign, seen = _assign(x, xb, cents, mode, d2)
            _add_sums(sums, counts, assign, seen)
    # the control stores each split's sums in bfloat16
    return [(bf16(s.astype(np.float32)).astype(np.float64), c)
            if mode == "bf16" else (s, c)
            for (_, mode), (s, c) in zip(rounds, out)]


def reference_rounds(points_path: str, sizes: dict,
                     cents_list: "list[np.ndarray]", modes="f32",
                     keep: "str | None" = None,
                     lost_split: "int | None" = None
                     ) -> "list[np.ndarray]":
    """``kmeans.reference_rounds`` over this module's ``_ref_chunk``: new
    centroids of one round for each given set of centroids, in ONE pass
    over the points (chunk = split, as the job sums them); a cluster that
    gets no point keeps its centroid. ``modes``, ``keep`` and
    ``lost_split`` as there."""
    rows, chunk = sizes["rows"], sizes["split_rows"]
    cents_list = [np.asarray(c, np.float32) for c in cents_list]
    los = list(range(0, rows, chunk))
    if isinstance(modes, str):
        modes = [[modes] * len(los)] * len(cents_list)
    jobs = [(points_path, lo, min(lo + chunk, rows),
             [(c, m[i]) for c, m in zip(cents_list, modes)], keep)
            for i, lo in enumerate(los)]
    with worker_pool(len(jobs)) as p:
        parts = p.map(_ref_chunk, jobs, chunksize=1)
    if lost_split is not None:
        del parts[lost_split]
    new = []
    for i, cents in enumerate(cents_list):
        s = sum(part[i][0] for part in parts)
        c = sum(part[i][1] for part in parts)
        out = cents.astype(np.float64)
        hit = c > 0
        out[hit] = s[hit] / c[hit][:, None]
        new.append(out)
    return new


# ------------------------------------------------------------ comparison


def check(jobs: "list[dict]", sizes: dict, seed: int, inputs: dict,
          limits: dict) -> dict:
    """``kmeans.check`` over this module's reference: every round of the
    window against it, one pass over the points."""
    n_splits = -(-sizes["rows"] // sizes["split_rows"])
    modes = [kmeans._modes(j, n_splits) for j in jobs]
    known = [(j, m) for j, m in zip(jobs, modes) if m is not None]
    given = [np.load(j["given"]) for j, _ in known]
    want = reference_rounds(inputs["points"], sizes, given,
                            [m for _, m in known]) if known else []
    gap = 0.0 if len(known) == len(jobs) else float("inf")
    for (j, _), g, w in zip(known, given, want):
        try:
            got = read_centroids(j["out"], g)
        except (OSError, ValueError, SyntaxError):
            gap = float("inf")
            continue
        gap = max(gap, centroid_gap(got, w))
    return {"centroid_gap": {"value": gap,
                             "limit": limits["centroid_gap"]},
            "rounds_compared": {"value": len(jobs), "limit": None},
            "maps_on_chip": {"value": sum(m.count("chip")
                                          for _, m in known),
                             "limit": None}}
