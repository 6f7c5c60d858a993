"""PageRank over a Graph500 Kronecker graph: the graph from the seed, one
PR iteration per job through the round driver, the plain numpy reference
of the specification's equation, and the comparison.

**The graph** is LDBC Graphalytics' ``graph500-<scale>``: the Graph500
Kronecker generator (scale, edge factor 16, initiator A, B, C = 0.57, 0.19,
0.19, vertex labels permuted at random), made undirected, with duplicate
edges and self-loops dropped and isolated vertices left out; each edge is
two arcs. Vertices are numbered 0 .. V-1 in the order of their generator
labels. It is written in the program's layout (``tpumr/ops/pagerank.py``):
``splits`` files of ``[arcs, 2]`` int32 (destination, source), each every
in-arc of one destination range, ordered by (destination, source), the
ranges cut at about equal arcs; and ``outdeg.npy``. Made in three passes
of a worker pool (``_edges`` to a file a chunk, ``_bucket`` back to this
process, ``_place`` into the split files), every array of a worker at most
32 MB: the machine that holds the chip counts memory that is mapped and
unmapped fast (PERF.md, section 6), and ends a call near 45 GiB written to its
disk, so the chunks are the only bytes written besides the graph.

**What is compared** (``check``): at most three rounds of the window, the
first, the last and one drawn from the seed, each against ONE iteration of
the reference from the ranks the program wrote for the round before:
``rank_gap``, the largest ``|program - reference| / reference`` over the
vertices. Every round of the window is also read whole: ``|V|`` finite
ranks summing to 1 (``rank_sum_gap``). The reference is
``np.bincount(dst, weights=c[src])`` in float64, ``c = PR / outdeg``;
it imports nothing of the program. The control: the reference with the
contributions in bfloat16.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import time

import numpy as np

from bench.cluster import (BACKEND, JOBC, REPO, TASKC, BenchFailure,
                           child_env, counter, kill_session)
from bench.pool import worker_pool

EDGES_PER_CHUNK = 1 << 20
BUCKETS = 128           # destination buckets of the middle pass
BLOCK = 1 << 22         # rows a block: an int64 block is 32 MB
PAGERANK = "PageRank"


# ------------------------------------------------------------------ data


def kronecker_edges(sizes: dict, seed: int, chunk: int, m: int
                    ) -> "tuple[np.ndarray, np.ndarray]":
    """Chunk ``chunk`` of the generator's edges, ``m`` of them, BEFORE the
    labels are permuted: the Graph500 reference's loop over the scale's
    bits, from the stream (seed, chunk)."""
    rng = np.random.default_rng([seed, chunk])
    a, b, c = sizes["a"], sizes["b"], sizes["c"]
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    u = np.zeros(m, np.int64)
    v = np.zeros(m, np.int64)
    for bit in range(sizes["scale"]):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        u |= ii.astype(np.int64) << bit
        v |= jj.astype(np.int64) << bit
    return u, v


def permutation(sizes: dict, seed: int) -> np.ndarray:
    """The label permutation, from the stream one past the chunks'."""
    n = 1 << sizes["scale"]
    n_chunks = -(-n * sizes["edge_factor"] // EDGES_PER_CHUNK)
    return np.random.default_rng([seed, n_chunks]).permutation(n) \
        .astype(np.int32)


def _edges(job: tuple) -> "list[int]":
    """Pass 1: one chunk's arcs, both ways, labels permuted, self-loops
    dropped, as sorted unique keys ``dst * n + src`` in a file; returns
    where each destination bucket starts in it."""
    sizes, seed, chunk, m, perm_path, out = job
    n = 1 << sizes["scale"]
    perm = np.load(perm_path, mmap_mode="r")
    u, v = kronecker_edges(sizes, seed, chunk, m)
    u, v = perm[u].astype(np.int64), perm[v].astype(np.int64)
    keep = u != v
    u, v = u[keep], v[keep]
    keys = np.unique(np.concatenate([u * n + v, v * n + u]))
    np.save(out, keys)
    width = n // BUCKETS
    return np.searchsorted(keys, np.arange(BUCKETS + 1, dtype=np.int64)
                           * width * n).tolist()


def _bucket(job: tuple) -> "tuple[np.ndarray, np.ndarray]":
    """Pass 2: one destination bucket's arcs from every chunk, duplicates
    dropped, and the bucket's in-degrees."""
    sizes, b, chunks = job
    n = 1 << sizes["scale"]
    width = n // BUCKETS
    parts = []
    for path, starts in chunks:
        keys = np.load(path, mmap_mode="r")
        parts.append(np.asarray(keys[starts[b]:starts[b + 1]]))
    keys = np.unique(np.concatenate(parts))
    return keys, np.bincount(keys // n - b * width, minlength=width) \
        .astype(np.int32)


def _place(job: tuple) -> int:
    """Pass 3: arcs of one bucket, all of one split's destination range
    (generator labels), renumbered, written as rows ``row ..`` of the
    split's file."""
    sizes, keys, ids_path, path, data_start, row = job
    n = 1 << sizes["scale"]
    ids = np.load(ids_path, mmap_mode="r")
    out = np.empty((min(BLOCK, len(keys)), 2), np.int32)
    with open(path, "r+b") as f:
        f.seek(data_start + row * 8)
        for x in range(0, len(keys), BLOCK):
            k = keys[x:x + BLOCK]
            o = out[:len(k)]
            o[:, 0] = ids[k // n]
            o[:, 1] = ids[k % n]
            f.write(memoryview(o).cast("B"))
    return len(keys)


def _npy(path: str, shape: tuple, dtype) -> int:
    """A ``.npy`` of this shape whose data is written later in place;
    returns where the data starts."""
    header = np.lib.format.header_data_from_array_1_0(
        np.empty((0,) + shape[1:], dtype))
    header["shape"] = shape
    with open(path, "wb") as f:
        np.lib.format.write_array_header_1_0(f, header)
        start = f.tell()
        f.truncate(start + int(np.prod(shape)) * np.dtype(dtype).itemsize)
    return start


def cut_ranges(indeg: np.ndarray, parts: int) -> "list[tuple[int, int]]":
    """``parts`` contiguous destination ranges of about equal arcs: range
    ``k`` ends where the in-degrees summed from vertex 0 first reach ``k /
    parts`` of the arcs."""
    total = np.cumsum(indeg, dtype=np.int64)
    want = np.arange(1, parts) * int(total[-1]) // parts
    bounds = [0]
    for k, e in enumerate(np.searchsorted(total, want)):
        # one vertex at least a range, and room for the ranges after it
        bounds.append(min(max(int(e) + 1, bounds[-1] + 1),
                          len(indeg) - (parts - 1 - k)))
    bounds.append(len(indeg))
    return list(zip(bounds[:-1], bounds[1:]))


def make_input(sizes: dict, seed: int, data_dir: str) -> dict:
    """The graph in the program's layout under ``data_dir/graph``."""
    n = 1 << sizes["scale"]
    edges = n * sizes["edge_factor"]
    work = os.path.join(data_dir, "work")
    graph = os.path.join(data_dir, "graph")
    os.makedirs(os.path.join(graph, "arcs"))
    os.makedirs(work)
    perm_path = os.path.join(work, "perm.npy")
    np.save(perm_path, permutation(sizes, seed))
    chunks = [(sizes, seed, i, min(EDGES_PER_CHUNK, edges - lo), perm_path,
               os.path.join(work, f"chunk-{i:05d}.npy"))
              for i, lo in enumerate(range(0, edges, EDGES_PER_CHUNK))]
    with worker_pool(len(chunks)) as p:
        starts = p.map(_edges, chunks, chunksize=1)
        located = [(c[-1], s) for c, s in zip(chunks, starts)]
        buckets = p.map(_bucket, [(sizes, b, located)
                                  for b in range(BUCKETS)], chunksize=1)
        for path, _ in located:
            os.remove(path)
        indeg = np.concatenate([deg for _, deg in buckets])
        present = indeg > 0
        labels = np.flatnonzero(present)            # vertex -> label
        ids = np.cumsum(present, dtype=np.int32) - 1  # label -> vertex
        ids_path = os.path.join(work, "ids.npy")
        np.save(ids_path, ids)
        deg = indeg[present]        # undirected: in-degree = out-degree
        np.save(os.path.join(graph, "outdeg.npy"), deg.astype(np.int32))
        cum = np.concatenate([[0], np.cumsum(indeg, dtype=np.int64)])
        width = n // BUCKETS
        jobs, splits = [], []
        for lo, hi in cut_ranges(deg, sizes["splits"]):
            dlo = int(labels[lo])
            dhi = int(labels[hi]) if hi < len(labels) else n
            path = os.path.join(graph, "arcs",
                                f"range-{lo:010d}-{hi:010d}.npy")
            start = _npy(path, (int(cum[dhi] - cum[dlo]), 2), np.int32)
            splits.append(path)
            for b in range(dlo // width, -(-dhi // width)):
                a, z = max(dlo, b * width), min(dhi, (b + 1) * width)
                keys = buckets[b][0]
                x, y = np.searchsorted(keys, [a * n, z * n])
                if y > x:
                    jobs.append((sizes, keys[x:y], ids_path, path, start,
                                 int(cum[a] - cum[dlo])))
        placed = sum(p.map(_place, jobs, chunksize=1))
    for path in (ids_path, perm_path):
        os.remove(path)
    os.rmdir(work)
    if placed != int(cum[-1]):
        raise BenchFailure(f"placed {placed} of {int(cum[-1])} arcs")
    return {"graph": graph, "vertices": int(present.sum()),
            "arcs": int(cum[-1]), "splits": splits}


def rows_per_job(sizes: dict) -> int:
    """The arcs of the seed's graph: known once it is made, so the session
    records them (``Session``)."""
    return sizes["arcs"]


# ------------------------------------------------------------- reference


def bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to bfloat16 (nearest even), as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


_loaded: "dict[str, np.ndarray]" = {}


def _sums(job: tuple):
    """Pool worker: for each contribution file, the float64 sums of
    ``c[src]`` by destination over rows ``[a, b)`` of a split file, with
    the first destination they start at."""
    path, a, b, contrib_paths = job
    arcs = np.load(path, mmap_mode="r")
    if set(_loaded) != set(contrib_paths):
        _loaded.clear()
        _loaded.update((p, np.load(p)) for p in contrib_paths)
    cs = [_loaded[p] for p in contrib_paths]
    if b <= a:
        return 0, [np.zeros(0) for _ in cs]
    d0, d1 = int(arcs[a, 0]), int(arcs[b - 1, 0])
    out = [np.zeros(d1 - d0 + 1) for _ in cs]
    for x in range(a, b, BLOCK):
        blk = np.asarray(arcs[x:min(x + BLOCK, b)])
        dst = blk[:, 0] - d0
        for o, c in zip(out, cs):
            o += np.bincount(dst, weights=c[blk[:, 1]], minlength=len(o))
    return d0, out


def arc_sums(inputs: dict, contribs: "list[np.ndarray]", tmp: str,
             lost_split: "int | None" = None) -> "list[np.ndarray]":
    """For each contribution vector ``c``, ``y[v]`` = the float64 sum of
    ``c[u]`` over the in-arcs ``(u, v)``, one pass over the arcs.
    ``lost_split`` plants a fault: that split's destinations get 0."""
    os.makedirs(tmp, exist_ok=True)
    paths = []
    for i, c in enumerate(contribs):
        paths.append(os.path.join(tmp, f"c{i}-{time.monotonic_ns()}.npy"))
        np.save(paths[-1], np.asarray(c))
    jobs = []
    for k, path in enumerate(inputs["splits"]):
        if k == lost_split:
            continue
        rows = np.load(path, mmap_mode="r").shape[0]
        jobs += [(path, a, min(a + 4 * BLOCK, rows), paths)
                 for a in range(0, rows, 4 * BLOCK)]
    ys = [np.zeros(inputs["vertices"]) for _ in contribs]
    try:
        with worker_pool(len(jobs)) as p:
            for d0, parts in p.imap_unordered(_sums, jobs):
                for y, part in zip(ys, parts):
                    y[d0:d0 + len(part)] += part
    finally:
        for path in paths:
            os.remove(path)
    return ys


def contributions(ranks: np.ndarray, outdeg: np.ndarray) -> np.ndarray:
    """``c = PR / outdeg`` in float64, 0 where a vertex has no out-arc."""
    c = np.zeros(len(ranks))
    out = outdeg > 0
    c[out] = np.asarray(ranks, np.float64)[out] / outdeg[out]
    return c


def iterate(prev: np.ndarray, y: np.ndarray, outdeg: np.ndarray,
            damping: float) -> np.ndarray:
    """One PR iteration of the specification from ``prev`` and the arc
    sums ``y`` of its contributions, float64."""
    v = len(prev)
    dangling = float(np.asarray(prev, np.float64)[outdeg == 0].sum())
    return (1.0 - damping) / v + damping * (y + dangling / v)


def rank_sum_gap(got: np.ndarray) -> float:
    """How far a round's ranks sum from 1, in float64."""
    return abs(float(np.asarray(got).sum(dtype=np.float64)) - 1.0)


def rank_gap(got: np.ndarray, want: np.ndarray) -> float:
    """The largest ``|got - want| / want``; inf for a wrong shape or a
    value that is not finite."""
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got.astype(np.float64) - want) / want))


def reference_rounds(inputs: dict, sizes: dict, given: "list[np.ndarray]",
                     tmp: str, damping: "float | None" = None,
                     contrib_of=None, lost_split: "int | None" = None,
                     lost_vertex: "int | None" = None
                     ) -> "list[np.ndarray]":
    """One iteration from each of the ``given`` rank vectors, in one pass
    over the arcs. ``contrib_of`` (the contributions' rounding),
    ``damping``, ``lost_split`` and ``lost_vertex`` (its in-arcs left
    unsummed) stand in for a faulty program."""
    outdeg = np.load(os.path.join(inputs["graph"], "outdeg.npy"))
    cs = [contributions(g, outdeg) for g in given]
    if contrib_of is not None:
        cs = [contrib_of(c) for c in cs]
    ys = arc_sums(inputs, cs, tmp, lost_split)
    if lost_vertex is not None:
        for y in ys:
            y[lost_vertex] = 0.0
    d = sizes["damping"] if damping is None else damping
    return [iterate(g, y, outdeg, d) for g, y in zip(given, ys)]


# ------------------------------------------------------------ the client


class Session:
    """One round driver for the whole run (``pagerank_client.py``): its
    first round on the fresh tracker is the cold job, as an iterating user
    runs them. Records the seed's arcs in ``sizes`` (``rows_per_job``)."""

    def __init__(self, cluster, sizes: dict, traffic: dict, inputs: dict,
                 out_dir: str, job_defs: "list[str]") -> None:
        self.cluster, self.inputs, self.out_dir = cluster, inputs, out_dir
        sizes["arcs"] = inputs["arcs"]
        self.job_defs = job_defs + list(traffic["job"].get("defs", []))
        self.proc = None
        self.started = 0

    def _start(self) -> None:
        self.started += 1
        self.out = os.path.join(self.out_dir, f"pagerank-out{self.started}")
        argv = [sys.executable,
                os.path.join(REPO, "bench", "families", "pagerank_client.py"),
                self.cluster.addr, f"file://{self.inputs['graph']}",
                f"file://{self.out}"]
        for d in self.job_defs:
            argv += ["-D", d]
        self._err = open(os.path.join(
            self.cluster.work, f"pagerank-client{self.started}.err"), "wb")
        self.proc = subprocess.Popen(
            argv, cwd=REPO, env=child_env({"JAX_PLATFORMS": "cpu"}),
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._err, text=True, start_new_session=True)
        self._read()    # "ready": the first state is written

    def _read(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise BenchFailure(
                f"the round driver exited rc={self.proc.poll()}; see "
                f"{self._err.name}")
        return json.loads(line)

    def restart(self) -> None:
        self.close()

    def submit(self) -> dict:
        if self.proc is None:
            self._start()
        t0 = time.monotonic()
        self.proc.stdin.write("round\n")
        self.proc.stdin.flush()
        ans = self._read()
        return {"name": ans["job_name"], "client_s": time.monotonic() - t0,
                "ok": ans["ok"], "round": ans["round"], "out": ans["out"],
                "state": ans["state"], "given": ans["given"]}

    def close(self) -> None:
        if self.proc is None:
            return
        try:
            self.proc.stdin.write("quit\n")
            self.proc.stdin.flush()
            self.proc.wait(timeout=10)
        except (OSError, subprocess.TimeoutExpired):
            pass
        kill_session(self.proc.pid)
        self.proc.wait()
        self.proc.stdout.close()
        self.proc.stdin.close()
        self._err.close()
        self.proc = None


def job_failure(r: dict, sizes: dict, on_chip: bool) -> "str | None":
    """Why this job counts under ``failed`` whatever its output: every
    split mapped once, every arc counted once on one backend, every map's
    range reaching the one reduce."""
    n_maps = sizes["splits"]
    tpu = counter(r, BACKEND, "TPU_MAP_TASKS")
    cpu = counter(r, BACKEND, "CPU_MAP_TASKS")
    arcs = counter(r, PAGERANK, "PAGERANK_ARCS_TPU") \
        + counter(r, PAGERANK, "PAGERANK_ARCS_CPU")
    if r["state"] != "SUCCEEDED":
        return f"state {r['state']}"
    if tpu <= 0:
        return "no map task ran on a TPU slot"
    if tpu + cpu != n_maps:
        return f"TPU {tpu} + CPU {cpu} map tasks != {n_maps} splits"
    if counter(r, JOBC, "TPU_DEMOTIONS"):
        return "a TPU map was demoted to a CPU slot"
    if counter(r, JOBC, "FAILED_MAP_TASKS"):
        return "failed map attempts"
    if counter(r, TASKC, "MAP_INPUT_RECORDS") != sizes["arcs"]:
        return (f"the maps read {counter(r, TASKC, 'MAP_INPUT_RECORDS')} "
                f"of {sizes['arcs']} arcs")
    if arcs != sizes["arcs"]:
        return (f"PAGERANK_ARCS_TPU + PAGERANK_ARCS_CPU = {arcs}, not the "
                f"graph's {sizes['arcs']} arcs")
    if counter(r, TASKC, "MAP_OUTPUT_RECORDS") != n_maps \
            or counter(r, TASKC, "REDUCE_INPUT_RECORDS") != n_maps:
        return "the reduce did not get one range from every map"
    return None


# ------------------------------------------------------------ comparison


def _ranks(state_dir: str) -> "np.ndarray | None":
    try:
        return np.load(os.path.join(state_dir, "ranks.npy"))
    except (OSError, ValueError):
        return None


def compared_rounds(n: int, seed: int) -> "list[int]":
    """The window's rounds compared with the reference: the first, the
    last and one drawn from the seed; never more than three."""
    return sorted({0, n - 1, int(np.random.default_rng(seed).integers(n))}) \
        if n else []


def check(jobs: "list[dict]", sizes: dict, seed: int, inputs: dict,
          limits: dict) -> dict:
    """At most three rounds of the window against one reference iteration
    each, and every round's ranks read whole. Returns ``{name: {"value":
    v, "limit": l}}``."""
    v = inputs["vertices"]
    sum_gap = 0.0
    for j in jobs:
        got = _ranks(j["state"])
        if got is None or got.shape != (v,) or not np.isfinite(got).all():
            sum_gap = float("inf")
            continue
        sum_gap = max(sum_gap, rank_sum_gap(got))
    chosen = [jobs[i] for i in compared_rounds(len(jobs), seed)]
    given = [_ranks(j["given"]) for j in chosen]
    gap = 0.0 if chosen else float("inf")
    if any(g is None or g.shape != (v,) for g in given):
        gap = float("inf")
    elif chosen:
        want = reference_rounds(inputs, sizes, given,
                                os.path.dirname(chosen[0]["state"]))
        for j, w in zip(chosen, want):
            got = _ranks(j["state"])
            gap = max(gap, float("inf") if got is None else rank_gap(got, w))
    return {"rank_gap": {"value": gap, "limit": limits["rank_gap"]},
            "rank_sum_gap": {"value": sum_gap,
                             "limit": limits["rank_sum_gap"]},
            "rounds_compared": {"value": len(chosen), "limit": None},
            "rounds_read": {"value": len(jobs), "limit": None}}


def _chain(inputs: dict, sizes: dict, rounds: "tuple[int, ...]", tmp: str
           ) -> "list[np.ndarray]":
    """The ranks given to these rounds of the reference's own chain from
    ``PR_0 = 1 / |V|`` (round 1 is the first iteration), float32 as the
    program carries them."""
    ranks = np.full(inputs["vertices"], 1.0 / inputs["vertices"],
                    np.float32)
    given = []
    for r in range(1, max(rounds) + 1):
        if r in rounds:
            given.append(ranks)
        if r < max(rounds):
            ranks = reference_rounds(inputs, sizes, [ranks], tmp)[0] \
                .astype(np.float32)
    return given


def control(sizes: dict, seed: int, inputs: dict, limits: dict,
            rounds: "tuple[int, ...]" = (2, 3, 4)) -> dict:
    """The control's reading at this size: the reference with the
    contributions in bfloat16 put in the program's place, compared as
    ``check`` compares a round."""
    tmp = os.path.join(os.path.dirname(inputs["graph"]), "control")
    given = _chain(inputs, sizes, rounds, tmp)
    want = reference_rounds(inputs, sizes, given, tmp)
    got = reference_rounds(inputs, sizes, given, tmp,
                           contrib_of=lambda c: bf16(c.astype(np.float32)))
    gaps = [rank_gap(g.astype(np.float32), w) for g, w in zip(got, want)]
    return {"rank_gap": {"value": max(gaps), "least": min(gaps),
                         "limit": limits["rank_gap"]}}


def faults(sizes: dict, seed: int, inputs: dict, limits: dict,
           rounds: "tuple[int, ...]" = (2, 3, 4)) -> dict:
    """Each planted fault's reading at this size, as ``check`` compares a
    round; the least over the rounds is the one that counts. The faults
    that lose rank mass are read by ``rank_sum_gap`` too (under
    ``<fault>.rank_sum_gap``): the check that reads every round."""
    tmp = os.path.join(os.path.dirname(inputs["graph"]), "control")
    given = _chain(inputs, sizes, rounds, tmp)
    want = reference_rounds(inputs, sizes, given, tmp)
    # the graph is undirected, so the most out-arcs is the most in-arcs
    hub = int(np.argmax(np.load(os.path.join(inputs["graph"],
                                             "outdeg.npy"))))
    planted = {
        "a_splits_range_left_unsummed": reference_rounds(
            inputs, sizes, given, tmp, lost_split=len(inputs["splits"]) // 2),
        "a_hub_left_unsummed": reference_rounds(inputs, sizes, given, tmp,
                                                lost_vertex=hub),
        "damping_0.86": reference_rounds(inputs, sizes, given, tmp,
                                         damping=0.86),
        "a_round_unchanged": given,
    }
    out = {name: {"least": min(rank_gap(np.asarray(g, np.float32), w)
                               for g, w in zip(got, want)),
                  "limit": limits["rank_gap"]}
           for name, got in planted.items()}
    for name in ("a_splits_range_left_unsummed", "a_hub_left_unsummed"):
        out[f"{name}.rank_sum_gap"] = {
            "least": min(rank_sum_gap(np.asarray(g, np.float32))
                         for g in planted[name]),
            "limit": limits["rank_sum_gap"]}
    return out
