"""PageRank, one iteration a job: the map kernel ``pagerank``, its numpy
twin, and the reducer that closes the round.

The equation is LDBC Graphalytics' (PR, damping ``d``, a fixed number of
iterations)::

    PR_i(v) = (1 - d) / |V| + d * sum over in-arcs (u, v) of c(u)
              + (d / |V|) * sum over dangling w of PR_{i-1}(w)

with ``c(u) = PR_{i-1}(u) / outdeg(u)`` and ``PR_0 = 1 / |V|``.

**The graph** (:func:`write_graph`) is a directory of dense splits, one
``.npy`` a split: ``<graph>/arcs/range-<lo>-<hi>.npy`` holds, as ``[arcs,
2]`` int32 rows (destination, source), every in-arc of the destinations
``lo .. hi - 1``, ordered by destination. The ranges are cut so that the
splits hold about equal ARCS (a power-law graph's hubs would otherwise make
one split many times the others). ``<graph>/outdeg.npy`` holds the
out-degrees. A job maps every split once (``DenseInputFormat``, one split
a file); through the HBM split cache the arcs stay on the chip from the
second round on.

**A round's state** is a directory: ``ranks.npy`` (float32 ``PR_i``) and
``contrib.npy`` (float32 ``c``, 0 where a vertex has no out-arc). The job
reads the state of the round before (``tpumr.pagerank.state``) and its
reducer writes the next (``tpumr.pagerank.state.out``); ``d`` is
Graphalytics' 0.85.

**The map** of one split gives ONE record, ``(lo, y)``: ``y[v - lo]`` is
the sum of ``c(u)`` over the in-arcs of ``v``, float32. On the chip
(:func:`pagerank_map_device`): a gather of ``c`` at the sources, a flag
where the destination changes, the segmented scan the aggregation's
kernel uses (``ops/segment_sum.py:segmented_scan``: a destination's last
arc ends with its sum, added in a tree fixed by the arcs' order), and one
gather of the sums at each destination's last arc. Where those lie is the
split's own (a binary search of log2(arcs) steps in its sorted
destinations), found once a tracker and kept in HBM beside the split. No
scatter, and no loop on the device. The arcs, the contributions and the
range are padded to shape buckets on the host (the arcs once, where the
split is staged: ``staged_form``), so every graph of about one size runs
the programs compiled once. The numpy twin adds each destination's arcs
in float64 and rounds once.

**The reduce** (one task) puts the ranges together, applies ``d``, the
dangling mass and ``(1 - d) / |V|``, and writes the next state.
"""

from __future__ import annotations

import functools
import io
import os
import re
from typing import Any, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from tpumr.mapred.api import Reducer
from tpumr.ops.registry import KernelMapper, register_kernel
from tpumr.ops.segment_sum import segmented_scan

DAMPING = 0.85

#: the side-file family of the contributions (ops/devcache.py): a round's
#: file is read once a host and uploaded once a device, by its identity
CONTRIB = "pagerank-contrib"

COUNTER_GROUP = "PageRank"
ARCS_TPU = "PAGERANK_ARCS_TPU"
ARCS_CPU = "PAGERANK_ARCS_CPU"

_RANGE = re.compile(r"range-(\d+)-(\d+)\.npy$")
_BLOCK = 1 << 20        # arcs a block of the numpy twin


# -------------------------------------------------------------- the layout


def split_name(lo: int, hi: int) -> str:
    return f"range-{lo:010d}-{hi:010d}.npy"


def range_of(path: str) -> "tuple[int, int]":
    """The destination range ``[lo, hi)`` a split file holds, from its
    name."""
    m = _RANGE.search(str(path))
    if m is None:
        raise ValueError(f"{path}: not a PageRank split (range-<lo>-<hi>.npy)")
    return int(m.group(1)), int(m.group(2))


def cut_ranges(indeg: np.ndarray, parts: int) -> "list[tuple[int, int]]":
    """``parts`` contiguous destination ranges over ``len(indeg)`` vertices
    holding about equal arcs: range ``k`` ends where the in-degrees summed
    from vertex 0 first reach ``k / parts`` of the arcs. No range is empty
    of vertices; a hub is never cut."""
    n = len(indeg)
    parts = max(1, min(parts, n))
    total = np.cumsum(indeg, dtype=np.int64)
    want = (np.arange(1, parts) * (int(total[-1]) if n else 0)) // parts
    ends = np.searchsorted(total, want, side="left") + 1
    bounds = [0]
    for e in ends:
        e = int(min(max(e, bounds[-1] + 1), n - (parts - len(bounds))))
        bounds.append(e)
    bounds.append(n)
    return list(zip(bounds[:-1], bounds[1:]))


def write_graph(out_dir: str, dst: np.ndarray, src: np.ndarray,
                vertices: int, parts: int) -> "list[str]":
    """Write the arcs ``src -> dst`` (vertices ``0 .. vertices - 1``) as
    the layout above: ``parts`` split files by destination range and the
    out-degrees. Returns the split files' paths. Local files only; the
    loader job that partitions an edge list on a cluster is not here."""
    dst = np.asarray(dst, np.int64)
    src = np.asarray(src, np.int64)
    order = np.lexsort((src, dst))
    dst, src = dst[order], src[order]
    arcs_dir = os.path.join(out_dir, "arcs")
    os.makedirs(arcs_dir, exist_ok=True)
    np.save(os.path.join(out_dir, "outdeg.npy"),
            np.bincount(src, minlength=vertices).astype(np.int32))
    paths = []
    indeg = np.bincount(dst, minlength=vertices)
    for lo, hi in cut_ranges(indeg, parts):
        a, b = np.searchsorted(dst, [lo, hi])
        path = os.path.join(arcs_dir, split_name(lo, hi))
        np.save(path, np.stack([dst[a:b], src[a:b]], axis=1).astype(np.int32))
        paths.append(path)
    return paths


def write_state(fs: Any, state_dir: str, ranks: np.ndarray,
                outdeg: np.ndarray, conf: Any = None) -> np.ndarray:
    """Write ``ranks`` and the contributions they give as a round's state;
    returns the contributions. Each file is written whole beside its
    final name and renamed onto it, so a reader never sees a torn file."""
    ranks = np.asarray(ranks, np.float32)
    contrib = np.zeros_like(ranks)
    out = outdeg > 0
    contrib[out] = ranks[out] / outdeg[out]
    fs.mkdirs(state_dir)
    tag = str(conf.get("tpumr.task.attempt.id") or "local") if conf \
        else "local"
    for name, arr in (("ranks.npy", ranks), ("contrib.npy", contrib)):
        buf = io.BytesIO()
        np.save(buf, arr)
        path = f"{state_dir.rstrip('/')}/{name}"
        tmp = f"{path}._{tag}.tmp"
        with fs.create(tmp) as f:
            f.write(buf.getvalue())
        if not fs.rename(tmp, path):
            fs.delete(tmp)
    return contrib


# ------------------------------------------------------------------ the map


def _split_of(task: Any):
    from tpumr.mapred.split import InputSplit
    split = InputSplit.from_dict(task.split)
    if getattr(split, "row_start", 0) != 0:
        raise ValueError(f"{split.path}: a PageRank split is a whole file "
                         "(set tpumr.dense.split.rows above its arcs)")
    return split


#: a padding arc's destination: after every real one, so it sums alone
_PAD_DST = np.iinfo(np.int32).max


def _bucket(n: int) -> int:
    from tpumr.parallel.device_sort import bucket_rows
    return bucket_rows(n, 1)


def pad_arcs(arcs: np.ndarray) -> np.ndarray:
    """A split's ``[n, 2]`` arcs as the chip holds them: padding arcs up
    to the shape bucket of ``device_sort.bucket_rows``, to ``_PAD_DST``
    from the split's own sources over again (so the gather reads them as
    it reads the real ones, not one address many times). Made on the
    host, once a split."""
    n = arcs.shape[0]
    out = np.empty((_bucket(n), 2), np.int32)
    out[:n] = arcs
    out[n:, 0] = _PAD_DST
    out[n:, 1] = np.resize(arcs[:len(out) - n, 1], len(out) - n) if n else 0
    return out


def pad_contrib(contrib: np.ndarray) -> np.ndarray:
    """The contributions as the chip holds them: float32, zeros up to the
    shape bucket of the vertices. Made on the host, once a round."""
    out = np.zeros(_bucket(len(contrib)), np.float32)
    out[:len(contrib)] = contrib
    return out


@functools.partial(jax.jit, static_argnums=(2,))
def _last_arcs(arcs, lo, nv):
    """The index of each destination's last arc in a split, -1 for a
    destination with no in-arc: a binary search in the sorted
    destinations, log2(arcs) steps unrolled, never a loop on the device.
    A property of the split alone, so it is computed once."""
    dst = arcs[:, 0]
    verts = lo + jnp.arange(nv, dtype=jnp.int32)
    end = jnp.searchsorted(dst, verts, side="right",
                           method="scan_unrolled").astype(jnp.int32) - 1
    has = (end >= 0) & (jnp.take(dst, jnp.maximum(end, 0),
                                 mode="clip") == verts)
    return jnp.where(has, end, -1)


@jax.jit
def _pagerank_map(arcs, contrib, ends):
    dst, src = arcs[:, 0], arcs[:, 1]
    x = jnp.take(contrib, src, mode="clip")
    first = jnp.concatenate([jnp.ones((1,), bool), dst[1:] != dst[:-1]])
    sums = segmented_scan(x, first)
    return jnp.where(ends >= 0,
                     jnp.take(sums, jnp.maximum(ends, 0), mode="clip"), 0.0)


def pagerank_map_device(arcs, contrib, lo: int, nv: int, ends_of=None):
    """``y`` of one split on the default device, padded: ``y[:nv]`` is the
    split's (``arcs [n, 2]`` int32 sorted by destination, ``n >= 1``;
    ``contrib`` float32 over the vertices). Right at any shape; given as
    the chip holds them (:func:`pad_arcs`, :func:`pad_contrib`) every
    split of one shape bucket runs one compiled program, the range being
    padded to its bucket here. ``ends_of(compute)`` may give the split's
    :func:`_last_arcs` from a cache; by default it is computed."""
    def compute():
        return _last_arcs(arcs, np.int32(lo), _bucket(nv))
    ends = compute() if ends_of is None else ends_of(compute)
    return _pagerank_map(arcs, contrib, ends)


def pagerank_map_numpy(arcs: np.ndarray, contrib: np.ndarray, lo: int,
                       nv: int) -> np.ndarray:
    """The numpy twin: the same ``y``, each destination's arcs added in
    float64 a block of arcs at a time and rounded to float32 once."""
    out = np.zeros(nv, np.float64)
    for a in range(0, arcs.shape[0], _BLOCK):
        blk = arcs[a:a + _BLOCK]
        d0, d1 = int(blk[0, 0]), int(blk[-1, 0])
        out[d0 - lo:d1 - lo + 1] += np.bincount(
            blk[:, 0] - d0, weights=contrib[blk[:, 1]],
            minlength=d1 - d0 + 1)
    return out.astype(np.float32)


class PageRankKernel(KernelMapper):
    """One split of arcs to one record, ``(lo, y)`` (module docstring)."""

    name = "pagerank"

    def staged_form(self, values):
        return pad_arcs(values)

    def map_batch_launch(self, batch, conf, task):
        from tpumr.core import tracing
        from tpumr.ops.devcache import (device_cached, device_side_file,
                                        file_version)
        split = _split_of(task)
        lo, hi = range_of(split.path)
        n = batch.num_records
        ctx = tracing.current()
        if ctx is not None and ctx[1].name == "tpu:execute":
            ctx[1].set(arcs=n, vertices=hi - lo, impl="xla")
        if n == 0:
            return (lo, hi - lo, np.zeros(hi - lo, np.float32))
        contrib = device_side_file(CONTRIB, _contrib_path(conf), conf,
                                   pad_contrib)
        # where each destination's sum ends is the split's own: found
        # once a tracker, kept beside the split in HBM
        version = file_version(split.path, conf)
        y = pagerank_map_device(
            batch.values, contrib, lo, hi - lo,
            lambda compute: device_cached(f"pagerank-ends:{split.path}",
                                          compute, conf, version))
        return (lo, hi - lo, y)

    def map_batch_drain(self, fetched, conf, task) -> Iterable[tuple]:
        lo, nv, y = fetched
        yield int(lo), np.asarray(y, np.float32)[:int(nv)]

    def map_batch_cpu(self, batch, conf, task) -> Iterable[tuple]:
        from tpumr.core import tracing
        from tpumr.ops.devcache import side_file
        lo, hi = range_of(_split_of(task).path)
        arcs = np.asarray(batch.values)
        with tracing.span("map:pagerank", backend="cpu", arcs=len(arcs),
                          vertices=hi - lo, impl="numpy"):
            contrib = side_file(CONTRIB, _contrib_path(conf), conf)
            y = pagerank_map_numpy(arcs, contrib.astype(np.float32,
                                                        copy=False),
                                   lo, hi - lo)
        yield lo, y

    def task_counters(self, records: int, on_tpu: bool) -> dict:
        return {(COUNTER_GROUP, ARCS_TPU if on_tpu else ARCS_CPU): records}


def degrees_path(conf) -> str:
    """``outdeg.npy`` beside the job's ``arcs`` input directory."""
    arcs = str(conf.get("mapred.input.dir") or "").split(",")[0].rstrip("/")
    return f"{arcs.rsplit('/', 1)[0]}/outdeg.npy"


def _contrib_path(conf) -> str:
    state = conf.get("tpumr.pagerank.state")
    if not state:
        raise ValueError("tpumr.pagerank.state not set (the round before)")
    return f"{str(state).rstrip('/')}/contrib.npy"


# --------------------------------------------------------------- the reduce


class PageRankReducer(Reducer):
    """Closes a round: the maps' ranges, put together in order, become
    ``PR_i`` and the next contributions, written to
    ``tpumr.pagerank.state.out`` (needs ``mapred.reduce.tasks=1``). A
    range missing, repeated or overlapping fails the task. Emits one
    record a range, ``(lo, vertices)``, as the job's own output."""

    def configure(self, conf) -> None:
        self._conf = conf
        self._ranges: "dict[int, np.ndarray]" = {}
        if int(conf.get("mapred.reduce.tasks", 1)) != 1:
            raise ValueError("PageRankReducer needs mapred.reduce.tasks=1:"
                             " the update must see every range")

    def reduce(self, key, values, output, reporter):
        for y in values:
            if int(key) in self._ranges:
                raise ValueError(f"range at {key} given twice")
            self._ranges[int(key)] = np.asarray(y, np.float32)
            output.collect(int(key), len(self._ranges[int(key)]))

    def abort(self) -> None:
        """A failed attempt publishes no state."""
        self._ranges.clear()

    def close(self) -> None:
        from tpumr.core import tracing
        from tpumr.fs.filesystem import FileSystem
        from tpumr.mapred.input_formats import load_dense
        from tpumr.ops.devcache import seed_side_file, side_file
        conf = self._conf
        out_dir = conf.get("tpumr.pagerank.state.out")
        if not out_dir:
            raise ValueError("tpumr.pagerank.state.out not set")
        outdeg = side_file("pagerank-outdeg", degrees_path(conf), conf)
        v = len(outdeg)
        with tracing.span("pagerank:update", vertices=v) as sp:
            y = np.zeros(v, np.float64)
            at = 0
            for lo in sorted(self._ranges):
                part = self._ranges[lo]
                if lo != at:
                    raise ValueError(f"ranges do not tile the vertices: "
                                     f"{at} then {lo}")
                y[lo:lo + len(part)] = part
                at = lo + len(part)
            if at != v:
                raise ValueError(f"ranges end at {at} of {v} vertices")
            state = str(conf.get("tpumr.pagerank.state")).rstrip("/")
            prev = load_dense(FileSystem.get(state, conf),
                              f"{state}/ranks.npy").astype(np.float64)
            dangling = float(prev[outdeg == 0].sum(dtype=np.float64))
            ranks = (1.0 - DAMPING) / v + DAMPING * (y + dangling / v)
            delta = float(np.abs(ranks - prev).sum())
            if sp is not None:
                sp.set(dangling_mass=dangling, delta_l1=delta)
            contrib = write_state(FileSystem.get(out_dir, conf), out_dir,
                                  ranks, outdeg, conf)
        try:
            seed_side_file(CONTRIB, f"{out_dir.rstrip('/')}/contrib.npy",
                           conf, contrib, pad_contrib)
        except Exception:  # noqa: BLE001 — residency is an
            pass           # optimization, never a dependency


register_kernel(PageRankKernel())
