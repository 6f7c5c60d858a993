"""K-Means map kernels: nearest-centroid assignment + device-side partial
aggregation.

The flagship workload (BASELINE.json north star: 100M points, ≥5× CPU-only).
The reference ran K-Means as a CUDA pipes binary fed one point per socket
record (the Shirahata paper's job; conf/mapred-site.xml pins 1 line per map).
Here the whole split is staged as a ``DenseBatch`` and:

- distances are one MXU matmul: ``d²(x,c) = |x|² - 2x·cᵀ + |c|²``;
- the per-cluster partial sums are a second MXU matmul
  (``one_hotᵀ @ points``), so a map task emits k tiny records — the
  all-reduce over centroids rides the shuffle, not per-point traffic;
- the default compute path is fused XLA (no 128-lane padding of narrow
  features — see :func:`assign_and_partials`); a Pallas kernel for
  the fused distance+argmin stays available via ``tpumr.kmeans.use.pallas``
  for wide-d inputs.
"""

from __future__ import annotations

from typing import Any, Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from tpumr.mapred.api import Mapper, Reducer
from tpumr.ops.registry import KernelMapper, register_kernel

_BIG = 1e30


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ----------------------------------------------------------------- XLA path


@jax.jit
def _assign_and_partials_jax(points, centroids):
    x2 = jnp.sum(points * points, axis=1, keepdims=True)
    c2 = jnp.sum(centroids * centroids, axis=1)
    d2 = x2 - 2.0 * jnp.dot(points, centroids.T,
                            preferred_element_type=jnp.float32) + c2[None, :]
    assign = jnp.argmin(d2, axis=1)
    onehot = jax.nn.one_hot(assign, centroids.shape[0], dtype=points.dtype)
    sums = jnp.dot(onehot.T, points, preferred_element_type=jnp.float32)
    counts = jnp.sum(onehot, axis=0).astype(jnp.int32)
    return assign.astype(jnp.int32), sums, counts


# ----------------------------------------------------------------- Pallas


def _assign_kernel(pts_ref, cent_ref, out_ref):
    pts = pts_ref[:]                      # [bn, d_p] VMEM
    cents = cent_ref[:]                   # [k_p, d_p] VMEM
    d2 = (jnp.sum(pts * pts, axis=1, keepdims=True)
          - 2.0 * jnp.dot(pts, cents.T, preferred_element_type=jnp.float32)
          + jnp.sum(cents * cents, axis=1)[None, :])
    out_ref[:] = jnp.argmin(d2, axis=1).astype(jnp.int32).reshape(-1, 1)


def pallas_assign(points: Any, centroids: Any, block_n: int = 2048,
                  interpret: bool = False):
    """Fused distance+argmin assign step as a Pallas TPU kernel. Inputs are
    padded to MXU-friendly tiles: feature dim to a multiple of 128 lanes,
    centroid rows to a multiple of 8 sublanes (padded rows pushed far away so
    argmin ignores them)."""
    n, d = points.shape
    k = centroids.shape[0]
    d_p = _round_up(max(d, 128), 128)
    k_p = _round_up(max(k, 8), 8)
    bn = min(block_n, _round_up(n, 8))
    n_p = _round_up(n, bn)

    pts = jnp.zeros((n_p, d_p), jnp.float32).at[:n, :d].set(points)
    cents = jnp.zeros((k_p, d_p), jnp.float32).at[:k, :d].set(centroids)
    if k_p > k:
        # push padding centroids far away in a dimension real points are 0 in
        cents = cents.at[k:, :].set(jnp.sqrt(_BIG))

    out = pl.pallas_call(
        _assign_kernel,
        grid=(n_p // bn,),
        in_specs=[pl.BlockSpec((bn, d_p), lambda i: (i, 0)),
                  pl.BlockSpec((k_p, d_p), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_p, 1), jnp.int32),
        interpret=interpret,
    )(pts, cents)
    return out[:n, 0]


def assign_and_partials(points, centroids, use_pallas: bool = False,
                        interpret: "bool | None" = None):
    """(assignments [n] i32, partial sums [k,d] f32, counts [k] i32).

    Default is the fused XLA path: the Pallas kernel pads the feature dim
    to the Mosaic 128-lane tile, which at d=16 is 8× the HBM traffic of
    the unpadded XLA program (tests/test_chip_compile.py asserts the XLA
    program keeps the [n, 16] layout). The Pallas kernel stays selectable
    for wide-d inputs where the padding vanishes. Speeds of either: not
    measured on the current machine (PERF.md)."""
    points = jnp.asarray(points, jnp.float32)
    centroids = jnp.asarray(centroids, jnp.float32)
    if use_pallas:
        if interpret is None:
            # Mosaic lowers for TPU only: where the points live on CPU
            # devices (tests, rehearsals) the kernel runs interpreted
            interpret = all(d.platform == "cpu" for d in points.devices())
        assign = pallas_assign(points, centroids, interpret=interpret)
        onehot = jax.nn.one_hot(assign, centroids.shape[0], dtype=jnp.float32)
        sums = jnp.dot(onehot.T, points, preferred_element_type=jnp.float32)
        counts = jnp.sum(onehot, axis=0).astype(jnp.int32)
        return assign, sums, counts
    return _assign_and_partials_jax(points, centroids)


# ------------------------------------------------------------ multi-chip


def make_distributed_step(mesh, axis_name: str = "data"):
    """SPMD K-Means step over a mesh: points stay sharded along the record
    axis; every chip computes local assignments + partial sums (two MXU
    matmuls) and ONE psum over ICI yields identical new centroids on every
    chip — the centroid all-reduce that rode the reference's HTTP shuffle +
    single reduce task now costs one collective (SURVEY.md §5 'distributed
    communication backend' TPU-native mapping).

    Returns jitted ``step(points_shard [N,d] sharded, centroids [k,d]
    replicated) -> (new_centroids [k,d] replicated, counts [k])``.
    """
    import functools
    from jax.sharding import PartitionSpec as P

    from tpumr.parallel import collectives

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(axis_name), P()), out_specs=(P(), P()))
    def step(points, centroids):
        # nested jit inlines during tracing — same program, public API
        _a, sums, counts = _assign_and_partials_jax(points, centroids)
        sums = collectives.psum(sums, axis_name)
        counts = collectives.psum(counts, axis_name)
        new = sums / jnp.maximum(counts, 1)[:, None].astype(sums.dtype)
        # empty clusters keep their old centroid
        new = jnp.where((counts > 0)[:, None], new, centroids)
        return new, counts

    return jax.jit(step)


# ----------------------------------------------------------------- mapper


_centroid_cache: dict[str, np.ndarray] = {}

#: host-cache bound: PIPELINE rounds version their centroid path (one
#: NEW entry per round, nothing invalidated), so the dict would other-
#: wise grow one k×d array per round for the life of the process
_CENTROID_CACHE_CAP = 8


def _load_centroids(conf) -> np.ndarray:
    from tpumr.fs.filesystem import FileSystem
    from tpumr.mapred.input_formats import load_dense
    path = conf.get("tpumr.kmeans.centroids")
    if not path:
        raise ValueError("tpumr.kmeans.centroids not set (path to .npy)")
    cached = _centroid_cache.get(path)
    if cached is None:
        fs = FileSystem.get(path, conf)
        while len(_centroid_cache) >= _CENTROID_CACHE_CAP:
            _centroid_cache.pop(next(iter(_centroid_cache)))
        cached = _centroid_cache[path] = load_dense(fs, path).astype(np.float32)
    return cached


def clear_centroid_cache() -> None:
    """SEQUENTIAL iterative drivers rewrite one centroid file between
    rounds, so both the host cache and the device-resident copy go
    stale and must be dropped per round. Pipeline loop nodes do NOT
    call this between rounds: their conf templates a fresh centroid
    path per round (``cents-r{round}.npy``), so every cache key stays
    valid — call :func:`clear_pipeline_caches` once at convergence or
    pipeline teardown instead."""
    from tpumr.ops.devcache import clear_device_cache
    _centroid_cache.clear()
    clear_device_cache("kmeans-centroids:")


def clear_pipeline_caches() -> None:
    """Pipeline teardown: prefix-clear the per-round centroid entries
    (host + HBM) in one sweep. During the rounds themselves nothing is
    cleared — round r+1's upload is a NEW tag, round r's entry ages out
    of the byte-budgeted device LRU naturally, and the devcache
    pre-seed in :class:`KMeansCentroidUpdateReducer` means the next
    round's centroids may never leave the device at all. Same sweep as
    :func:`clear_centroid_cache`; the distinct name is the distinct
    CONTRACT (once at teardown vs once per round)."""
    clear_centroid_cache()


def _device_centroids(conf):
    """Centroids as a DEVICE-resident array, uploaded once per
    (file, device) instead of once per map task (one upload instead of
    25 of identical bytes per job; see ops/devcache.py)."""
    from tpumr.ops.devcache import device_cached
    host = _load_centroids(conf)
    tag = f"kmeans-centroids:{conf.get('tpumr.kmeans.centroids')}"
    return device_cached(tag, host.astype(np.float32, copy=False), conf)


def assign_and_partials_numpy(points: np.ndarray, centroids: np.ndarray,
                              chunk: int = 1 << 16
                              ) -> tuple[np.ndarray, np.ndarray]:
    """Vectorized host twin of :func:`assign_and_partials` for CPU map
    slots: chunked ``|x|² - 2x·cᵀ + |c|²`` + argmin (BLAS matmul), partial
    sums via per-dimension bincount (C-speed scatter-add). Returns
    (sums [k,d] f32, counts [k] i64)."""
    points = np.asarray(points, np.float32)
    centroids = np.asarray(centroids, np.float32)
    k, d = centroids.shape
    c2 = np.einsum("kd,kd->k", centroids, centroids)
    sums = np.zeros((k, d), np.float32)
    counts = np.zeros(k, np.int64)
    for lo in range(0, points.shape[0], chunk):
        block = points[lo:lo + chunk]
        # |x|² is constant per row — argmin doesn't need it
        d2 = c2[None, :] - 2.0 * (block @ centroids.T)
        assign = np.argmin(d2, axis=1)
        counts += np.bincount(assign, minlength=k)
        for j in range(d):
            sums[:, j] += np.bincount(assign, weights=block[:, j],
                                      minlength=k)
    return sums, counts


class KMeansCpuMapper(Mapper):
    """CPU-slot mapper for the same job: per-record nearest centroid in
    numpy — deliberately the 'slow backend' the hybrid scheduler profiles
    against (≈ running the CPU pipes binary)."""

    def configure(self, conf) -> None:
        self._centroids = _load_centroids(conf)

    def map(self, key, row, output, reporter):
        c = self._centroids
        d2 = ((c - np.asarray(row)[None, :]) ** 2).sum(axis=1)
        cid = int(np.argmin(d2))
        output.collect(cid, (np.asarray(row, np.float32), 1))


#: convergence counter the iterative driver (pipeline loop node) reads:
#: total centroid movement this round, in milli-units (counters are
#: integral) — ``converge={"group": "KMeans", "counter":
#: "CENTROID_SHIFT_MILLI", "op": "le", "value": T}``
SHIFT_COUNTER_GROUP = "KMeans"
SHIFT_COUNTER = "CENTROID_SHIFT_MILLI"


class KMeansCentroidUpdateReducer(Reducer):
    """Round-closing reducer for ITERATIVE kmeans: averages the maps'
    (partial_sum, count) records into the new centroids, writes them as
    the NEXT round's ``.npy`` (``tpumr.kmeans.centroids.out`` — a fresh
    round-templated path, so no cache is ever rewritten-under), emits
    the centroid-shift convergence counter, and pre-seeds the device
    cache under the next round's tag: on a single-host cluster the new
    centroids are HBM-resident before round r+1's first map asks —
    between rounds they never leave the device. Requires
    ``mapred.reduce.tasks=1`` (the update needs every cluster id).

    Also emits (cid, new_centroid) records like the plain
    CentroidReducer, so the round job's committed output remains the
    inspectable artifact."""

    def __init__(self) -> None:
        self._sums: "dict[int, np.ndarray]" = {}
        self._counts: "dict[int, int]" = {}
        self._conf = None
        self._reporter = None

    def configure(self, conf) -> None:
        self._conf = conf
        if int(conf.get("mapred.reduce.tasks", 1)) != 1:
            raise ValueError(
                "KMeansCentroidUpdateReducer needs mapred.reduce.tasks"
                "=1 — the centroid update must see every cluster")

    def reduce(self, key, values, output, reporter):
        self._reporter = reporter
        total, n = None, 0
        for s, c in values:
            s = np.asarray(s, dtype=np.float64)
            total = s if total is None else total + s
            n += int(c)
        cid = int(key)
        self._sums[cid] = total
        self._counts[cid] = n
        output.collect(cid, (total / max(1, n)).tolist())

    def abort(self) -> None:
        """Failed/killed attempt (reduce_task's reducer abort seam): a
        PARTIALLY-fed run must never publish next-round state — its
        rename would replace the commit winner's complete file with
        partial aggregates."""
        self._sums.clear()
        self._counts.clear()

    def close(self) -> None:
        conf = self._conf
        out_path = conf.get("tpumr.kmeans.centroids.out") if conf else None
        if not out_path:
            return   # plain (non-iterative) use: output records suffice
        prev = _load_centroids(conf)
        new = prev.copy()
        for cid, total in self._sums.items():
            if 0 <= cid < new.shape[0] and self._counts[cid] > 0:
                new[cid] = (total / self._counts[cid]).astype(np.float32)
        # write-then-rename: a twin killed MID-WRITE must never leave
        # a truncated file at the final path (fs.create truncates — a
        # direct write could corrupt a completed file). The bytes are
        # deterministic, so on filesystems whose rename replaces
        # (local os.replace, mem) the landing order is irrelevant; on
        # a DFS that REFUSES an existing destination the first writer
        # simply wins — either way the tmp must not linger.
        import io as _io

        from tpumr.fs.filesystem import FileSystem
        buf = _io.BytesIO()
        np.save(buf, np.ascontiguousarray(new))
        fs = FileSystem.get(out_path, conf)
        tmp = (f"{out_path}._"
               f"{conf.get('tpumr.task.attempt.id') or 'local'}.tmp")
        with fs.create(tmp) as f:
            f.write(buf.getvalue())
        if not fs.rename(tmp, out_path):
            try:
                fs.delete(tmp)
            except OSError:
                pass
        shift = float(np.abs(new - prev).sum())
        if self._reporter is not None:
            self._reporter.incr_counter(SHIFT_COUNTER_GROUP,
                                        SHIFT_COUNTER,
                                        int(round(shift * 1000)))
        # HBM pre-seed: register the new centroids under the NEXT
        # round's cache tag so round r+1's maps on this host hit the
        # device copy without touching storage (best-effort — a distant
        # tracker's maps just upload once, as before)
        try:
            from tpumr.ops.devcache import device_cached
            device_cached(f"kmeans-centroids:{out_path}",
                          new.astype(np.float32, copy=False), conf)
        except Exception:  # noqa: BLE001 — residency is an
            pass           # optimization, never a dependency


class KMeansAssignKernel(KernelMapper):
    name = "kmeans-assign"
    cpu_mapper_class = KMeansCpuMapper

    def map_batch_launch(self, batch, conf, task):
        """Two-phase protocol: dispatch the assign+partials program and
        hand the [k,d] sums / [k] counts back as device arrays — the
        runner fetches a whole window of tasks in one roundtrip."""
        from tpumr.core import tracing
        centroids = _device_centroids(conf)
        use_pallas = conf.get_boolean("tpumr.kmeans.use.pallas", False)
        ctx = tracing.current()
        if ctx is not None and ctx[1].name == "tpu:execute":
            # the runner's span around this call: its shape, and which of
            # the two implementations ran it
            ctx[1].set(rows=int(batch.values.shape[0]),
                       d=int(centroids.shape[1]), k=int(centroids.shape[0]),
                       impl="pallas" if use_pallas else "xla")
        _assign, sums, counts = assign_and_partials(batch.values, centroids,
                                                    use_pallas=use_pallas)
        return (sums, counts)

    def map_batch_drain(self, fetched, conf, task) -> Iterable[tuple]:
        sums, counts = (np.asarray(a) for a in fetched)
        for cid in range(sums.shape[0]):
            if counts[cid] > 0:
                yield int(cid), (sums[cid], int(counts[cid]))

    def map_batch_cpu(self, batch, conf, task) -> Iterable[tuple]:
        """Vectorized CPU-slot path: same pre-aggregated output shape as
        the device kernel, so reduce sees identical records either way."""
        centroids = _load_centroids(conf)
        sums, counts = assign_and_partials_numpy(np.asarray(batch.values),
                                                 centroids)
        for cid in range(centroids.shape[0]):
            if counts[cid] > 0:
                yield int(cid), (sums[cid], int(counts[cid]))


register_kernel(KMeansAssignKernel())
