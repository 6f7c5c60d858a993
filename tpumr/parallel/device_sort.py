"""Device global sort: range-partition → ICI all-to-all → per-device sort.

This is the device data plane of the framework's *device-shuffled reduce*
(`tpumr.mapred.device_shuffle`): the role the reference implements as R
parallel HTTP fetch streams + k-way disk merges (ReduceTask.java:659
ReduceCopier ↔ TaskTracker.java:4050 MapOutputServlet, merge :399-409)
becomes three XLA programs over a mesh:

1. ``compute_dest`` — every record's destination range from sampled key
   splitters (≈ TotalOrderPartitioner's bisect, vectorized on device);
2. ``shuffle_dense`` (tpumr.parallel.shuffle) — ONE ``lax.all_to_all``
   moves every record to the device that owns its range;
3. ``sort_local_shards`` — each device lexsorts what it received.

Keys are fixed-width byte strings (the device-sortable case called out in
SURVEY.md §7: terasort's 10-byte keys); they are packed into big-endian
uint32 columns so lexicographic byte order == multi-column numeric order,
avoiding any dependence on 64-bit ints (jax_enable_x64 stays off).
"""

from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from tpumr.core import tracing


def num_key_columns(klen: int) -> int:
    return -(-klen // 4)


def key_columns(records, klen: int):
    """[n, >=klen] uint8 → [n, ceil(klen/4)] uint32, big-endian packed.
    Trailing bytes of the last column are zero-padded (a constant suffix
    shared by every record, so order is preserved). Works under jit and on
    host numpy alike."""
    xp = jnp if isinstance(records, jax.Array) else np
    ncols = num_key_columns(klen)
    n = records.shape[0]
    padded = xp.zeros((n, ncols * 4), dtype=xp.uint8)
    if isinstance(records, jax.Array):
        padded = padded.at[:, :klen].set(records[:, :klen])
    else:
        padded[:, :klen] = records[:, :klen]
    b = padded.reshape(n, ncols, 4).astype(xp.uint32)
    return (b[..., 0] << 24) | (b[..., 1] << 16) | (b[..., 2] << 8) | b[..., 3]


def _lex_gt(key_cols, splitter_cols):
    """[n, c] > [c] lexicographically → [n] bool (key strictly greater)."""
    ncols = key_cols.shape[1]
    xp = jnp if isinstance(key_cols, jax.Array) else np
    gt = xp.zeros(key_cols.shape[0], dtype=bool)
    eq = xp.ones(key_cols.shape[0], dtype=bool)
    for c in range(ncols):
        gt = gt | (eq & (key_cols[:, c] > splitter_cols[c]))
        eq = eq & (key_cols[:, c] == splitter_cols[c])
    return gt


def compute_dest(key_cols, splitter_cols):
    """Destination range per record: ``sum_j (key > splitter_j)`` — matches
    the host TotalOrderPartitioner convention (keys equal to a cut stay in
    the lower range). ``splitter_cols`` is [r-1, c]; loop is unrolled (r is
    the reduce count, small) so memory stays O(n)."""
    xp = jnp if isinstance(key_cols, jax.Array) else np
    dest = xp.zeros(key_cols.shape[0], dtype=xp.int32)
    for j in range(splitter_cols.shape[0]):
        dest = dest + _lex_gt(key_cols, splitter_cols[j]).astype(xp.int32)
    return dest


@functools.lru_cache(maxsize=32)
def _make_dest_fn(mesh: Mesh, klen: int, splitters_key: bytes,
                  ranges_per_dev: int, axis_name: str):
    splitters = np.frombuffer(splitters_key, dtype=np.uint8).reshape(-1, klen)
    splitter_cols = key_columns(splitters, klen) if len(splitters) else \
        np.zeros((0, num_key_columns(klen)), np.uint32)

    @partial(jax.shard_map, mesh=mesh, in_specs=P(axis_name),
             out_specs=P(axis_name))
    def _dest(records):
        cols = key_columns(records, klen)
        rng = compute_dest(cols, jnp.asarray(splitter_cols))
        return rng // ranges_per_dev

    return jax.jit(_dest)


def make_dest_fn(mesh: Mesh, klen: int, splitters: np.ndarray,
                 ranges_per_dev: int, axis_name: str = "data"):
    """Jitted SPMD map records→destination *device* (range // ranges_per_dev).
    ``splitters`` is [r-1, klen] uint8 (may be empty for r == 1)."""
    return _make_dest_fn(mesh, klen, splitters.astype(np.uint8).tobytes(),
                         ranges_per_dev, axis_name)


@functools.lru_cache(maxsize=32)
def _make_sort_fn(mesh: Mesh, klen: int, axis_name: str):
    @partial(jax.shard_map, mesh=mesh, in_specs=(P(axis_name), P(axis_name)),
             out_specs=(P(axis_name), P(axis_name)))
    def _sort(records, valid):
        cols = key_columns(records, klen)
        # lexsort: LAST key is primary → (least-significant col … col0,
        # invalid-last) so each device's shard comes back valid-records-
        # first in ascending key order
        keys = tuple(cols[:, c] for c in range(cols.shape[1] - 1, -1, -1))
        order = jnp.lexsort(keys + (~valid,))
        return jnp.take(records, order, axis=0), jnp.take(valid, order)

    return jax.jit(_sort)


def make_sort_fn(mesh: Mesh, klen: int, axis_name: str = "data"):
    """Jitted SPMD per-device sort of received records by their leading
    ``klen`` key bytes; invalid (padding) slots sort to the end of each
    device's shard."""
    return _make_sort_fn(mesh, klen, axis_name)


@functools.lru_cache(maxsize=8)
def _argsort_keys(ncols: int):
    """Jitted stable argsort of [n, ncols] uint32 key columns (ascending
    lexicographic, column 0 most significant)."""
    @jax.jit
    def _argsort(cols):
        keys = tuple(cols[:, c] for c in range(ncols - 1, -1, -1))
        return jnp.lexsort(keys)

    return _argsort


def device_partition_sort(mesh: Mesh, records: np.ndarray, klen: int,
                          splitters: np.ndarray, num_ranges: int,
                          capacity: int | None = None,
                          max_retries: int = 2,
                          axis_name: str = "data"):
    """Full device path: records [N, w] uint8 (first ``klen`` bytes = the
    sort key) → per-device key-sorted rows. ``records`` is padded internally
    to a mesh-size multiple; a trailing validity byte distinguishes real
    rows from padding after the exchange.

    Returns ``(shards, total_capacity_overflowed)`` where ``shards`` is a
    list of ``n_dev`` numpy arrays (device d's received rows, key-sorted,
    padding removed) or ``None`` when every retry overflowed (caller falls
    back to the host path — the reference's disk-spill role,
    ReduceTask.java:1080 ShuffleRamManager budget semantics).

    Under a traced task both branches record the same three spans:
    ``dshuffle:pack`` (host: what goes to the device is laid out),
    ``dshuffle:device`` (from the first dispatch until the host holds the
    result: copy in, the programs, copy out) and ``dshuffle:gather``
    (host: rows into their final order).
    """
    from tpumr.parallel.mesh import shard_over
    from tpumr.parallel.shuffle import shuffle_dense

    n_dev = mesh.shape[axis_name]
    n0, w = records.shape
    ranges_per_dev = -(-num_ranges // n_dev)

    if n_dev == 1:
        # single-device mesh: the all-to-all exchange is the identity, so
        # only the SORT KEYS visit the device — upload [n, ceil(klen/4)]
        # uint32 columns, argsort there, download the [n] permutation,
        # and gather the full rows on the host. The transfer is
        # ~n x (4 x cols + 4) bytes instead of 2 x n x w (rows up +
        # sorted rows down); the value payload never leaves the host.
        if n0 == 0:
            return [records.copy()], 0
        with tracing.span("dshuffle:pack") as sp:
            kcols = key_columns(records, klen)
            # pad to the next power of two with all-FF sentinel keys so
            # the jitted argsort compiles once per size BUCKET, not per
            # exact n (XLA recompiles per shape, and a variadic sort is
            # the slowest compile on this path —
            # tests/test_chip_compile.py records it). lexsort is stable,
            # so pad rows (indices >= n0) land after real rows even on
            # all-FF keys.
            n_pad = 1 << max(4, (n0 - 1).bit_length())
            if n_pad != n0:
                padded = np.full((n_pad, kcols.shape[1]), 0xFFFFFFFF,
                                 np.uint32)
                padded[:n0] = kcols
                kcols = padded
            if sp is not None:
                sp.set(n_pad=n_pad, bytes_in=int(kcols.nbytes))
        with tracing.span("dshuffle:device", devices=1, retries=0,
                          bytes_in=int(kcols.nbytes)) as sp:
            order = np.asarray(_argsort_keys(kcols.shape[1])(kcols))
            if sp is not None:
                sp.set(bytes_out=int(order.nbytes))
        with tracing.span("dshuffle:gather", rows=n0,
                          bytes=int(records.nbytes)):
            if n_pad != n0:
                order = order[order < n0]
            return [records[order]], 0

    # trailing validity byte + pad rows (zeros → marked invalid) so the
    # leading dim divides the mesh; pads route to device 0 and are masked
    # out on the host after the sort
    with tracing.span("dshuffle:pack") as sp:
        n = -(-n0 // n_dev) * n_dev
        ext = np.zeros((n, w + 1), dtype=np.uint8)
        ext[:n0, :w] = records
        ext[:n0, w] = 1
        sharded = shard_over(mesh, ext, axis_name)
        if sp is not None:
            sp.set(n_pad=n, bytes_in=int(ext.nbytes))

    with tracing.span("dshuffle:device", devices=n_dev,
                      bytes_in=int(ext.nbytes)) as sp:
        dest = make_dest_fn(mesh, klen, splitters, ranges_per_dev,
                            axis_name)(sharded)

        if capacity is None:
            # balanced per-(src,dst) load with 2x headroom for sampling
            # skew; the receive side is only the ACTIVE destination
            # devices (when num_ranges < mesh size, fewer devices share
            # the whole load — dividing by n_dev² would systematically
            # overflow)
            active = max(1, -(-num_ranges // ranges_per_dev))
            capacity = max(16, int(2 * n / (n_dev * active)))
        overflowed = 0
        for attempt in range(max_retries + 1):
            res = shuffle_dense(mesh, sharded, dest, capacity=capacity,
                                axis_name=axis_name)
            if sp is not None:
                sp.set(retries=attempt)
            if int(res.overflow) == 0:
                break
            overflowed = int(res.overflow)
            capacity *= 2
        else:
            return None, overflowed

        sorted_recs, sorted_valid = make_sort_fn(mesh, klen, axis_name)(
            res.values, res.valid)
        host_recs = np.asarray(sorted_recs)
        host_valid = np.asarray(sorted_valid)
        if sp is not None:
            sp.set(bytes_out=int(host_recs.nbytes + host_valid.nbytes))
    with tracing.span("dshuffle:gather") as sp:
        per_dev = host_recs.shape[0] // n_dev
        shards = []
        for d in range(n_dev):
            lo, hi = d * per_dev, (d + 1) * per_dev
            rows = host_recs[lo:hi]
            # mask-filter (order-preserving): drop unfilled slots AND
            # padding
            mask = host_valid[lo:hi] & (rows[:, w] == 1)
            shards.append(rows[mask][:, :w])
        if sp is not None:
            sp.set(rows=sum(s.shape[0] for s in shards),
                   bytes=sum(int(s.nbytes) for s in shards))
    return shards, overflowed
