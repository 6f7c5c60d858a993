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

What comes back is the rows the job has, not the slots the exchange
reserved (twice the balanced load): the sort leaves each device's live
rows first, a small program counts them, and only that prefix is fetched,
in pieces of a fixed row count, each piece leaving its device as flat
little-endian 32-bit words and landing once, in its place in the shard
the caller gets (``fetch_live_rows``).

The mesh path compiles once per SHAPE BUCKET, never per job: the sampled
splitters are a runtime argument of ``compute_dest``'s program, and row
counts are padded up to ``bucket_rows`` (each device's share rounded up to
4, 5, 6 or 7 x 2^k), from which the exchange's capacity, the sort's shape
and the fetched piece's size follow; where a piece starts is a runtime
argument of its program.

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


def bucket_rows(n: int, n_dev: int) -> int:
    """The row count the mesh branch pads ``n`` rows to, from ``n`` and the
    mesh size alone: each device's share ``ceil(n / n_dev)`` rounded up to
    the next of 4, 5, 6, 7 x 2^k (its top three bits kept; at least 64),
    times ``n_dev``. Every shape the three programs compile for follows
    from it, so inputs whose shares fall between the same two edges run
    the same executables. Padding is under 25 % of ``n`` (plus the
    64-row floor a device): four buckets an octave, where the one-device
    branch's power of two would copy up to 2x the rows in and out."""
    local = -(-n // n_dev)
    if local <= 64:
        return 64 * n_dev
    step = 1 << ((local - 1).bit_length() - 3)
    return -(-local // step) * step * n_dev


def splitter_columns(splitters: np.ndarray, klen: int,
                     num_ranges: int) -> np.ndarray:
    """``[r-1, klen]`` uint8 cut points → the ``[r-1, cols]`` uint32 array
    the destination program takes at run time. A SHORT cut list
    (write_partition_file dedups duplicate samples) is filled up to
    ``num_ranges - 1`` rows of all-FF words, which no key exceeds: a
    missing splitter acts as +inf, and the shape stays the job's."""
    ncols = num_key_columns(klen)
    cols = key_columns(np.asarray(splitters, np.uint8).reshape(-1, klen),
                       klen) if len(splitters) else \
        np.zeros((0, ncols), np.uint32)
    fill = num_ranges - 1 - cols.shape[0]
    if fill > 0:
        cols = np.concatenate(
            [cols, np.full((fill, ncols), 0xFFFFFFFF, np.uint32)])
    return cols


@functools.lru_cache(maxsize=32)
def make_dest_fn(mesh: Mesh, klen: int, ranges_per_dev: int, active: int,
                 axis_name: str = "data"):
    """Jitted SPMD map ``(rows, splitter_cols)`` → destination *device*
    (range // ranges_per_dev). ``rows`` are sharded and end in a validity
    byte; ``splitter_cols`` (``splitter_columns``) is replicated and a
    RUNTIME argument, so one executable serves every job of the same
    shapes. Padding rows (validity 0) carry no record: they are dealt
    round-robin over the ``active`` receiving devices, so that no bucket
    of the exchange fills with them."""
    @partial(jax.shard_map, mesh=mesh, in_specs=(P(axis_name), P()),
             out_specs=P(axis_name))
    def _dest(records, splitter_cols):
        cols = key_columns(records, klen)
        dev = compute_dest(cols, splitter_cols) // ranges_per_dev
        spread = jnp.arange(records.shape[0], dtype=jnp.int32) % active
        return jnp.where(records[:, -1] == 1, dev, spread)

    return jax.jit(_dest)


@functools.lru_cache(maxsize=32)
def make_sort_fn(mesh: Mesh, klen: int, axis_name: str = "data"):
    """Jitted SPMD per-device sort of received rows by their leading
    ``klen`` key bytes. Slots the exchange left unfilled and padding rows
    (trailing validity byte 0) sort to the end of each device's shard;
    the returned mask marks the live rows, a prefix of the shard."""
    @partial(jax.shard_map, mesh=mesh, in_specs=(P(axis_name), P(axis_name)),
             out_specs=(P(axis_name), P(axis_name)))
    def _sort(records, valid):
        live = valid & (records[:, -1] == 1)
        cols = key_columns(records, klen)
        # lexsort: LAST key is primary → (least-significant col … col0,
        # dead-last) so each device's shard comes back live-rows-first
        # in ascending key order
        keys = tuple(cols[:, c] for c in range(cols.shape[1] - 1, -1, -1))
        order = jnp.lexsort(keys + (~live,))
        return jnp.take(records, order, axis=0), jnp.take(live, order)

    return jax.jit(_sort)


@functools.lru_cache(maxsize=32)
def make_count_fn(mesh: Mesh, axis_name: str = "data"):
    """Jitted SPMD count of each device's live rows (``make_sort_fn``'s
    mask) → ``[n_dev]`` int32: the integers that decide what is fetched."""
    @partial(jax.shard_map, mesh=mesh, in_specs=P(axis_name),
             out_specs=P(axis_name))
    def _count(live):
        return jnp.sum(live, dtype=jnp.int32)[None]

    return jax.jit(_count)


def piece_rows(local: int, per_dev: int) -> int:
    """Rows a fetched piece holds, from the bucket alone: an eighth of a
    device's share of the padded rows (``local``, 4 to 7 x 2^k), at least
    64, at most the ``per_dev`` slots a device has. A device with a
    balanced load comes back in eight pieces or nine, under an eighth of
    ``local`` fetched beyond its count."""
    return min(max(64, local // 8), per_dev)


@functools.lru_cache(maxsize=32)
def make_piece_fn(mesh: Mesh, w: int, piece: int, axis_name: str = "data"):
    """Jitted SPMD map ``(sorted rows, start)`` → ``piece`` rows of every
    device's shard from row ``start`` on (a RUNTIME argument: one
    executable a bucket serves every piece of every job), the validity
    byte dropped, as flat 32-bit words: byte ``4i + k`` of a row, padded
    with zeros to whole words, is bits ``8k`` and up of its word ``i``,
    which is the order a little-endian host reads them back in."""
    words = -(-w // 4)

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(axis_name), P()),
             out_specs=P(axis_name))
    def _piece(records, start):
        rows = jax.lax.dynamic_slice_in_dim(records, start, piece)[:, :w]
        rows = jnp.pad(rows, ((0, 0), (0, 4 * words - w)))
        b = [rows[:, k::4].astype(jnp.uint32) for k in range(4)]
        return (b[0] | (b[1] << 8) | (b[2] << 16) | (b[3] << 24)).reshape(-1)

    return jax.jit(_piece)


#: piece programs dispatched, and their copies to the host started, beyond
#: the piece being landed: the next pieces travel while this one is copied
PIECES_IN_FLIGHT = 2


def fetch_live_rows(mesh: Mesh, sorted_recs, live, w: int, piece: int,
                    axis_name: str = "data"):
    """The live prefix of every device's sorted shard → ``(shards, bytes,
    pieces)``: ``n_dev`` ``[count_d, w]`` uint8 arrays, the bytes that
    left the devices for them, the pieces fetched. Device d's shard comes
    in ``ceil(count_d / piece)`` pieces and a device without rows sends
    none; all devices' copies of a round are started before the first is
    read, and each piece is copied once, into its place."""
    n_dev = mesh.shape[axis_name]
    per_dev = sorted_recs.shape[0] // n_dev
    counts = np.asarray(make_count_fn(mesh, axis_name)(live))
    fn = make_piece_fn(mesh, w, piece, axis_name)
    shards = [np.empty((int(c), w), np.uint8) for c in counts]
    rounds = -(-int(counts.max()) // piece)
    bytes_back, pieces, flight = int(counts.nbytes), 0, []

    def launch(r: int) -> None:
        # the last piece of a full shard may not start at r * piece: it
        # is cut from the shard's end, and the host skips what it has
        lo = min(r * piece, per_dev - piece)
        out = fn(sorted_recs, np.int32(lo))
        parts = {}
        for s in out.addressable_shards:
            d = (s.index[0].start or 0) // s.data.shape[0]
            if counts[d] > r * piece:
                s.data.copy_to_host_async()
                parts[d] = s.data
        flight.append((r, r * piece - lo, parts))

    for r in range(min(PIECES_IN_FLIGHT, rounds)):
        launch(r)
    while flight:
        r, skip, parts = flight.pop(0)
        if r + PIECES_IN_FLIGHT < rounds:
            launch(r + PIECES_IN_FLIGHT)
        for d, part in parts.items():
            got = np.asarray(part)
            bytes_back += int(got.nbytes)
            pieces += 1
            take = min(piece, int(counts[d]) - r * piece)
            rows = got.view(np.uint8).reshape(piece, -1)
            shards[d][r * piece:r * piece + take] = \
                rows[skip:skip + take, :w]
    return shards, bytes_back, pieces


@functools.lru_cache(maxsize=8)
def _argsort_keys(ncols: int):
    """Jitted stable argsort of [n, ncols] uint32 key columns (ascending
    lexicographic, column 0 most significant)."""
    @jax.jit
    def _argsort(cols):
        keys = tuple(cols[:, c] for c in range(ncols - 1, -1, -1))
        return jnp.lexsort(keys)

    return _argsort


@functools.lru_cache(maxsize=8)
def sort_columns(key_cols: int):
    """Jitted stable sort of ``[cols, n]`` uint32 words, one column a row
    of the array, by their first ``key_cols`` columns (ascending
    lexicographic, column 0 most significant); the other columns travel
    with their keys. The order is made a key column at a time, least
    significant first, in a loop whose body is ONE stable single-key sort
    that carries the permutation: the sort's compile time grows with its
    operands (three keys and an index compile for 74 s on the chip's host,
    a key and a payload for 26 s, all five columns at once for over two
    minutes), and the loop compiles its body once whatever the key's
    width. The columns are gathered once, at the end."""
    @jax.jit
    def _sort_words(words):
        def one_pass(i, perm):
            column = jax.lax.dynamic_index_in_dim(
                words, key_cols - 1 - i, 0, keepdims=False)
            return jax.lax.sort((jnp.take(column, perm), perm), num_keys=1,
                                is_stable=True)[1]

        perm = jax.lax.fori_loop(
            0, key_cols, one_pass,
            jnp.arange(words.shape[1], dtype=jnp.int32))
        return jnp.take(words, perm, axis=1)

    return _sort_words


@functools.lru_cache(maxsize=32)
def _piece_of_columns(piece: int):
    """Jitted ``(table [cols, n], start)`` → columns ``start`` to ``start
    + piece`` as flat 32-bit words (``start`` a RUNTIME argument: one
    executable a bucket serves every piece of every job)."""
    @jax.jit
    def _piece(table, start):
        return jax.lax.dynamic_slice_in_dim(table, start, piece,
                                            axis=1).reshape(-1)

    return _piece


def fetch_live_columns(table, live: int, piece: int):
    """The first ``live`` columns of a device ``[cols, n]`` uint32 table →
    ``(host [cols, live], bytes, pieces)``, in pieces of a fixed column
    count as flat 32-bit words (``fetch_live_rows`` has why), each landed
    once, the next ones travelling meanwhile."""
    cols, n = table.shape
    out = np.empty((cols, live), np.uint32)
    fn = _piece_of_columns(piece)
    rounds = -(-live // piece)
    flight: list = []

    def launch(r: int) -> None:
        # the last piece of a full table is cut from its end
        lo = min(r * piece, n - piece)
        part = fn(table, np.int32(lo))
        part.copy_to_host_async()
        flight.append((r, r * piece - lo, part))

    for r in range(min(PIECES_IN_FLIGHT, rounds)):
        launch(r)
    bytes_back = 0
    while flight:
        r, skip, part = flight.pop(0)
        if r + PIECES_IN_FLIGHT < rounds:
            launch(r + PIECES_IN_FLIGHT)
        got = np.asarray(part).reshape(cols, piece)
        bytes_back += int(got.nbytes)
        take = min(piece, live - r * piece)
        out[:, r * piece:r * piece + take] = got[:, skip:skip + take]
    return out, bytes_back, rounds


def reduce_words(records: np.ndarray, klen: int) -> np.ndarray:
    """What a reduce kernel's device call sends up for ``[n, klen + v]``
    uint8 rows (``v`` a whole number of words): ``[key columns + v / 4,
    n]`` uint32, one column a row of the array, the key big-endian
    (``key_columns``), the value's words as the host reads them."""
    n, w = records.shape
    kc = num_key_columns(klen)
    out = np.empty((kc + (w - klen) // 4, n), np.uint32)
    out[:kc] = key_columns(records, klen).T
    out[kc:] = np.ascontiguousarray(records[:, klen:]).view("<u4").T
    return out


def rows_of_words(words: np.ndarray, klen: int) -> np.ndarray:
    """``reduce_words`` undone: ``[cols, n]`` uint32 → ``[n, klen + v]``
    uint8 rows."""
    kc = num_key_columns(klen)
    n = words.shape[1]
    out = np.empty((n, klen + 4 * (words.shape[0] - kc)), np.uint8)
    out[:, :klen] = np.ascontiguousarray(words[:kc].T).astype(">u4") \
        .view(np.uint8).reshape(n, 4 * kc)[:, :klen]
    out[:, klen:] = np.ascontiguousarray(words[kc:].T).astype("<u4") \
        .view(np.uint8).reshape(n, -1)
    return out


def _sort_and_reduce(words: np.ndarray, klen: int, reduce,
                     stats: "dict | None"):
    """The one-device call of a job whose reducer is a kernel: the key
    words AND the value column go up, sort and kernel run back to back on
    the device, and only the groups come back, as the live prefix of the
    kernel's table in flat 32-bit words. ``words`` is ``reduce_words`` of
    the job's rows. Returns the groups' rows ``[groups, klen + v]``."""
    kc = num_key_columns(klen)
    cols, n0 = words.shape
    with tracing.span("dshuffle:pack") as sp:
        # padded to the bucket with all-FF keys and zero values: the sort
        # is stable, so padding lands after the real rows even where a
        # real key is all FF, and the kernel is told how many are real
        n_pad = bucket_rows(n0, 1)
        padded = np.empty((cols, n_pad), np.uint32)
        padded[:, :n0] = words
        padded[:kc, n0:] = 0xFFFFFFFF
        padded[kc:, n0:] = 0
        if sp is not None:
            sp.set(n_pad=n_pad, bytes_in=int(padded.nbytes))
    with tracing.span("dshuffle:device", devices=1, retries=0,
                      bytes_in=int(padded.nbytes)) as dev_sp:
        with tracing.span("dshuffle:sort") as sp:
            ordered = _ready(sp, sort_columns(kc)(padded))
        with tracing.span("dshuffle:reduce", rows=n0,
                          kernel=reduce.name) as sp:
            table, groups = reduce.device_program(kc)(ordered, np.int32(n0))
            groups = int(groups)        # waits for the kernel
            got, bytes_back, pieces = fetch_live_columns(
                table, groups, max(64, n_pad // 64))
            bytes_back += 4             # the count
            if sp is not None:
                sp.set(groups=groups, bytes_back=bytes_back, pieces=pieces)
        if dev_sp is not None:
            dev_sp.set(bytes_out=bytes_back)
    if stats is not None:
        stats.update(pad_rows=n_pad - n0, retries=0, reduced_groups=groups,
                     reduce_bytes_back=bytes_back)
    with tracing.span("dshuffle:gather", rows=groups,
                      bytes=groups * (klen + 4 * (cols - kc))):
        return rows_of_words(got, klen)


def _ready(sp, out):
    """``out``, waited for where a span is open to time it: an untraced
    job dispatches the next program without waiting."""
    return jax.block_until_ready(out) if sp is not None else out


def device_partition_sort(mesh: Mesh, records: np.ndarray, klen: int,
                          splitters: np.ndarray, num_ranges: int,
                          capacity: int | None = None,
                          max_retries: int = 2,
                          axis_name: str = "data",
                          stats: "dict | None" = None,
                          key_words: "np.ndarray | None" = None,
                          reduce=None,
                          reduce_words_made: "np.ndarray | None" = None):
    """Full device path: records [N, w] uint8 (first ``klen`` bytes = the
    sort key) → per-device key-sorted rows. On a mesh ``records`` is dealt
    evenly over the devices and padded to ``bucket_rows(N, n_dev)``; a
    trailing validity byte distinguishes real rows from padding. The
    exchange's per-(src, dst) ``capacity`` (twice the bucket's balanced
    load unless given), the sort's shape and the size of a fetched piece
    follow from the bucket, and the splitters and a piece's start are
    runtime arguments, so a second input in the same bucket compiles
    nothing, whatever each device's count. An overflow is retried with
    doubled capacity (a second and third bucket of the exchange, the sort
    and the fetch). Of the ``n_dev x capacity`` slots a device then holds
    only its live rows come back (``fetch_live_rows``), without their
    validity byte, in pieces that land in the returned shard directly.

    Returns ``(shards, total_capacity_overflowed)`` where ``shards`` is a
    list of ``n_dev`` numpy arrays (device d's received rows, key-sorted,
    padding removed) or ``None`` when every retry overflowed (caller falls
    back to the host path — the reference's disk-spill role,
    ReduceTask.java:1080 ShuffleRamManager budget semantics). The mesh
    branch notes ``pad_rows`` (what the bucket added), ``retries``
    (overflow retries made) and, with a result, ``bytes_back`` (what was
    copied from the devices) in ``stats``. ``key_words`` hands over
    ``key_columns(records, klen)`` where the caller has made it already:
    the one-device branch then sends those and computes none; the mesh
    branch, whose devices make their own from the rows, has no use for
    them.

    ``reduce`` names the job's reducer where it is a kernel
    (``tpumr.ops.registry.ReduceKernel``). Both branches keep one
    contract: with ``stats["reduced_groups"]`` set the shards hold the
    kernel's OUTPUT rows (one a group, key-sorted), reduced on the device
    where they were sorted; without it they hold the sorted rows, and the
    caller reduces them with the kernel's numpy twin. The one-device
    branch reduces (``_sort_and_reduce``; ``reduce_words_made`` hands
    over ``reduce_words(records, klen)`` where the caller has made them);
    the mesh branch has no kernel program yet and returns its rows.

    Under a traced task both branches record the same three spans:
    ``dshuffle:pack`` (host: what goes to the device is laid out),
    ``dshuffle:device`` (from the first dispatch until the host holds the
    result: copy in, the programs, copy out) and ``dshuffle:gather``
    (host: rows into their final order; on a mesh they arrive in it, and
    the span only says how many). On a mesh ``dshuffle:device`` has a
    child per step, each closed when its result is ready:
    ``dshuffle:put``, ``dshuffle:dest``, ``dshuffle:exchange`` (one per
    attempt), ``dshuffle:sort``, ``dshuffle:get`` (from the count to the
    last piece landed: ``bytes``, ``live_rows``, ``pieces``).
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
        if reduce is not None:
            words = reduce_words(records, klen) \
                if reduce_words_made is None else reduce_words_made
            if words.shape[1] != n0:
                raise ValueError(f"reduce words of {words.shape[1]} rows "
                                 f"handed over with {n0} rows")
            return [_sort_and_reduce(words, klen, reduce, stats)], 0
        if key_words is not None and key_words.shape[0] != n0:
            raise ValueError(f"key words of {key_words.shape[0]} rows "
                             f"handed over with {n0} rows")
        with tracing.span("dshuffle:pack") as sp:
            kcols = key_columns(records, klen) if key_words is None \
                else key_words
            # pad to the next power of two with all-FF sentinel keys so
            # the jitted argsort compiles once per size BUCKET, not per
            # exact n (XLA recompiles per shape, and a variadic sort is
            # the slowest compile on this path —
            # tests/test_chip_compile.py records it). lexsort is stable,
            # so pad rows (indices >= n0) land after real rows even on
            # all-FF keys.
            n_pad = 1 << max(4, (n0 - 1).bit_length())
            if n_pad != n0:
                padded = np.empty((n_pad, kcols.shape[1]), np.uint32)
                padded[:n0] = kcols
                padded[n0:] = 0xFFFFFFFF
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

    # each device's share of the rows, then padding up to the bucket
    # (zeros with validity byte 0): every shape below follows from the
    # bucket, so another input in it compiles nothing
    with tracing.span("dshuffle:pack") as sp:
        n = bucket_rows(n0, n_dev)
        local = n // n_dev
        ext = np.zeros((n_dev, local, w + 1), dtype=np.uint8)
        for d, share in enumerate(np.array_split(records, n_dev)):
            ext[d, :len(share), :w] = share
            ext[d, :len(share), w] = 1
        ext = ext.reshape(n, w + 1)
        if sp is not None:
            sp.set(n_pad=n, bytes_in=int(ext.nbytes))
    if stats is not None:
        stats.update(pad_rows=n - n0, retries=0)

    with tracing.span("dshuffle:device", devices=n_dev,
                      bytes_in=int(ext.nbytes)) as dev_sp:
        with tracing.span("dshuffle:put", bytes=int(ext.nbytes)) as sp:
            sharded = _ready(sp, shard_over(mesh, ext, axis_name))
        # the receive side is only the ACTIVE destination devices (when
        # num_ranges < mesh size, fewer devices share the whole load)
        active = max(1, -(-num_ranges // ranges_per_dev))
        with tracing.span("dshuffle:dest") as sp:
            dest = _ready(sp, make_dest_fn(
                mesh, klen, ranges_per_dev, active, axis_name)(
                    sharded, splitter_columns(splitters, klen, num_ranges)))

        if capacity is None:
            # balanced per-(src,dst) load of the BUCKET with 2x headroom
            # for sampling skew (dividing by n_dev² where fewer devices
            # receive would systematically overflow)
            capacity = max(16, 2 * local // active)
        overflowed = 0
        for attempt in range(max_retries + 1):
            with tracing.span("dshuffle:exchange", capacity=capacity,
                              attempt=attempt) as sp:
                res = shuffle_dense(mesh, sharded, dest, capacity=capacity,
                                    axis_name=axis_name)
                lost = int(res.overflow)    # waits for the exchange
                if sp is not None:
                    sp.set(overflow=lost)
            if dev_sp is not None:
                dev_sp.set(retries=attempt)
            if stats is not None:
                stats["retries"] = attempt
            if lost == 0:
                break
            overflowed = lost
            capacity *= 2
        else:
            return None, overflowed

        with tracing.span("dshuffle:sort") as sp:
            sorted_recs, live = _ready(sp, make_sort_fn(
                mesh, klen, axis_name)(res.values, res.valid))
        with tracing.span("dshuffle:get") as sp:
            # the sort put the live rows first: unfilled slots and
            # padding stay on the devices, the validity byte too
            shards, bytes_out, pieces = fetch_live_rows(
                mesh, sorted_recs, live, w,
                piece_rows(local, n_dev * capacity), axis_name)
            rows_out = sum(s.shape[0] for s in shards)
            if sp is not None:
                sp.set(bytes=bytes_out, live_rows=rows_out, pieces=pieces)
        if dev_sp is not None:
            dev_sp.set(bytes_out=bytes_out)
        if stats is not None:
            stats["bytes_back"] = bytes_out
    # the pieces landed in the shards themselves, so nothing is left to
    # move: the span stays for those who read the phases by name
    with tracing.span("dshuffle:gather", rows=rows_out, bytes=rows_out * w):
        pass
    return shards, overflowed
