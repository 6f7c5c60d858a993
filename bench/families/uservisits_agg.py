"""The UserVisits aggregation family: ``SELECT sourceIP, SUM(adRevenue)
FROM UserVisits GROUP BY sourceIP`` (Pavlo et al., SIGMOD 2009, section
4.3.3) over text rows made from the seed, each job one ``tpumr examples
uservisits-agg`` client as a user types it, the plain numpy reference, and
the comparison.

The table: nine ``|``-delimited columns at their declared widths
(``sourceIP VARCHAR(16)``, ``destURL VARCHAR(100)``, ``visitDate DATE``,
``adRevenue FLOAT``, ``userAgent VARCHAR(64)``, ``countryCode
VARCHAR(3)``, ``languageCode VARCHAR(6)``, ``searchWord VARCHAR(32)``,
``duration INT``). ``sourceIP`` is one of ``groups`` distinct dotted quads
drawn from the seed, uniformly a row; ``adRevenue`` a decimal string of
whole cents in [1.00, 1000.00); the seven columns the query does not read
are strings of a random length up to their width, so rows vary in width.

What is compared (``check``): every part file of every job of the window,
read back in range order by a parser of this module's own
(``parse_container``; nothing of ``tpumr`` is imported here), against the
reference: the rows' two columns made again from the seed (not read from
any file), the distinct 16-byte keys in byte order, each group's sum in
**float64** of the float32 values. ``groups_wrong`` counts positions that
hold another key than the reference's (a missing, surplus or misplaced key
counts; limit 0). ``sum_gap`` is the widest relative gap of a group's sum
to the reference's. The control sums in bfloat16.
"""

from __future__ import annotations

import glob
import os
import time

import numpy as np

from bench.cluster import BACKEND, TASKC, BenchFailure, counter
from bench.pool import worker_pool

KEY_LEN, VALUE_LEN = 16, 4
DELIM, NEWLINE = ord("|"), ord("\n")
#: filler characters: '0'..'z', which holds neither the delimiter nor a
#: newline
_LO, _HI = 48, 123
#: (width, least length) of the columns between and after the two the
#: query reads: destURL; visitDate; userAgent, countryCode, languageCode,
#: searchWord; duration (an INT of one to five digits)
_DEST, _DATE = (100, 1), (10, 10)
_TAIL = ((64, 1), (3, 1), (6, 1), (32, 1))
_DURATION = (5, 1)
CHUNK = 250_000         # rows made at a time: buffers of about 40 MB


# ------------------------------------------------------------------ data


def _file_rows(rows: int, files: int, index: int) -> "tuple[int, int]":
    per = rows // files
    lo = per * index
    return lo, (rows - lo if index == files - 1 else per)


def group_keys(seed: int, groups: int) -> "tuple[np.ndarray, np.ndarray]":
    """The ``groups`` distinct ``sourceIP`` values of this seed as text:
    ``([groups, 16]`` uint8 padded with zero bytes, their lengths)``, in
    the order of the draw (group ``g`` of a row is an index into it)."""
    rng = np.random.default_rng([seed, 0xA11])
    draw = rng.integers(0, 1 << 32, size=groups + groups // 8 + 1024,
                        dtype=np.uint64)
    _, first = np.unique(draw, return_index=True)
    ips = draw[np.sort(first)][:groups]
    if ips.shape[0] != groups:
        raise BenchFailure(f"drew {ips.shape[0]} distinct addresses of "
                           f"{groups}")
    text = np.zeros((groups, KEY_LEN), np.uint8)
    at = np.zeros(groups, np.int64)
    rows = np.arange(groups)
    for k, shift in enumerate((24, 16, 8, 0)):
        octet = ((ips >> np.uint64(shift)) & np.uint64(255)).astype(np.int64)
        for div, least in ((100, 100), (10, 10), (1, 0)):
            has = octet >= least
            text[rows[has], at[has]] = ord("0") + octet[has] // div % 10
            at += has
        if k < 3:
            text[rows, at] = ord(".")
            at += 1
    return text, at


def query_columns(seed: int, index: int, chunk: int, n: int, groups: int
                  ) -> "tuple[np.ndarray, np.ndarray]":
    """The two columns the query reads, for ``n`` rows of chunk ``chunk``
    of file ``index``: each row's group, and its ``adRevenue`` in whole
    cents (1.00 to 999.99)."""
    rng = np.random.default_rng([seed, index, chunk, 0])
    return (rng.integers(0, groups, size=n),
            rng.integers(100, 100_000, size=n))


def chunk_text(seed: int, index: int, chunk: int, n: int, groups: int,
               keys: np.ndarray, key_len: np.ndarray) -> np.ndarray:
    """The bytes of ``n`` rows, newline after each."""
    g, cents = query_columns(seed, index, chunk, n, groups)
    rng = np.random.default_rng([seed, index, chunk, 1])
    lens = [key_len[g]]
    for width, least in (_DEST, _DATE):
        lens.append(rng.integers(least, width + 1, size=n))
    lens.append(4 + (cents >= 1000) + (cents >= 10_000))
    for width, least in _TAIL + (_DURATION,):
        lens.append(rng.integers(least, width + 1, size=n))
    lens = np.stack(lens).astype(np.int64)              # [9, n]
    row_len = lens.sum(axis=0) + 9                      # 8 '|' and a '\n'
    row_at = np.concatenate([[0], np.cumsum(row_len)])
    start = row_at[:-1] + np.concatenate(
        [np.zeros((1, n), np.int64), np.cumsum(lens[:-1] + 1, axis=0)])
    buf = rng.integers(_LO, _HI, size=int(row_at[-1]), dtype=np.uint8)
    buf[start[1:] - 1] = DELIM
    buf[row_at[1:] - 1] = NEWLINE
    for k in range(KEY_LEN):                            # sourceIP
        has = k < lens[0]
        buf[start[0][has] + k] = keys[g[has], k]
    for k in range(10):                                 # visitDate
        at = start[2] + k
        buf[at] = ord("-") if k in (4, 7) else ord("0") + buf[at] % 10
    end = start[3] + lens[3]                            # adRevenue
    buf[end - 1] = ord("0") + cents % 10
    buf[end - 2] = ord("0") + cents // 10 % 10
    buf[end - 3] = ord(".")
    buf[end - 4] = ord("0") + cents // 100 % 10
    for back, least, div in ((5, 1000, 1000), (6, 10_000, 10_000)):
        has = cents >= least
        buf[end[has] - back] = ord("0") + cents[has] // div % 10
    for k in range(_DURATION[0]):                       # duration
        has = k < lens[8]
        at = start[8][has] + k
        buf[at] = ord("0") + buf[at] % 10
    return buf


def _chunks(n: int) -> "list[int]":
    return [min(CHUNK, n - lo) for lo in range(0, n, CHUNK)]


def _write_file(job: tuple) -> int:
    path, seed, index, n, groups = job
    keys, key_len = group_keys(seed, groups)
    with open(path, "wb") as f:
        for c, rows in enumerate(_chunks(n)):
            f.write(chunk_text(seed, index, c, rows, groups, keys,
                               key_len).tobytes())
    return n


def make_input(sizes: dict, seed: int, data_dir: str) -> dict:
    table = os.path.join(data_dir, "uservisits")
    os.makedirs(table)
    jobs = [(os.path.join(table, f"part-{i:05d}.txt"), seed, i,
             _file_rows(sizes["rows"], sizes["files"], i)[1],
             sizes["groups"]) for i in range(sizes["files"])]
    with worker_pool(len(jobs), most=8) as p:
        written = sum(p.map(_write_file, jobs, chunksize=1))
    if written != sizes["rows"]:
        raise BenchFailure(f"wrote {written} of {sizes['rows']} rows")
    return {"table": table}


def rows_per_job(sizes: dict) -> int:
    return sizes["rows"]


# ------------------------------------------------------------- reference


def table_columns(sizes: dict, seed: int
                  ) -> "tuple[np.ndarray, np.ndarray, np.ndarray]":
    """The whole table's two columns made again from the seed: ``(group of
    each row, adRevenue of each row as the float32 the decimal string
    parses to, the map task that reads the row)``: a row's map is its
    64 MB split, which the reference does not know, so the third is the
    row's FILE, the nearest thing it does."""
    g, cents, part = [], [], []
    for i in range(sizes["files"]):
        n = _file_rows(sizes["rows"], sizes["files"], i)[1]
        for c, rows in enumerate(_chunks(n)):
            a, b = query_columns(seed, i, c, rows, sizes["groups"])
            g.append(a)
            cents.append(b)
            part.append(np.full(rows, i, np.int32))
    return (np.concatenate(g), (np.concatenate(cents) / 100.0)
            .astype(np.float32), np.concatenate(part))


def _as_s16(keys: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(keys).view(f"S{KEY_LEN}")[:, 0]


def reference(sizes: dict, seed: int, g: "np.ndarray | None" = None,
              revenue: "np.ndarray | None" = None
              ) -> "tuple[np.ndarray, np.ndarray]":
    """``(keys [G, 16] uint8 in byte order, sums [G] float64)``: the
    distinct keys of the rows and each group's sum, in float64, of its
    rows' float32 values. ``g`` / ``revenue`` put other rows in the
    table's place (the planted faults)."""
    if g is None:
        g, revenue, _ = table_columns(sizes, seed)
    keys, _ = group_keys(seed, sizes["groups"])
    sums = np.bincount(g, weights=revenue.astype(np.float64),
                       minlength=sizes["groups"])
    present = np.flatnonzero(np.bincount(g, minlength=sizes["groups"]))
    _, order = np.unique(_as_s16(keys[present]), return_index=True)
    present = present[order]
    return keys[present], sums[present]


def least_share_row(g: np.ndarray, revenue: np.ndarray) -> int:
    """The row that is the smallest share of its group's sum, never a
    group's only row: where one row left out or counted twice shows
    least."""
    sums = np.bincount(g, weights=revenue.astype(np.float64))
    share = revenue / sums[g]
    share[np.bincount(g)[g] < 2] = np.inf
    return int(np.argmin(share))


def _bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to the nearest bfloat16 (ties to even), as
    float32."""
    bits = np.ascontiguousarray(x, np.float32).view(np.uint32)
    bits = bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))
    return (bits & np.uint32(0xFFFF0000)).view(np.float32)


def sums_in_bfloat16(sizes: dict, g: np.ndarray, revenue: np.ndarray
                     ) -> np.ndarray:
    """Each group's sum, one row after another in the table's order, with
    the values and every partial sum rounded to bfloat16: the control."""
    order = np.argsort(g, kind="stable")
    gs, v = g[order], _bf16(revenue[order])
    first = np.ones(gs.shape[0], bool)
    first[1:] = gs[1:] != gs[:-1]
    start = np.maximum.accumulate(np.where(first, np.arange(gs.shape[0]), 0))
    rank = np.arange(gs.shape[0]) - start
    acc = np.zeros(sizes["groups"], np.float32)
    for r in range(int(rank.max()) + 1):
        at = rank == r
        acc[gs[at]] = _bf16(acc[gs[at]] + v[at])
    return acc.astype(np.float64)


_FRAME = 3 + KEY_LEN + 3 + VALUE_LEN
#: each field of a record: the length of what follows (one byte below
#: 128), the typed codec's tag for raw bytes, the payload's length
_KEY_HEAD = (2 + KEY_LEN, 1, KEY_LEN)
_VALUE_HEAD = (2 + VALUE_LEN, 1, VALUE_LEN)


def _vint(buf: bytes, pos: int) -> "tuple[int, int]":
    """A little-endian base-128 unsigned integer at ``pos``, and the
    position after it."""
    value = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, pos
        shift += 7


def parse_container(buf: bytes) -> "np.ndarray | None":
    """The ``[n, 20]`` records of a SequenceFile of 16 + 4 byte records,
    parsed from the container's description alone (no code of the
    program): ``TSEQ``, version 1, metadata, a 16-byte sync marker; then
    blocks, each a big-endian length and a body (the record count, then
    the records), with ``0xFFFFFFFF`` and the marker in between. None
    where the bytes are anything else (a compressed body too)."""
    if buf[:4] != b"TSEQ" or buf[4] != 1:
        return None
    meta_len, pos = _vint(buf, 5)
    pos += meta_len
    sync = buf[pos:pos + 16]
    pos += 16
    blocks = []
    try:
        while pos < len(buf):
            length = int.from_bytes(buf[pos:pos + 4], "big")
            pos += 4
            if length == 0xFFFFFFFF:
                if buf[pos:pos + 16] != sync:
                    return None
                pos += 16
                continue
            n, body = _vint(buf, pos)
            if pos + length > len(buf) or pos + length - body != n * _FRAME:
                return None
            blocks.append(np.frombuffer(buf, np.uint8, n * _FRAME, body)
                          .reshape(n, _FRAME))
            pos += length
    except IndexError:
        return None
    if not blocks:
        return np.zeros((0, KEY_LEN + VALUE_LEN), np.uint8)
    frames = np.concatenate(blocks)
    v0 = 3 + KEY_LEN
    if (frames[:, :3] != _KEY_HEAD).any() \
            or (frames[:, v0:v0 + 3] != _VALUE_HEAD).any():
        return None     # records are not 16 + 4 bytes
    return np.concatenate([frames[:, 3:v0], frames[:, v0 + 3:]], axis=1)


def read_output(out_dir: str) -> "tuple[np.ndarray, np.ndarray] | None":
    """``(keys [n, 16], sums [n] float32)`` of a job's output, part files
    in range (name) order; None where a file is not what the job
    writes."""
    paths = sorted(glob.glob(os.path.join(out_dir, "part-*")))
    if not paths:
        return None
    parts = []
    for p in paths:
        with open(p, "rb") as f:
            parts.append(parse_container(f.read()))
    if any(x is None for x in parts):
        return None
    rows = np.concatenate(parts)
    return rows[:, :KEY_LEN], np.ascontiguousarray(
        rows[:, KEY_LEN:]).view("<f4")[:, 0]


def compare(got: "tuple[np.ndarray, np.ndarray] | None",
            want: "tuple[np.ndarray, np.ndarray]") -> "tuple[int, float]":
    """``(groups_wrong, sum_gap)`` of one job's output against the
    reference. Keys are compared position by position, so one key
    missing, surplus or out of order counts with all that follow it; the
    sums are compared key by key wherever the output's key is one of the
    reference's. No key in common reads an infinite gap."""
    want_keys, want_sums = want
    if got is None:
        return int(want_keys.shape[0]), float("inf")
    keys, sums = got
    n = min(keys.shape[0], want_keys.shape[0])
    wrong = int((keys[:n] != want_keys[:n]).any(axis=1).sum()
                + abs(keys.shape[0] - want_keys.shape[0]))
    ref, out = _as_s16(want_keys), _as_s16(keys)
    at = np.minimum(np.searchsorted(ref, out), ref.shape[0] - 1)
    same = ref[at] == out
    if not same.any():
        return wrong, float("inf")
    gaps = np.abs(sums[same].astype(np.float64) - want_sums[at[same]]) \
        / np.abs(want_sums[at[same]])
    gap = float(gaps.max())
    return wrong, gap if gap == gap else float("inf")   # nan is no pass


# ------------------------------------------------------------ the client


class Session:
    """Each job is one ``tpumr examples uservisits-agg`` client of the
    same table into a fresh output directory."""

    def __init__(self, cluster, sizes: dict, traffic: dict, inputs: dict,
                 out_dir: str, job_defs: "list[str]") -> None:
        self.cluster, self.inputs, self.out_dir = cluster, inputs, out_dir
        self.args = list(traffic["job"]["args"])
        self.generic = []
        for d in job_defs + list(traffic["job"].get("defs", [])):
            self.generic += ["-D", d]
        self.n = 0

    def restart(self) -> None:
        pass

    def submit(self) -> dict:
        self.n += 1
        out = os.path.join(self.out_dir, f"agg-out{self.n}")
        t0 = time.monotonic()
        run = self.cluster.run_client(
            "uservisits-agg", self.cluster.tpumr_argv(
                self.generic + ["examples", "uservisits-agg",
                                f"file://{self.inputs['table']}",
                                f"file://{out}"] + self.args),
            timeout=300, env_extra={"JAX_PLATFORMS": "cpu"})
        return {"name": "uservisits-agg",
                "client_s": time.monotonic() - t0, "ok": run["rc"] == 0,
                "out": out, "stderr": run["stderr"][-1500:]}

    def close(self) -> None:
        pass


def job_failure(r: dict, sizes: dict, on_chip: bool) -> "str | None":
    """The guarantees a job's counters can break whatever it wrote."""
    rows = sizes["rows"]
    if r["state"] != "SUCCEEDED":
        return f"state {r['state']}"
    for name in ("MAP_INPUT_RECORDS", "MAP_OUTPUT_RECORDS",
                 "REDUCE_INPUT_RECORDS"):
        if counter(r, TASKC, name) != rows:
            return f"{name} is {counter(r, TASKC, name)}, not {rows}"
    if counter(r, BACKEND, "SHUFFLE_HOST_FALLBACKS"):
        return "the device shuffle fell back to the host sort"
    if counter(r, BACKEND, "TPU_SHUFFLE_RECORDS") != rows:
        return (f"the device shuffle moved "
                f"{counter(r, BACKEND, 'TPU_SHUFFLE_RECORDS')} records")
    if counter(r, BACKEND, "REDUCE_HOST_TWIN"):
        return "the groups were summed on the host, by the kernel's twin"
    if counter(r, BACKEND, "TPU_REDUCE_RECORDS") != rows:
        return (f"the device reduced "
                f"{counter(r, BACKEND, 'TPU_REDUCE_RECORDS')} records")
    groups = counter(r, BACKEND, "TPU_REDUCE_GROUPS")
    for name in ("REDUCE_INPUT_GROUPS", "REDUCE_OUTPUT_RECORDS"):
        if counter(r, TASKC, name) != groups:
            return (f"{name} is {counter(r, TASKC, name)}, the device's "
                    f"groups {groups}")
    if on_chip and counter(r, BACKEND, "DEVICE_SORT_ON_ACCEL") <= 0:
        return "the device sort did not run on a chip"
    if on_chip and counter(r, BACKEND, "DEVICE_REDUCE_ON_ACCEL") <= 0:
        return "the device reduce did not run on a chip"
    return None


# ------------------------------------------------------------ comparison


def check(jobs: "list[dict]", sizes: dict, seed: int, inputs: dict,
          limits: dict) -> dict:
    want = reference(sizes, seed)
    wrong, gap = 0, 0.0
    for j in jobs:
        w, g = compare(read_output(j["out"]), want)
        wrong, gap = max(wrong, w), max(gap, g)
    return {"groups_wrong": {"value": wrong,
                             "limit": limits["groups_wrong"]},
            "sum_gap": {"value": gap if jobs else float("inf"),
                        "limit": limits["sum_gap"]},
            "jobs_compared": {"value": len(jobs), "limit": None}}


def control(sizes: dict, seed: int, inputs: dict, limits: dict) -> dict:
    """The control's reading at this size: every group's sum made in
    bfloat16, put in the program's place."""
    g, revenue, _ = table_columns(sizes, seed)
    want = reference(sizes, seed, g, revenue)
    keys, _ = group_keys(seed, sizes["groups"])
    low = sums_in_bfloat16(sizes, g, revenue)
    present = np.flatnonzero(np.bincount(g, minlength=sizes["groups"]))
    order = np.argsort(_as_s16(keys[present]))
    got = (keys[present][order], low[present][order].astype(np.float32))
    wrong, gap = compare(got, want)
    return {"groups_wrong": {"value": wrong,
                             "limit": limits["groups_wrong"]},
            "sum_gap": {"value": gap, "limit": limits["sum_gap"]}}


def faults(sizes: dict, seed: int, inputs: dict, limits: dict) -> dict:
    """Each planted fault's reading at this size, as ``check`` compares a
    job: the reference with the fault planted, in float32 where the
    program sums in float32, against the reference without. One row left
    out or counted twice is planted where it shows LEAST (the row that is
    the smallest share of its group's sum); two neighbours' sums are
    swapped in three places and the least counts; a map's output lost is
    a whole input file's rows."""
    g, revenue, part = table_columns(sizes, seed)
    want = reference(sizes, seed, g, revenue)
    least = least_share_row(g, revenue)

    def reading(g2, revenue2, swap=None):
        keys, s = reference(sizes, seed, g2, revenue2)
        s = s.astype(np.float32)
        if swap is not None:
            s[[swap, swap + 1]] = s[[swap + 1, swap]]
        wrong, gap = compare((keys, s), want)
        return {"least": gap, "groups_wrong": wrong,
                "limit": limits["sum_gap"]}

    keep = np.ones(g.shape[0], bool)
    keep[least] = False
    twice = np.concatenate([np.arange(g.shape[0]), [least]])
    n_groups = want[0].shape[0]
    swaps = [reading(g, revenue, swap=at)
             for at in (0, n_groups // 2, n_groups - 2)]
    return {"a_row_left_out": reading(g[keep], revenue[keep]),
            "a_row_counted_twice": reading(g[twice], revenue[twice]),
            "two_sums_swapped": min(swaps, key=lambda c: c["least"]),
            "a_maps_output_lost": reading(g[part != 0], revenue[part != 0])}
