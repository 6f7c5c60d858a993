"""The TeraSort family: 100-byte rows with uniform 10-byte keys from the
seed, each job one ``tpumr examples terasort`` client as a user types it,
the plain numpy reference, and the comparison.

What is compared (``check``): every part file of every job of the window,
read back in range order, row by row against the reference: the rows this
module makes again from the seed (not read from any file), ordered by
numpy. ``rows_wrong`` counts positions that hold another row than the
reference's (a missing or surplus row counts too); the limit is 0. The
control breaks the guarantee the configuration states, a total order on
the whole 10-byte key: it orders by the first four key bytes alone.

The input has to be in the program's SequenceFile container and is written
through ``tpumr.io.sequencefile``; the output is read back by a parser of
this module's own (``parse_container``), so a fault of the container's code
that writer and reader share does not cancel out.
"""

from __future__ import annotations

import glob
import os
import time

import numpy as np

from bench.cluster import BACKEND, BenchFailure, counter
from bench.pool import worker_pool

KEY_LEN, VALUE_LEN = 10, 90
_LO, _HI = 0x20, 0x7E       # ' '..'~', teragen's key alphabet


# ------------------------------------------------------------------ data


def _part_rows(rows: int, parts: int, index: int) -> "tuple[int, int]":
    per = rows // parts
    lo = per * index
    return lo, (rows - lo if index == parts - 1 else per)


def gen_rows(seed: int, index: int, row_start: int, n: int) -> np.ndarray:
    """Part ``index``: ``[n, 100]`` uint8, keys uniform over the printable
    alphabet from (seed, index), values the ten-digit row id and filler."""
    rng = np.random.default_rng([seed, index])
    out = np.full((n, KEY_LEN + VALUE_LEN), ord("."), np.uint8)
    out[:, :KEY_LEN] = rng.integers(_LO, _HI + 1, size=(n, KEY_LEN),
                                    dtype=np.uint8)
    ids = row_start + np.arange(n, dtype=np.int64)
    divs = 10 ** np.arange(9, -1, -1, dtype=np.int64)
    out[:, KEY_LEN:KEY_LEN + 10] = (ids[:, None] // divs % 10
                                    + ord("0")).astype(np.uint8)
    return out


def _write_part(job: tuple) -> int:
    from tpumr.io import sequencefile
    path, seed, index, row_start, n = job
    rows = gen_rows(seed, index, row_start, n)
    with open(path, "wb") as f:
        w = sequencefile.Writer(f)
        w.append_fixed_rows(rows, KEY_LEN)
        w.close()
    return n


def make_input(sizes: dict, seed: int, data_dir: str) -> dict:
    gen = os.path.join(data_dir, "gen")
    os.makedirs(gen)
    jobs = [(os.path.join(gen, f"part-{i:05d}"), seed, i,
             *_part_rows(sizes["rows"], sizes["maps"], i))
            for i in range(sizes["maps"])]
    with worker_pool(len(jobs), most=8) as p:
        written = sum(p.map(_write_part, jobs, chunksize=1))
    if written != sizes["rows"]:
        raise BenchFailure(f"wrote {written} of {sizes['rows']} rows")
    return {"gen": gen}


def rows_per_job(sizes: dict) -> int:
    return sizes["rows"]


# ------------------------------------------------------------- reference


def row_hash(rows: np.ndarray) -> np.ndarray:
    """One 64-bit hash per 100-byte row (multiply-mix over 13 words)."""
    n = rows.shape[0]
    buf = np.zeros((n, 104), np.uint8)
    buf[:, :KEY_LEN + VALUE_LEN] = rows
    words = buf.view(np.uint64)
    mult = (np.arange(1, words.shape[1] + 1, dtype=np.uint64)
            * np.uint64(0x9E3779B97F4A7C15)) | np.uint64(1)
    h = (words * mult[None, :]).sum(axis=1, dtype=np.uint64)
    h ^= h >> np.uint64(29)
    h *= np.uint64(0xBF58476D1CE4E5B9)
    h ^= h >> np.uint64(32)
    return h


def _ref_part(job: tuple):
    seed, index, row_start, n = job
    rows = gen_rows(seed, index, row_start, n)
    keys = rows[:, :KEY_LEN]
    # big-endian 10 bytes as (u64, u16): numeric order == byte order
    hi = keys[:, :8].copy().view(">u8")[:, 0].astype(np.uint64)
    lo = keys[:, 8:].copy().view(">u2")[:, 0].astype(np.uint16)
    return hi, lo, row_hash(rows)


def reference_sorted(sizes: dict, seed: int, mode: str = "full"
                     ) -> np.ndarray:
    """The row hashes of the whole input in the order a sort must give
    them. ``mode="prefix4"`` is the control: stable order by the first
    four key bytes alone."""
    jobs = [(seed, i, *_part_rows(sizes["rows"], sizes["maps"], i))
            for i in range(sizes["maps"])]
    with worker_pool(len(jobs), most=8) as p:
        parts = p.map(_ref_part, jobs, chunksize=1)
    hi = np.concatenate([p[0] for p in parts])
    lo = np.concatenate([p[1] for p in parts])
    h = np.concatenate([p[2] for p in parts])
    if mode == "prefix4":
        order = np.argsort(hi >> np.uint64(32), kind="stable")
    else:
        order = np.lexsort((lo, hi))
    return h[order]


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
    """The ``[n, 100]`` rows of a SequenceFile of 10 + 90 byte records,
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
        return None     # rows are not 10 + 90 bytes
    return np.concatenate([frames[:, 3:v0], frames[:, v0 + 3:]], axis=1)


def _read_part(path: str):
    """Row hashes of one part file, in file order."""
    with open(path, "rb") as f:
        rows = parse_container(f.read())
    return None if rows is None else row_hash(rows)


def read_output(out_dir: str) -> "np.ndarray | None":
    """Row hashes of a job's output, part files in range (name) order."""
    paths = sorted(glob.glob(os.path.join(out_dir, "part-*")))
    if not paths:
        return None
    with worker_pool(len(paths), most=8) as p:
        parts = p.map(_read_part, paths, chunksize=1)
    if any(x is None for x in parts):
        return None
    return np.concatenate(parts)


def rows_wrong(got: "np.ndarray | None", want: np.ndarray) -> int:
    if got is None:
        return int(want.size)
    n = min(got.size, want.size)
    return int(np.count_nonzero(got[:n] != want[:n])
               + abs(got.size - want.size))


# ------------------------------------------------------------ the client


class Session:
    """Each job is one ``tpumr examples terasort`` client of the same
    input into a fresh output directory."""

    def __init__(self, cluster, sizes: dict, traffic: dict, inputs: dict,
                 out_dir: str, job_defs: "list[str]") -> None:
        self.cluster, self.sizes, self.inputs = cluster, sizes, inputs
        self.out_dir = out_dir
        self.args = list(traffic["job"]["args"])
        self.generic = []
        for d in job_defs + list(traffic["job"].get("defs", [])):
            self.generic += ["-D", d]
        self.n = 0

    def restart(self) -> None:
        pass

    def submit(self) -> dict:
        self.n += 1
        out = os.path.join(self.out_dir, f"tera-out{self.n}")
        t0 = time.monotonic()
        run = self.cluster.run_client(
            "terasort", self.cluster.tpumr_argv(
                self.generic + ["examples", "terasort",
                                f"file://{self.inputs['gen']}",
                                f"file://{out}"] + self.args),
            timeout=1100, env_extra={"JAX_PLATFORMS": "cpu"})
        return {"name": "terasort", "client_s": time.monotonic() - t0,
                "ok": run["rc"] == 0, "out": out,
                "stderr": run["stderr"][-1500:]}

    def close(self) -> None:
        pass


def job_failure(r: dict, sizes: dict, on_chip: bool) -> "str | None":
    if r["state"] != "SUCCEEDED":
        return f"state {r['state']}"
    if counter(r, BACKEND, "SHUFFLE_HOST_FALLBACKS"):
        return "the device shuffle fell back to the host sort"
    moved = counter(r, BACKEND, "TPU_SHUFFLE_RECORDS")
    if moved != sizes["rows"]:
        return f"the device shuffle moved {moved} records"
    if on_chip and counter(r, BACKEND, "DEVICE_SORT_ON_ACCEL") <= 0:
        return "the device sort did not run on a chip"
    return None


# ------------------------------------------------------------ comparison


def check(jobs: "list[dict]", sizes: dict, seed: int, inputs: dict,
          limits: dict) -> dict:
    want = reference_sorted(sizes, seed)
    wrong = 0
    for j in jobs:
        wrong = max(wrong, rows_wrong(read_output(j["out"]), want))
    return {"rows_wrong": {"value": wrong, "limit": limits["rows_wrong"]},
            "jobs_compared": {"value": len(jobs), "limit": None}}


def control(sizes: dict, seed: int, inputs: dict, limits: dict) -> dict:
    """The control's reading at this size: the input ordered by the first
    four key bytes alone, put in the program's place."""
    want = reference_sorted(sizes, seed)
    got = reference_sorted(sizes, seed, mode="prefix4")
    return {"rows_wrong": {"value": rows_wrong(got, want),
                           "limit": limits["rows_wrong"]}}
