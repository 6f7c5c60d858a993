#!/usr/bin/env python3
"""What the gang reduce's write phase costs on this host, by its parts.

    python misc/write_probe.py [--rows 10000000] [--ranges 4] [--width 100]
                               [--klen 10] [--dir .scratch/write_probe]

The write phase (``tpumr/mapred/device_shuffle.py``) cuts a key-sorted shard
at the job's splitters and writes each range as a SequenceFile of fixed-width
records (``tpumr/io/sequencefile.py:Writer.append_fixed_rows``). This makes
``rows`` sorted rows from a seed and times, on whatever host it runs on (it
never touches a device):

- ``cut``: the linear count the cut was before PR 34 (kept here as the plain
  reference) against the bisection, which have to agree;
- ``copy``: a row's way into its frame, in three forms, each into one chunk
  buffer of whole blocks and handed to the file as a view: ``3d`` (one strided
  assignment over [blocks, records, bytes] for the keys and one for the
  values), ``2d`` (the same two a block), ``take`` (``np.take`` of a block's
  columns with ``out=``, the constants laid again after it), and ``before``
  (a whole-range ``frames`` array, ``tobytes`` a block, ``head + body``);
- ``writer``: ``Writer.append_fixed_rows`` as shipped, one range alone, the
  ranges one after another, and the ranges at once on a thread each, at
  several chunk sizes.

One JSON line per reading on stdout. The files go under ``--dir`` and are
removed; the four forms of the copy have to leave the same bytes.
"""

import argparse
import hashlib
import json
import os
import shutil
import struct
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpumr.io import sequencefile as sf  # noqa: E402
from tpumr.io.writable import _vint_bytes  # noqa: E402
from tpumr.mapred.device_shuffle import _range_boundaries  # noqa: E402
from tpumr.parallel.device_sort import _lex_gt, key_columns  # noqa: E402

PER = 1000  # records a block, as Writer's default


def say(**kw):
    print(json.dumps(kw), flush=True)


def timed(fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    return time.perf_counter() - t0, out


def linear_cut(keys, splitters):
    """The cut as it was: a count of the keys above each splitter."""
    kcols = key_columns(keys, keys.shape[1])
    scols = key_columns(splitters, keys.shape[1])
    return [int(keys.shape[0] - _lex_gt(kcols, s).sum()) for s in scols]


class Chunk:
    """A buffer of ``blocks`` slots (length word, record count, frames), the
    constants laid once: the probe's stand-in for the shipped layout, with
    every block behind a sync marker (true of all but a file's first)."""

    def __init__(self, frame, blocks, sync):
        count = _vint_bytes(PER)
        body = len(count) + PER * frame.size
        self.slot = 20 + 4 + body
        self.buf = np.empty(blocks * self.slot, np.uint8)
        slots = self.buf.reshape(blocks, self.slot)
        slots[:, :24 + len(count)] = np.frombuffer(
            struct.pack(">I", 0xFFFFFFFF) + sync + struct.pack(">I", body)
            + count, np.uint8)
        self.frames = frame.laid_out(np.ndarray(
            (blocks, PER, frame.size), np.uint8, self.buf,
            offset=24 + len(count), strides=(self.slot, frame.size, 1)))
        self.view = memoryview(self.buf)


def copy_forms(rows, klen, path, chunk_bytes):
    """Seconds of each form of the copy for one range's full blocks."""
    frame = sf._FixedFrame(klen, rows.shape[1] - klen)
    blocks = rows.shape[0] // PER
    sync = b"S" * 16
    probe = Chunk(frame, 1, sync)
    at_once = max(1, min(blocks, chunk_bytes // probe.slot))
    colmap = np.zeros(frame.size, np.intp)
    colmap[frame._key_at:frame._key_at + klen] = np.arange(klen)
    colmap[frame._value_at:] = np.arange(klen, rows.shape[1])

    def run(form):
        ch = Chunk(frame, at_once, sync)
        with open(path, "wb") as f:
            for lo in range(0, blocks, at_once):
                nb = min(at_once, blocks - lo)
                src = rows[lo * PER:(lo + nb) * PER].reshape(nb, PER, -1)
                if form == "3d":
                    frame.fill(ch.frames[:nb], src)
                elif form == "2d":
                    for b in range(nb):
                        frame.fill(ch.frames[b], src[b])
                else:
                    for b in range(nb):
                        np.take(src[b], colmap, axis=1, out=ch.frames[b],
                                mode="clip")
                        frame.laid_out(ch.frames[b])
                f.write(ch.view[:nb * ch.slot])

    def before():
        frames = frame.laid_out(np.empty((rows.shape[0], frame.size),
                                         np.uint8))
        frame.fill(frames, rows)
        head = struct.pack(">I", 0xFFFFFFFF) + sync
        with open(path, "wb") as f:
            for lo in range(0, blocks * PER, PER):
                body = _vint_bytes(PER) + frames[lo:lo + PER].tobytes()
                f.write(head)
                f.write(struct.pack(">I", len(body)))
                f.write(body)

    out = {}
    digests = set()
    for form in ("3d", "2d", "take"):
        out[form] = round(timed(run, form)[0], 4)
        digests.add(hashlib.sha1(open(path, "rb").read()).hexdigest())
    out["before"] = round(timed(before)[0], 4)
    digests.add(hashlib.sha1(open(path, "rb").read()).hexdigest())
    out["same_bytes"] = len(digests) == 1
    return out


def write_range(rows, klen, path):
    with open(path, "wb") as f:
        w = sf.Writer(f)
        w.append_fixed_rows(rows, klen)
        w.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=10_000_000)
    ap.add_argument("--ranges", type=int, default=4)
    ap.add_argument("--width", type=int, default=100)
    ap.add_argument("--klen", type=int, default=10)
    ap.add_argument("--dir", default=".scratch/write_probe")
    ap.add_argument("--repeats", type=int, default=3)
    a = ap.parse_args(argv)
    os.makedirs(a.dir, exist_ok=True)
    say(host_cores=os.cpu_count(), rows=a.rows, ranges=a.ranges,
        width=a.width, chunk_bytes_shipped=sf._BULK_CHUNK_BYTES)

    rng = np.random.default_rng(3400000019)
    rows = rng.integers(0, 256, size=(a.rows, a.width), dtype=np.uint8)
    keys = rows[:, :a.klen]
    order = np.lexsort(tuple(keys[:, c] for c in range(a.klen - 1, -1, -1)))
    rows = rows[order]
    del order
    keys = rows[:, :a.klen]
    at = [round(i * a.rows / a.ranges) for i in range(1, a.ranges)]
    splitters = keys[at].copy()

    t_lin, lin = timed(linear_cut, keys, splitters)
    t_bis, bis = timed(_range_boundaries, keys, splitters, 0, a.ranges)
    say(what="cut", linear_s=round(t_lin, 4), bisect_s=round(t_bis, 6),
        agree=lin == bis)
    cuts = [0] + bis + [a.rows]
    ranges = [rows[lo:hi] for lo, hi in zip(cuts, cuts[1:])]
    paths = [os.path.join(a.dir, f"part-{r:05d}") for r in range(a.ranges)]

    for chunk in (1 << 18, 1 << 20, 1 << 22, 1 << 24, 1 << 26):
        for rep in range(a.repeats):
            say(what="copy", chunk_bytes=chunk, rep=rep,
                **copy_forms(ranges[0], a.klen, paths[0], chunk))

    shipped = sf._BULK_CHUNK_BYTES
    try:
        for chunk in (1 << 18, 1 << 20, 1 << 22, 1 << 24, 1 << 26):
            sf._BULK_CHUNK_BYTES = chunk
            for rep in range(a.repeats):
                alone = timed(write_range, ranges[0], a.klen, paths[0])[0]
                t0 = time.perf_counter()
                for r in range(a.ranges):
                    write_range(ranges[r], a.klen, paths[r])
                serial = time.perf_counter() - t0
                threads = [threading.Thread(
                    target=write_range, args=(ranges[r], a.klen, paths[r]))
                    for r in range(a.ranges)]
                t0 = time.perf_counter()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                at_once = time.perf_counter() - t0
                say(what="writer", chunk_bytes=chunk, rep=rep,
                    alone_s=round(alone, 4), one_after_another_s=round(
                        serial, 4), at_once_s=round(at_once, 4))
    finally:
        sf._BULK_CHUNK_BYTES = shipped
    shutil.rmtree(a.dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
