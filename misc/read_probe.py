#!/usr/bin/env python3
"""What a sort map's read of its split costs on this host, in two forms.

    python misc/read_probe.py [--rows 1250000] [--siblings 0,2,7]
                              [--repeats 3] [--dir .scratch/read_probe]

A map of ``terasort-10m.device-shuffle`` reads one split of a SequenceFile
of 100-byte rows (10-byte keys, 1000 records a block, a sync marker before
every block) as one RecordBatch (``tpumr/io/sequencefile.py:
Reader.read_batch_range``). This writes one file of the cell's layout
(``--rows`` rows: the cell's 10M rows over 8 files), takes its second split
(64 MB on, to the end: what the benchmark's splits are) and times, on
whatever host it runs on (it never touches a device):

- ``blocks``: the read as it was before the strided form, kept here as the
  plain reference: a byte at a time to the first marker, then four reads a
  block, a numpy parse a block and one concatenation at the end;
- ``strided``: ``read_batch_range`` as shipped: the split in a few large
  reads into one buffer, its blocks parsed as one strided view.

Each form is timed alone and beside ``--siblings`` other threads reading
the same split the same way the whole time, and beside ``--spinners``
threads that only run Python (what a neighbour that never gives the
interpreter away looks like to a read); a reading gives the read's wall
seconds and the CPU seconds of its own thread (the difference is time spent
waiting: for the interpreter, for the disk). ``cold`` readings drop the
file from the page cache first (``posix_fadvise``). The two forms have to
give the same batch. One JSON line per reading on stdout; the file goes
under ``--dir`` and is removed.
"""

import argparse
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
from tpumr.io.recordbatch import RecordBatch  # noqa: E402
from tpumr.io.writable import _TAG_BYTES, _vint_at  # noqa: E402

SPLIT = 64 << 20  # the cell's split size (the local block size)


def say(**kw):
    print(json.dumps(kw), flush=True)


# ----------------------------------------------- the form before, verbatim


def _old_parse_fixed_block(body):
    try:
        n, rec0 = _vint_at(body, 0)
        if n <= 0:
            return None
        kser_len, kser0 = _vint_at(body, rec0)
        if body[kser0] != _TAG_BYTES[0]:
            return None
        klen, kpay0 = _vint_at(body, kser0 + 1)
        if kser0 + kser_len != kpay0 + klen:
            return None
        vser_len, vser0 = _vint_at(body, kpay0 + klen)
        if body[vser0] != _TAG_BYTES[0]:
            return None
        vlen, vpay0 = _vint_at(body, vser0 + 1)
        if vser0 + vser_len != vpay0 + vlen:
            return None
    except IndexError:
        return None
    frame = vpay0 + vlen - rec0
    if len(body) - rec0 != n * frame:
        return None
    arr = np.frombuffer(body, np.uint8, n * frame, rec0).reshape(n, frame)
    kpay = kpay0 - rec0
    vhdr = kpay + klen
    vpay = vpay0 - rec0
    meta_idx = np.concatenate([np.arange(0, kpay), np.arange(vhdr, vpay)])
    if n > 1 and not (arr[1:, meta_idx] == arr[0, meta_idx]).all():
        return None
    return arr[:, kpay:kpay + klen], arr[:, vpay:vpay + vlen]


def _old_sync(f, pos, header_end, sync):
    if pos <= header_end:
        f.seek(header_end)
        return True
    f.seek(pos + 4)
    window = f.read(sf.SYNC_SIZE)
    if len(window) < sf.SYNC_SIZE:
        return False
    buf = bytearray(window)
    while bytes(buf) != sync:
        nxt = f.read(1)
        if not nxt:
            return False
        buf = buf[1:] + nxt
    return True


def blocks_form(path, start, end, stats):
    """The whole-split read as it was (fixed-width blocks only: the probe's
    file has no other kind)."""
    with open(path, "rb") as f:
        r = sf.Reader(f)
        hend, sync = r._header_end, r._sync
        if end <= hend or not _old_sync(f, start, hend, sync):
            return RecordBatch.empty()
        if start > hend and f.tell() - sf.SYNC_SIZE - 4 >= end:
            return RecordBatch.empty()
        keys, vals, reads = [], [], 0
        while True:
            pos = f.tell()
            raw = f.read(4)
            reads += 1
            if len(raw) < 4:
                break
            (length,) = struct.unpack(">I", raw)
            if length == 0xFFFFFFFF:
                reads += 1
                if f.read(sf.SYNC_SIZE) != sync:
                    raise IOError("corrupt file: bad sync marker")
                if pos >= end:
                    break
                continue
            body = f.read(length)
            reads += 1
            k, v = _old_parse_fixed_block(body)
            keys.append(k)
            vals.append(v)
        stats.update(reads=reads)
    k, v = np.concatenate(keys), np.concatenate(vals)
    n = k.shape[0]
    ko = (np.arange(n + 1, dtype=np.int64) * k.shape[1]).astype(np.int32)
    vo = (np.arange(n + 1, dtype=np.int64) * v.shape[1]).astype(np.int32)
    return RecordBatch(k.reshape(-1), ko, v.reshape(-1), vo)


def strided_form(path, start, end, stats):
    with open(path, "rb") as f:
        return sf.Reader(f).read_batch_range(start, end, stats)


FORMS = {"blocks": blocks_form, "strided": strided_form}


# ----------------------------------------------------------------- timing


def timed_read(form, path, start, end):
    stats = {}
    c0, t0 = time.thread_time(), time.perf_counter()
    batch = FORMS[form](path, start, end, stats)
    wall, cpu = time.perf_counter() - t0, time.thread_time() - c0
    # what the reader says it did: its read calls, and how it parsed
    stats["parsed_as"] = stats.pop("form", "blocks")
    return wall, cpu, stats, batch


def beside(form, path, start, end, siblings, repeats, spin=False):
    """``repeats`` timed reads on a thread of their own while ``siblings``
    threads read the same split the same way (or, with ``spin``, count in
    Python), without pause."""
    stop = threading.Event()

    def sibling():
        while not stop.is_set():
            if spin:
                sum(range(10_000))
            else:
                FORMS[form](path, start, end, {})

    out = []

    def measured():
        time.sleep(0.2 if siblings else 0)  # the siblings are under way
        for _ in range(repeats):
            wall, cpu, stats, batch = timed_read(form, path, start, end)
            out.append((wall, cpu, stats, batch.num_records))

    threads = [threading.Thread(target=sibling) for _ in range(siblings)]
    for t in threads:
        t.start()
    m = threading.Thread(target=measured)
    m.start()
    m.join()
    stop.set()
    for t in threads:
        t.join()
    return out


def drop_cache(path):
    fd = os.open(path, os.O_RDONLY)
    try:
        os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
    finally:
        os.close(fd)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_250_000)
    ap.add_argument("--siblings", default="0,2,7")
    ap.add_argument("--spinners", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--dir", default=".scratch/read_probe")
    a = ap.parse_args(argv)
    os.makedirs(a.dir, exist_ok=True)
    path = os.path.join(a.dir, "part-00000")
    rows = np.random.default_rng(3700000001).integers(
        0, 256, size=(a.rows, 100), dtype=np.uint8)
    with open(path, "wb") as f:
        w = sf.Writer(f)
        w.append_fixed_rows(rows, 10)
        w.close()
    del rows
    size = os.path.getsize(path)
    start = SPLIT if size > SPLIT * 1.1 else 0  # the last split, as cut
    say(host_cores=os.cpu_count(), python=sys.version.split()[0],
        switch_interval_s=sys.getswitchinterval(), file_bytes=size,
        split=[start, size])

    got = [timed_read(form, path, start, size)[3] for form in FORMS]
    say(what="same_batch", same=all(
        np.array_equal(getattr(got[0], c), getattr(got[1], c))
        for c in ("key_data", "key_offsets", "value_data",
                  "value_offsets")), records=got[0].num_records)
    del got

    for form in FORMS:
        drop_cache(path)
        wall, cpu, stats, _ = timed_read(form, path, start, size)
        say(what="read", form=form, siblings=0, cold=True,
            wall_s=round(wall, 4), cpu_s=round(cpu, 4), **stats)
    for siblings in (int(s) for s in a.siblings.split(",")):
        for form in FORMS:
            for rep, (wall, cpu, stats, n) in enumerate(beside(
                    form, path, start, size, siblings, a.repeats)):
                say(what="read", form=form, siblings=siblings, rep=rep,
                    wall_s=round(wall, 4), cpu_s=round(cpu, 4),
                    records=n, **stats)
    for form in FORMS if a.spinners else ():
        for rep, (wall, cpu, stats, n) in enumerate(beside(
                form, path, start, size, a.spinners, a.repeats, spin=True)):
            say(what="read", form=form, spinners=a.spinners, rep=rep,
                wall_s=round(wall, 4), cpu_s=round(cpu, 4), records=n,
                **stats)
    shutil.rmtree(a.dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
