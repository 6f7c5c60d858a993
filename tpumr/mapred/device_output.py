"""Device-resident OUTPUT chaining — dataflow stays in HBM across jobs.

Inputs already have the HBM split cache (tpu_runner.split_cache); this
module gives kernel OUTPUTS the same residency, so a chained pipeline
(matmul → consumer, round N → round N+1) consumes its predecessor's
output without the device→host→device round trip. Extends the
reference's device-binding role (pipes Application.java:162-181 pins a
binary to a device) into dataflow: what the previous kernel left on the
chip IS the next job's input.

Protocol (all host-side bookkeeping; the array never moves):

1. the TPU runner, after ``map_batch_launch``, asks the kernel for
   ``device_output_rows(state)`` — the device array whose host image the
   task's output FILE will contain — and ``offer``\\ s it under the
   attempt id (only when the job's output format claims device rows,
   so non-dense jobs can never strand HBM here);
2. the dense output writer, on close, writes the .npy part file from the
   fetched host rows, then ``claim``\\ s the device array and
   ``publish``\\ es it keyed by a CONTENT fingerprint of the written
   bytes (size + sha1 of head and tail windows) — path-independent, so
   the commit rename of part files cannot stale the key;
3. a later job staging a DenseSplit of that file computes the same
   fingerprint from an 8 KB read and, on hit, slices its row range from
   the resident array ON DEVICE — zero storage read, zero upload.

Entries live in the same per-device LRU byte budget as input splits
(``tpumr.tpu.split.cache.mb``): residency is an optimization, never a
correctness dependency — the file on storage remains the truth (the
reference's fault-tolerance stance: device state is reconstructible).
"""

from __future__ import annotations

import hashlib
import threading
from typing import Any

#: fingerprint window at each end of the file
_FP_WINDOW = 4096

_lock = threading.Lock()
#: attempt_id -> device rows awaiting the writer's claim (bounded: only
#: dense-output jobs offer, and a crashed writer's entry is evicted)
_pending: dict[str, Any] = {}
_PENDING_CAP = 16
#: flips once anything was ever published in this process: lookup()
#: returns instantly until then, so jobs that never chain pay zero
#: fingerprint reads on their cache misses
_published_any = False


def offer(attempt_id: str, rows: Any) -> None:
    with _lock:
        while len(_pending) >= _PENDING_CAP:
            _pending.pop(next(iter(_pending)))
        _pending[attempt_id] = rows


def claim(attempt_id: str) -> Any:
    with _lock:
        return _pending.pop(attempt_id, None)


def fingerprint(head: bytes, tail: bytes, size: int,
                mtime: float) -> str:
    """Cache-key identity of one written file: size + mtime + head/tail
    windows. mtime disambiguates re-runs whose output happens to share
    size and boundary bytes (rename preserves mtime, so commit
    promotion keeps the key valid); head/tail windows disambiguate
    same-mtime different content. The fingerprint only SELECTS the
    candidate — correctness comes from :func:`lookup` verifying the
    publisher's full-content sha1 on the first hit, so a boundary-alias
    file can never serve wrong data."""
    h = hashlib.sha1()
    h.update(str(size).encode())
    h.update(repr(mtime).encode())
    h.update(head)
    h.update(tail)
    return h.hexdigest()


def _cache(conf: Any, device: Any):
    from tpumr.mapred.tpu_runner import split_cache
    cache_mb = conf.get_int("tpumr.tpu.split.cache.mb", 2048)
    return split_cache(device, cache_mb * 1024 * 1024)


#: (path, size, mtime, fp) identities whose FULL content has been
#: verified against the published sha — later hits on the same on-disk
#: identity skip the verification read. _verify_locks serializes the
#: first hit per identity so parallel map tasks of one chained job
#: don't each hash the same multi-GB file.
_verified: set = set()
_verify_locks: dict = {}


def publish(conf: Any, rows: Any, file_bytes_head: bytes,
            file_bytes_tail: bytes, size: int, mtime: float,
            full_sha: "str | None" = None) -> None:
    """Register a device row-matrix as resident image of a just-written
    file (writer side — fingerprint from the in-memory bytes + the
    written file's stat mtime, which the commit rename preserves).
    ``full_sha`` is the sha1 of the COMPLETE file bytes — the writer
    holds them all — so the consumer's first hit can verify the match
    beyond the boundary windows."""
    global _published_any
    try:
        devs = list(rows.devices())
    except Exception:  # noqa: BLE001 — host array slipped through
        return
    key = ("devout", fingerprint(file_bytes_head, file_bytes_tail, size,
                                 mtime))
    _cache(conf, devs[0]).put(key, {"rows": rows, "sha": full_sha},
                              int(rows.nbytes))
    _published_any = True


def lookup(conf: Any, device: Any, fs: Any, path: str, size: int,
           mtime: float):
    """The whole-file resident array for ``path``, or None. Costs one
    8 KB read to fingerprint the file — and nothing at all until some
    job in this process has actually published an output. The FIRST hit
    per on-disk identity additionally reads the whole file and checks
    the publisher's full-content sha1: it costs one local sequential
    read where the read AND the upload are being skipped, and it closes the
    boundary-window aliasing hole (same size+mtime+8 KB edges, different
    middle) that probabilistic fingerprints leave open."""
    if not _published_any:
        return None
    if not conf.get_boolean("tpumr.tpu.output.cache", True):
        return None
    try:
        with fs.open(path) as f:
            head = f.read(_FP_WINDOW)
            if size > _FP_WINDOW:
                f.seek(max(_FP_WINDOW, size - _FP_WINDOW))
                tail = f.read(_FP_WINDOW)
            else:
                tail = b""
    except OSError:
        return None
    fp = fingerprint(head, tail, size, mtime)
    key = ("devout", fp)
    cache = _cache(conf, device)
    entry = cache.get(key)
    if entry is None:
        return None
    sha = entry.get("sha")
    ident = (path, size, mtime, fp)
    if sha is not None and ident not in _verified:
        with _lock:
            vlock = _verify_locks.setdefault(ident, threading.Lock())
        with vlock:
            if ident not in _verified:   # first arrival verifies; the
                try:                     # rest wait and reuse the result
                    h = hashlib.sha1()
                    with fs.open(path) as f:
                        while True:
                            chunk = f.read(1 << 20)
                            if not chunk:
                                break
                            h.update(chunk)
                except OSError:
                    return None
                if h.hexdigest() != sha:
                    return None          # alias: fall back to real read
                with _lock:
                    if len(_verified) > 4096:
                        _verified.clear()
                        _verify_locks.clear()
                    _verified.add(ident)
    return entry["rows"]


def head_tail(data: bytes) -> "tuple[bytes, bytes, int]":
    """The (head, tail, size) fingerprint inputs for in-memory bytes —
    MUST mirror :func:`lookup`'s read pattern exactly."""
    head = data[:_FP_WINDOW]
    tail = data[max(_FP_WINDOW, len(data) - _FP_WINDOW):] \
        if len(data) > _FP_WINDOW else b""
    return head, tail, len(data)
