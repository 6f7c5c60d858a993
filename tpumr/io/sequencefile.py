"""SequenceFile — the framework's key/value container format.

≈ ``org.apache.hadoop.io.SequenceFile`` (reference: src/core/org/apache/
hadoop/io/SequenceFile.java, 3256 LoC): a binary stream of key/value records
with a header, periodic 16-byte sync markers enabling split-at-any-offset
reads, and optional block compression. Differences from the reference,
deliberately: record-compressed mode is dropped (block mode dominates), and
keys/values are raw bytes produced by :mod:`tpumr.io.writable`'s typed codec
rather than class-name-bound Writables (the header carries codec metadata
instead of Java class names).
"""

from __future__ import annotations

import os
import struct
from io import BytesIO
from typing import Any, BinaryIO, Iterator

from tpumr.io.compress import get_codec
from tpumr.io.writable import (_vint_bytes, deserialize, read_vint, serialize,
                               write_vint)

MAGIC = b"TSEQ"
VERSION = 1
SYNC_SIZE = 16
SYNC_INTERVAL = 100 * SYNC_SIZE  # bytes between syncs ≈ SequenceFile.SYNC_INTERVAL
_SYNC_ESCAPE = 0xFFFFFFFF  # uint32 length sentinel preceding a sync marker
#: bytes of blocks a bulk append frames before it hands them to the stream:
#: small enough to stay in a core's cache between the copy in and the
#: write, large enough that a write is not a call a block (sized on the
#: chip's host: PERF.md section 6, PR 34)
_BULK_CHUNK_BYTES = 4 << 20


class _FixedFrame:
    """One record of a ``klen``-byte key and a ``vlen``-byte value as
    ``append(bytes, bytes)`` frames it: each field behind its serialized
    length, its tag and its payload length, which are constants of the
    file. Works on arrays of frames of any leading shape."""

    def __init__(self, klen: int, vlen: int) -> None:
        import numpy as np

        def prefix(length: int) -> "np.ndarray":
            ser = serialize(b"\x00" * length)
            tag = ser[:len(ser) - length]  # tag+vint, payload off
            return np.frombuffer(_vint_bytes(len(ser)) + tag, np.uint8)

        self._kf, self._vf = prefix(klen), prefix(vlen)
        self._klen = klen
        self._key_at = len(self._kf)
        self._value_at = self._key_at + klen + len(self._vf)
        self.size = self._value_at + vlen

    def laid_out(self, frames):
        """``frames`` ([..., size] uint8) with the constants written."""
        frames[..., :self._key_at] = self._kf
        frames[..., self._key_at + self._klen:self._value_at] = self._vf
        return frames

    def fill(self, frames, rows) -> None:
        """Each row's key and value copied into its frame."""
        frames[..., self._key_at:self._key_at + self._klen] = \
            rows[..., :self._klen]
        frames[..., self._value_at:] = rows[..., self._klen:]


class Writer:
    """Stream writer. ``block_size`` records are buffered then flushed as one
    (optionally compressed) block behind a sync marker."""

    def __init__(self, stream: BinaryIO, codec: str = "none",
                 metadata: dict[str, str] | None = None,
                 block_records: int = 1000) -> None:
        self._out = stream
        self._codec = get_codec(codec)
        self._block_records = max(1, block_records)
        self._sync = os.urandom(SYNC_SIZE)
        self._buf: list[tuple[bytes, bytes]] = []
        self._since_sync = 0
        meta = dict(metadata or {})
        meta["codec"] = self._codec.name
        header = BytesIO()
        header.write(MAGIC)
        header.write(bytes((VERSION,)))
        mb = serialize(meta)
        write_vint(header, len(mb))  # type: ignore[arg-type]
        header.write(mb)             # type: ignore[arg-type]
        header.write(self._sync)
        self._out.write(header.getvalue())

    def append(self, key: Any, value: Any) -> None:
        self.append_raw(serialize(key), serialize(value))  # type: ignore[arg-type]

    def append_raw(self, kbytes: bytes, vbytes: bytes) -> None:
        self._buf.append((kbytes, vbytes))
        if len(self._buf) >= self._block_records:
            self._flush_block()

    def _flush_block(self) -> None:
        if not self._buf:
            return
        body = BytesIO()
        write_vint(body, len(self._buf))
        for k, v in self._buf:
            write_vint(body, len(k))
            body.write(k)
            write_vint(body, len(v))
            body.write(v)
        self._emit_block(body.getvalue())
        self._buf.clear()

    def _emit_block(self, body: bytes) -> None:
        payload = self._codec.compress(body)
        if self._since_sync >= SYNC_INTERVAL:
            self._out.write(struct.pack(">I", _SYNC_ESCAPE))
            self._out.write(self._sync)
            self._since_sync = 0
        self._out.write(struct.pack(">I", len(payload)))
        self._out.write(payload)
        self._since_sync += len(payload) + 4

    def append_fixed_rows(self, rows, klen: int) -> None:
        """Vectorized bulk append of fixed-width raw records: ``rows`` is a
        ``[n, klen+vlen] uint8`` array whose first ``klen`` bytes per row
        are the key. Produces byte-identical framing to per-record
        ``append(bytes, bytes)`` calls (every serialized length is a
        per-file constant, so frames are a numpy tile job) — the write
        path of the device-shuffled reduce, where per-record Python append
        would dominate the whole job.

        A row is copied once: into its frame, in a buffer this call
        allocates once and hands the stream views of. Neither the copy
        nor the stream's ``write`` holds the interpreter, so several
        writers on threads of one process run beside each other."""
        import numpy as np
        n = int(rows.shape[0])
        if n == 0:
            return
        self._flush_block()  # keep scalar-appended records ordered first
        frame = _FixedFrame(klen, int(rows.shape[1]) - klen)
        per = self._block_records  # same block granularity as scalar appends
        # without a codec a full block is its frames behind constants; a
        # codec wants a block's bytes, and a partial last block has a
        # record count of its own: those go a block at a time
        done = n // per * per if self._codec.name == "none" else 0
        if done:
            self._append_full_blocks(rows[:done], frame)
        if done < n:
            frames = frame.laid_out(np.empty(
                (min(per, n - done), frame.size), np.uint8))
            for lo in range(done, n, per):
                block = frames[:min(per, n - lo)]
                frame.fill(block, rows[lo:lo + per])
                self._emit_block(_vint_bytes(block.shape[0])
                                 + block.tobytes())

    def _append_full_blocks(self, rows, frame: "_FixedFrame") -> None:
        """Uncompressed blocks of exactly ``block_records`` rows each, a
        chunk of blocks at a time. A block has a slot of fixed size in the
        chunk's buffer: sync escape and marker, length word and record
        count, laid once like the field prefixes inside the frames, then
        the frames. The escape and marker go to the stream only where
        ``_emit_block`` would have written them, so what is written is a
        slot from its start or from its length word, and neighbouring
        slots written whole leave in one ``write``."""
        import numpy as np
        per = self._block_records
        head = 4 + SYNC_SIZE           # the escape and the marker
        body = len(_vint_bytes(per)) + per * frame.size
        before = struct.pack(">I", _SYNC_ESCAPE) + self._sync \
            + struct.pack(">I", body) + _vint_bytes(per)
        slot = head + 4 + body
        blocks = rows.shape[0] // per
        at_once = max(1, min(blocks, _BULK_CHUNK_BYTES // slot))
        buf = np.empty(at_once * slot, np.uint8)
        buf.reshape(at_once, slot)[:, :len(before)] = np.frombuffer(
            before, np.uint8)
        frames = frame.laid_out(np.ndarray(
            (at_once, per, frame.size), np.uint8, buf,
            offset=len(before), strides=(slot, frame.size, 1)))
        view = memoryview(buf)
        for lo in range(0, blocks, at_once):
            nb = min(at_once, blocks - lo)
            frame.fill(frames[:nb], rows[lo * per:(lo + nb) * per]
                       .reshape(nb, per, rows.shape[1]))
            start = 0
            for b in range(nb):
                synced = self._since_sync >= SYNC_INTERVAL
                if not synced:
                    # this block starts at its length word: what is laid
                    # out before it leaves first
                    if b:
                        self._out.write(view[start:b * slot])
                    start = b * slot + head
                self._since_sync = (0 if synced else self._since_sync) \
                    + body + 4
            self._out.write(view[start:nb * slot])

    def sync_now(self) -> None:
        self.sync_pos()

    def sync_pos(self) -> int:
        """Flush pending records, emit a sync marker, and return the escape
        offset — a position where ``Reader.sync(pos)`` lands exactly (the
        seekable-entry contract MapFile indexes rely on)."""
        self._flush_block()
        pos = self._out.tell()
        self._out.write(struct.pack(">I", _SYNC_ESCAPE))
        self._out.write(self._sync)
        self._since_sync = 0
        return pos

    def close(self) -> None:
        """Flush pending records. The caller owns (and closes) the stream."""
        self._flush_block()
        self._out.flush()

    def __enter__(self) -> "Writer":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()


def _parse_fixed_block(body: bytes):
    """Vectorized block parse: when every record in the block shares the
    first record's exact frame bytes outside the two payloads — i.e.
    bytes-tagged keys and values of one constant width each, the terasort
    layout — the whole block is one ``[n, frame]`` reshape. Returns
    ``(keys [n, klen] u8, values [n, vlen] u8)`` or None (caller falls
    back to the per-record parser)."""
    import numpy as np

    from tpumr.io.writable import _TAG_BYTES, _vint_at
    try:
        n, rec0 = _vint_at(body, 0)
        if n <= 0:
            return None
        # first record, scalar: vint(len kser) ++ kser ++ vint(len vser)
        # ++ vser, where kser = tag ++ vint(klen) ++ key payload
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
    # every non-payload column must match record 0's bytes exactly (same
    # lengths, same tags) — a cheap full proof that the reshape is valid
    kpay = kpay0 - rec0
    vhdr = kpay + klen
    vpay = vpay0 - rec0
    meta_idx = np.concatenate([np.arange(0, kpay),
                               np.arange(vhdr, vpay)])
    if n > 1 and not (arr[1:, meta_idx] == arr[0, meta_idx]).all():
        return None
    return arr[:, kpay:kpay + klen], arr[:, vpay:vpay + vlen]


class Reader:
    """Stream reader; supports ``sync(pos)`` — skip forward to the first sync
    marker at/after ``pos`` then read whole blocks — which is what makes a
    SequenceFile splittable at arbitrary byte offsets (the InputFormat
    contract, ≈ SequenceFile.Reader.sync)."""

    def __init__(self, stream: BinaryIO) -> None:
        self._in = stream
        if self._in.read(len(MAGIC)) != MAGIC:
            raise ValueError("not a tpumr SequenceFile (bad magic)")
        version = self._in.read(1)[0]
        if version != VERSION:
            raise ValueError(f"unsupported SequenceFile version {version}")
        mlen = read_vint(self._in)
        self.metadata: dict[str, str] = deserialize(self._in.read(mlen))
        self._codec = get_codec(self.metadata.get("codec", "none"))
        self._sync = self._in.read(SYNC_SIZE)
        self._header_end = self._in.tell()

    def __iter__(self) -> Iterator[tuple[Any, Any]]:
        for k, v in self.iter_raw():
            yield deserialize(k), deserialize(v)

    def _position_for_range(self, start: int, end: int) -> bool:
        """Position the stream at the first block of split [start, end);
        False when the split owns nothing. The ownership rule shared by
        the per-record and batch readers (every record is read by exactly
        one of a set of covering splits, ≈ SequenceFileRecordReader)."""
        if end <= self._header_end:
            # the header's trailing sync marker is the file's first boundary:
            # a split ending at/inside the header owns nothing (its successor
            # starting there syncs to header_end and owns the first block)
            return False
        if not self.sync(start):
            return False
        if start > self._header_end:
            # boundary = position of the 4-byte escape preceding the marker we
            # landed on; if it is already past `end` this split owns nothing
            boundary = self._in.tell() - SYNC_SIZE - 4
            if boundary >= end:
                return False
        return True

    def iter_range(self, start: int, end: int) -> Iterator[tuple[Any, Any]]:
        """Records of the split [start, end): from the first sync at/after
        ``start`` up to the first sync at/after ``end``."""
        if not self._position_for_range(start, end):
            return
        for k, v in self.iter_raw(end=end):
            yield deserialize(k), deserialize(v)

    def iter_block_bodies(self, end: int | None = None) -> Iterator[bytes]:
        """Decompressed block bodies from the current position; stops at
        the first sync at/after ``end`` (iter_raw's end-side rule)."""
        while True:
            pos = self._in.tell()
            raw = self._in.read(4)
            if len(raw) < 4:
                return
            (length,) = struct.unpack(">I", raw)
            if length == _SYNC_ESCAPE:
                marker = self._in.read(SYNC_SIZE)
                if marker != self._sync:
                    raise IOError("corrupt file: bad sync marker")
                if end is not None and pos >= end:
                    return
                continue
            payload = self._in.read(length)
            if len(payload) < length:
                raise EOFError("truncated block")
            yield self._codec.decompress(payload)

    def iter_raw(self, end: int | None = None) -> Iterator[tuple[bytes, bytes]]:
        for body in self.iter_block_bodies(end):
            block = BytesIO(body)
            n = read_vint(block)
            for _ in range(n):
                klen = read_vint(block)
                k = block.read(klen)
                vlen = read_vint(block)
                v = block.read(vlen)
                yield k, v

    def read_batch_range(self, start: int, end: int):
        """Records of the split [start, end) as one
        :class:`~tpumr.io.recordbatch.RecordBatch` — the whole-split read
        for kernel jobs. Blocks whose serialized records all share the
        first record's byte-level frame (fixed-width bytes keys/values —
        the terasort layout) parse as ONE numpy reshape; anything else
        falls back to the per-record path with the same
        bytes/str/serialize value semantics as the reader-drain staging
        path (tpu_runner.stage_batch)."""
        import numpy as np

        from tpumr.io.recordbatch import RecordBatch
        from tpumr.io.writable import serialize

        if not self._position_for_range(start, end):
            return RecordBatch.empty()

        key_chunks: list[np.ndarray] = []   # [n, klen] u8 per fast block
        val_chunks: list[np.ndarray] = []
        slow: list[tuple[bytes, bytes]] = []  # (key, value) payloads

        for body in self.iter_block_bodies(end):
            if body[:1] == b"\x00":  # vint 0: empty block, nothing to parse
                continue
            if not slow:
                parsed = _parse_fixed_block(body)
                if parsed is not None and key_chunks and (
                        parsed[0].shape[1] != key_chunks[0].shape[1]
                        or parsed[1].shape[1] != val_chunks[0].shape[1]):
                    parsed = None  # widths changed across blocks: go slow
                if parsed is not None:
                    key_chunks.append(parsed[0])
                    val_chunks.append(parsed[1])
                    continue
                # first ragged block: demote prior fast chunks to the slow
                # list so record order is preserved (and stay slow — a
                # mixed file is rare and order beats vectorization)
                for karr, varr in zip(key_chunks, val_chunks):
                    slow.extend((karr[i].tobytes(), varr[i].tobytes())
                                for i in range(karr.shape[0]))
                key_chunks, val_chunks = [], []
            block = BytesIO(body)
            n = read_vint(block)
            for _ in range(n):
                klen = read_vint(block)
                k = deserialize(block.read(klen))
                vlen = read_vint(block)
                v = deserialize(block.read(vlen))
                k = k if isinstance(k, (bytes, bytearray)) else (
                    k.encode("utf-8") if isinstance(k, str) else serialize(k))
                v = v if isinstance(v, (bytes, bytearray)) else (
                    v.encode("utf-8") if isinstance(v, str) else serialize(v))
                slow.append((bytes(k), bytes(v)))

        if slow:
            return RecordBatch.from_pairs(slow)
        if not key_chunks:
            return RecordBatch.empty()
        keys = np.concatenate(key_chunks)
        vals = np.concatenate(val_chunks)
        n = keys.shape[0]
        ko = (np.arange(n + 1, dtype=np.int64) * keys.shape[1]).astype(np.int32)
        vo = (np.arange(n + 1, dtype=np.int64) * vals.shape[1]).astype(np.int32)
        return RecordBatch(keys.reshape(-1), ko, vals.reshape(-1), vo)

    def sync(self, pos: int) -> bool:
        """Position the reader at the first sync marker at/after byte ``pos``.
        Returns False if no further sync exists (reader is at EOF)."""
        if pos <= self._header_end:
            self._in.seek(self._header_end)
            return True
        # Boundary identity is the 4-byte escape position: a marker "belongs"
        # to pos iff its escape starts at >= pos, i.e. the marker pattern
        # itself starts at >= pos+4. Scanning from pos+4 keeps this side
        # consistent with iter_raw's end-side rule (escape pos >= end), so
        # adjacent splits never double-own the 4-byte escape window.
        self._in.seek(pos + 4)
        # scan for the 16-byte marker
        window = self._in.read(SYNC_SIZE)
        if len(window) < SYNC_SIZE:
            return False
        buf = bytearray(window)
        while bytes(buf) != self._sync:
            nxt = self._in.read(1)
            if not nxt:
                return False
            buf = buf[1:] + nxt
        return True

    def tell(self) -> int:
        return self._in.tell()

    def close(self) -> None:
        """No-op: the caller owns (and closes) the stream."""

    def __enter__(self) -> "Reader":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()
