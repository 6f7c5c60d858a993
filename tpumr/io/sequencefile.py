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
#: bytes a whole-split read asks its stream for in one call: a 64 MB split
#: is a handful of calls, not four a block (each call gives the
#: interpreter away and, beside busy threads, waits to win it back)
_READ_CHUNK_BYTES = 16 << 20
#: room a split's buffer keeps past the split's end for the rest of its
#: last block and the sync that ends it; a longer block grows the buffer
_TAIL_BYTES = 256 << 10
#: bytes ``Reader.sync`` reads a call while it looks for the next marker
_SCAN_BYTES = 128 << 10


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


def _fixed_run(buf, at: int, length: int, count: int, stride: int):
    """Vectorized parse of ``count`` block bodies of ``length`` bytes each,
    the first at ``buf[at]`` and the rest ``stride`` bytes apart (``buf``
    a 1-D uint8 array): when every record of them shares the first
    record's exact frame bytes outside the two payloads — i.e.
    bytes-tagged keys and values of one constant width each, the terasort
    layout — and every block the first one's record count, the run is one
    ``[count, n, frame]`` strided view of ``buf``. Returns the views
    ``(keys [count, n, klen], values [count, n, vlen])`` or None (the
    caller falls back to the per-record parser)."""
    import numpy as np

    from tpumr.io.writable import _TAG_BYTES, _vint_at
    body = memoryview(buf)
    try:
        n, rec0 = _vint_at(body, at)
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
    head = rec0 - at
    frame = vpay0 + vlen - rec0
    if length - head != n * frame:
        return None
    heads = np.ndarray((count, head), np.uint8, buf, at, (stride, 1))
    frames = np.ndarray((count, n, frame), np.uint8, buf, rec0,
                        (stride, frame, 1))
    # every block's record count and every record's non-payload columns
    # must match the first record's bytes exactly (same lengths, same
    # tags) — a cheap full proof that the view is valid
    kpay = kpay0 - rec0
    vhdr = kpay + klen
    vpay = vpay0 - rec0
    if not ((heads == heads[0]).all()
            and (frames[..., :kpay] == frames[0, 0, :kpay]).all()
            and (frames[..., vhdr:vpay] == frames[0, 0, vhdr:vpay]).all()):
        return None
    return frames[..., kpay:vhdr], frames[..., vpay:]


def _parse_fixed_block(body: bytes):
    """One block body as :func:`_fixed_run` parses it: ``(keys [n, klen]
    u8, values [n, vlen] u8)`` or None."""
    import numpy as np
    run = _fixed_run(np.frombuffer(body, np.uint8), 0, len(body), 1, 0)
    return None if run is None else (run[0][0], run[1][0])


def _fixed_batch(keys: list, values: list):
    """One RecordBatch of fixed-width records from parsed runs (views of
    shape ``[..., klen]`` and ``[..., vlen]``, every run of one width):
    each run copied once into the batch's two contiguous columns."""
    import numpy as np

    from tpumr.io.recordbatch import RecordBatch
    klen, vlen = keys[0].shape[-1], values[0].shape[-1]
    sizes = [int(np.prod(k.shape[:-1])) for k in keys]
    n = sum(sizes)
    kd = np.empty((n, klen), np.uint8)
    vd = np.empty((n, vlen), np.uint8)
    at = 0
    for k, v, m in zip(keys, values, sizes):
        kd[at:at + m].reshape(k.shape)[...] = k
        vd[at:at + m].reshape(v.shape)[...] = v
        at += m
    # one numpy call a column: each call on a large array gives the
    # interpreter away, and beside busy threads waits to win it back
    ko = np.arange(n + 1, dtype=np.int32)
    ko *= klen
    vo = np.arange(n + 1, dtype=np.int32)
    vo *= vlen
    return RecordBatch(kd.reshape(-1), ko, vd.reshape(-1), vo)


def _strided_batch(buf, blocks: "list[tuple[int, int]]"):
    """A split's uncompressed blocks (``(offset, length)`` of each body in
    ``buf``) parsed a run at a time: neighbouring blocks of one length at
    one stride are one :func:`_fixed_run`, so a split of full blocks
    behind sync markers is one strided view, and a last partial block or
    any block that breaks the stride is a run of its own. Returns the
    RecordBatch, or None where a run is not fixed-width or the widths
    change between runs (the caller parses the blocks one at a time)."""
    keys: list = []
    values: list = []
    i = 0
    while i < len(blocks):
        at, length = blocks[i]
        j, stride = i + 1, 0
        if j < len(blocks) and blocks[j][1] == length:
            stride = blocks[j][0] - at
            while (j < len(blocks) and blocks[j][1] == length
                   and blocks[j][0] - blocks[j - 1][0] == stride):
                j += 1
        run = _fixed_run(buf, at, length, j - i, stride)
        if run is None or (keys and (
                run[0].shape[-1] != keys[0].shape[-1]
                or run[1].shape[-1] != values[0].shape[-1])):
            return None
        keys.append(run[0])
        values.append(run[1])
        i = j
    return _fixed_batch(keys, values)


def _batch_of_bodies(bodies: "Iterator[bytes]"):
    """Decompressed block bodies as one RecordBatch, a block at a time:
    blocks whose records all share the first record's frame parse as ONE
    numpy reshape each; anything else falls back to the per-record path
    with the same bytes/str/serialize value semantics as the reader-drain
    staging path (tpu_runner.stage_batch)."""
    from tpumr.io.recordbatch import RecordBatch

    key_chunks: list = []   # [n, klen] u8 per fast block
    val_chunks: list = []
    slow: list[tuple[bytes, bytes]] = []  # (key, value) payloads

    for body in bodies:
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
    return _fixed_batch(key_chunks, val_chunks)


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
        #: read calls this reader has made on its stream past the header,
        #: and the bytes they brought
        self.reads = 0
        self.bytes_read = 0

    def _read(self, n: int) -> bytes:
        got = self._in.read(n)
        self.reads += 1
        self.bytes_read += len(got)
        return got

    def _readinto(self, view: memoryview) -> int:
        n = self._in.readinto(view) or 0
        self.reads += 1
        self.bytes_read += n
        return n

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
            raw = self._read(4)
            if len(raw) < 4:
                return
            (length,) = struct.unpack(">I", raw)
            if length == _SYNC_ESCAPE:
                marker = self._read(SYNC_SIZE)
                if marker != self._sync:
                    raise IOError("corrupt file: bad sync marker")
                if end is not None and pos >= end:
                    return
                continue
            payload = self._read(length)
            if len(payload) < length:
                raise EOFError("truncated block")
            yield self._codec.decompress(payload)

    def _read_owned_blocks(self, end: int):
        """The blocks of a split from the current position (its first
        block) up to the first sync at/after ``end``: one buffer filled by
        a few large reads, then walked with no I/O. Returns ``(buf,
        [(offset, length) of each block body in buf])``; the walk, its
        end-side rule and its errors are :meth:`iter_block_bodies`'."""
        import numpy as np
        base = self._in.tell()
        buf = np.empty(max(0, end - base) + _TAIL_BYTES, np.uint8)
        filled = 0

        def fill(upto: int) -> bool:
            """Read on until ``buf[:upto]`` holds the file's bytes; False
            where the file ends first."""
            nonlocal buf, filled
            if upto > buf.shape[0]:  # a last block longer than the room
                grown = np.empty(upto + _TAIL_BYTES, np.uint8)
                grown[:filled] = buf[:filled]
                buf = grown
            while filled < upto:
                got = self._readinto(memoryview(buf)[
                    filled:min(buf.shape[0], filled + _READ_CHUNK_BYTES)])
                if not got:
                    return False
                filled += got
            return True

        blocks: list[tuple[int, int]] = []
        at = 0
        while at + 4 <= filled or fill(at + 4):
            (length,) = struct.unpack_from(">I", buf, at)
            if length == _SYNC_ESCAPE:
                marker = at + 4
                if not (marker + SYNC_SIZE <= filled
                        or fill(marker + SYNC_SIZE)) or \
                        buf[marker:marker + SYNC_SIZE].tobytes() != self._sync:
                    raise IOError("corrupt file: bad sync marker")
                if base + at >= end:
                    break
                at = marker + SYNC_SIZE
                continue
            if not (at + 4 + length <= filled or fill(at + 4 + length)):
                raise EOFError("truncated block")
            blocks.append((at + 4, length))
            at += 4 + length
        return buf, blocks

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

    def read_batch_range(self, start: int, end: int,
                         stats: "dict | None" = None):
        """Records of the split [start, end) as one
        :class:`~tpumr.io.recordbatch.RecordBatch` — the whole-split read
        for kernel jobs and vectorized maps. The split's blocks are read
        into one buffer in a few calls. Where the file shows uncompressed
        blocks of fixed-width bytes keys and values (the terasort layout),
        every block parses at once as one strided view (``strided``);
        anything else — a codec, ragged records, widths that change
        between blocks, an empty block — parses a block at a time
        (``blocks``) with the same records. ``stats``, where given, gets
        the split's ``bytes`` and ``reads`` (read calls) and that
        ``form``."""
        reads, nbytes = self.reads, self.bytes_read
        buf, blocks = (self._read_owned_blocks(end)
                       if self._position_for_range(start, end)
                       else (None, []))
        batch = _strided_batch(buf, blocks) \
            if blocks and self._codec.name == "none" else None
        form = "blocks" if batch is None else "strided"
        if batch is None:
            batch = _batch_of_bodies(
                self._codec.decompress(buf[at:at + length].tobytes())
                for at, length in blocks)
        if stats is not None:
            stats.update(bytes=self.bytes_read - nbytes,
                         reads=self.reads - reads, form=form)
        return batch

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
        at = pos + 4
        self._in.seek(at)
        # scan for the 16-byte marker a chunk at a time, keeping the last
        # SYNC_SIZE - 1 bytes of a chunk for a marker cut between two
        carry = b""
        while True:
            chunk = self._read(_SCAN_BYTES)
            if not chunk:
                return False
            window = carry + chunk
            found = window.find(self._sync)
            if found >= 0:
                self._in.seek(at - len(carry) + found + SYNC_SIZE)
                return True
            at += len(chunk)
            carry = window[1 - SYNC_SIZE:]

    def tell(self) -> int:
        return self._in.tell()

    def close(self) -> None:
        """No-op: the caller owns (and closes) the stream."""

    def __enter__(self) -> "Reader":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()
