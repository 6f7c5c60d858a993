"""Input formats: split computation + record readers.

≈ ``org.apache.hadoop.mapred.{InputFormat,FileInputFormat,TextInputFormat,
SequenceFileInputFormat}`` and ``mapred/lib/{NLineInputFormat,
CombineFileInputFormat}``. Split sizing follows FileInputFormat.getSplits
(reference: src/mapred/org/apache/hadoop/mapred/FileInputFormat.java):
``split_size = max(min_size, min(goal_size, block_size))``, with block
locality hints from FileSystem.get_block_locations feeding the scheduler's
locality caches.

New for TPU: :class:`DenseInputFormat` — dense numeric datasets split by row
range (DenseSplit); its splits are what the TPU map runner stages into HBM
whole. The reference's GPU config achieved kernel-sized batches by pinning
NLineInputFormat to 1 line per map (conf/mapred-site.xml:14-21); DenseSplit
makes the batch a first-class unit instead.
"""

from __future__ import annotations

from io import BytesIO
from typing import Any, Iterator

import numpy as np

from tpumr.fs.filesystem import FileStatus, FileSystem, Path
from tpumr.io import sequencefile
from tpumr.mapred.split import DenseSplit, FileSplit, InputSplit


class InputFormat:
    def get_splits(self, conf: Any, num_splits: int) -> list[InputSplit]:
        raise NotImplementedError

    def get_record_reader(self, split: InputSplit, conf: Any,
                          reporter: Any = None) -> Iterator[tuple[Any, Any]]:
        raise NotImplementedError


def _hidden(name: str) -> bool:
    return name.startswith("_") or name.startswith(".")


class FileInputFormat(InputFormat):
    """Base: input path listing + block-aligned split computation."""

    splittable = True

    def list_input_files(self, conf: Any) -> list[tuple[FileSystem, FileStatus]]:
        out: list[tuple[FileSystem, FileStatus]] = []
        for p in conf.get_strings("mapred.input.dir"):
            fs = FileSystem.get(p, conf)
            if any(c in p for c in "*?["):
                stats = fs.glob_status(p)
            elif fs.exists(p):
                st = fs.get_status(p)
                stats = fs.list_status(p) if st.is_dir else [st]
            else:
                raise FileNotFoundError(f"input path does not exist: {p}")
            for st in stats:
                if st.is_dir:
                    for sub in fs.list_files(st.path, recursive=True):
                        if not _hidden(sub.path.name):
                            out.append((fs, sub))
                elif not _hidden(st.path.name):
                    out.append((fs, st))
        return out

    def get_splits(self, conf: Any, num_splits: int) -> list[InputSplit]:
        files = self.list_input_files(conf)
        total = sum(st.length for _, st in files)
        goal = max(1, total // max(1, num_splits))
        min_size = conf.get_int("mapred.min.split.size", 1)
        max_size = conf.get_int("mapred.max.split.size", 2**63 - 1)
        splits: list[InputSplit] = []
        for fs, st in files:
            if st.length == 0:
                continue
            if not self.splittable:
                hosts = _hosts(fs, st, 0, st.length)
                splits.append(FileSplit(hosts, str(st.path), 0, st.length))
                continue
            split_size = max(min_size, min(goal, st.block_size, max_size))
            pos = 0
            remaining = st.length
            # FileInputFormat's SPLIT_SLOP: tail smaller than 1.1×split rides
            # along with the last split
            while remaining / split_size > 1.1:
                hosts = _hosts(fs, st, pos, split_size)
                splits.append(FileSplit(hosts, str(st.path), pos, split_size))
                pos += split_size
                remaining -= split_size
            if remaining:
                hosts = _hosts(fs, st, pos, remaining)
                splits.append(FileSplit(hosts, str(st.path), pos, remaining))
        return splits


def _hosts(fs: FileSystem, st: FileStatus, offset: int, length: int) -> list[str]:
    locs = fs.get_block_locations(st.path, offset, length)
    hosts: list[str] = []
    for loc in locs:
        for h in loc.hosts:
            if h not in hosts:
                hosts.append(h)
    return hosts


class LineRecordReader:
    """≈ org.apache.hadoop.mapred.LineRecordReader: a split [start, start+len)
    owns every line that *begins* strictly after start (or at 0), reading past
    the end to finish its final line."""

    def __init__(self, fs: FileSystem, path: str, start: int, length: int,
                 keep_bytes: bool = False) -> None:
        self._f = fs.open(path)
        self._end = start + length
        self._keep_bytes = keep_bytes
        self._pos = start
        self._f.seek(start)
        if start > 0:
            # skip the partial line owned by the previous split
            self._pos += len(self._f.readline())

    def __iter__(self) -> Iterator[tuple[int, Any]]:
        # a line whose first byte sits at pos <= end belongs to this split
        # (the next split discards it as its leading partial line) — the
        # LineRecordReader ownership rule that makes coverage exact
        while self._pos <= self._end:
            line = self._f.readline()
            if not line:
                break
            offset = self._pos
            self._pos += len(line)
            stripped = line.rstrip(b"\r\n")
            yield offset, (stripped if self._keep_bytes
                           else stripped.decode("utf-8", errors="replace"))
        self._f.close()


class TextInputFormat(FileInputFormat):
    """≈ org.apache.hadoop.mapred.TextInputFormat: (byte offset, line)."""

    keep_bytes = False

    def get_record_reader(self, split, conf, reporter=None):
        assert isinstance(split, FileSplit)
        fs = FileSystem.get(split.path, conf)
        return iter(LineRecordReader(fs, split.path, split.start,
                                     split.split_length, self.keep_bytes))

    @staticmethod
    def _read_owned_bytes(split, conf, stats: "dict | None" = None
                          ) -> bytes:
        """The split's OWNED byte range under the LineRecordReader
        ownership rule — the subtlest invariant of text splitting, so it
        lives exactly once: skip the partial first line when start > 0,
        own every line beginning at pos <= end (reading past end to
        finish it; a line starting exactly AT end is owned too — the
        next split discards it as its leading partial)."""
        fs = FileSystem.get(split.path, conf)
        with fs.open(split.path) as f:
            f.seek(split.start)
            buf = f.read(split.split_length)
            if split.start > 0:
                nl = buf.find(b"\n")
                if nl < 0:
                    return b""                  # mid-line: owns nothing
                buf = buf[nl + 1:]
            buf += f.readline()
        if stats is not None:   # summed over a combined split's parts
            stats["bytes"] = stats.get("bytes", 0) + len(buf)
        return buf

    def read_batch(self, split, conf, stats=None):
        """Whole-split vectorized read for kernel jobs: ONE file read +
        C-speed newline scan instead of 100k+ Python ``readline`` calls.
        Ownership matches :class:`LineRecordReader` exactly (see
        :meth:`_read_owned_bytes`); trailing ``\\r``/``\\n`` stripped
        per line. ``stats``, where given, gets the owned ``bytes``."""
        from tpumr.io.recordbatch import RecordBatch
        assert isinstance(split, FileSplit)
        buf = self._read_owned_bytes(split, conf, stats)
        if not buf:
            return RecordBatch.empty()
        arr = np.frombuffer(buf, dtype=np.uint8)
        nls = np.flatnonzero(arr == 0x0A).astype(np.int64)
        # line spans [start, end): starts = 0 and nl+1; a trailing chunk
        # with no final newline is still a line (EOF case)
        starts = np.concatenate(([0], nls + 1))
        ends = np.concatenate((nls, [arr.shape[0]]))
        if starts[-1] >= arr.shape[0] and len(starts) > 1:
            starts, ends = starts[:-1], ends[:-1]  # buf ended with \n
        # rstrip(b"\r\n"): drop newlines and any trailing CRs per line
        mask = arr != 0x0A
        while True:
            has_cr = (ends > starts) & (arr[np.maximum(ends - 1, 0)] == 0x0D)
            if not has_cr.any():
                break
            ends = ends - has_cr
            mask[ends[has_cr]] = False
        value_data = arr[mask]
        lengths = ends - starts
        offsets = np.zeros(len(lengths) + 1, np.int32)
        np.cumsum(lengths, out=offsets[1:])
        n = len(lengths)
        batch = RecordBatch(np.zeros(0, np.uint8), np.zeros(n + 1, np.int32),
                            value_data, offsets)
        if not self.keep_bytes and (value_data > 0x7F).any():
            # reader parity: TextInputFormat values pass through
            # decode('utf-8', 'replace') — identical to raw bytes for
            # valid UTF-8 (checked strictly with \n separators so a line
            # ending mid-sequence can't be masked by its successor), so
            # only genuinely invalid input pays the per-line fallback
            try:
                batch.joined_values(0x0A).decode("utf-8")
            except UnicodeDecodeError:
                return RecordBatch.from_values(
                    batch.value(i).decode("utf-8", "replace").encode()
                    for i in range(n))
        return batch


class RawTextInputFormat(TextInputFormat):
    """Whole-split text as ONE record: the boundary-corrected buffer
    (same ownership rule as TextInputFormat — skip the leading partial
    line, finish the trailing one) without any line parsing. For
    whitespace-tokenizing kernels (wordcount) newlines are just another
    separator, so per-line machinery is pure overhead — this format
    removes it (measured: the line scan + join cost more than the
    native tokenizer itself). MAP_INPUT_RECORDS counts splits, not
    lines — documented divergence."""

    keep_bytes = True

    def read_batch(self, split, conf, stats=None):
        from tpumr.io.recordbatch import RecordBatch
        assert isinstance(split, FileSplit)
        buf = self._read_owned_bytes(split, conf, stats)
        if not buf:
            return RecordBatch.empty()
        # zero-copy: the batch's value_data is a view over buf
        return RecordBatch(np.zeros(0, np.uint8),
                           np.zeros(2, np.int32),
                           np.frombuffer(buf, dtype=np.uint8),
                           np.array([0, len(buf)], dtype=np.int32))


class BytesTextInputFormat(TextInputFormat):
    """Like TextInputFormat but values stay raw bytes (terasort rows)."""
    keep_bytes = True


class KeyValueTextInputFormat(TextInputFormat):
    """≈ mapred/KeyValueTextInputFormat.java: each line splits at the
    first separator byte (``key.value.separator.in.input.line``, default
    TAB) into (key, value); a line with no separator becomes (line, "")."""

    # values here are the part AFTER the separator — the whole-line batch
    # fast path would hand kernels the wrong bytes
    read_batch = None

    def get_record_reader(self, split, conf, reporter=None):
        # FIRST BYTE of the configured separator, as the reference does
        # (KeyValueLineRecordReader takes separator.charAt(0)); an empty
        # config value falls back to TAB instead of crashing the task
        sep = (str(conf.get("key.value.separator.in.input.line", "\t"))
               or "\t")[:1]
        for _offset, line in super().get_record_reader(split, conf,
                                                       reporter):
            k, _, v = line.partition(sep)
            yield k, v


class NLineInputFormat(FileInputFormat):
    """≈ mapred/lib/NLineInputFormat.java: one split per N lines — the knob
    the reference's GPU config used to make one map = one kernel launch
    (conf/mapred-site.xml:14-21, mapreduce.job.maps via N=1)."""

    def get_splits(self, conf, num_splits):
        n = conf.get_int("mapred.line.input.format.linespermap", 1)
        splits: list[InputSplit] = []
        for fs, st in self.list_input_files(conf):
            with fs.open(st.path) as f:
                pos = 0
                count = 0
                begin = 0
                for line in f:
                    count += 1
                    pos += len(line)
                    if count == n:
                        splits.append(FileSplit(_hosts(fs, st, begin, pos - begin),
                                                str(st.path), begin, pos - begin))
                        begin = pos
                        count = 0
                if count:
                    splits.append(FileSplit(_hosts(fs, st, begin, pos - begin),
                                            str(st.path), begin, pos - begin))
        return splits

    def get_record_reader(self, split, conf, reporter=None):
        assert isinstance(split, FileSplit)
        fs = FileSystem.get(split.path, conf)
        # NLine splits are exact line ranges: read [start, end) verbatim
        f = fs.open(split.path)
        f.seek(split.start)

        def gen():
            pos = split.start
            end = split.start + split.split_length
            while pos < end:
                line = f.readline()
                if not line:
                    break
                offset = pos
                pos += len(line)
                yield offset, line.rstrip(b"\r\n").decode("utf-8", errors="replace")
            f.close()

        return gen()


class SequenceFileInputFormat(FileInputFormat):
    """≈ org.apache.hadoop.mapred.SequenceFileInputFormat: typed k/v records,
    sync-aligned split reads."""

    def get_record_reader(self, split, conf, reporter=None):
        assert isinstance(split, FileSplit)
        fs = FileSystem.get(split.path, conf)
        f = fs.open(split.path)
        reader = sequencefile.Reader(f)

        def gen():
            try:
                yield from reader.iter_range(split.start,
                                            split.start + split.split_length)
            finally:
                f.close()

        return gen()

    def read_batch(self, split, conf, stats=None):
        """Whole-split read for kernel jobs and vectorized maps —
        fixed-width bytes records (terasort's 10+90 layout) parse as one
        strided view of the split's blocks; anything else falls back to
        the per-record parser with reader-equivalent value bytes
        (sequencefile.Reader.read_batch_range, which fills ``stats``)."""
        assert isinstance(split, FileSplit)
        fs = FileSystem.get(split.path, conf)
        with fs.open(split.path) as f:
            return sequencefile.Reader(f).read_batch_range(
                split.start, split.start + split.split_length, stats)


class WholeFileInputFormat(FileInputFormat):
    """One record per file: (path, bytes). Not splittable."""

    splittable = False

    def get_record_reader(self, split, conf, reporter=None):
        assert isinstance(split, FileSplit)
        fs = FileSystem.get(split.path, conf)
        return iter([(split.path, fs.read_bytes(split.path))])


class CombineFileInputFormat(FileInputFormat):
    """≈ mapred/lib/CombineFileInputFormat.java (simplified): packs many
    small whole files into few splits, bounded by mapred.max.split.size."""

    def get_splits(self, conf, num_splits):
        files = self.list_input_files(conf)
        total = sum(st.length for _, st in files)
        target = conf.get_int("mapred.max.split.size", 2**63 - 1)
        if target in (0, 2**63 - 1):
            target = max(1, total // max(1, num_splits))
        splits: list[InputSplit] = []
        cur: list[FileSplit] = []
        cur_bytes = 0
        for fs, st in files:
            cur.append(FileSplit(_hosts(fs, st, 0, st.length), str(st.path),
                                 0, st.length))
            cur_bytes += st.length
            if cur_bytes >= target:
                splits.append(MultiFileSplit(sum((s.locations for s in cur), []),
                                             parts=[(s.path, s.start, s.split_length)
                                                    for s in cur]))
                cur, cur_bytes = [], 0
        if cur:
            splits.append(MultiFileSplit(sum((s.locations for s in cur), []),
                                         parts=[(s.path, s.start, s.split_length)
                                                for s in cur]))
        return splits

    def get_record_reader(self, split, conf, reporter=None):
        assert isinstance(split, MultiFileSplit)

        def gen():
            for path, start, length in split.parts:
                fs = FileSystem.get(path, conf)
                yield from LineRecordReader(fs, path, start, length)

        return gen()

    def read_batch(self, split, conf, stats=None):
        """Kernel jobs over many small files: one vectorized text batch
        per part, concatenated — no per-line Python."""
        from tpumr.io.recordbatch import RecordBatch
        assert isinstance(split, MultiFileSplit)
        text = TextInputFormat()
        return RecordBatch.concat([
            text.read_batch(FileSplit([], path, start, length), conf, stats)
            for path, start, length in split.parts])


from dataclasses import dataclass, field  # noqa: E402


@dataclass
class MultiFileSplit(InputSplit):
    """≈ mapred/MultiFileSplit.java: several (path, start, length) chunks."""
    parts: list = field(default_factory=list)

    @property
    def length(self) -> int:
        return sum(p[2] for p in self.parts)


# ------------------------------------------------------------ dense (TPU)


def load_dense(fs: FileSystem, path: str) -> np.ndarray:
    """Load a whole .npy array through the FS abstraction."""
    data = fs.read_bytes(path)
    return np.load(BytesIO(data), allow_pickle=False)


def read_npy_header(f: Any) -> tuple[tuple[int, ...], np.dtype, int]:
    """Parse only the npy header: (shape, dtype, data_offset). C-order
    required (we address rows by byte range)."""
    version = np.lib.format.read_magic(f)
    if version == (1, 0):
        shape, fortran, dtype = np.lib.format.read_array_header_1_0(f)
    else:
        shape, fortran, dtype = np.lib.format.read_array_header_2_0(f)
    if fortran:
        raise ValueError("Fortran-order .npy not supported for dense splits")
    return shape, dtype, f.tell()


class DenseInputFormat(InputFormat):
    """Dense numeric input: each input path is a .npy 2-D array; splits are
    row ranges sized so one split = one HBM staging unit (default rows per
    split chosen from tpumr.dense.split.rows or evenly by num_splits).
    Split computation parses only npy headers; readers seek straight to the
    row range — no full-file loads."""

    def get_splits(self, conf, num_splits):
        splits: list[InputSplit] = []
        for p in conf.get_strings("mapred.input.dir"):
            fs = FileSystem.get(p, conf)
            stats = ([fs.get_status(p)] if not fs.get_status(p).is_dir
                     else [s for s in fs.list_files(p, recursive=True)
                           if s.path.name.endswith(".npy")])
            for st in stats:
                with fs.open(st.path) as f:
                    shape, dtype, offset = read_npy_header(f)
                rows = shape[0]
                cols = int(np.prod(shape[1:])) if len(shape) > 1 else 1
                row_bytes = cols * dtype.itemsize
                per = conf.get_int("tpumr.dense.split.rows", 0) or \
                    max(1, -(-rows // max(1, num_splits)))
                for start in range(0, rows, per):
                    n = min(per, rows - start)
                    hosts = _hosts(fs, st, offset + start * row_bytes,
                                   n * row_bytes)
                    splits.append(DenseSplit(hosts, str(st.path), start, n,
                                             row_bytes, dtype.str, cols,
                                             offset))
        return splits

    def get_record_reader(self, split, conf, reporter=None):
        """CPU fallback path: one record per row (id, row array). The TPU
        runner bypasses this and calls :meth:`read_batch`. Rows are
        copied per record: read_batch hands out a read-only view (the
        zero-copy staging contract) but user mappers may mutate their
        row in place."""
        batch = self.read_batch(split, conf)
        ids = batch.ids if batch.ids is not None else np.arange(len(batch))
        return iter((int(i), np.array(row)) for i, row in
                    zip(ids, batch.values))

    def read_batch(self, split, conf):
        from tpumr.io.recordbatch import DenseBatch
        assert isinstance(split, DenseSplit)
        fs = FileSystem.get(split.path, conf)
        with fs.open(split.path) as f:
            f.seek(split.data_offset + split.row_start * split.row_bytes)
            raw = f.read(split.num_rows * split.row_bytes)
        # read-only view over the freshly-read buffer: consumers compute
        # from it or device_put it, never mutate — copying would double
        # the memory traffic of exactly the multi-GB staging path
        arr = np.frombuffer(raw, dtype=np.dtype(split.dtype)).reshape(
            split.num_rows, split.cols)
        ids = np.arange(split.row_start, split.row_start + split.num_rows,
                        dtype=np.int64)
        return DenseBatch(arr, ids)
