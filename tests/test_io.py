"""Serialization / RecordBatch / SequenceFile tests ≈ reference io tests
(src/test/org/apache/hadoop/io/: TestWritable, TestSequenceFile,
TestText…)."""

from io import BytesIO

import numpy as np
import pytest

from tpumr.io import sequencefile
from tpumr.io.compress import get_codec, codec_for_path
from tpumr.io.recordbatch import DenseBatch, RecordBatch
from tpumr.io.writable import (
    deserialize, read_vint, serialize, write_vint, zigzag, unzigzag,
)


def test_vint_roundtrip():
    for v in [0, 1, 127, 128, 300, 2**31, 2**60]:
        buf = BytesIO()
        write_vint(buf, v)
        buf.seek(0)
        assert read_vint(buf) == v


def test_zigzag():
    for v in [0, -1, 1, -64, 63, -(2**40), 2**40]:
        assert unzigzag(zigzag(v)) == v


@pytest.mark.parametrize("obj", [
    None, True, False, b"raw\x00bytes", "unicode é中", 0, -17, 2**50,
    3.14159, [1, "two", b"three", [4.0]], {"k": 1, b"b": [None, True]},
])
def test_serialize_roundtrip(obj):
    assert deserialize(serialize(obj)) == obj


def test_serialize_ndarray():
    arr = np.arange(12, dtype=np.float32).reshape(3, 4)
    out = deserialize(serialize(arr))
    np.testing.assert_array_equal(out, arr)
    assert out.dtype == np.float32


def test_recordbatch_roundtrip():
    pairs = [(b"key1", b"val1"), (b"", b"v"), (b"longer-key", b"")]
    rb = RecordBatch.from_pairs(pairs)
    assert rb.num_records == 3
    assert rb.to_pairs() == pairs
    assert rb.key(2) == b"longer-key"


def test_recordbatch_padded():
    rb = RecordBatch.from_values([b"abc", b"defgh", b""])
    padded, lengths = rb.padded_values(4, fill=0)
    assert padded.shape == (3, 4)
    assert bytes(padded[0]) == b"abc\x00"
    assert bytes(padded[1]) == b"defg"  # truncated at width
    assert lengths.tolist() == [3, 5, 0]


def test_recordbatch_concat_slice():
    a = RecordBatch.from_pairs([(b"a", b"1")])
    b = RecordBatch.from_pairs([(b"b", b"2"), (b"c", b"3")])
    cat = RecordBatch.concat([a, b])
    assert cat.to_pairs() == [(b"a", b"1"), (b"b", b"2"), (b"c", b"3")]
    sl = cat.slice(1, 3)
    assert sl.to_pairs() == [(b"b", b"2"), (b"c", b"3")]


def test_densebatch():
    d1 = DenseBatch(np.ones((2, 3), np.float32), np.arange(2, dtype=np.int64))
    d2 = DenseBatch(np.zeros((1, 3), np.float32), np.array([5], np.int64))
    cat = DenseBatch.concat([d1, d2])
    assert cat.num_records == 3
    assert cat.ids.tolist() == [0, 1, 5]


@pytest.mark.parametrize("codec", ["none", "zlib", "gzip", "bzip2",
                                   "lzma", "tlz"])
def test_codec_roundtrip(codec):
    c = get_codec(codec)
    data = b"some repetitive data " * 100
    assert c.decompress(c.compress(data)) == data


class TestTlzCodec:
    """Native fast shuffle/spill codec (native/tlz ≈ the reference's
    JNI compression tier) — native and pure-Python ends must agree on
    the frame format in every combination."""

    PAYLOADS = [b"", b"x", b"abc" * 5000, bytes(range(256)) * 300,
                b"aaaaaaaaab" * 1 + b"Z" * 100 + b"aaaaaaaaab" * 40]

    def test_native_and_python_interop(self):
        import os
        from tpumr.io.compress import TlzCodec
        c = TlzCodec()
        rnd = os.urandom(50_000)              # stored-mode path
        for data in self.PAYLOADS + [rnd]:
            native = c.compress(data)
            if TlzCodec.available():
                # python reader decodes native frames
                assert TlzCodec._py_decompress(native) == data
            assert c.decompress(native) == data
            # python stored frames decode natively
            stored = TlzCodec._py_store(data)
            assert c.decompress(stored) == data

    def test_corrupt_frames_raise(self):
        import struct
        from tpumr.io.compress import TlzCodec
        c = TlzCodec()
        frame = bytearray(c.compress(b"abcabcabc" * 1000))
        with pytest.raises(ValueError):
            c.decompress(b"NOPE" + bytes(frame[4:]))
        with pytest.raises(ValueError):
            c.decompress(bytes(frame[: len(frame) // 2]))
        with pytest.raises(ValueError):
            TlzCodec._py_decompress(bytes(frame[: len(frame) // 2]))
        # a bit-flipped LENGTH header must raise, never size a huge
        # allocation off untrusted bytes
        bomb = bytes(frame[:4]) + struct.pack("<Q", 1 << 60) \
            + bytes(frame[12:])
        with pytest.raises(ValueError, match="implausible|corrupt"):
            c.decompress(bomb)

    def test_compresses_text_class_data(self):
        from tpumr.io.compress import TlzCodec
        if not TlzCodec.available():
            pytest.skip("no C toolchain")
        c = TlzCodec()
        data = b"word0001\t17\nword0002\t3\n" * 20000
        out = c.compress(data)
        assert len(out) < len(data) // 2      # real compression
        assert c.decompress(out) == data


def test_codec_for_path():
    assert codec_for_path("x.gz").name == "gzip"
    assert codec_for_path("x.txt") is None


@pytest.mark.parametrize("codec", ["none", "zlib"])
def test_sequencefile_roundtrip(codec):
    buf = BytesIO()
    with sequencefile.Writer(buf, codec=codec, block_records=3) as w:
        for i in range(10):
            w.append(f"key{i}", {"n": i, "payload": b"x" * i})
    # Writer closes buf; re-wrap its bytes
    data = buf.getvalue()
    r = sequencefile.Reader(BytesIO(data))
    items = list(r)
    assert len(items) == 10
    assert items[0] == ("key0", {"n": 0, "payload": b""})
    assert items[9][1]["n"] == 9


def test_sequencefile_sync_split():
    buf = BytesIO()
    w = sequencefile.Writer(buf, block_records=5)
    for i in range(100):
        w.append(i, b"v" * 50)
        if i % 20 == 19:
            w.sync_now()
    w._flush_block()
    data = buf.getvalue()
    # read from the middle: sync() must land on a block boundary
    r = sequencefile.Reader(BytesIO(data))
    assert r.sync(len(data) // 2)
    tail = list(r)
    assert 0 < len(tail) < 100
    keys = [k for k, _ in tail]
    assert keys == sorted(keys)
    assert keys[-1] == 99


class TestAppendFixedRows:
    def test_byte_identical_to_per_record_appends(self):
        """Bulk fixed-width append must produce exactly the framing of n
        scalar append() calls (same reader, same sync semantics)."""
        import io as _io
        import os as _os
        import numpy as np
        from tpumr.io import sequencefile as sf
        rng = np.random.default_rng(0)
        rows = rng.integers(0, 256, size=(2500, 14), dtype=np.uint8)
        orig = _os.urandom
        _os.urandom = lambda n: b"S" * n  # pin sync for comparability
        try:
            # DEFAULT block size: the contract must hold for production
            # writers (_SeqWriter passes no block_records)
            b1, b2 = _io.BytesIO(), _io.BytesIO()
            w1 = sf.Writer(b1)
            w1.append_fixed_rows(rows, 10)
            w1.close()
            w2 = sf.Writer(b2)
            for r in rows:
                w2.append(bytes(r[:10]), bytes(r[10:]))
            w2.close()
        finally:
            _os.urandom = orig
        assert b1.getvalue() == b2.getvalue()

    def test_roundtrip_and_mixed_appends(self):
        import io as _io
        import numpy as np
        from tpumr.io import sequencefile as sf
        rng = np.random.default_rng(1)
        rows = rng.integers(0, 256, size=(300, 12), dtype=np.uint8)
        b = _io.BytesIO()
        w = sf.Writer(b)
        w.append(b"first-0000", b"xx")       # scalar before bulk: ordered
        w.append_fixed_rows(rows, 10)
        w.append(b"last-00000", b"yy")
        w.close()
        b.seek(0)
        recs = list(sf.Reader(b))
        assert len(recs) == 302
        assert recs[0] == (b"first-0000", b"xx")
        assert recs[1] == (bytes(rows[0, :10]), bytes(rows[0, 10:]))
        assert recs[-1] == (b"last-00000", b"yy")

    def test_zero_width_values(self):
        import io as _io
        import numpy as np
        from tpumr.io import sequencefile as sf
        rows = np.arange(50, dtype=np.uint8).reshape(5, 10)
        b = _io.BytesIO()
        w = sf.Writer(b)
        w.append_fixed_rows(rows, 10)
        w.close()
        b.seek(0)
        assert list(sf.Reader(b)) == [(bytes(r), b"") for r in rows]


def _pinned_writer(stream, **kw):
    """A Writer whose sync marker is 16 S's, so two files compare."""
    import os as _os
    from tpumr.io import sequencefile as sf
    orig = _os.urandom
    _os.urandom = lambda n: b"S" * n
    try:
        return sf.Writer(stream, **kw)
    finally:
        _os.urandom = orig


def _chunk_rows(width, klen, block_records):
    """Rows in one chunk of the bulk writer: the whole blocks that fit its
    buffer (a block's slot is its frames behind 24 bytes and the count)."""
    from tpumr.io import sequencefile as sf
    from tpumr.io.writable import _vint_bytes
    frame = sf._FixedFrame(klen, width - klen).size
    slot = 24 + len(_vint_bytes(block_records)) + block_records * frame
    return max(1, sf._BULK_CHUNK_BYTES // slot) * block_records


#: (rows a bulk call, ...) in row counts or in chunks of the writer
_N = {"none": [0], "one": [1], "a-block-less-one": [999],
      "a-block": [1000], "a-block-and-one": [1001],
      "a-chunk": ["chunk"], "a-chunk-and-one": ["chunk+1"],
      "chunks-and-a-partial-block": ["3chunk+517"],
      "two-calls-in-a-row": [1500, 2000],
      "a-call-of-none-between": [700, 0, 1300]}


@pytest.mark.parametrize("scalars", [False, True],
                         ids=["bulk-alone", "scalar-appends-around"])
@pytest.mark.parametrize("shape", [
    (100, 10, 1000, "none"),   # the sort's rows: a block is 106 KB
    (14, 10, 1000, "none"),
    (14, 10, 7, "none"),       # a block under SYNC_INTERVAL: syncs now and then
    (14, 10, 1, "none"),       # a record a block
    (5, 3, 1000, "none"),
    (10, 10, 1000, "none"),    # zero-width values
    (20, 16, 1000, "none"),    # the aggregation's groups
    (14, 10, 1000, "zlib"),    # a codec wants a block's bytes
    (14, 10, 7, "zlib"),
], ids=lambda s: "w%d-k%d-b%d-%s" % s)
@pytest.mark.parametrize("calls", list(_N), ids=list(_N))
def test_bulk_append_is_byte_identical_to_per_record_appends(
        calls, shape, scalars, monkeypatch):
    """``append_fixed_rows`` against n ``append(bytes, bytes)`` calls, a
    bulk call starting and ending a block as it always has: for every n
    around a block and around a chunk of the writer's buffer, every block
    size, rows so narrow that blocks share a sync marker, a compressing
    codec, scalar appends before and after, calls in a row."""
    import io as _io
    import numpy as np
    from tpumr.io import sequencefile as sf
    width, klen, per, codec = shape
    # a chunk of a few blocks, so that several chunks are a few thousand
    # rows whatever the block size
    monkeypatch.setattr(sf, "_BULK_CHUNK_BYTES",
                        4 * (24 + 3 + per * (width + 6)) + 11)
    chunk = _chunk_rows(width, klen, per)
    assert chunk == 4 * per
    counts = [n if isinstance(n, int) else
              {"chunk": chunk, "chunk+1": chunk + 1,
               "3chunk+517": 3 * chunk + 517}[n] for n in _N[calls]]
    rng = np.random.default_rng(sum(counts) + width)
    bulk, plain = _io.BytesIO(), _io.BytesIO()
    w1 = _pinned_writer(bulk, codec=codec, block_records=per)
    w2 = _pinned_writer(plain, codec=codec, block_records=per)
    if scalars:
        for w in (w1, w2):
            w.append(b"first", b"xx")
            w.append("second", 2)
    for n in counts:
        rows = rng.integers(0, 256, size=(n, width), dtype=np.uint8)
        w1.append_fixed_rows(rows, klen)
        if n:
            w2._flush_block()
            for r in rows:
                w2.append(bytes(r[:klen]), bytes(r[klen:]))
            w2._flush_block()
    if scalars:
        for w in (w1, w2):
            w.append(b"last", b"yy")
    w1.close()
    w2.close()
    assert bulk.getvalue() == plain.getvalue()
    assert w1._since_sync == w2._since_sync
    bulk.seek(0)
    assert sum(1 for _ in sf.Reader(bulk)) == sum(counts) + 3 * scalars


def test_bulk_append_of_rows_that_are_a_strided_view():
    """Rows that are columns of a wider array (no row-contiguous bytes to
    reshape) are framed like their copy."""
    import io as _io
    import numpy as np
    rng = np.random.default_rng(9)
    wide = rng.integers(0, 256, size=(2300, 40), dtype=np.uint8)
    outs = []
    for rows in (wide[:, 5:19], np.ascontiguousarray(wide[:, 5:19])):
        b = _io.BytesIO()
        w = _pinned_writer(b)
        w.append_fixed_rows(rows, 10)
        w.close()
        outs.append(b.getvalue())
    assert outs[0] == outs[1]


def test_bulk_appends_on_threads_each_write_their_own_file():
    """Four writers at once, a file each, the interpreter switching threads
    every few microseconds: each file is what its writer alone wrote."""
    import io as _io
    import sys
    import threading
    import numpy as np
    rng = np.random.default_rng(11)
    rows = [rng.integers(0, 256, size=(30_000 + i, 100), dtype=np.uint8)
            for i in range(4)]

    def write(r, out):
        w = _pinned_writer(out)
        w.append_fixed_rows(r, 10)
        w.close()

    alone = [_io.BytesIO() for _ in rows]
    for r, out in zip(rows, alone):
        write(r, out)
    beside = [_io.BytesIO() for _ in rows]
    threads = [threading.Thread(target=write, args=a)
               for a in zip(rows, beside)]
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads)
    assert [b.getvalue() for b in beside] == [a.getvalue() for a in alone]


# ---------------------------------------------------------------- TFile


class TestTFile:
    """≈ io/file/tfile TestTFile*: sorted container, block index,
    range scanners, meta blocks."""

    def _build(self, f, n=500, codec="zlib", block_bytes=512):
        from tpumr.io import tfile
        with tfile.Writer(f, codec=codec, block_bytes=block_bytes) as w:
            for i in range(n):
                w.append(f"k{i:06d}".encode(), f"v{i}".encode() * 3)
            w.write_meta("stats", b'{"rows": 500}')
        return f

    def test_roundtrip_and_block_index(self):
        import io as _io

        from tpumr.io import tfile
        f = self._build(_io.BytesIO())
        r = tfile.Reader(f)
        assert r.num_records == 500
        assert len(r.block_keys) > 5, "never rolled a block"
        recs = list(r)
        assert len(recs) == 500
        assert recs[0][0] == b"k000000" and recs[-1][0] == b"k000499"
        assert recs == sorted(recs)

    def test_seek_and_range_scanner(self):
        import io as _io

        from tpumr.io import tfile
        r = tfile.Reader(self._build(_io.BytesIO()))
        # exact get
        assert r.get(b"k000123") == b"v123" * 3
        assert r.get(b"nope") is None
        # range [k000100, k000110)
        keys = [k for k, _ in r.scanner(b"k000100", b"k000110")]
        assert keys == [f"k{i:06d}".encode() for i in range(100, 110)]
        # seek positions at first key >= target
        it = r.seek_to(b"k000250")
        assert next(it)[0] == b"k000250"

    def test_meta_blocks(self):
        import io as _io

        from tpumr.io import tfile
        r = tfile.Reader(self._build(_io.BytesIO()))
        assert r.meta_names() == ["stats"]
        assert r.meta("stats") == b'{"rows": 500}'

    def test_out_of_order_append_rejected(self):
        import io as _io

        from tpumr.io import tfile
        w = tfile.Writer(_io.BytesIO())
        w.append(b"b", b"1")
        with pytest.raises(tfile.TFileError, match="out of order"):
            w.append(b"a", b"2")

    def test_uncompressed_and_corrupt_magic(self):
        import io as _io

        from tpumr.io import tfile
        f = self._build(_io.BytesIO(), codec="none")
        r = tfile.Reader(f)
        assert r.get(b"k000001") == b"v1v1v1"
        with pytest.raises(tfile.TFileError, match="magic"):
            tfile.Reader(_io.BytesIO(b"not a tfile at all"))

    def test_duplicate_keys_across_block_boundary(self):
        """Equal keys spanning a block boundary: scans starting at that
        key must include records from the EARLIER block too."""
        import io as _io

        from tpumr.io import tfile
        w = tfile.Writer(_io.BytesIO(), codec="none", block_bytes=16)
        for i in range(6):
            w.append(b"dup", b"v%d" % i)
        w.append(b"zz", b"tail")
        f = w._f
        w.close()
        r = tfile.Reader(f)
        assert len(r.block_keys) >= 2
        vals = [v for k, v in r.scanner(b"dup") if k == b"dup"]
        assert vals == [b"v%d" % i for i in range(6)]
        assert r.get(b"dup") == b"v0"
