"""Kernel mapper tests: numeric parity vs numpy references, Pallas interpret
mode on CPU (tests/test_chip_compile.py compiles them for a described
v5e; chip_smoke.py runs them on one)."""

import numpy as np
import pytest

from tpumr.io.recordbatch import DenseBatch, RecordBatch
from tpumr.mapred.jobconf import JobConf
from tpumr.ops import get_kernel, kernels
from tpumr.ops.kmeans import assign_and_partials, pallas_assign


def _np_assign(points, centroids):
    d2 = ((points[:, None, :] - centroids[None, :, :]) ** 2).sum(-1)
    return d2.argmin(1)


def test_registry_lists_builtins():
    names = kernels()
    for expected in ["kmeans-assign", "matmul-block", "pi-sampler",
                     "wordcount", "grep"]:
        assert expected in names
    with pytest.raises(KeyError):
        get_kernel("nope")


def test_kmeans_assign_jax_matches_numpy():
    rng = np.random.default_rng(0)
    pts = rng.normal(size=(257, 5)).astype(np.float32)
    cents = rng.normal(size=(7, 5)).astype(np.float32)
    assign, sums, counts = assign_and_partials(pts, cents, use_pallas=False)
    expect = _np_assign(pts, cents)
    np.testing.assert_array_equal(np.asarray(assign), expect)
    assert int(np.asarray(counts).sum()) == 257
    for c in range(7):
        mask = expect == c
        if mask.any():
            np.testing.assert_allclose(np.asarray(sums)[c], pts[mask].sum(0),
                                       rtol=1e-4)


@pytest.mark.parametrize("impl", ["device", "numpy"])
def test_kmeans_assign_at_sift_widths_matches_a_float32_reference(impl):
    """d = 128, k = 1024 on seeded whole-number points 0..255 (SIFT's
    values), against plain ``jax.numpy`` in float32 at the highest matmul
    precision: the device kernel's assignments, sums and counts, and the
    CPU slot's numpy twin's sums and counts."""
    import jax
    import jax.numpy as jnp

    from tpumr.ops.kmeans import assign_and_partials_numpy
    rng = np.random.default_rng(128_1024)
    centres = rng.exponential(32.0, (2048, 128))
    pts = np.clip(np.rint(centres[rng.integers(0, 2048, 4096)]
                          + 12.0 * rng.standard_normal((4096, 128))),
                  0, 255).astype(np.float32)
    cents = pts[:1024] + np.float32(0.25)    # off the points: no exact tie
    with jax.default_matmul_precision("highest"):
        x, c = jnp.asarray(pts), jnp.asarray(cents)
        d2 = (jnp.sum(x * x, axis=1, keepdims=True) - 2.0 * (x @ c.T)
              + jnp.sum(c * c, axis=1)[None, :])
        want = np.asarray(jnp.argmin(d2, axis=1))
        margin = np.sort(np.asarray(d2), axis=1)
    # the data decides every row by far more than float32 rounds away
    assert (margin[:, 1] - margin[:, 0]).min() > 1.0
    want_counts = np.bincount(want, minlength=1024)
    want_sums = np.zeros((1024, 128), np.float64)
    np.add.at(want_sums, want, pts.astype(np.float64))
    assert len(np.unique(want)) > 900       # the clusters are in use
    if impl == "device":
        assign, sums, counts = assign_and_partials(pts, cents)
        np.testing.assert_array_equal(np.asarray(assign), want)
    else:
        sums, counts = assign_and_partials_numpy(pts, cents, chunk=1000)
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
    # whole numbers under 2^24: the sums are exact in float32
    np.testing.assert_array_equal(np.asarray(sums, np.float64), want_sums)


def test_kmeans_pallas_interpret_matches_numpy():
    rng = np.random.default_rng(1)
    pts = rng.normal(size=(100, 3)).astype(np.float32)
    cents = rng.normal(size=(5, 3)).astype(np.float32)
    out = np.asarray(pallas_assign(pts, cents, block_n=32, interpret=True))
    np.testing.assert_array_equal(out, _np_assign(pts, cents))


def test_kmeans_kernel_mapper_partials(tmp_path):
    from tpumr.ops.kmeans import clear_centroid_cache
    clear_centroid_cache()
    rng = np.random.default_rng(2)
    pts = rng.normal(size=(64, 4)).astype(np.float32)
    cents = rng.normal(size=(3, 4)).astype(np.float32)
    cpath = tmp_path / "c.npy"
    np.save(cpath, cents)
    conf = JobConf()
    conf.set("tpumr.kmeans.centroids", f"file://{cpath}")
    kernel = get_kernel("kmeans-assign")
    out = dict(kernel.map_batch(DenseBatch(pts, np.arange(64)), conf, None))
    expect = _np_assign(pts, cents)
    total = 0
    for cid, (s, n) in out.items():
        mask = expect == cid
        assert n == mask.sum()
        np.testing.assert_allclose(s, pts[mask].sum(0), rtol=1e-4)
        total += n
    assert total == 64


def test_matmul_kernel(tmp_path):
    from tpumr.ops.matmul import clear_b_cache
    clear_b_cache()
    rng = np.random.default_rng(3)
    a = rng.normal(size=(16, 8)).astype(np.float32)
    b = rng.normal(size=(8, 12)).astype(np.float32)
    np.save(tmp_path / "b.npy", b)
    conf = JobConf()
    conf.set("tpumr.matmul.b", f"file://{tmp_path}/b.npy")
    conf.set("tpumr.matmul.bf16", False)
    kernel = get_kernel("matmul-block")
    [(row0, c)] = list(kernel.map_batch(
        DenseBatch(a, np.arange(100, 116)), conf, None))
    assert row0 == 100
    np.testing.assert_allclose(c, a @ b, rtol=1e-4)


def test_pi_kernel_reasonable():
    conf = JobConf()
    kernel = get_kernel("pi-sampler")
    batch = RecordBatch.from_values([b"1 20000", b"2 20000"])
    out = dict(kernel.map_batch(batch, conf, None))
    assert out["total"] == 40000
    pi = 4.0 * out["inside"] / out["total"]
    assert abs(pi - np.pi) < 0.05


def test_wordcount_kernel_matches_split():
    text = ["the quick brown fox", "the lazy dog", "", "fox    fox"]
    batch = RecordBatch.from_values([t.encode() for t in text])
    out = dict(get_kernel("wordcount").map_batch(batch, JobConf(), None))
    assert out == {"the": 2, "quick": 1, "brown": 1, "fox": 3,
                   "lazy": 1, "dog": 1}


def test_grep_kernel():
    conf = JobConf()
    conf.set("tpumr.grep.pattern", r"err[a-z]+")
    batch = RecordBatch.from_values([b"error here", b"no match",
                                     b"errand and error"])
    out = dict(get_kernel("grep").map_batch(batch, conf, None))
    assert out == {"error": 2, "errand": 1}


class TestVectorizedTokenizer:
    """tokenize_count (numpy byte-matrix) and tokenize_count_native
    (native/textkit single-pass C) must both match bytes.split()/Counter
    exactly — including non-UTF8 bytes, NULs inside tokens, and every
    whitespace class."""

    CASES = [
        b"", b" \t\n\v\f\r ", b"a", b" a ", b"a b a\nc\t\tb",
        b"\x00weird\x00 to\x00kens \x00",
        b"x" * 300 + b" " + b"x" * 300,          # long tokens (>8 bytes)
        bytes(range(256)) * 20,                   # all byte values
    ]

    def test_numpy_path_matches_counter(self):
        from collections import Counter

        from tpumr.ops.wordcount import tokenize_count
        for d in self.CASES:
            assert dict(tokenize_count(d)) == dict(Counter(d.split())), d[:32]

    def test_native_path_matches_counter(self):
        import shutil

        import pytest as _pytest
        from collections import Counter

        from tpumr.ops.wordcount import tokenize_count_native
        if shutil.which("cc") is None:
            _pytest.skip("no C toolchain")
        for d in self.CASES:
            got = tokenize_count_native(d)
            if got is None:
                _pytest.skip("native tokenizer unavailable")
            assert dict(got) == dict(Counter(d.split())), d[:32]

    def test_kernel_job_output_unchanged(self):
        """The wordcount kernel end-to-end (large enough to take the
        vectorized path) produces the same counts as the naive mapper."""
        from tpumr.fs import FileSystem, get_filesystem
        from tpumr.mapred import JobConf, run_job
        fs = get_filesystem("mem:///")
        text = b"".join(b"tok%03d fixed\n" % (i % 101)
                        for i in range(20000))   # > 64 KiB
        fs.write_bytes("/vt/in.txt", text)
        conf = JobConf()
        conf.set_input_paths("mem:///vt/in.txt")
        conf.set_output_path("mem:///vt/out")
        conf.set_map_kernel("wordcount")
        conf.set("mapred.reducer.class",
                 "tpumr.examples.basic.LongSumReducer")
        conf.set("tpumr.local.run.on.tpu", True)
        assert run_job(conf).successful
        out = b"".join(fs.read_bytes(st.path)
                       for st in fs.list_status("/vt/out")
                       if "part-" in str(st.path))
        counts = dict(l.split(b"\t") for l in out.splitlines())
        assert counts[b"fixed"] == b"20000"
        assert counts[b"tok000"] == b"199"   # ceil(20000/101)
        FileSystem.clear_cache()

    def test_raw_text_multi_split_boundary_ownership(self, tmp_path):
        """A wordcount job forced into MANY RawTextInputFormat splits
        must count every word exactly once — the split-boundary
        ownership rule (skip leading partial, finish trailing line) is
        exercised across dozens of boundaries, at varied line lengths
        so boundaries land mid-line, at line starts, and on newlines."""
        from collections import Counter

        from tpumr.fs import FileSystem
        from tpumr.mapred import JobConf, run_job
        import random
        random.seed(4)
        lines = []
        for i in range(4000):
            lines.append(" ".join(
                f"w{random.randrange(50):02d}"
                for _ in range(random.randrange(1, 9))))
        text = ("\n".join(lines) + "\n").encode()
        expected = Counter(text.split())
        p = tmp_path / "multi.txt"
        p.write_bytes(text)
        conf = JobConf()
        conf.set_input_paths(f"file://{p}")
        conf.set_output_path(f"file://{tmp_path}/out")
        from tpumr.mapred.input_formats import RawTextInputFormat
        conf.set_input_format(RawTextInputFormat)
        conf.set("mapred.max.split.size", 997)   # prime: odd boundaries
        conf.set("fs.local.block.size", 997)
        conf.set_map_kernel("wordcount")
        conf.set("mapred.reducer.class",
                 "tpumr.examples.basic.LongSumReducer")
        assert run_job(conf).successful
        got = {}
        import glob
        for part in glob.glob(f"{tmp_path}/out/part-*"):
            for line in open(part, "rb").read().splitlines():
                k, v = line.rsplit(b"\t", 1)
                got[k] = int(v)
        assert got == dict(expected)
        FileSystem.clear_cache()


class TestDeviceConstantCache:
    """ops/devcache.py: side-input uploads happen once per (tag, device),
    not once per map task."""

    def setup_method(self):
        from tpumr.ops import devcache
        devcache.clear_device_cache()
        # the byte budget is fixed at first construction; tests that
        # set their own budget need a fresh singleton
        devcache._cache = None

    def test_same_device_array_across_calls(self):
        import numpy as np
        from tpumr.ops.devcache import device_cached
        host = np.arange(12, dtype=np.float32).reshape(3, 4)
        a1 = device_cached("t:x", host)
        a2 = device_cached("t:x", host)
        assert a1 is a2          # no second upload
        np.testing.assert_array_equal(np.asarray(a1), host)

    def test_prefix_clear_and_budget_eviction(self):
        import numpy as np
        from tpumr.ops import devcache
        from tpumr.ops.devcache import clear_device_cache, device_cached

        class Conf:
            def get(self, k, d=None):
                return 1 if k == "tpumr.ops.device.cache.mb" else d

        half = np.zeros((150, 1024), np.float32)      # ~0.6 MB each
        a = device_cached("a:1", half, Conf())
        device_cached("b:1", half, Conf())            # evicts a:1 (LRU)
        assert device_cached("b:1", half, Conf()) is not None
        a2 = device_cached("a:1", half, Conf())       # re-upload: new obj
        assert a2 is not a
        clear_device_cache("a:")
        assert device_cached("a:1", half, Conf()) is not a2  # was dropped

    def test_kernels_reuse_device_side_inputs(self, tmp_path):
        """kmeans centroids and matmul B resolve to the SAME device
        array across tasks of a job (and re-upload after the iterative
        driver's clear)."""
        import numpy as np
        from tpumr.mapred.jobconf import JobConf
        from tpumr.ops.kmeans import _device_centroids, clear_centroid_cache
        from tpumr.ops.matmul import _device_b, clear_b_cache
        np.save(tmp_path / "c.npy", np.zeros((3, 4), np.float32))
        np.save(tmp_path / "b.npy", np.ones((4, 4), np.float32))
        conf = JobConf()
        conf.set("tpumr.kmeans.centroids", f"file://{tmp_path}/c.npy")
        conf.set("tpumr.matmul.b", f"file://{tmp_path}/b.npy")
        clear_centroid_cache(); clear_b_cache()
        c1, c2 = _device_centroids(conf), _device_centroids(conf)
        assert c1 is c2
        b1, b2 = _device_b(conf), _device_b(conf)
        assert b1 is b2
        clear_centroid_cache()
        assert _device_centroids(conf) is not c1   # rewritten rounds re-upload
        clear_b_cache()
        assert _device_b(conf) is not b1
