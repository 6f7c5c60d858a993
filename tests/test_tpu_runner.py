"""End-to-end jobs through the TPU map runner (CPU backend in tests; the
runner/kernels are backend-agnostic JAX). This is the seam the reference
exercised only by hand (SURVEY.md §4.8: zero GPU tests) — here it's the
deterministic path: run_on_tpu tasks select TpuMapRunner exactly like
MapTask.java:433-438 selects PipesGPUMapRunner."""

import numpy as np

from tpumr.core.counters import BackendCounter, TaskCounter
from tpumr.fs import get_filesystem
from tpumr.mapred import JobConf, Reducer, run_job
from tpumr.mapred.input_formats import DenseInputFormat


class CentroidReducer(Reducer):
    """Sums (partial_sum, count) pairs into a new centroid."""

    def reduce(self, key, values, output, reporter):
        total = None
        n = 0
        for s, c in values:
            total = s if total is None else total + s
            n += c
        output.collect(key, (total / max(1, n)).tolist())


def _save_npy(fs, path, arr):
    import io
    buf = io.BytesIO()
    np.save(buf, arr)
    fs.write_bytes(path, buf.getvalue())


def test_kmeans_job_on_tpu_runner():
    from tpumr.ops.kmeans import clear_centroid_cache
    clear_centroid_cache()
    fs = get_filesystem("mem:///")
    rng = np.random.default_rng(42)
    # three well-separated blobs
    blobs = np.concatenate([
        rng.normal(loc=c, scale=0.1, size=(50, 2))
        for c in [(0, 0), (5, 5), (-5, 5)]
    ]).astype(np.float32)
    rng.shuffle(blobs)
    _save_npy(fs, "/km/points.npy", blobs)
    cents = np.array([[0.5, 0.5], [4, 4], [-4, 4]], np.float32)
    _save_npy(fs, "/km/centroids.npy", cents)

    conf = JobConf()
    conf.set_input_paths("mem:///km/points.npy")
    conf.set_output_path("mem:///km/out")
    conf.set_input_format(DenseInputFormat)
    conf.set("tpumr.dense.split.rows", 40)
    conf.set("tpumr.kmeans.centroids", "mem:///km/centroids.npy")
    conf.set_map_kernel("kmeans-assign")
    conf.set_reducer_class(CentroidReducer)
    conf.set_num_reduce_tasks(1)
    conf.set("tpumr.local.run.on.tpu", True)

    result = run_job(conf)
    assert result.successful
    # backend counters prove TPU-runner placement
    assert result.counters.value(BackendCounter.GROUP,
                                 BackendCounter.TPU_MAP_TASKS) == result.num_maps
    assert result.counters.value(BackendCounter.GROUP,
                                 BackendCounter.CPU_MAP_TASKS) == 0
    assert result.counters.value(BackendCounter.GROUP,
                                 BackendCounter.TPU_DEVICE_BYTES_STAGED) > 0
    assert result.counters.value(TaskCounter.FRAMEWORK_GROUP,
                                 TaskCounter.MAP_INPUT_RECORDS) == 150

    lines = fs.read_bytes("mem:///km/out/part-00000").decode().splitlines()
    got = {}
    for ln in lines:
        k, v = ln.split("\t")
        got[int(k)] = eval(v)  # list literal
    assert len(got) == 3
    for cid, target in [(0, (0, 0)), (1, (5, 5)), (2, (-5, 5))]:
        np.testing.assert_allclose(got[cid], target, atol=0.2)


def test_same_job_runs_on_cpu_mapper():
    """The same K-Means job with run-on-tpu off uses the CPU mapper — the
    dual-backend contract the hybrid scheduler depends on."""
    from tpumr.ops.kmeans import KMeansCpuMapper, clear_centroid_cache
    clear_centroid_cache()
    fs = get_filesystem("mem:///")
    pts = np.array([[0.1, 0], [4.9, 5], [0, 0.2], [5, 4.8]], np.float32)
    _save_npy(fs, "/km2/points.npy", pts)
    _save_npy(fs, "/km2/centroids.npy", np.array([[0, 0], [5, 5]], np.float32))

    conf = JobConf()
    conf.set_input_paths("mem:///km2/points.npy")
    conf.set_output_path("mem:///km2/out")
    conf.set_input_format(DenseInputFormat)
    conf.set("tpumr.kmeans.centroids", "mem:///km2/centroids.npy")
    conf.set_mapper_class(KMeansCpuMapper)
    conf.set_reducer_class(CentroidReducer)
    conf.set_num_reduce_tasks(1)

    result = run_job(conf)
    assert result.successful
    assert result.counters.value(BackendCounter.GROUP,
                                 BackendCounter.CPU_MAP_TASKS) > 0
    assert result.counters.value(BackendCounter.GROUP,
                                 BackendCounter.TPU_MAP_TASKS) == 0


def test_wordcount_kernel_job_via_record_reader():
    """Text input has no read_batch: the runner drains the record reader
    into a RecordBatch. Input-record counting must not double-count."""
    fs = get_filesystem("mem:///")
    fs.write_bytes("/wc/in.txt", b"alpha beta\nbeta gamma\n" * 10)
    conf = JobConf()
    conf.set_input_paths("mem:///wc/in.txt")
    conf.set_output_path("mem:///wc/out")
    conf.set_map_kernel("wordcount")

    class Sum(__import__("tpumr.mapred.api", fromlist=["Reducer"]).Reducer):
        def reduce(self, key, values, output, reporter):
            output.collect(key, sum(values))

    conf.set_reducer_class(Sum)
    conf.set_num_reduce_tasks(1)
    conf.set("tpumr.local.run.on.tpu", True)
    result = run_job(conf)
    assert result.successful
    assert result.counters.value(TaskCounter.FRAMEWORK_GROUP,
                                 TaskCounter.MAP_INPUT_RECORDS) == 20
    out = dict(ln.split("\t") for ln in
               fs.read_bytes("mem:///wc/out/part-00000").decode().splitlines())
    assert out == {"alpha": "10", "beta": "20", "gamma": "10"}


def _kmeans_conf(fs, tag, n=150, rows_per_split=40):
    rng = np.random.default_rng(42)
    pts = rng.normal(size=(n, 2)).astype(np.float32)
    _save_npy(fs, f"/{tag}/points.npy", pts)
    _save_npy(fs, f"/{tag}/centroids.npy",
              np.array([[0, 0], [5, 5], [-5, 5]], np.float32))
    conf = JobConf()
    conf.set_input_paths(f"mem:///{tag}/points.npy")
    conf.set_output_path(f"mem:///{tag}/out")
    conf.set_input_format(DenseInputFormat)
    conf.set("tpumr.dense.split.rows", rows_per_split)
    conf.set("tpumr.kmeans.centroids", f"mem:///{tag}/centroids.npy")
    conf.set_map_kernel("kmeans-assign")
    conf.set_reducer_class(CentroidReducer)
    conf.set_num_reduce_tasks(1)
    conf.set("tpumr.local.run.on.tpu", True)
    return conf


def test_pipelined_window_fetches_once_per_window(monkeypatch):
    """The map phase of a kernel job batches ALL tasks' device→host
    transfers into one jax.device_get per pipeline window, so
    device_get calls per job are O(tasks/window), not O(tasks)."""
    import jax

    from tpumr.ops.kmeans import clear_centroid_cache
    clear_centroid_cache()
    fs = get_filesystem("mem:///")
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: (calls.append(1), real(x))[1])

    conf = _kmeans_conf(fs, "pw", n=150, rows_per_split=40)  # 4 splits
    result = run_job(conf)
    assert result.successful
    assert result.num_maps == 4
    assert len(calls) == 1  # one window, one roundtrip

    # window smaller than the task count: one fetch per window
    calls.clear()
    clear_centroid_cache()
    conf2 = _kmeans_conf(fs, "pw2", n=150, rows_per_split=40)
    conf2.set("tpumr.tpu.pipeline.window", 2)
    result2 = run_job(conf2)
    assert result2.successful
    assert len(calls) == 2  # ceil(4/2)


def test_pipeline_window_byte_budget_closes_window_early(monkeypatch):
    """The window is byte-bounded: staged inputs stay device-resident
    until the window fetch, so a tiny budget must split one count-window
    into several fetches (and still produce a correct job)."""
    import jax

    from tpumr.ops.kmeans import clear_centroid_cache
    clear_centroid_cache()
    fs = get_filesystem("mem:///")
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: (calls.append(1), real(x))[1])

    conf = _kmeans_conf(fs, "pb", n=150, rows_per_split=40)  # 4 splits
    conf.set("tpumr.tpu.pipeline.window.mb", 0)  # every task busts the budget
    conf.set("tpumr.tpu.split.cache", False)
    result = run_job(conf)
    assert result.successful
    assert len(calls) == 4  # one-task windows


def test_pipelined_window_output_matches_per_task_path():
    """Window on vs off (window=0 forces the per-task path) produce
    byte-identical job output."""
    from tpumr.ops.kmeans import clear_centroid_cache
    fs = get_filesystem("mem:///")

    outs = []
    for i, window in enumerate((32, 0)):
        clear_centroid_cache()
        conf = _kmeans_conf(fs, f"pe{i}")
        conf.set("tpumr.tpu.pipeline.window", window)
        assert run_job(conf).successful
        outs.append(fs.read_bytes(f"mem:///pe{i}/out/part-00000"))
    assert outs[0] == outs[1]


def test_pi_kernel_launch_drain_stays_on_device_until_fetch():
    """pi-sampler's launch dispatches every sample block without a sync;
    records appear only at drain, and totals match the sample count."""
    from tpumr.mapred.split import InputSplit
    from tpumr.ops import get_kernel
    import jax

    kernel = get_kernel("pi-sampler")
    assert type(kernel).supports_launch()

    class B:
        num_records = 3
        def value(self, i):
            return f"{i} 1000".encode()

    conf = JobConf()
    state = kernel.map_batch_launch(B(), conf, None)
    out = dict(kernel.map_batch_drain(jax.device_get(state), conf, None))
    assert out["total"] == 3000
    assert 0 < out["inside"] <= 3000


def test_pipeline_window_kernel_error_fails_job_cleanly():
    """A kernel that raises mid-window must fail the job with the real
    error (no hang, no partial commit)."""
    import pytest

    from tpumr.ops.registry import KernelMapper, register_kernel

    class BoomKernel(KernelMapper):
        name = "boom-on-third"
        calls = [0]

        def map_batch_launch(self, batch, conf, task):
            self.calls[0] += 1
            if self.calls[0] == 3:
                raise RuntimeError("kernel exploded on split 3")
            import jax.numpy as jnp
            return (jnp.zeros(2),)

        def map_batch_drain(self, fetched, conf, task):
            yield 0, float(fetched[0][0])

    register_kernel(BoomKernel())
    fs = get_filesystem("mem:///")
    pts = np.zeros((160, 2), np.float32)
    import io as _io
    buf = _io.BytesIO()
    np.save(buf, pts)
    fs.write_bytes("/bw/points.npy", buf.getvalue())
    conf = JobConf()
    conf.set_input_paths("mem:///bw/points.npy")
    conf.set_output_path("mem:///bw/out")
    conf.set_input_format(DenseInputFormat)
    conf.set("tpumr.dense.split.rows", 40)  # 4 splits, one window
    conf.set_map_kernel("boom-on-third")
    conf.set_num_reduce_tasks(0)
    conf.set("tpumr.local.run.on.tpu", True)
    with pytest.raises(RuntimeError, match="kernel exploded"):
        run_job(conf)
    assert not fs.exists("mem:///bw/out/part-00000")  # nothing committed


def test_hbm_split_cache_hit_on_second_round():
    """Iterative jobs stage each dense split once: round 2 reports zero
    newly-staged device bytes (HBM-resident split cache)."""
    from tpumr.mapred.tpu_runner import clear_split_caches, _split_caches
    from tpumr.ops.kmeans import clear_centroid_cache
    clear_split_caches()
    clear_centroid_cache()
    fs = get_filesystem("mem:///")
    pts = np.random.default_rng(7).normal(size=(64, 2)).astype(np.float32)
    _save_npy(fs, "/kc/points.npy", pts)
    _save_npy(fs, "/kc/centroids.npy", np.eye(2, dtype=np.float32))

    def round_conf(i):
        conf = JobConf()
        conf.set_input_paths("mem:///kc/points.npy")
        conf.set_output_path(f"mem:///kc/out{i}")
        conf.set_input_format(DenseInputFormat)
        conf.set("tpumr.kmeans.centroids", "mem:///kc/centroids.npy")
        conf.set_map_kernel("kmeans-assign")
        conf.set_reducer_class(CentroidReducer)
        conf.set_num_reduce_tasks(1)
        conf.set("tpumr.local.run.on.tpu", True)
        return conf

    r1 = run_job(round_conf(1))
    staged1 = r1.counters.value(BackendCounter.GROUP,
                                BackendCounter.TPU_DEVICE_BYTES_STAGED)
    assert staged1 == pts.nbytes
    r2 = run_job(round_conf(2))
    staged2 = r2.counters.value(BackendCounter.GROUP,
                                BackendCounter.TPU_DEVICE_BYTES_STAGED)
    assert staged2 == 0
    assert any(c.hits > 0 for c in _split_caches.values())
    clear_split_caches()
