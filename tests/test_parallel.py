"""Parallel-layer tests on the virtual 8-device CPU mesh: collectives,
device shuffle, sequence-parallel map, distributed K-Means step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpumr.parallel import (
    make_mesh, replicate, ring_pass, sequence_parallel_map, shard_over,
    shuffle_dense,
)
from tpumr.parallel.collectives import map_reduce
from tpumr.parallel.seqmap import ring_scan_map

NDEV = 8


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= NDEV, "conftest must force 8 CPU devices"
    return make_mesh(NDEV)


def test_mesh_shapes():
    m = make_mesh(8)
    assert m.shape == {"data": 8}
    m2 = make_mesh(shape=(4, 2), axis_names=("data", "model"))
    assert m2.shape == {"data": 4, "model": 2}
    with pytest.raises(ValueError):
        make_mesh(shape=(64,))


def test_shard_and_replicate(mesh):
    x = np.arange(32, dtype=np.float32).reshape(16, 2)
    xs = shard_over(mesh, x)
    assert xs.sharding.spec[0] == "data"
    np.testing.assert_array_equal(np.asarray(xs), x)
    c = replicate(mesh, np.ones(3))
    assert c.sharding.spec == jax.sharding.PartitionSpec()


def test_map_reduce_sums_over_mesh(mesh):
    x = np.arange(64, dtype=np.float32).reshape(64, 1)
    xs = shard_over(mesh, x)
    fn = map_reduce(mesh, lambda shard: {"s": jnp.sum(shard),
                                         "n": jnp.array(shard.shape[0])})
    out = fn(xs)
    assert float(out["s"]) == x.sum()
    assert int(out["n"]) == 64  # psum of per-shard counts


def test_shuffle_dense_repartitions_by_key(mesh):
    rng = np.random.default_rng(0)
    n, d = 512, 4
    values = rng.normal(size=(n, d)).astype(np.float32)
    keys = rng.integers(0, 1000, size=n).astype(np.int32)
    dest = (keys % NDEV).astype(np.int32)

    vs = shard_over(mesh, values)
    ds = shard_over(mesh, dest)
    ks = shard_over(mesh, keys)
    res = shuffle_dense(mesh, vs, ds, capacity=n // NDEV, keys=ks)
    assert int(res.overflow) == 0

    got_vals = np.asarray(res.values)
    got_valid = np.asarray(res.valid)
    got_keys = np.asarray(res.keys)
    # received arrays are globally sharded: device p holds slots
    # [p*ndev*cap, (p+1)*ndev*cap) — every valid record must have landed on
    # the device matching its key, and nothing may be lost
    cap = n // NDEV
    per_dev = NDEV * cap
    seen = []
    for p in range(NDEV):
        sl = slice(p * per_dev, (p + 1) * per_dev)
        vmask = got_valid[sl]
        kk = got_keys[sl][vmask]
        assert (kk % NDEV == p).all(), f"wrong-device records on {p}"
        seen.extend(kk.tolist())
    assert sorted(seen) == sorted(keys.tolist())
    # spot-check payloads travelled with their keys
    lookup = {}
    for i in range(n):
        lookup.setdefault(int(keys[i]), []).append(values[i])
    flat_valid = got_valid
    for idx in np.nonzero(flat_valid)[0][:50]:
        k = int(got_keys[idx])
        assert any(np.allclose(got_vals[idx], v) for v in lookup[k])


def test_shuffle_overflow_detected(mesh):
    n = 64
    values = np.ones((n, 2), np.float32)
    dest = np.zeros(n, np.int32)  # everything to device 0 — skew
    res = shuffle_dense(mesh, shard_over(mesh, values),
                        shard_over(mesh, dest), capacity=2)
    # each device could send only 2 of its 8 records to dev 0
    assert int(res.overflow) == n - NDEV * 2
    assert int(np.asarray(res.valid).sum()) == NDEV * 2


def test_sequence_parallel_map(mesh):
    x = np.arange(64, dtype=np.float32)
    fn = sequence_parallel_map(mesh, lambda s: s * 2 + 1)
    out = np.asarray(fn(shard_over(mesh, x)))
    np.testing.assert_array_equal(out, x * 2 + 1)


def test_ring_pass_rotates_shards(mesh):
    x = np.repeat(np.arange(NDEV, dtype=np.float32), 4)  # shard i holds i
    out = np.asarray(ring_pass(mesh)(shard_over(mesh, x)))
    expect = np.repeat((np.arange(NDEV) - 1) % NDEV, 4).astype(np.float32)
    np.testing.assert_array_equal(out, expect)


def test_ring_scan_folds_entire_axis(mesh):
    """After n hops of the ring every chip's state has seen every shard."""
    x = np.arange(64, dtype=np.float32)
    init = np.zeros(64, np.float32)  # per-chip state, sharded (8 each)
    fn = ring_scan_map(mesh, lambda state, visiting, hop: state + visiting.sum())
    out = np.asarray(fn(shard_over(mesh, init), shard_over(mesh, x)))
    np.testing.assert_allclose(out, np.full(64, x.sum()))


def test_distributed_kmeans_step_matches_single_device(mesh):
    from tpumr.ops.kmeans import make_distributed_step, _assign_and_partials_jax
    rng = np.random.default_rng(5)
    pts = rng.normal(size=(256, 4)).astype(np.float32)
    cents = rng.normal(size=(5, 4)).astype(np.float32)

    step = make_distributed_step(mesh)
    new_c, counts = step(shard_over(mesh, pts), replicate(mesh, cents))

    # single-device reference
    _a, sums, cnt = _assign_and_partials_jax(pts, cents)
    expect = np.where(np.asarray(cnt)[:, None] > 0,
                      np.asarray(sums) / np.maximum(np.asarray(cnt), 1)[:, None],
                      cents)
    np.testing.assert_allclose(np.asarray(new_c), expect, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(cnt))


def test_multihost_spec_and_single_host_noop(monkeypatch):
    """Multi-host bring-up: conf keys beat env, nothing-configured is a
    single-host no-op whose global mesh covers the local devices."""
    import jax

    from tpumr.mapred.jobconf import JobConf
    from tpumr.parallel import multihost

    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    monkeypatch.delenv("JAX_NUM_PROCESSES", raising=False)
    monkeypatch.delenv("JAX_PROCESS_ID", raising=False)
    assert multihost.distributed_spec(None) is None

    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "envhost:1234")
    monkeypatch.setenv("JAX_NUM_PROCESSES", "4")
    monkeypatch.setenv("JAX_PROCESS_ID", "2")
    spec = multihost.distributed_spec(None)
    assert spec == {"coordinator_address": "envhost:1234",
                    "num_processes": 4, "process_id": 2}

    conf = JobConf()
    conf.set("tpumr.distributed.coordinator", "confhost:9")
    conf.set("tpumr.distributed.num.processes", 8)
    spec = multihost.distributed_spec(conf)
    assert spec["coordinator_address"] == "confhost:9"   # conf wins
    assert spec["num_processes"] == 8
    assert spec["process_id"] == 2                       # env fallback

    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS")
    monkeypatch.delenv("JAX_NUM_PROCESSES")
    monkeypatch.delenv("JAX_PROCESS_ID")
    assert multihost.ensure_initialized(None) is False   # no-op path
    mesh = multihost.global_mesh(None)
    assert mesh.devices.size == len(jax.devices())
    assert multihost.process_info() == (0, 1)


class TestPersistentCompilationCache:
    """Cache placement (parallel/jaxruntime.py): JAX_COMPILATION_CACHE_DIR
    wins and the code then sets no directory; else the operator's
    ``tpumr.jax.cache.dir``; else one fixed directory in the checkout."""

    @pytest.fixture
    def runtime(self, monkeypatch):
        """jaxruntime reset before and after, JAX's cache dir restored,
        and no cache directory in the environment unless a test sets it."""
        import jax

        from tpumr.parallel import jaxruntime
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        prev = jax.config.jax_compilation_cache_dir
        jaxruntime._reset_for_tests()
        yield jaxruntime
        jax.config.update("jax_compilation_cache_dir", prev)
        jaxruntime._reset_for_tests()

    def test_conf_key_lands_in_jax_config(self, runtime, tmp_path):
        import jax

        from tpumr.mapred.jobconf import JobConf
        conf = JobConf()
        conf.set("tpumr.jax.cache.dir", str(tmp_path / "jc"))
        got = runtime.configure_persistent_cache(conf)
        assert got == str(tmp_path / "jc")
        assert jax.config.jax_compilation_cache_dir == got
        # two calls agree: the second caller (different conf) is a no-op
        other = JobConf()
        other.set("tpumr.jax.cache.dir", str(tmp_path / "other"))
        assert runtime.configure_persistent_cache(other) == got
        assert not (tmp_path / "other").exists()

    @pytest.mark.parametrize("off", ["none", "off", "disabled", ""])
    def test_disabled_by_conf(self, runtime, off):
        import jax

        from tpumr.mapred.jobconf import JobConf
        prev = jax.config.jax_compilation_cache_dir
        conf = JobConf()
        conf.set("tpumr.jax.cache.dir", off)
        assert runtime.configure_persistent_cache(conf) is None
        assert jax.config.jax_compilation_cache_dir == prev

    def test_environment_variable_wins_and_code_sets_no_directory(
            self, runtime, tmp_path, monkeypatch):
        """With JAX_COMPILATION_CACHE_DIR set, JAX's own handling stands:
        configure_persistent_cache must not update the directory (JAX
        read the variable at import; what it holds now is untouched)."""
        import jax

        from tpumr.mapred.jobconf import JobConf
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "from-env"))
        updates = []
        real_update = jax.config.update
        monkeypatch.setattr(
            jax.config, "update",
            lambda k, v: (updates.append(k), real_update(k, v))[1])
        before = jax.config.jax_compilation_cache_dir
        conf = JobConf()
        conf.set("tpumr.jax.cache.dir", str(tmp_path / "from-conf"))
        got = runtime.configure_persistent_cache(conf)
        assert "jax_compilation_cache_dir" not in updates
        assert got == before == jax.config.jax_compilation_cache_dir
        assert not (tmp_path / "from-conf").exists()

    def test_unset_gives_the_fixed_directory_in_the_checkout(
            self, runtime, monkeypatch):
        import os

        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        assert runtime.DEFAULT_CACHE_DIR == os.path.join(repo_root,
                                                         ".jax_cache")
        monkeypatch.setenv("HOME", "/nonexistent-home")  # never consulted
        got = runtime.configure_persistent_cache(None)
        assert got == runtime.DEFAULT_CACHE_DIR
        assert runtime.configure_persistent_cache(None) == got

    def test_environment_variable_reaches_a_fresh_process(self, tmp_path):
        """End to end in a fresh interpreter: the variable alone places
        the cache, whatever the conf says."""
        import os
        import subprocess
        import sys
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        prog = (
            "import sys\n"
            "sys.path.insert(0, %r)\n"
            "from tpumr.mapred.jobconf import JobConf\n"
            "from tpumr.parallel.jaxruntime import "
            "configure_persistent_cache\n"
            "conf = JobConf()\n"
            "conf.set('tpumr.jax.cache.dir', %r)\n"
            "print(configure_persistent_cache(conf))\n"
        ) % (repo_root, str(tmp_path / "from-conf"))
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "from-env"))
        out = subprocess.run([sys.executable, "-c", prog], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == str(tmp_path / "from-env")
        assert not (tmp_path / "from-conf").exists()

    def test_cache_populates_and_hits_across_processes(self, tmp_path):
        """Two fresh processes share compiles through the cache dir —
        process 1 populates entries, process 2 must HIT (adds none).
        Deterministic entry-count assertions, no wall-clock ratios."""
        import os
        import subprocess
        import sys
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        prog = (
            "import sys\n"
            "sys.path.insert(0, %r)\n"
            "from tpumr.mapred.jobconf import JobConf\n"
            "from tpumr.parallel.jaxruntime import "
            "configure_persistent_cache\n"
            "conf = JobConf()\n"
            "conf.set('tpumr.jax.cache.dir', %r)\n"
            "conf.set('tpumr.jax.cache.min.compile.secs', 0.0)\n"
            "configure_persistent_cache(conf)\n"
            "import jax, jax.numpy as jnp\n"
            "f = jax.jit(lambda x: jnp.sort(x * 2 + 1, axis=0))\n"
            "f(jnp.zeros((4096, 8))).block_until_ready()\n"
        ) % (repo_root, str(tmp_path / "xc"))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("JAX_COMPILATION_CACHE_DIR", None)  # the conf key is tested
        entries = []
        for _ in range(2):
            out = subprocess.run([sys.executable, "-c", prog], env=env,
                                 capture_output=True, text=True, timeout=120)
            assert out.returncode == 0, out.stderr
            entries.append(sorted(os.listdir(tmp_path / "xc")))
        assert entries[0], "cache dir never populated"
        # process 2 compiled nothing new — it loaded process 1's entries
        assert entries[1] == entries[0], (entries[0], entries[1])
