"""PageRank, one iteration a job (``tpumr/ops/pagerank.py``,
``tpumr examples pagerank``): the benchmark's Graph500 generator against a
direct construction, the split cut, the device program and its numpy twin
against a plain float64 reference, a hub, the job through the local runner
and through an in-process cluster on both backends, the shared segmented
scan, and the side-file loader keyed by file identity (K-Means' rewritten
centroid file on a cluster)."""

import io
import json
import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench.families import pagerank as family  # noqa: E402
from tpumr.ops import pagerank as pr  # noqa: E402

SEED = 2_400_000_041
D = 0.85
GAP = 1e-5          # the float32 program against float64, here


def reference(dst, src, vertices: int, iterations: int) -> np.ndarray:
    """The specification's equation in float64, plainly."""
    outdeg = np.bincount(src, minlength=vertices)
    ranks = np.full(vertices, 1.0 / vertices)
    for _ in range(iterations):
        c = np.where(outdeg > 0, ranks / np.maximum(outdeg, 1), 0.0)
        y = np.bincount(dst, weights=c[src], minlength=vertices)
        dangling = ranks[outdeg == 0].sum()
        ranks = (1 - D) / vertices + D * (y + dangling / vertices)
    return ranks


def graph(kind: str, vertices: int = 3000, arcs: int = 40000):
    """(dst, src) of a seeded graph: ``undirected`` (both ways, no
    self-loop, no duplicate, no isolated vertex) or ``directed`` with
    vertices that have no out-arc (dangling) and some with no in-arc."""
    rng = np.random.default_rng([SEED, len(kind)])
    u = (vertices * rng.random(arcs) ** 2).astype(np.int64)
    v = rng.integers(0, vertices, arcs)
    if kind == "directed":
        keep = (u != v) & (v < vertices - 100)      # the last 100 dangle
        return u[keep], v[keep]
    keep = u != v
    keys = np.unique(np.concatenate([u[keep] * vertices + v[keep],
                                     v[keep] * vertices + u[keep]]))
    dst, src = keys // vertices, keys % vertices
    present = np.zeros(vertices, bool)
    present[dst] = True
    ids = np.cumsum(present) - 1
    return ids[dst], ids[src]


# --------------------------------------------------- the generator and cut


def _direct_graph(sizes: dict, seed: int):
    """The Graph500 loop edge by edge over the generator's own draws, the
    labels permuted, both ways, self-loops and duplicates dropped, the
    labels left renumbered in order: sorted (dst, src) rows."""
    n = 1 << sizes["scale"]
    edges = n * sizes["edge_factor"]
    a, b, c = sizes["a"], sizes["b"], sizes["c"]
    ab, c_norm, a_norm = a + b, c / (1 - (a + b)), a / (a + b)
    perm = family.permutation(sizes, seed)
    arcs = set()
    for chunk, lo in enumerate(range(0, edges, family.EDGES_PER_CHUNK)):
        m = min(family.EDGES_PER_CHUNK, edges - lo)
        rng = np.random.default_rng([seed, chunk])
        draws = [(rng.random(m), rng.random(m))
                 for _ in range(sizes["scale"])]
        for e in range(m):
            u = v = 0
            for bit, (r1, r2) in enumerate(draws):
                ii = r1[e] > ab
                jj = r2[e] > (c_norm if ii else a_norm)
                u += int(ii) << bit
                v += int(jj) << bit
            u, v = int(perm[u]), int(perm[v])
            if u != v:
                arcs.add((u, v))
                arcs.add((v, u))
    ids = {x: i for i, x in enumerate(sorted({u for u, _ in arcs}))}
    return np.array(sorted((ids[u], ids[v]) for u, v in arcs), np.int64)


@pytest.mark.parametrize("scale", [8, 10, 12])
def test_the_generator_is_graph500s_loop_deduplicated(tmp_path, scale):
    sizes = {"scale": scale, "edge_factor": 16, "a": 0.57, "b": 0.19,
             "c": 0.19, "splits": 4}
    inputs = family.make_input(sizes, SEED, str(tmp_path))
    got = np.concatenate([np.load(p) for p in inputs["splits"]])
    want = _direct_graph(sizes, SEED)
    assert got.dtype == np.int32 and got.shape[1] == 2
    assert np.array_equal(got.astype(np.int64), want)
    assert inputs["arcs"] == len(want)
    assert inputs["vertices"] == want[:, 0].max() + 1
    outdeg = np.load(os.path.join(inputs["graph"], "outdeg.npy"))
    assert np.array_equal(outdeg, np.bincount(want[:, 1]))
    assert (outdeg > 0).all()       # no isolated vertex is left


def _check_cut(paths: "list[str]", vertices: int, arcs: int) -> None:
    """The split files tile the vertices by destination range, every arc
    is in one file, destinations are ordered, and the files hold about
    equal arcs (apart by at most the largest in-degree)."""
    at, total, sizes = 0, 0, []
    for p in paths:
        lo, hi = pr.range_of(p)
        a = np.load(p)
        assert lo == at and hi > lo
        assert ((a[:, 0] >= lo) & (a[:, 0] < hi)).all()
        assert (np.diff(a[:, 0]) >= 0).all()
        at, total = hi, total + len(a)
        sizes.append(len(a))
    assert at == vertices and total == arcs
    dst = np.concatenate([np.load(p)[:, 0] for p in paths])
    hub = np.bincount(dst).max()
    assert max(sizes) - min(sizes) <= 2 * hub + 1


@pytest.mark.parametrize("who", ["program", "benchmark"])
def test_the_cut_covers_every_arc_once_in_balanced_ranges(tmp_path, who):
    if who == "program":
        dst, src = graph("directed")
        paths = pr.write_graph(str(tmp_path), dst, src, 3000, 6)
        _check_cut(paths, 3000, len(dst))
        return
    sizes = {"scale": 12, "edge_factor": 16, "a": 0.57, "b": 0.19,
             "c": 0.19, "splits": 6}
    inputs = family.make_input(sizes, SEED, str(tmp_path))
    _check_cut(inputs["splits"], inputs["vertices"], inputs["arcs"])


def test_a_hub_is_never_cut_and_no_range_is_empty():
    indeg = np.array([1, 1, 1000, 1, 1, 1, 1, 1])
    for cut in (pr.cut_ranges, family.cut_ranges):
        ranges = cut(indeg, 4)
        assert len(ranges) == 4
        assert ranges[0][0] == 0 and ranges[-1][1] == 8
        assert all(lo < hi for lo, hi in ranges)
        assert all(a[1] == b[0] for a, b in zip(ranges, ranges[1:]))


# ------------------------------------------------ the map and the reference


def _iterate_with(impl: str, dst, src, vertices: int, iterations: int,
                  tmp_path) -> np.ndarray:
    """``iterations`` rounds with the map of ``impl`` over the split files
    and the reducer's arithmetic (the reducer's own, through its class)."""
    paths = pr.write_graph(str(tmp_path / "g"), dst, src, vertices, 5)
    outdeg = np.load(tmp_path / "g" / "outdeg.npy")
    ranks = np.full(vertices, 1.0 / vertices, np.float32)
    for _ in range(iterations):
        c = np.where(outdeg > 0, ranks / np.maximum(outdeg, 1),
                     0).astype(np.float32)
        y = np.zeros(vertices, np.float32)
        for p in paths:
            lo, hi = pr.range_of(p)
            arcs = np.load(p)
            if impl == "device":
                y[lo:hi] = np.asarray(pr.pagerank_map_device(
                    pr.pad_arcs(arcs), pr.pad_contrib(c), lo,
                    hi - lo))[:hi - lo]
            else:
                y[lo:hi] = pr.pagerank_map_numpy(arcs, c, lo, hi - lo)
        dangling = ranks[outdeg == 0].sum(dtype=np.float64)
        ranks = ((1 - D) / vertices
                 + D * (y + dangling / vertices)).astype(np.float32)
    return ranks


@pytest.mark.parametrize("iterations", [1, 3])
@pytest.mark.parametrize("kind", ["undirected", "directed"])
@pytest.mark.parametrize("impl", ["device", "numpy"])
def test_the_map_against_the_float64_reference(tmp_path, impl, kind,
                                               iterations):
    dst, src = graph(kind)
    vertices = int(max(dst.max(), src.max())) + 1
    got = _iterate_with(impl, dst, src, vertices, iterations, tmp_path)
    want = reference(dst, src, vertices, iterations)
    assert np.max(np.abs(got - want) / want) < GAP
    assert abs(got.sum(dtype=np.float64) - 1) < 1e-5


@pytest.mark.parametrize("impl", ["device", "numpy"])
def test_a_hub_of_a_hundred_thousand_in_arcs(impl):
    """One destination with 10^5 in-arcs (graph500-23's largest has about
    2.6 x 10^5): the device's tree of float32 adds stays within the
    tolerance of the float64 sum, and so does the twin's one rounding."""
    rng = np.random.default_rng(SEED)
    hub = 100_000
    dst = np.concatenate([np.full(hub, 7), 8 + np.arange(5000) // 3])
    src = rng.integers(0, 50_000, len(dst))
    arcs = np.stack([dst, src], axis=1).astype(np.int32)
    c = (rng.random(50_000) * 2e-7).astype(np.float32)
    want = np.bincount(dst, weights=c.astype(np.float64)[src],
                       minlength=8 + 5000 // 3 + 1)[5:]
    if impl == "device":
        got = np.asarray(pr.pagerank_map_device(arcs, c, 5,
                                                len(want)))[:len(want)]
    else:
        got = pr.pagerank_map_numpy(arcs, c, 5, len(want))
    assert got[0] == got[1] == 0.0                  # no in-arc
    hit = want > 0
    assert np.max(np.abs(got[hit] - want[hit]) / want[hit]) < GAP


def test_graphs_of_about_one_size_reuse_the_compiled_program():
    """Every seed's graph has its own arc and vertex counts; padded to
    their buckets on the host they run the executables the first one
    compiled, and no program pads them on the device."""
    rng = np.random.default_rng(SEED)
    sizes = []
    for n in (50_000, 51_234, 52_001):
        dst = np.sort(rng.integers(0, 900, n))
        arcs = np.stack([dst, rng.integers(0, 3000 + n % 7, n)],
                        axis=1).astype(np.int32)
        c = rng.random(3000 + n % 7).astype(np.float32)
        want = pr.pagerank_map_numpy(arcs, c, 0, 900)
        got = np.asarray(pr.pagerank_map_device(
            pr.pad_arcs(arcs), pr.pad_contrib(c), 0, 900))[:900]
        assert np.max(np.abs(got - want) / np.maximum(want, 1e-30)) < GAP
        sizes.append((pr._pagerank_map._cache_size(),
                      pr._last_arcs._cache_size()))
    assert sizes[0] == sizes[1] == sizes[2]


def test_a_split_is_staged_padded_once_and_counts_its_own_arcs(tmp_path):
    """On a TPU slot a split is uploaded in the kernel's staged form,
    padded to its bucket on the host; the split cache keeps it with the
    split's own count, which is what the runners count. A CPU slot
    stages the file's rows as they are."""
    import jax
    from tpumr.examples.pagerank import round_conf
    from tpumr.mapred.split import InputSplit
    from tpumr.mapred.input_formats import DenseInputFormat
    from tpumr.mapred.tpu_runner import clear_split_caches, stage_batch
    dst, src = graph("undirected")
    vertices = int(dst.max()) + 1
    (path,) = pr.write_graph(str(tmp_path / "g"), dst, src, vertices, 1)
    conf = round_conf(f"file://{tmp_path}/g", f"file://{tmp_path}/w", 1)
    (split,) = DenseInputFormat().get_splits(conf, 1)

    class Task:
        pass
    task = Task()
    task.split = split.to_dict()
    assert InputSplit.from_dict(task.split).num_rows == len(dst)
    device = jax.devices()[0]
    clear_split_caches()
    try:
        first, _, staged = stage_batch(conf, None, task, device)
        again, _, restaged = stage_batch(conf, None, task, device)
    finally:
        clear_split_caches()
    padded = pr.pad_arcs(np.load(path))
    assert first.values.shape == padded.shape != (len(dst), 2)
    assert np.asarray(first.values).tobytes() == padded.tobytes()
    assert staged == padded.nbytes and restaged == 0
    assert first.num_records == again.num_records == len(dst)
    assert again.values is first.values
    host, _, _ = stage_batch(conf, None, task)
    assert host.num_records == len(dst) and host.values.shape == (len(dst), 2)


def test_the_device_program_has_no_scatter_and_no_loop():
    import jax
    arcs = jax.ShapeDtypeStruct((1 << 16, 2), np.int32)
    c = jax.ShapeDtypeStruct((4096,), np.float32)
    ends = jax.ShapeDtypeStruct((512,), np.int32)
    lo = jax.ShapeDtypeStruct((), np.int32)
    for text in (pr._pagerank_map.lower(arcs, c, ends).as_text(),
                 pr._last_arcs.lower(arcs, lo, 512).as_text()):
        assert "scatter" not in text and "while" not in text
        assert "gather" in text


# ------------------------------------------------------------------ the job


def _example(graph_dir: str, work: str, defs: "list[str]",
             iterations: int = 3) -> None:
    from tpumr.examples import main
    argv = ["pagerank", graph_dir, work, "--iterations", str(iterations)]
    for d in defs:
        argv += ["-D", d]
    assert main(argv) == 0


@pytest.mark.parametrize("on_tpu", [False, True])
def test_the_example_through_the_local_runner(tmp_path, on_tpu):
    dst, src = graph("directed")
    pr.write_graph(str(tmp_path / "g"), dst, src, 3000, 4)
    _example(f"file://{tmp_path}/g", f"file://{tmp_path}/w",
             [f"tpumr.local.run.on.tpu={str(on_tpu).lower()}"])
    want = reference(dst, src, 3000, 3)
    got = np.load(tmp_path / "w" / "r3" / "ranks.npy")
    assert np.max(np.abs(got - want) / want) < GAP


def test_the_example_on_a_cluster_maps_on_both_backends(tmp_path):
    """``tpumr examples pagerank --iterations 3`` against an in-process
    cluster (one TPU slot, three CPU slots): the first job's maps land on
    both backends, every job counts each arc once on the backend that
    mapped it, and the ranks are the reference's."""
    from tpumr.mapred.mini_cluster import MiniMRCluster
    from tpumr.mapred.jobconf import JobConf
    dst, src = graph("undirected")
    vertices = int(dst.max()) + 1
    pr.write_graph(str(tmp_path / "g"), dst, src, vertices, 8)
    conf = JobConf()
    conf.set("tpumr.history.dir", str(tmp_path / "hist"))
    with MiniMRCluster(num_trackers=1, cpu_slots=3, tpu_slots=1,
                       conf=conf) as c:
        _example(f"file://{tmp_path}/g", f"file://{tmp_path}/w",
                 [f"mapred.job.tracker={c.master_address}"])
        time.sleep(0.5)
        rollups = []
        for p in sorted((tmp_path / "hist").glob("metrics-job_*.json")):
            rollups.append(json.loads(p.read_text()))
    assert len(rollups) == 3
    both = {"tpu": 0, "cpu": 0}
    for r in rollups:
        g = r["counters"][pr.COUNTER_GROUP]
        tpu, cpu = g.get(pr.ARCS_TPU, 0), g.get(pr.ARCS_CPU, 0)
        assert tpu + cpu == len(dst)
        both["tpu"] += tpu
        both["cpu"] += cpu
    assert both["tpu"] > 0 and both["cpu"] > 0
    want = reference(dst, src, vertices, 3)
    for it in (1, 2, 3):
        got = np.load(tmp_path / "w" / f"r{it}" / "ranks.npy")
        assert np.max(np.abs(got - reference(dst, src, vertices, it))
                      / reference(dst, src, vertices, it)) < GAP
    assert np.allclose(got, want, rtol=GAP)


def test_a_range_missing_fails_the_reduce(tmp_path):
    from tpumr.mapred.jobconf import JobConf
    dst, src = graph("undirected")
    vertices = int(dst.max()) + 1
    pr.write_graph(str(tmp_path / "g"), dst, src, vertices, 2)
    outdeg = np.load(tmp_path / "g" / "outdeg.npy")
    from tpumr.fs import get_filesystem
    pr.write_state(get_filesystem("file:///"), f"{tmp_path}/r0",
                   np.full(vertices, 1.0 / vertices), outdeg)
    conf = JobConf()
    conf.set_input_paths(f"file://{tmp_path}/g/arcs")
    conf.set("tpumr.pagerank.state", f"{tmp_path}/r0")
    conf.set("tpumr.pagerank.state.out", f"{tmp_path}/r1")
    red = pr.PageRankReducer()
    red.configure(conf)

    class Out:
        def collect(self, k, v):
            pass
    red.reduce(0, iter([np.zeros(10, np.float32)]), Out(), None)
    with pytest.raises(ValueError, match="ranges"):
        red.close()
    assert not (tmp_path / "r1").exists()


# --------------------------------------------------------- shared pieces


def _segment_sum_before(key_cols: int):
    """The aggregation's kernel as it was before its scan was shared."""
    import jax
    import jax.numpy as jnp
    from tpumr.ops.segment_sum import _pulled, _shifted

    @jax.jit
    def program(words, n_live):
        n = words.shape[1]
        keys = words[:key_cols]
        idx = jnp.arange(n, dtype=jnp.int32)
        valid = idx < n_live
        differs = jnp.any(keys != _shifted(keys, 1, 0), axis=0)
        first = differs | (idx == 0) | ~valid
        x = jnp.where(valid, jax.lax.bitcast_convert_type(
            words[key_cols], jnp.float32), 0.0)
        f, shift = first, 1
        while shift < n:
            x = jnp.where(f, x, _shifted(x, shift, 0.0) + x)
            f = f | _shifted(f, shift, True)
            shift *= 2
        last = valid & _pulled(first, 1, True)
        group = jnp.cumsum((first & valid).astype(jnp.int32)) - 1
        groups = jnp.sum(first & valid, dtype=jnp.int32)
        rows = jnp.concatenate(
            [keys, jax.lax.bitcast_convert_type(x, jnp.uint32)[None]])
        live, move, shift = last, idx - group, 1
        while shift < n:
            come = _pulled(live & ((move & shift) != 0), shift, False)
            stay = live & ((move & shift) == 0)
            rows = jnp.where(come[None], _pulled(rows, shift, 0), rows)
            move = jnp.where(come, _pulled(move, shift, 0), move)
            live = come | stay
            shift *= 2
        return rows, groups

    return program


def test_the_aggregations_kernel_is_bit_for_bit_what_it_was():
    from tpumr.ops.segment_sum import segment_sum_host, segment_sum_program
    rng = np.random.default_rng(SEED)
    runs = np.concatenate([np.arange(1, 60), [300, 1, 1, 2049]])
    keys = np.sort(rng.choice(1 << 30, len(runs), replace=False)) \
        .astype(np.uint32)
    n, pad = int(runs.sum()), 8192
    words = np.zeros((2, pad), np.uint32)
    words[0, :n] = np.repeat(keys, runs)
    words[0, n:] = 0xFFFFFFFF
    words[1, :n] = rng.uniform(1, 1000, n).astype(np.float32).view(np.uint32)
    now, g_now = segment_sum_program(1)(words, np.int32(n))
    before, g_before = _segment_sum_before(1)(words, np.int32(n))
    assert int(g_now) == int(g_before) == len(runs)
    assert np.asarray(now).tobytes() == np.asarray(before).tobytes()
    rows = np.concatenate([words[0, :n].astype(">u4").view(np.uint8)
                           .reshape(n, 4),
                           words[1, :n].astype("<u4").view(np.uint8)
                           .reshape(n, 4)], axis=1)
    twin = segment_sum_host(rows, 4)
    sums = np.asarray(now)[1, :len(runs)].view(np.float32)
    assert twin[:, 4:].copy().view("<f4")[:, 0].tobytes() == sums.tobytes()


def test_a_side_file_rewritten_in_place_is_read_again(tmp_path):
    from tpumr.ops import devcache
    from tpumr.mapred.jobconf import JobConf
    conf = JobConf()
    path = f"file://{tmp_path}/c.npy"
    np.save(tmp_path / "c.npy", np.zeros(4, np.float32))
    a = devcache.device_side_file("t-side", path, conf)
    assert devcache.device_side_file("t-side", path, conf) is a
    time.sleep(0.01)
    np.save(tmp_path / "c.npy", np.ones(4, np.float32))   # same length
    b = devcache.device_side_file("t-side", path, conf)
    assert np.asarray(b).tolist() == [1.0] * 4
    assert devcache.side_file("t-side", path, conf).tolist() == [1.0] * 4
    # the stale version's copies are gone
    assert sum(1 for k, _ in devcache._cache_for(conf).snapshot()
               if k[0] == f"t-side:{path}") == 1
    devcache.clear_side_files("t-side")


def test_a_seeded_side_file_is_on_the_device_as_a_map_would_upload_it(
        tmp_path, monkeypatch):
    """The reducer seeds the next round's contributions on the slot
    devices in the form the maps upload (padded to the vertices' bucket),
    and keeps the host copy as written."""
    import jax
    from tpumr.mapred.jobconf import JobConf
    from tpumr.ops import devcache
    from tpumr.parallel import jaxruntime
    monkeypatch.setattr(jaxruntime, "known_accelerator_devices",
                        lambda: jax.devices()[:1])
    conf = JobConf()
    contrib = np.arange(1, 3388, dtype=np.float32)
    np.save(tmp_path / "contrib.npy", contrib)
    path = f"file://{tmp_path}/contrib.npy"
    try:
        with jax.default_device(jax.devices()[0]):
            devcache.seed_side_file("t-seed", path, conf, contrib,
                                    pr.pad_contrib)
            got = devcache.device_side_file("t-seed", path, conf,
                                            pr.pad_contrib)
        assert np.asarray(got).tobytes() == pr.pad_contrib(contrib).tobytes()
        assert got.shape == (pr._bucket(len(contrib)),)
        assert devcache.side_file("t-seed", path, conf) is contrib
    finally:
        devcache.clear_side_files("t-seed")


def test_kmeans_rewriting_one_centroid_file_iterates_on_a_cluster(tmp_path):
    """``tpumr examples kmeans`` rewrites ONE ``centroids.npy`` between
    rounds. The tracker keys its copies by the file's path, length and
    mtime, so round 2 on a cluster reads round 1's centroids, not the
    seeds again, and the second round's centroids are the reference's."""
    from tpumr.examples import main
    from tpumr.mapred.mini_cluster import MiniMRCluster
    rng = np.random.default_rng(SEED)
    points = rng.uniform(-4, 4, (900, 2)).astype(np.float32)
    np.save(tmp_path / "p.npy", points)
    with MiniMRCluster(num_trackers=1, cpu_slots=2, tpu_slots=1) as c:
        assert main(["kmeans", f"file://{tmp_path}/p.npy",
                     f"file://{tmp_path}/out", "-k", "3", "-i", "2",
                     "--split-rows", "300",
                     "-D", f"mapred.job.tracker={c.master_address}"]) == 0
    rounds = [points[:3].astype(np.float64)]
    for _ in range(2):
        cents = rounds[-1]
        d2 = ((points[:, None, :] - cents[None]) ** 2).sum(axis=2)
        a = d2.argmin(axis=1)
        rounds.append(np.array([points[a == k].mean(axis=0)
                                if (a == k).any() else cents[k]
                                for k in range(3)]))
    assert np.abs(rounds[2] - rounds[1]).max() > 0.05   # round 2 moves
    got = np.load(io.BytesIO((tmp_path / "out" / "centroids.npy")
                             .read_bytes()))
    assert np.allclose(got, rounds[2], atol=1e-3)
