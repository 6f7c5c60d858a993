#!/usr/bin/env python3
"""Time one PageRank split's map on whatever device this process has, and
its numpy twin on this host: alone, and beside two numpy maps running in
threads of the same interpreter (as a tracker runs a TPU map beside CPU
maps).

    python3 misc/pagerank_probe.py [--arcs 65000000,32000000,16000000]
                                   [--vertices 4610000] [--repeats 5]

A split of ``A`` arcs holds every in-arc of a range of about
``vertices * A / total_arcs`` destinations (graph500-23: 4.61M vertices,
259M arcs); destinations follow a power law inside the range, sources are
uniform over the vertices. One JSON line a measurement: seconds are the
median of ``--repeats`` runs, a device map timed from its dispatch to its
result on the host, as a map task fetches it. Rehearse on the CPU with
``JAX_PLATFORMS=cpu python3 misc/pagerank_probe.py --arcs 400000
--vertices 20000 --repeats 2``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TOTAL_ARCS = 259_000_000


def make_split(arcs: int, vertices: int, seed: int = 7):
    """``[arcs, 2]`` int32 (destination, source) ordered by destination,
    and the range ``[lo, hi)`` it covers."""
    rng = np.random.default_rng(seed)
    nv = max(1, vertices * arcs // TOTAL_ARCS)
    lo = vertices // 3
    dst = lo + np.minimum((nv * rng.random(arcs) ** 3).astype(np.int64),
                          nv - 1)
    dst.sort()
    out = np.empty((arcs, 2), np.int32)
    out[:, 0] = dst
    out[:, 1] = rng.integers(0, vertices, arcs, dtype=np.int32)
    return out, lo, lo + nv


def median_s(fn, repeats: int) -> float:
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arcs", default="65000000,32000000,16000000")
    ap.add_argument("--vertices", type=int, default=4_610_000)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from tpumr.ops.pagerank import (_last_arcs, _pagerank_map,
                                    pagerank_map_numpy)
    from tpumr.ops.segment_sum import segmented_scan

    dev = jax.devices()[0]
    print(json.dumps({"device": dev.platform, "kind": dev.device_kind,
                      "cores": os.cpu_count()}), flush=True)
    contrib = np.random.default_rng(1).random(args.vertices,
                                              dtype=np.float32) * 1e-7
    c_dev = jax.device_put(contrib, dev)
    gather = jax.jit(lambda c, a: jnp.take(c, a[:, 1], mode="clip"))
    scan = jax.jit(lambda x, a: segmented_scan(
        x, jnp.concatenate([jnp.ones((1,), bool),
                            a[1:, 0] != a[:-1, 0]])))
    for n in [int(a) for a in args.arcs.split(",")]:
        arcs, lo, hi = make_split(n, args.vertices)
        t0 = time.perf_counter()
        a_dev = jax.device_put(arcs, dev)
        a_dev.block_until_ready()
        stage_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        ends = _last_arcs(a_dev, np.int32(lo), hi - lo)
        ends.block_until_ready()
        ends_s = time.perf_counter() - t0

        def device_map():
            return np.asarray(_pagerank_map(a_dev, c_dev, ends))

        t0 = time.perf_counter()
        device_map()
        compile_s = time.perf_counter() - t0
        x = gather(c_dev, a_dev)
        scan(x, a_dev).block_until_ready()
        row = {"arcs": n, "vertices": hi - lo, "stage_s": stage_s,
               "first_ends_s": ends_s, "first_call_s": compile_s,
               "device_map_s": median_s(device_map, args.repeats),
               "gather_s": median_s(
                   lambda: gather(c_dev, a_dev).block_until_ready(),
                   args.repeats),
               "scan_s": median_s(
                   lambda: scan(x, a_dev).block_until_ready(),
                   args.repeats)}
        want = pagerank_map_numpy(arcs, contrib, lo, hi - lo)
        got = device_map()
        row["max_rel_gap"] = float(np.max(np.abs(got - want)
                                          / np.maximum(want, 1e-30)))
        row["cpu_map_s"] = median_s(
            lambda: pagerank_map_numpy(arcs, contrib, lo, hi - lo),
            max(1, args.repeats // 2))

        # beside two numpy maps in threads of this interpreter
        stop = threading.Event()

        def spin():
            while not stop.is_set():
                pagerank_map_numpy(arcs, contrib, lo, hi - lo)

        threads = [threading.Thread(target=spin, daemon=True)
                   for _ in range(2)]
        for t in threads:
            t.start()
        time.sleep(0.5)
        row["device_map_beside_2_s"] = median_s(device_map, args.repeats)
        row["cpu_map_beside_2_s"] = median_s(
            lambda: pagerank_map_numpy(arcs, contrib, lo, hi - lo),
            max(1, args.repeats // 2))
        stop.set()
        for t in threads:
            t.join()
        row["cpu_over_device"] = row["cpu_map_s"] / row["device_map_s"]
        print(json.dumps(row), flush=True)
        del a_dev, x
    return 0


if __name__ == "__main__":
    sys.exit(main())
