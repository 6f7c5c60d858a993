#!/usr/bin/env python3
"""Time the two K-Means assign implementations on whatever device the
process has: the fused XLA program (``_assign_and_partials_jax``) and the
Pallas kernel with its one-hot sums outside (``assign_and_partials(...,
use_pallas=True)``), at one split's shape.

    python3 misc/assign_probe.py [--rows 500000] [--d 128] [--k 1024]

Prints one JSON line an implementation: the milliseconds of each of
``--reps`` calls after a warm-up call, host clock around
``block_until_ready``, and whether the two agree. Points are whole numbers
0..255 from a fixed seed, the centroids the first ``k`` rows. Rehearse on
the CPU with ``--rows 4096 --k 64`` (the Pallas kernel then runs
interpreted; its times mean nothing there).
"""

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpumr.ops.kmeans import assign_and_partials  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=500_000)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--k", type=int, default=1024)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()
    dev = jax.devices()[0]
    rng = np.random.default_rng(30)
    host = rng.integers(0, 256, (args.rows, args.d)).astype(np.float32)
    points = jax.device_put(host, dev)
    cents = jax.device_put(host[:args.k] + np.float32(0.25), dev)
    results = {}
    for impl in ("xla", "pallas"):
        def call():
            return jax.block_until_ready(assign_and_partials(
                points, cents, use_pallas=impl == "pallas"))
        t0 = time.monotonic()
        out = call()
        first = time.monotonic() - t0
        ms = []
        for _ in range(args.reps):
            t0 = time.monotonic()
            call()
            ms.append(1e3 * (time.monotonic() - t0))
        results[impl] = [np.asarray(a) for a in out]
        print(json.dumps({
            "impl": impl, "rows": args.rows, "d": args.d, "k": args.k,
            "platform": dev.platform, "kind": dev.device_kind,
            "first_call_s": first, "ms": ms,
            "peak_bytes": (dev.memory_stats() or {}).get(
                "peak_bytes_in_use")}), flush=True)
    same = float(np.mean(results["xla"][0] == results["pallas"][0]))
    print(json.dumps({
        "assignments_equal_share": same,
        "sums_max_abs_gap": float(np.max(np.abs(
            results["xla"][1] - results["pallas"][1])))}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
