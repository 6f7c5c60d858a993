"""A pool of spawned worker processes for bulk host work (making input,
the plain reference)."""

from __future__ import annotations

import contextlib
import multiprocessing
import os

_BLAS = ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS")


@contextlib.contextmanager
def worker_pool(n_jobs: int, most: int = 12):
    """At most ``most`` workers, one core left free, each with ONE BLAS
    thread (a dozen workers with a dozen threads each take five times as
    long). The variables are set only while the workers start; no daemon
    sees them."""
    workers = max(1, min(n_jobs, (os.cpu_count() or 2) - 1, most))
    old = {k: os.environ.get(k) for k in _BLAS}
    os.environ.update({k: "1" for k in _BLAS})
    try:
        pool = multiprocessing.get_context("spawn").Pool(workers)
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    with pool:
        yield pool
