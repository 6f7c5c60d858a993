"""Device-resident cache for kernel SIDE INPUTS (DistributedCache files).

The split cache (tpu_runner.HbmSplitCache) keeps each task's INPUT split
resident in HBM; this is the same machinery applied to the constants
every task of a job shares — K-Means centroids, the matmul B matrix —
which the reference shipped per-node via the DistributedCache
(filecache/) and each GPU task re-uploaded per launch. Here a job of
25 map tasks makes one host→device transfer per (side input, device)
instead of 25 transfers of IDENTICAL bytes — a 1 KB centroid array for
kmeans, a 64 MB B at 4096² for matmul. What the saved uploads are worth
on a given machine is a measurement (PERF.md), not stated here.

One byte-budgeted :class:`HbmSplitCache` (``tpumr.ops.device.cache.mb``,
default 1024, fixed at first use) keyed by (tag, current default
device): tasks bind devices via ``jax.default_device``
(tpu_runner._select_device), so per-device residency falls out of the
key. Tags embed the source path; iterative drivers that rewrite a side
file between rounds clear by prefix (clear_centroid_cache /
clear_b_cache call :func:`clear_device_cache` with their tag family).
"""

from __future__ import annotations

import threading
from typing import Any

_lock = threading.Lock()
_cache = None           # lazily-built HbmSplitCache


def _cache_for(conf: Any):
    global _cache
    with _lock:
        if _cache is None:
            budget_mb = 1024
            if conf is not None:
                try:
                    budget_mb = int(conf.get("tpumr.ops.device.cache.mb",
                                             1024))
                except (TypeError, ValueError):
                    pass
            from tpumr.mapred.tpu_runner import HbmSplitCache
            _cache = HbmSplitCache(budget_mb * 1024 * 1024)
        return _cache


def device_cached(tag: str, host_array: Any, conf: Any = None) -> Any:
    """The device-resident image of ``host_array`` under ``tag`` for the
    CURRENT default device — uploaded once, returned from HBM after."""
    import jax
    import jax.numpy as jnp

    key = (tag, str(jax.config.jax_default_device))
    cache = _cache_for(conf)
    hit = cache.get(key)
    if hit is not None:
        return hit
    arr = jnp.asarray(host_array)          # the one upload
    cache.put(key, arr, int(getattr(arr, "nbytes", 0)))
    return arr


def clear_device_cache(tag_prefix: "str | None" = None) -> None:
    with _lock:
        cache = _cache
    if cache is None:
        return
    if tag_prefix is None:
        cache.clear()
    else:
        cache.drop_where(lambda k: k[0].startswith(tag_prefix))


def inventory(max_tags: int = 32) -> "dict[str, int]":
    """Resident tag → total bytes across devices, MRU-first, bounded to
    ``max_tags`` entries — the devcache inventory trackers piggyback on
    heartbeats so the scheduler can place tasks where their side inputs
    already live. Cheap (one locked snapshot) and safe pre-first-use
    (empty dict when the cache was never built)."""
    with _lock:
        cache = _cache
    if cache is None:
        return {}
    tags: "dict[str, int]" = {}
    # snapshot is LRU→MRU; walk reversed so the bound keeps HOT tags
    for key, nbytes in reversed(cache.snapshot()):
        tag = key[0] if isinstance(key, tuple) else str(key)
        if tag in tags:
            tags[tag] += nbytes
        elif len(tags) < max_tags:
            tags[tag] = nbytes
    return tags


def occupancy() -> "dict[str, Any]":
    """Gauge-shaped occupancy summary: entry count, resident bytes, and
    per-tag-family byte totals (family = tag prefix before ':')."""
    with _lock:
        cache = _cache
    if cache is None:
        return {"entries": 0, "bytes": 0, "families": {}}
    snap = cache.snapshot()
    families: "dict[str, int]" = {}
    total = 0
    for key, nbytes in snap:
        tag = key[0] if isinstance(key, tuple) else str(key)
        family = tag.split(":", 1)[0]
        families[family] = families.get(family, 0) + nbytes
        total += nbytes
    return {"entries": len(snap), "bytes": total, "families": families}
