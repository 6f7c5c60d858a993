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
key. Tags embed the source path.

A side input read from a FILE goes through :func:`side_file` /
:func:`device_side_file`, keyed by the file's identity (path, length,
mtime): a driver that rewrites one file between rounds (``tpumr examples
kmeans``' ``centroids.npy``) is read anew on every tracker at the cost of
one ``stat`` a map, and a copy of the old bytes is never served.
:func:`seed_side_file` registers bytes a task has just written under the
file's new identity, so the next round's maps on this host find them
resident. Clearing by prefix (:func:`clear_side_files`,
:func:`clear_device_cache`) frees memory; it is never needed for
freshness.
"""

from __future__ import annotations

import collections
import threading
from typing import Any, Callable

import numpy as np

_lock = threading.Lock()
_cache = None           # lazily-built HbmSplitCache

#: host copies of side files, (tag, length, mtime) -> array, LRU first;
#: a loop that writes a new file a round adds one entry a round
_host: "collections.OrderedDict[tuple, np.ndarray]" = \
    collections.OrderedDict()
_HOST_CAP = 8


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


def device_cached(tag: str, host_array: Any, conf: Any = None,
                  version: Any = None) -> Any:
    """The device-resident image of ``host_array`` under ``tag`` for the
    CURRENT default device — uploaded once, returned from HBM after.
    ``host_array`` may be a callable giving the array, called on a miss
    only. With a ``version`` (a file's length and mtime) the image of
    another version of the same tag is dropped on a miss: it is stale."""
    import jax
    import jax.numpy as jnp

    dev = str(jax.config.jax_default_device)
    key = (tag, dev) if version is None else (tag, dev, version)
    cache = _cache_for(conf)
    hit = cache.get(key)
    if hit is not None:
        return hit
    if version is not None:
        cache.drop_where(lambda k: k[:2] == (tag, dev) and k != key)
    if callable(host_array):
        host_array = host_array()
    arr = jnp.asarray(host_array)          # the one upload
    cache.put(key, arr, int(getattr(arr, "nbytes", 0)))
    return arr


def file_version(path: str, conf: Any) -> "tuple[int, float]":
    """(length, mtime) of a side-input file: one ``stat``."""
    from tpumr.fs.filesystem import FileSystem
    st = FileSystem.get(path, conf).get_status(path)
    return int(st.length), float(st.mtime)


def side_file(family: str, path: str, conf: Any,
              version: "tuple | None" = None) -> np.ndarray:
    """The ``.npy`` side input at ``path`` as a host array, read once for
    each (path, length, mtime) and kept for the next map."""
    from tpumr.fs.filesystem import FileSystem
    from tpumr.mapred.input_formats import load_dense
    key = (f"{family}:{path}",) + (version or file_version(path, conf))
    with _lock:
        hit = _host.get(key)
        if hit is not None:
            _host.move_to_end(key)
            return hit
    arr = load_dense(FileSystem.get(path, conf), path)
    _keep_host(key, arr)
    return arr


def device_side_file(family: str, path: str, conf: Any,
                     prepare: "Callable[[np.ndarray], np.ndarray] | None"
                     = None) -> Any:
    """The side input at ``path`` resident on the CURRENT default device,
    uploaded once for each (path, length, mtime, device); ``prepare``
    (a cast, a pad) makes the host array that is uploaded."""
    version = file_version(path, conf)

    def host():
        arr = side_file(family, path, conf, version)
        return arr if prepare is None else prepare(arr)
    return device_cached(f"{family}:{path}", host, conf, version)


def seed_side_file(family: str, path: str, conf: Any, array: np.ndarray,
                   prepare: "Callable[[np.ndarray], np.ndarray] | None"
                   = None) -> None:
    """``array`` was just written to ``path``: keep it on the host, and on
    every TPU slot device this process already serves, under the file's
    new identity, so the next round's maps here read neither the file
    nor the host copy. ``prepare`` is :func:`device_side_file`'s, so the
    device copy is what a map would have uploaded. A process with no slot
    device stays off JAX."""
    from tpumr.parallel.jaxruntime import known_accelerator_devices
    version = file_version(path, conf)
    _keep_host((f"{family}:{path}",) + version, array)
    devices = known_accelerator_devices()
    if devices:
        import jax
        image = array if prepare is None else prepare(array)
        for d in devices:
            with jax.default_device(d):
                device_cached(f"{family}:{path}", image, conf, version)


def _keep_host(key: tuple, arr: np.ndarray) -> None:
    with _lock:
        for k in [k for k in _host if k[0] == key[0] and k != key]:
            del _host[k]        # another version of this file: stale
        _host[key] = arr
        while len(_host) > _HOST_CAP:
            _host.popitem(last=False)


def clear_side_files(family: str) -> None:
    """Drop every host and device copy of this family's side files."""
    with _lock:
        for k in [k for k in _host if k[0].startswith(family + ":")]:
            del _host[k]
    clear_device_cache(family + ":")


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
