"""Process-level JAX runtime configuration: which devices back this
process's TPU slots, and where compiled programs persist.

**Accelerator devices.** :func:`accelerator_devices` is the one answer to
"which devices are the accelerator slots of this process" (≈ the
reference's per-node GPU device ids): the local devices of platform
``tpu``. When the CPU backend was asked for explicitly (``jax_platforms``
leads with ``cpu`` — the test suite and CPU rehearsals do that), the CPU
devices stand in. Anything else is an error: a TPU slot never silently
runs on whatever backend JAX fell back to.

**Compile cache.** The reference amortizes task start-up with JVM reuse
(JvmManager.java:322 reapJvm); the TPU-native equivalent of that cost is
XLA compilation — a fresh worker process otherwise pays every kernel/sort
compile again. The persistent compilation cache makes compiles durable
ACROSS processes: first worker populates, every later worker (or restart,
or next job) loads from disk instead of compiling. Processes share
compiles only through one directory that stays put, so it is never
derived from a pid, a time or a temporary name:

- ``JAX_COMPILATION_CACHE_DIR`` set in the environment: JAX's own
  handling of it stands and this module sets no directory;
- else ``tpumr.jax.cache.dir`` when the operator configured it (``none``
  disables);
- else ``<checkout>/.jax_cache``, next to the ``tpumr`` package.

``tpumr.jax.cache.min.compile.secs``: only persist compiles that took at
least this long (default 0.5s — skips trivial host-callback jits, keeps
every kernel/sort compile that matters).
"""

from __future__ import annotations

import os
import threading
from typing import Any

_lock = threading.Lock()
_configured = False
#: what accelerator_devices() last answered (empty before its first call)
_known_devices: list = []

#: the fixed default: inside the checkout, shared by every daemon and
#: script started from it
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_persistent_cache(conf: Any = None) -> "str | None":
    """Idempotently point JAX at the persistent compilation cache; first
    caller in the process wins. Returns the cache dir (None = disabled).
    Cheap after the first call — safe on every device-path entry."""
    global _configured
    import jax
    if _configured:
        return jax.config.jax_compilation_cache_dir
    with _lock:
        if _configured:
            return jax.config.jax_compilation_cache_dir
        _configured = True
        min_secs = 0.5
        if conf is not None:
            min_secs = conf.get_float("tpumr.jax.cache.min.compile.secs",
                                      0.5)
        # placed from outside, the directory is JAX's to read: set none
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            path = (conf.get("tpumr.jax.cache.dir")
                    if conf is not None else None)
            if path is None:
                path = DEFAULT_CACHE_DIR
            if str(path).lower() in ("", "none", "off", "disabled"):
                return None
            try:
                os.makedirs(path, exist_ok=True)
            except OSError:
                return None  # the cache is an optimization only
            jax.config.update("jax_compilation_cache_dir", str(path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_secs)
        return jax.config.jax_compilation_cache_dir


def _reset_for_tests() -> None:
    global _configured
    with _lock:
        _configured = False


def cpu_backend_requested() -> bool:
    """True when the caller explicitly asked JAX for the CPU backend
    (``JAX_PLATFORMS=cpu...`` or the same through ``jax.config``)."""
    import jax
    platforms = jax.config.jax_platforms or ""
    return platforms.split(",")[0].strip().lower() == "cpu"


def accelerator_devices() -> list:
    """The devices that back this process's TPU slots, slot *i* on
    element *i*. Raises when JAX has no ``tpu`` device and the CPU
    backend was not explicitly requested — that is a chip that failed
    to initialise, not a place to run TPU tasks."""
    global _known_devices
    import jax
    devices = jax.local_devices()
    tpus = [d for d in devices if d.platform == "tpu"]
    if tpus or cpu_backend_requested():
        _known_devices = tpus or list(devices)
        return list(_known_devices)
    raise RuntimeError(
        "no TPU device: jax.local_devices() is "
        f"{[str(d) for d in devices]} and the CPU backend was not "
        "requested explicitly. A TPU slot does not run on a fallback "
        "backend; fix the chip, configure zero TPU slots "
        "(mapred.tasktracker.map.tpu.tasks.maximum=0), or set "
        "JAX_PLATFORMS=cpu to rehearse on CPU devices.")


def known_accelerator_devices() -> list:
    """The slot devices this process has ALREADY been told of by
    :func:`accelerator_devices`; empty before that. For a caller that
    must never be the one to initialise a JAX backend: a metrics scrape
    in a tracker without TPU slots would otherwise reach for the chip
    another process owns."""
    return list(_known_devices)


def accelerator_device(dev_id: int = -1):
    """The device of TPU slot ``dev_id`` (≈ GPUDeviceId → cudaSetDevice);
    a negative id means "unbound" and takes the first device. An id past
    the last device is an error, never folded onto another device."""
    devices = accelerator_devices()
    if dev_id < 0:
        return devices[0]
    if dev_id >= len(devices):
        raise RuntimeError(
            f"TPU slot {dev_id} has no device: this process has "
            f"{len(devices)} accelerator device(s)")
    return devices[dev_id]


def describe_devices(devices: list, n_slots: int) -> dict:
    """What the tracker logs at start-up: platform, kind and count of
    this process's accelerator devices as JAX reports them, and the ids
    of the ones its ``n_slots`` TPU slots are bound to."""
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "slot_device_ids": [int(d.id) for d in devices[:n_slots]]}
