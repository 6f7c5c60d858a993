"""Device shuffle — MapReduce's all-to-all on ICI.

The reference's shuffle is R parallel HTTP fetch streams per reduce
(ReduceTask.java:659 MapOutputCopier ↔ TaskTracker.java:4050
MapOutputServlet) with a RAM budget (ShuffleRamManager, :1080). On a mesh,
the same repartition-by-key is ONE collective: every device buckets its
records by destination, pads buckets to a static capacity (XLA needs static
shapes — SURVEY.md §7 'Shuffle on TPU' hard part), and a single
``lax.all_to_all`` exchanges them over ICI. Records that exceed a bucket's
capacity are counted, not silently dropped — the caller retries with a
bigger capacity or falls back to the host shuffle path (the reference's
disk-spill fallback role).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


@dataclass
class ShuffleResult:
    """Per-device view after the exchange (leading dim = this device's
    received slots)."""
    values: Any          # [n_dev * capacity, ...] received records
    valid: Any           # [n_dev * capacity] bool mask
    overflow: Any        # int — TOTAL records dropped across all senders
    keys: Any = None     # [n_dev * capacity] routing keys if requested


def _bucket_local(values, dest, n_dev: int, capacity: int, keys=None):
    """Scatter local records into a [n_dev, capacity, ...] send buffer."""
    n = dest.shape[0]
    order = jnp.argsort(dest, stable=True)
    sdest = dest[order]
    svals = values[order]
    # index of each record within its destination bucket: position minus the
    # index of the bucket's first record (searchsorted on the sorted dests)
    first = jnp.searchsorted(sdest, sdest, side="left")
    slot = jnp.arange(n) - first
    # a record is droppable (counted in overflow) if its bucket is full OR
    # its destination is out of range — jitted scatters silently drop/wrap
    # out-of-bounds indices, which would violate the "counted, not silently
    # dropped" contract
    dest_ok = (sdest >= 0) & (sdest < n_dev)
    ok = (slot < capacity) & dest_ok
    overflow = jnp.sum(~ok).astype(jnp.int32)
    # overflow records scatter into a sacrificial extra slot (capacity) that
    # is sliced off — clipping them into slot capacity-1 would overwrite the
    # legitimate record there; invalid dests are rerouted to bucket 0's
    # sacrificial slot
    sdest = jnp.where(dest_ok, sdest, 0)
    slot_c = jnp.where(ok, jnp.minimum(slot, capacity), capacity)
    send = jnp.zeros((n_dev, capacity + 1) + values.shape[1:], values.dtype)
    send = send.at[sdest, slot_c].set(svals)[:, :capacity]
    mask = jnp.zeros((n_dev, capacity + 1), jnp.bool_).at[sdest, slot_c] \
        .set(ok)[:, :capacity]
    out = [send, mask, overflow]
    if keys is not None:
        skeys = keys[order]
        kbuf = jnp.zeros((n_dev, capacity + 1), keys.dtype).at[sdest, slot_c] \
            .set(skeys)[:, :capacity]
        out.append(kbuf)
    return out


@functools.lru_cache(maxsize=64)
def make_shuffle(mesh: Mesh, capacity: int, axis_name: str = "data",
                 with_keys: bool = False):
    """Build the jitted SPMD shuffle. Inputs per device shard:
    ``values [n_local, ...]``, ``dest [n_local] int32`` (destination device),
    optionally ``keys [n_local]`` routing keys carried alongside."""
    n_dev = mesh.shape[axis_name]

    in_specs = (P(axis_name), P(axis_name)) + ((P(axis_name),) if with_keys else ())
    out_specs = (P(axis_name), P(axis_name), P(axis_name)) + \
        ((P(axis_name),) if with_keys else ())

    @partial(jax.shard_map, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    def _shuffle(values, dest, *maybe_keys):
        keys = maybe_keys[0] if maybe_keys else None
        parts = _bucket_local(values, dest, n_dev, capacity, keys)
        send, mask, overflow = parts[0], parts[1], parts[2]
        recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
        rmask = lax.all_to_all(mask, axis_name, split_axis=0, concat_axis=0,
                               tiled=False)
        flat_vals = recv.reshape((n_dev * capacity,) + recv.shape[2:])
        flat_mask = rmask.reshape(n_dev * capacity)
        outs = [flat_vals, flat_mask, overflow.reshape(1)]
        if keys is not None:
            kbuf = parts[3]
            rkeys = lax.all_to_all(kbuf, axis_name, split_axis=0,
                                   concat_axis=0, tiled=False)
            outs.append(rkeys.reshape(n_dev * capacity))
        return tuple(outs)

    return jax.jit(_shuffle)


def shuffle_dense(mesh: Mesh, values, dest, capacity: int | None = None,
                  axis_name: str = "data", keys=None) -> ShuffleResult:
    """One-call shuffle of globally-sharded arrays. ``values``/``dest`` are
    sharded over ``axis_name`` (n divisible by mesh size). ``capacity`` is
    per-(src,dst) bucket slots; default 2× the balanced load."""
    n_dev = mesh.shape[axis_name]
    n = values.shape[0]
    if n % n_dev:
        raise ValueError(f"global length {n} not divisible by mesh size {n_dev}")
    local_n = n // n_dev
    if capacity is None:
        capacity = max(1, int(2 * local_n / n_dev))
    fn = make_shuffle(mesh, capacity, axis_name, with_keys=keys is not None)
    args = (values, dest) + ((keys,) if keys is not None else ())
    out = fn(*args)
    res = ShuffleResult(values=out[0], valid=out[1],
                        overflow=np.asarray(out[2]).sum())
    if keys is not None:
        res.keys = out[3]
    return res
