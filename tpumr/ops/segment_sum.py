"""Grouped float32 sum over key-sorted rows: the reduce kernel of a
``SELECT key, SUM(value) ... GROUP BY key`` job behind the device shuffle.

The sort has put equal keys side by side, so a group is a run of rows whose
key words are equal, and the kernel is three steps in fixed shapes:

1. **boundaries**: row ``i`` starts a group where any key word differs
   from row ``i - 1``'s (row 0 always does);
2. **segment sum**: a segmented inclusive scan by doubling. After the pass
   with shift ``s`` row ``i`` holds the sum of the last ``min(2s, rows of
   its group up to i)`` values, made as ``(sum of the s rows before those)
   + (sum of the last s)``: a row's left part is always added to its right
   part, and the group's last row ends with the group's sum. **The order
   of the additions** is therefore fixed by the sorted order alone: for a
   group of ``L`` rows, the tree that splits off the largest power of two
   below ``L`` from the RIGHT end, again and again; every addition is one
   float32 add. The numpy twin makes the same passes, so the two agree
   add for add;
3. **compaction**: the last row of group ``g`` moves left to slot ``g``
   through a compress network: its displacement ``i - g`` is taken one bit
   a pass, least significant first, each pass a shift by a power of two
   and a select. Displacements never decrease along the rows, which is
   what makes every pass free of collisions.

No scatter, no gather and no second sort: every pass is elementwise over
shifted copies, so the program is bound by HBM and compiles in seconds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from tpumr.ops.registry import ReduceKernel, register_reduce_kernel

VALUE_BYTES = 4


def _shifted(x, shift: int, fill):
    """``x`` moved ``shift`` places to the right along its last axis,
    ``fill`` coming in on the left."""
    pad = jnp.full(x.shape[:-1] + (shift,), fill, x.dtype)
    return jnp.concatenate([pad, x[..., :-shift]], axis=-1)


def _pulled(x, shift: int, fill):
    """``x`` moved ``shift`` places to the left, ``fill`` on the right."""
    pad = jnp.full(x.shape[:-1] + (shift,), fill, x.dtype)
    return jnp.concatenate([x[..., shift:], pad], axis=-1)


def segmented_scan(x, first):
    """Step 2 of the module's docstring: the segmented inclusive scan of
    float32 ``x`` along its last axis, a segment starting wherever
    ``first`` is set (``first[0]`` must be). Each segment's last element
    ends with the segment's sum, added in the fixed tree order the
    docstring states; ``log2(n)`` elementwise passes, no loop on the
    device. The aggregation's kernel and the PageRank map call it."""
    n = x.shape[-1]
    f, shift = first, 1
    while shift < n:
        x = jnp.where(f, x, _shifted(x, shift, 0.0) + x)
        f = f | _shifted(f, shift, True)
        shift *= 2
    return x


@functools.lru_cache(maxsize=8)
def segment_sum_program(key_cols: int):
    """The jitted device program ``(words [key_cols + 1, n] uint32,
    n_live) -> (table, groups)``; the module's docstring has the steps.
    ``words`` holds the key columns and then the float32 values' bits,
    ordered by key with the ``n_live`` real rows first; ``table`` has
    their shape, column ``g`` the key words and the sum's bits of group
    ``g`` for ``g < groups``."""
    @jax.jit
    def _segment_sum(words, n_live):
        n = words.shape[1]
        keys = words[:key_cols]
        idx = jnp.arange(n, dtype=jnp.int32)
        valid = idx < n_live
        differs = jnp.any(keys != _shifted(keys, 1, 0), axis=0)
        # padding rows are a group each, so that no sum runs into them
        # and every flag there is set from the start
        first = differs | (idx == 0) | ~valid
        x = segmented_scan(jnp.where(valid, jax.lax.bitcast_convert_type(
            words[key_cols], jnp.float32), 0.0), first)
        last = valid & _pulled(first, 1, True)
        group = jnp.cumsum((first & valid).astype(jnp.int32)) - 1
        groups = jnp.sum(first & valid, dtype=jnp.int32)
        # compress: the last row of group g goes from i to g
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

    return _segment_sum


def group_starts(keys: np.ndarray) -> np.ndarray:
    """``[n]`` bool: row ``i`` of key-sorted ``[n, klen]`` uint8 keys
    starts a group (its key differs from the row before)."""
    first = np.ones(keys.shape[0], bool)
    if keys.shape[0] > 1:
        first[1:] = (keys[1:] != keys[:-1]).any(axis=1)
    return first


def segment_sum_host(rows: np.ndarray, klen: int) -> np.ndarray:
    """The numpy twin: key-sorted ``[n, klen + 4]`` uint8 rows (key, then
    a little-endian float32) → ``[groups, klen + 4]``, each group's key
    and float32 sum, added in the device program's order."""
    n = rows.shape[0]
    if n == 0:
        return np.zeros((0, klen + VALUE_BYTES), np.uint8)
    first = group_starts(rows[:, :klen])
    x = np.ascontiguousarray(rows[:, klen:klen + VALUE_BYTES]) \
        .view("<f4")[:, 0].astype(np.float32)
    f, shift = first.copy(), 1
    while shift < n and not f.all():
        open_ = ~f[shift:]
        summed = x[:-shift] + x[shift:]
        x[shift:][open_] = summed[open_]
        f[shift:] |= f[:-shift].copy()
        shift *= 2
    last = np.ones(n, bool)
    last[:-1] = first[1:]
    out = np.empty((int(last.sum()), klen + VALUE_BYTES), np.uint8)
    out[:, :klen] = rows[last, :klen]
    out[:, klen:] = x[last].astype("<f4").view(np.uint8).reshape(-1, 4)
    return out


class SegmentSumF32(ReduceKernel):
    """``SUM(value) GROUP BY key`` for 4-byte little-endian float32
    values."""

    name = "segment-sum-f32"
    value_bytes = VALUE_BYTES

    def device_program(self, key_cols: int):
        return segment_sum_program(key_cols)

    def reduce_host(self, rows: np.ndarray, klen: int) -> np.ndarray:
        return segment_sum_host(rows, klen)


register_reduce_kernel(SegmentSumF32())
