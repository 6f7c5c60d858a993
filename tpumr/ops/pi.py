"""Monte-Carlo π estimation map kernel.

≈ ``PiEstimator`` (reference: src/examples/org/apache/hadoop/examples/
PiEstimator.java, 353 LoC — halton-sequence sampling, one map per (offset,
size) pair). Each input record is ``"<seed> <num_samples>"``; the kernel
draws the whole sample block on device and reduces to two counters — the
map's output is 2 records regardless of sample count.
"""

from __future__ import annotations

import functools
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from tpumr.mapred.api import Mapper
from tpumr.ops.registry import KernelMapper, register_kernel


@functools.partial(jax.jit, static_argnames=("n",))
def _count_inside(seed: int, n: int):
    key = jax.random.key(seed)
    pts = jax.random.uniform(key, (n, 2), dtype=jnp.float32)
    # int32: per-call n is bounded far below 2^31; totals accumulate in Python
    return jnp.sum(jnp.sum(pts * pts, axis=1) <= 1.0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n",))
def _count_inside_many(seeds, n: int):
    """All of a task's same-size sample blocks in ONE dispatch:
    ``lax.map`` runs the blocks sequentially on device (same transient
    memory as one block), so a task costs one small seed-array upload +
    one program launch instead of a scalar upload + dispatch per record.
    Per-seed results are bit-identical to :func:`_count_inside`."""
    def one(seed):
        key = jax.random.key(seed)
        pts = jax.random.uniform(key, (n, 2), dtype=jnp.float32)
        return jnp.sum(jnp.sum(pts * pts, axis=1) <= 1.0).astype(jnp.int32)
    return jax.lax.map(one, seeds)


def _parse(value) -> tuple[int, int]:
    s = value.decode() if isinstance(value, (bytes, bytearray)) else str(value)
    seed_s, n_s = s.split()
    return int(seed_s), int(n_s)


class PiCpuMapper(Mapper):
    def map(self, key, value, output, reporter):
        seed, n = _parse(value)
        rng = np.random.default_rng(seed)
        pts = rng.random((n, 2), dtype=np.float32)
        inside = int(((pts * pts).sum(axis=1) <= 1.0).sum())
        output.collect("inside", inside)
        output.collect("total", n)


class PiSamplerKernel(KernelMapper):
    name = "pi-sampler"
    cpu_mapper_class = PiCpuMapper

    def map_batch_launch(self, batch, conf, task):
        """Group the task's records by sample count and launch ONE
        program per distinct n (usually exactly one) — the per-block
        device counters stay on device until the runner's single fetch.
        The original path synced once per record; the first batched
        version still dispatched once per record."""
        from collections import defaultdict
        groups: "dict[int, list[int]]" = defaultdict(list)
        total = 0
        for i in range(batch.num_records):
            seed, n = _parse(batch.value(i))
            groups[n].append(seed)
            total += n
        counts = [
            # mask to uint32 EXPLICITLY: numpy 2 refuses out-of-range
            # casts, and jax folds seeds to uint32 anyway (verified
            # key(-1) == key(2**32-1)) — negative/wide seeds keep the
            # per-record path's semantics instead of crashing the task
            _count_inside_many(np.asarray(
                [s & 0xFFFFFFFF for s in seeds], np.uint32), n)
            for n, seeds in groups.items()]
        return {"inside": counts, "total": total}

    def map_batch_drain(self, fetched, conf, task) -> Iterable[tuple]:
        yield "inside", sum(int(np.asarray(c).sum())
                            for c in fetched["inside"])
        yield "total", int(fetched["total"])

    def map_batch_cpu(self, batch, conf, task) -> Iterable[tuple]:
        """Vectorized host sampling — whole blocks per numpy call (CPU
        slots stay batch-speed in hybrid runs)."""
        inside = 0
        total = 0
        for i in range(batch.num_records):
            seed, n = _parse(batch.value(i))
            rng = np.random.default_rng(seed)
            pts = rng.random((n, 2), dtype=np.float32)
            inside += int(((pts * pts).sum(axis=1) <= 1.0).sum())
            total += n
        yield "inside", inside
        yield "total", total


register_kernel(PiSamplerKernel())
