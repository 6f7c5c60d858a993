"""Blocked matrix-multiply map kernel.

≈ the reference's GPU-pipes matrix-multiply example job (external to the
tree; BASELINE.json config 4). Each map task owns a row-block of A (its
DenseSplit) and computes ``C_block = A_block @ B`` with B distributed as a
side file (the DistributedCache role). The matmul itself is handed to XLA —
a single ``jnp.dot`` already lowers to optimally-tiled MXU code, and
hand-scheduling it in Pallas would only match it (pallas_guide: don't
re-schedule what the compiler does well). bfloat16 inputs with float32
accumulation are the default on TPU.
"""

from __future__ import annotations

from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from tpumr.mapred.api import Mapper
from tpumr.ops.registry import KernelMapper, register_kernel

_b_cache: dict[str, np.ndarray] = {}


def _load_b(conf) -> np.ndarray:
    from tpumr.fs.filesystem import FileSystem
    from tpumr.mapred.input_formats import load_dense
    path = conf.get("tpumr.matmul.b")
    if not path:
        raise ValueError("tpumr.matmul.b not set (path to .npy of B)")
    cached = _b_cache.get(path)
    if cached is None:
        fs = FileSystem.get(path, conf)
        cached = _b_cache[path] = load_dense(fs, path)
    return cached


def clear_b_cache() -> None:
    from tpumr.ops.devcache import clear_device_cache
    _b_cache.clear()
    clear_device_cache("matmul-b:")


def _device_b(conf):
    """B as a DEVICE-resident array, uploaded once per (file, device):
    without this every map task re-ships the full B (64 MB at 4096²) to
    the device (see ops/devcache.py)."""
    from tpumr.ops.devcache import device_cached
    host = _load_b(conf)
    return device_cached(f"matmul-b:{conf.get('tpumr.matmul.b')}",
                         host, conf)


@jax.jit
def _matmul_bf16(a, b):
    return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


@jax.jit
def _matmul_f32(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def block_matmul(a, b, bf16: bool = True):
    return (_matmul_bf16 if bf16 else _matmul_f32)(jnp.asarray(a), jnp.asarray(b))


class MatmulCpuMapper(Mapper):
    """CPU slot path: one row at a time through numpy (the profiled slow
    backend)."""

    def configure(self, conf) -> None:
        self._b = _load_b(conf)

    def map(self, key, row, output, reporter):
        output.collect(int(key), np.asarray(row) @ self._b)


class MatmulBlockKernel(KernelMapper):
    name = "matmul-block"
    cpu_mapper_class = MatmulCpuMapper

    def map_batch_launch(self, batch, conf, task):
        b = _device_b(conf)
        bf16 = conf.get_boolean("tpumr.matmul.bf16", True)
        c = block_matmul(batch.values, b, bf16=bf16)
        row0 = int(batch.ids[0]) if batch.ids is not None else 0
        return {"c": c, "row0": row0}

    def map_batch_drain(self, fetched, conf, task) -> Iterable[tuple]:
        yield (int(fetched["row0"]), np.asarray(fetched["c"]))

    def device_output_rows(self, state):
        """Output-chaining hook: C stays resident so a consumer job
        (DenseNpyOutputFormat → DenseInputFormat) reads it from HBM
        instead of storage plus a fresh upload."""
        return state["c"]

    def map_batch_cpu(self, batch, conf, task) -> Iterable[tuple]:
        """Vectorized host twin (BLAS) — CPU slots do the whole block in
        one gemm, keeping the hybrid comparison batch-vs-batch."""
        b = _load_b(conf)
        c = np.asarray(batch.values, np.float32) @ np.asarray(b, np.float32)
        row0 = int(batch.ids[0]) if batch.ids is not None else 0
        yield (row0, c)


register_kernel(MatmulBlockKernel())
