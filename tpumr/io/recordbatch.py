"""Columnar record batches — the device-facing record format.

This is the core data-format departure from the reference: where Hadoop's
pipes path streams one Writable record at a time over a socket to the GPU
process (per-record hot loop, mapred/pipes/PipesGPUMapRunner.java:97-107 →
BinaryProtocol MAP_ITEM), the TPU build stages an entire InputSplit into HBM
as a small set of dense arrays and runs the mapper as one XLA/Pallas program.

Two shapes of batch:

- :class:`RecordBatch` — variable-length byte records (text lines, terasort
  rows…): one flat ``uint8`` data array + ``int32`` offset arrays per column.
  Device kernels consume either the flat+offsets form or a padded
  ``[n, width] uint8`` view (fixed width ⇒ static shapes for XLA).
- :class:`DenseBatch` — numeric records (K-Means points, matmul blocks):
  a dense ``[n, d]`` array, MXU-ready.

Both are host-side numpy containers; ``tpumr.mapred.tpu_runner`` is what
moves them into HBM.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Iterable, Iterator

import numpy as np


def _build_offsets(items: list[bytes]) -> tuple[np.ndarray, np.ndarray]:
    offsets = np.zeros(len(items) + 1, dtype=np.int32)
    if items:
        np.cumsum([len(b) for b in items], out=offsets[1:])
    data = np.frombuffer(b"".join(items), dtype=np.uint8).copy()
    return data, offsets


@dataclass
class RecordBatch:
    """Variable-length byte records as flat data + offsets columns."""

    key_data: np.ndarray            # uint8 [total_key_bytes]
    key_offsets: np.ndarray         # int32 [n+1]
    value_data: np.ndarray          # uint8 [total_value_bytes]
    value_offsets: np.ndarray       # int32 [n+1]

    # ------------------------------------------------------------ construct

    @classmethod
    def from_pairs(cls, pairs: Iterable[tuple[bytes, bytes]]) -> "RecordBatch":
        keys, values = [], []
        for k, v in pairs:
            keys.append(bytes(k))
            values.append(bytes(v))
        kd, ko = _build_offsets(keys)
        vd, vo = _build_offsets(values)
        return cls(kd, ko, vd, vo)

    @classmethod
    def from_values(cls, values: Iterable[bytes]) -> "RecordBatch":
        """Key-less batch (keys empty) — e.g. raw text lines."""
        vals = [bytes(v) for v in values]
        vd, vo = _build_offsets(vals)
        n = len(vals)
        return cls(np.zeros(0, np.uint8), np.zeros(n + 1, np.int32), vd, vo)

    @classmethod
    def empty(cls) -> "RecordBatch":
        z = np.zeros(0, np.uint8)
        o = np.zeros(1, np.int32)
        return cls(z, o.copy(), z.copy(), o.copy())

    # ------------------------------------------------------------ inspect

    @property
    def num_records(self) -> int:
        return len(self.key_offsets) - 1

    def __len__(self) -> int:
        return self.num_records

    def key(self, i: int) -> bytes:
        return self.key_data[self.key_offsets[i]: self.key_offsets[i + 1]].tobytes()

    def value(self, i: int) -> bytes:
        return self.value_data[self.value_offsets[i]: self.value_offsets[i + 1]].tobytes()

    def __iter__(self) -> Iterator[tuple[bytes, bytes]]:
        for i in range(self.num_records):
            yield self.key(i), self.value(i)

    def to_pairs(self) -> list[tuple[bytes, bytes]]:
        return list(self)

    @property
    def nbytes(self) -> int:
        return int(self.key_data.nbytes + self.value_data.nbytes
                   + self.key_offsets.nbytes + self.value_offsets.nbytes)

    @property
    def value_lengths(self) -> np.ndarray:
        return (self.value_offsets[1:] - self.value_offsets[:-1]).astype(np.int32)

    def joined_values(self, sep: int = 0x20) -> bytes:
        """All values as one buffer with ``sep`` between records — the
        whole-split view for kernels that scan bytes (tokenizers, regex):
        one C-level ``np.insert`` instead of per-record Python or an
        O(total) int64 scatter."""
        n = self.num_records
        if n == 0:
            return b""
        return np.insert(self.value_data,
                         self.value_offsets[1:-1].astype(np.int64),
                         sep).tobytes()

    # ------------------------------------------------------------ device views

    def padded_values(self, width: int, fill: int = 0) -> tuple[np.ndarray, np.ndarray]:
        """Return ``([n, width] uint8, [n] int32 lengths)`` — the static-shape
        view device kernels consume. Records longer than ``width`` are
        truncated (callers pick width ≥ max length when loss matters)."""
        return _pad(self.value_data, self.value_offsets, width, fill)

    def padded_keys(self, width: int, fill: int = 0) -> tuple[np.ndarray, np.ndarray]:
        return _pad(self.key_data, self.key_offsets, width, fill)

    # ------------------------------------------------------------ combine

    @classmethod
    def concat(cls, batches: "list[RecordBatch]") -> "RecordBatch":
        if not batches:
            return cls.empty()
        kd = np.concatenate([b.key_data for b in batches])
        vd = np.concatenate([b.value_data for b in batches])

        def cat_offsets(offs: list[np.ndarray]) -> np.ndarray:
            out = [offs[0]]
            base = int(offs[0][-1])
            for o in offs[1:]:
                out.append(o[1:] + base)
                base += int(o[-1])
            return np.concatenate(out).astype(np.int32)

        return cls(kd, cat_offsets([b.key_offsets for b in batches]),
                   vd, cat_offsets([b.value_offsets for b in batches]))

    def slice(self, start: int, stop: int) -> "RecordBatch":
        ko = self.key_offsets[start: stop + 1]
        vo = self.value_offsets[start: stop + 1]
        return RecordBatch(
            self.key_data[ko[0]: ko[-1]].copy(), (ko - ko[0]).astype(np.int32),
            self.value_data[vo[0]: vo[-1]].copy(), (vo - vo[0]).astype(np.int32),
        )


def _pad(data: np.ndarray, offsets: np.ndarray, width: int,
         fill: int) -> tuple[np.ndarray, np.ndarray]:
    n = len(offsets) - 1
    lengths = (offsets[1:] - offsets[:-1]).astype(np.int32)
    if n and data.shape[0] == n * width and (lengths == width).all():
        # fixed-width records: the padded view IS a reshape (no copy)
        return data.reshape(n, width), lengths
    out = np.full((n, width), fill, dtype=np.uint8)
    # vectorized gather: for each row, take min(len, width) bytes
    take = np.minimum(lengths, width)
    # build flat source indices
    row_idx = np.repeat(np.arange(n), take)
    col_idx = np.concatenate([np.arange(t) for t in take]) if n else np.zeros(0, np.int64)
    src_idx = np.repeat(offsets[:-1], take) + col_idx
    out[row_idx, col_idx] = data[src_idx]
    return out, lengths


@dataclass
class DenseBatch:
    """Dense numeric records ``[n, d]`` (+ optional int64 record ids).

    The K-Means / matmul / pi map path: what the reference shipped to a CUDA
    binary one text line at a time (NLineInputFormat, conf/mapred-site.xml:
    14-21 pins 1 line per map), we ship as one MXU-friendly array.
    """

    values: np.ndarray                       # [n, d] float32/bf16/…
    ids: np.ndarray | None = None            # [n] int64 record ids
    meta: dict = field(default_factory=dict)
    #: the split's own records where ``values`` was padded past them for
    #: the device (``KernelMapper.staged_form``); None: every row counts
    rows: int | None = None

    @property
    def num_records(self) -> int:
        return int(self.values.shape[0] if self.rows is None else self.rows)

    def __len__(self) -> int:
        return self.num_records

    @property
    def nbytes(self) -> int:
        return int(self.values.nbytes + (self.ids.nbytes if self.ids is not None else 0))

    @classmethod
    def concat(cls, batches: "list[DenseBatch]") -> "DenseBatch":
        if not batches:
            return cls(np.zeros((0, 0), np.float32))
        vals = np.concatenate([b.values for b in batches], axis=0)
        ids = None
        if all(b.ids is not None for b in batches):
            ids = np.concatenate([b.ids for b in batches])
        return cls(vals, ids)
