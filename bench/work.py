"""The operations and bytes a kernel's algorithm needs for a call, from its
shapes alone: the numerator of a roofline share. Counted on the rows, not
on whatever padded shape an implementation runs, so that the share reads
the same work whatever implements it."""

from __future__ import annotations


def kmeans_assign(n: int, d: int, k: int) -> dict:
    """Assign ``n`` float32 points of width ``d`` to the nearest of ``k``
    centroids and sum them per cluster: the points are read once
    (``4nd`` bytes), the centroids read and the sums and counts written
    (``4kd + 4kd + 4k``); two ``n x d x k`` matrix products (distances,
    one-hot sums) are ``4ndk`` operations."""
    return {"bytes": 4 * n * d + 8 * k * d + 4 * k,
            "flops": 4 * n * d * k}


def argsort(n: int, key_words: int = 3) -> dict:
    """Order ``n`` keys of ``key_words`` uint32 words: one pass reads the
    key words and writes an int32 index. A comparison sort makes many
    passes; a count of passes is the implementation's, not the work's."""
    return {"bytes": 4 * n * key_words + 4 * n, "flops": 0}


def least_seconds(work: dict, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(work["flops"] / peak["flops_bf16"],
               work["bytes"] / peak["hbm_bytes_per_s"])
