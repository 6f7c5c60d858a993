"""The bytes the mesh device shuffle's three programs need for a call, per
device, from the job's rows alone (``bench/work.py`` says why: never from
a padded shape). ``n`` rows of ``width`` bytes, the first ``key_bytes`` the
key, dealt evenly over ``n_dev`` devices; uniform keys, so every device
also receives ``n / n_dev`` rows."""

from __future__ import annotations


def dest(n: int, n_dev: int, key_bytes: int) -> dict:
    """A destination per row: the key is read, an int32 written."""
    return {"bytes": n / n_dev * (key_bytes + 4), "flops": 0}


def exchange(n: int, n_dev: int, width: int) -> dict:
    """Every row is read once where it lies and written once where it
    belongs; all but one in ``n_dev`` of them cross to another chip."""
    rows = n / n_dev
    return {"bytes": 2 * rows * width, "flops": 0,
            "ici_bytes": rows * width * (n_dev - 1) / n_dev}


def sort(n: int, n_dev: int, width: int) -> dict:
    """A device's rows are read once and written once in key order. A
    comparison sort makes many passes; a count of passes is the
    implementation's, not the work's."""
    return {"bytes": 2 * n / n_dev * width, "flops": 0}


def least_seconds(work: dict, peak: dict, ici_peak: dict) -> float:
    """The least time one chip could take: the larger of its HBM bytes
    over the HBM peak and the bytes that cross chips over the chip's
    whole ICI peak (all links at once: no share can read above 100 %)."""
    return max(work["bytes"] / peak["hbm_bytes_per_s"],
               work.get("ici_bytes", 0) / ici_peak["ici_bytes_per_s"])
