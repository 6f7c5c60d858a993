"""The operations and bytes the aggregation's two device steps need for a
job, from its rows and groups alone (never the padded bucket, nor the
passes an implementation makes): the numerators of ``agg_sort_roofline``
and ``agg_segment_sum_roofline``. ``bench/work.py`` has the rule."""

from __future__ import annotations

from bench import work

KEY_WORDS = 4           # a 16-byte key


def sort(rows: int) -> dict:
    """Order ``rows`` keys of four uint32 words: ``work.argsort``."""
    return work.argsort(rows, KEY_WORDS)


def segment_sum(rows: int, groups: int) -> dict:
    """Sum a float32 column over runs of equal 16-byte keys in sorted
    order: every row's key and value are read once through the
    permutation (16 + 4 bytes and a 4-byte index), every group's key and
    sum written once (20 bytes). One add a row is no FLOP term worth
    counting beside 24 bytes: the step is HBM-bound."""
    return {"bytes": rows * (16 + 4 + 4) + groups * 20, "flops": 0}
