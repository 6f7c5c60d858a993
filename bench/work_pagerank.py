"""The useful HBM bytes of one PageRank map, from its arcs and its range's
vertices alone (never a padded shape, nor the passes an implementation
makes): the numerator of ``pagerank_map_roofline``. ``bench/work.py`` has
the rule."""

from __future__ import annotations


def pagerank_map(arcs: int, vertices: int) -> dict:
    """Sum ``c[src]`` by destination over a split: every arc's
    (destination, source) pair read once (8 bytes) and its source's
    contribution gathered (4 bytes), every destination's sum written once
    (4 bytes). A random 4-byte read is counted at 4 bytes, though the
    memory moves a whole burst for it, so a gather reads LOW against this
    roofline by construction. One add an arc is no FLOP term worth
    counting beside 12 bytes."""
    return {"bytes": 12 * arcs + 4 * vertices, "flops": 0}
