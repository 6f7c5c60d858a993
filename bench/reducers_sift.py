"""A reader for the SIFT K-Means cell (found by ``run.find_reducer``, as
``reducers.py`` says): how long CPU slots went on with maps after the
chip's last, from the program's spans. A program that records no such
span gives None: the metric is left out.
"""

from __future__ import annotations

from bench.reducers_spans import _by_job, _maps, _mean, _spans


def cpu_overhang_s(obs: dict):
    """Per job, the end of the last CPU map's ``task:launch`` minus the
    end of the last TPU map's, 0 where the chip ended last (or no map ran
    on a CPU slot); the mean over the window's jobs that ran a map on the
    chip."""
    over = []
    for spans in _by_job(_spans(obs)).values():
        tpu = [s["end"] for s in _maps(spans, "task:launch", "tpu")]
        cpu = [s["end"] for s in _maps(spans, "task:launch", "cpu")]
        if tpu:
            over.append(max(0.0, max(cpu, default=0.0) - max(tpu)))
    return _mean(over)
