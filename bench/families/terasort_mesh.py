"""TeraSort whose gang reduce has to run across a mesh of chips: the
``terasort`` family as it is (input from the seed, the plain numpy
reference, ``check``, ``control``, the client), with one more guarantee
held against every job: the exchange and the sort ran over as many devices
as the configuration's ``sizes["mesh"]`` says (``TPU_SHUFFLE_DEVICES``). A
program that writes no such counter, or that sorted on one chip of a
four-chip host, gives an unsound job.
"""

from __future__ import annotations

from bench.cluster import BACKEND, counter
from bench.families import terasort
from bench.families.terasort import (Session, check, control,  # noqa: F401
                                     make_input, rows_per_job)


def job_failure(r: dict, sizes: dict, on_chip: bool) -> "str | None":
    why = terasort.job_failure(r, sizes, on_chip)
    if why:
        return why
    ran_over = counter(r, BACKEND, "TPU_SHUFFLE_DEVICES")
    if ran_over != sizes["mesh"]:
        return (f"the exchange and the sort ran over {ran_over} device(s), "
                f"not {sizes['mesh']}")
    return None
