"""Example programs + driver.

≈ the reference's ``src/examples/org/apache/hadoop/examples`` tree with its
``ExampleDriver`` (ExampleDriver.java): a name→program registry the CLI
dispatches to (``tpumr examples <name> <args>``). Each program is a
function ``main(argv: list[str]) -> int``.
"""

from __future__ import annotations

import sys
from typing import Callable

_PROGRAMS: dict[str, tuple[Callable[[list[str]], int], str]] = {}


def register(name: str, description: str):
    def deco(fn):
        _PROGRAMS[name] = (fn, description)
        return fn
    return deco


def programs() -> dict[str, str]:
    _load_all()
    return {k: v[1] for k, v in sorted(_PROGRAMS.items())}


def _load_all() -> None:
    # import for registration side effects
    from tpumr.examples import basic  # noqa: F401
    from tpumr.examples import join  # noqa: F401
    from tpumr.examples import pagerank  # noqa: F401
    from tpumr.examples import random_writer  # noqa: F401
    from tpumr.examples import secondary_sort  # noqa: F401
    from tpumr.examples import sleep  # noqa: F401
    from tpumr.examples import sort  # noqa: F401
    from tpumr.examples import terasort  # noqa: F401
    from tpumr.examples import uservisits  # noqa: F401


def main(argv: list[str]) -> int:
    """≈ ExampleDriver.main: dispatch by program name."""
    _load_all()
    if not argv or argv[0] in ("-h", "--help", "help"):
        print("Valid program names are:", file=sys.stderr)
        for name, desc in programs().items():
            print(f"  {name}: {desc}", file=sys.stderr)
        return 0 if argv else 255
    name, *rest = argv
    if name not in _PROGRAMS:
        print(f"Unknown program '{name}'", file=sys.stderr)
        for prog, desc in programs().items():
            print(f"  {prog}: {desc}", file=sys.stderr)
        return 255
    return _PROGRAMS[name][0](rest)
