#!/usr/bin/env python3
"""Read a cell's control at the cell's own size.

    python3 bench/control.py --workload <cell> --seed <n> [--seed <n> ...]

The control is the plain reference put in the program's place with what a
later PR would be tempted by: computed in the next precision down (K-Means:
bfloat16), or with one stated guarantee broken (TeraSort: ordered by a key
prefix). It has to come out as NOT correct; its readings set the upper end
of each limit (PERF.md). ``--faults`` reads, the same way, the faults the
family plants in its reference (K-Means: rows a map leaves out, a split's
partial sums lost). The benchmark's own runs never run it. It needs
no chip and starts no daemon; it uses the seed's input where a run of the
cell left it and makes it otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import run  # noqa: E402


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--faults", action="store_true",
                    help="read the family's planted faults instead")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = run.load_cell(run.load_benchmark(), args.workload)
    cfg = cell["config"]
    sizes = dict(cfg["sizes"], **(cfg["rehearse"] if args.rehearse else {}))
    passed = 0
    for seed in args.seed:
        inputs = run.prepare_input(cell, sizes, seed, args.rehearse)
        if args.faults:     # every fault has to fail on its least reading
            for name, c in cell["family"].faults(
                    sizes, seed, inputs, cfg["limits"]).items():
                correct = c["least"] <= c["limit"]
                passed += correct
                print(json.dumps({"fault_of": args.workload, "seed": seed,
                                  "fault": name, "correct": correct, **c}),
                      flush=True)
            continue
        checks = cell["family"].control(sizes, seed, inputs, cfg["limits"])
        correct = run.judge(checks)
        passed += correct
        print(json.dumps({"control_of": args.workload, "seed": seed,
                          "correct": correct, "checks": checks}),
              flush=True)
    return 1 if passed else 0     # a control or fault that passes is the fault


if __name__ == "__main__":
    sys.exit(main())
