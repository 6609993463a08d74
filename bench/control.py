"""Readings that set the limits of ``correct``: the program's and the
controls', on many seeds, at a cell's own size.

    python3 bench/control.py --workload keys_random --seeds 1 2 3 [--program]

For each seed the cell's relation is drawn and the plain reference built.
Each of the relation's controls (``CONTROLS`` in
``bench/relations/<relation>.py``) stands in the program's place: the
reference with one guarantee broken, such as ``int_keys``'s ``int8_key``,
keys held at 8 bits of precision for the route and the order alike, the
step to a narrower key that would tempt a later change.

With ``--program`` the program itself runs one job per seed, through the
same call as the benchmark's window, in this one process (one warm-up job
first).  One JSON line per seed and answer gives every compared number.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def readings(cell: run.Cell, seeds, program: bool) -> list[dict]:
    relation = run.load_relation(cell)
    rows = []
    if program:
        relation.Workload(cell, seeds[0]).job()  # warm-up: compile or load
    for seed in seeds:
        work = relation.Workload(cell, seed)
        answers = {}
        if program:
            _, answers["program"] = work.job()
        for name, make in relation.CONTROLS.items():
            answers[name] = make(work)
        ref = work.reference()
        for name, answer in answers.items():
            row = {"seed": seed, "answer": name, **ref.compare(answer)}
            rows.append(row)
            print(json.dumps(row), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(run.ROOT / "src"))
    if args.program:
        run.require_chip(cell.chips)
        run.enable_cache()
    readings(cell, args.seeds, args.program)
    return 0


if __name__ == "__main__":
    sys.exit(main())
