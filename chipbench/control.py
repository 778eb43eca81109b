"""Readings that set a cell's limit: the program's gap to the reference on a
dozen seeds or more (the lower reading), and the control's (the upper).

    python3 chipbench/control.py --workload g500s19-u12-2 --seeds 1-12

The configuration states float32 tables and float32 multiply-adds, so the
control is the reference with every table stored in bfloat16, put in the
program's place: its answers are compared with the reference's on the same
colorings.  The reference at ``Precision.HIGH`` is read beside it.  In one
process: set-up once, then per seed the window's stream for as many batches as
hold the answers a run compares, then, with the program's state gone, the
references on each picked answer.  Prints one JSON line per seed and, last,
the largest program gap and each control's smallest gap.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def seeds_arg(text: str):
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


CONTROLS = ("high", "bfloat16")


def readings(spec, seeds, *, controls=CONTROLS, require_tpu: bool = True):
    """Per seed, the program's gap and each control's: the largest over the
    answers a run would compare."""
    import run

    cell = run.build(spec, run.seed_key(seeds[0], run.WARM), require_tpu=require_tpu)
    count = spec["traffic"]["check_colorings"]
    steps = -(-count // cell.batch)
    windows = {}
    for seed in seeds:
        key = run.seed_key(seed, run.WINDOW)
        stream = cell.counter.sample_stream(key, batch=cell.batch)
        windows[seed] = (key, np.asarray([next(stream) for _ in range(steps)], np.float64))
    del stream  # it holds the Counter, and with it the plan on the chips
    run.free_program(cell)
    out = []
    for seed, (key, answers) in windows.items():
        picks = run.pick_answers(seed, count, answers.size)
        want = run.reference_answers(spec, cell, key, answers.shape, picks)
        prog = max(run.rel_gap(answers.flat[p], w) for p, w in zip(picks, want))
        row = {"seed": seed, "program_gap": prog}
        for precision in controls:
            ctrl = run.reference_answers(spec, cell, key, answers.shape, picks, precision)
            row[precision] = max(run.rel_gap(c, w) for c, w in zip(ctrl, want))
        out.append(row)
        print(json.dumps(row), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds_arg, required=True, help="e.g. 1-12 or 3,5,8")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH_DIR]
    import run

    spec = run.load_cell(args.workload)
    run.use_compile_cache()
    rows = readings(spec, args.seeds)
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "lower": max(r["program_gap"] for r in rows),
                      **{p: min(r[p] for r in rows) for p in CONTROLS}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
