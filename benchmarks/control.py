"""Readings that the limits of ``benchmarks/limits/<job>.json`` are set
from, at a cell's own size, many seeds in one process:

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3,... --controls 3 --out <file.jsonl>

For every seed: set-up, the warm rounds and one window round through the
cell's own job, then the comparison; the numbers are the program's (the
lower readings).  For the first ``--controls`` seeds the comparison also
reads the control, the reference in the program's place with the gradient
pair in bfloat16 (``*_low``), and the faults planted in the reference's
place: half of the rows left out and the rest doubled (``*_half``), the
margin of one round before (``*_stale``), the root's cut moved by 16 bins
(``split_gap_moved``), every second cut left out (``bin_mass_gap_half``).  The benchmark's own runs never come here.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse-rows", type=int, default=None)
    args = ap.parse_args(argv)

    cell = run.load_cell(args.workload)
    run.find_device(int(cell["chips"]), bool(args.rehearse_rows))
    from xgboost_tpu.serving.warmcache import configure_persistent_cache

    configure_persistent_cache()
    job = run.load_module("jobs", cell["traffic"]["job"])
    env = {"log": run.log, "rehearse_rows": args.rehearse_rows}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        state = job.setup(cell, seed, env)
        job.window(state, 0.1)
        extra = i < args.controls
        numbers = job.compare(state, env, lower_precision=extra, faults=extra)
        numbers.update(seed=seed, workload=args.workload,
                       round_s=state.clocks["round_s"])
        with open(args.out, "a") as fh:
            fh.write(json.dumps(numbers) + "\n")
        run.log(f"seed {seed}: " + json.dumps(numbers))
        del state
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
