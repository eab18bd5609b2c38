"""Readings that set a cell's correctness limit, on the chip.

    python3 benchmarks/chip/control.py --workload qwen05_fast_chat \
        --seeds 1,2,3 --seconds 20

For each seed, in one process: a full run of the cell (set-up, window,
the plain reference over the sample), and on the same sample each
control of its family's ``CONTROLS`` put in the program's place. One JSON
line per seed: for the served tokens (``program``) and for each control,
every number the cell's limits file names, beside its limit, and the
``correct`` those numbers give. A limit lies between the largest program
reading and the smallest control reading of its number, and each
control has to come out ``correct: false``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import harness


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    cell = harness.resolve(harness.ROOT, args.workload)
    harness.use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(cell, seed, args.seconds, False, peak=None,
                               t_process=time.perf_counter(), control=True)
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "readings": res["control"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
