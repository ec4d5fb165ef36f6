#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program and its control.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

Run on the chip, from the root of a checkout, at the cell's own size and
load.  For each seed, one run's window as the benchmark makes it, then the
numbers that decide ``correct`` (``bench/reference.py``) for two sets of
answers to the same queries: the program's, and the control's -- the
exact reference computed in bfloat16, the precision below the float32
that the configurations state, put in the program's place.  Prints one
JSON line per seed.  A limit lies above the program's largest reading and
below the control's smallest.  The benchmark's runs do not run this.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import reference, registry, run  # noqa: E402


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    cell = registry.resolve(run.ROOT, args.workload)
    devs = run.start_jax(cell)
    k = cell.config["k"]
    for seed in (int(s) for s in args.seeds.split(",")):
        m = run.measure(cell, seed, args.seconds, False, devs)
        true_ids = reference.exact_topk(m.x, m.q, k)[1]
        ctl_d, ctl_i = reference.control_answers(m.x, m.q, k)
        print(json.dumps({
            "seed": seed, "answers": len(m.q), "failed": m.failed,
            "program": m.numbers(true_ids),
            "control": reference.compare(m.x, m.q, ctl_i, ctl_d, true_ids, 0),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
