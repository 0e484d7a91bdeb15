"""The check's control: the plain reference in float32, put in the
program's place, against the same reference in the configuration's
float64.  Each compared number must read far above its limit here,
or the check could not tell a float32 program from a sound one.

    python bench/control.py --workload <cell> --seeds 1 2 3

For every seed it draws the answers a run would compare (the same
realizations, the same sample) and prints the three numbers as one
JSON line.  NumPy only; it never touches an accelerator.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR]

LOWER = {"float64": "float32"}


def readings(cell: dict, seed: int) -> dict:
    """The control's three numbers for one seed."""
    from harness import check, traffic

    cfg, tr = cell["config"], cell["traffic"]
    K, B = int(tr["realizations"]), len(tr["scenarios"])
    picks = check.sample(K, B, int(tr["sample"]), seed)
    reals = {k: traffic.realize(tr, cfg, seed, k) for k in sorted({k for k, _ in picks})}
    jobs = []
    for k, i in picks:
        arr, sim_seed = reals[k]["arrivals"][i], reals[k]["sim_seeds"][i]
        for dt in (cfg["precision"], LOWER[cfg["precision"]]):
            jobs.append({"arrivals": arr, "cfg": cfg, "dtype": dt,
                         "sim_seed": sim_seed})
    outs = check.references(jobs)
    per = [check.compare(outs[2 * n + 1], outs[2 * n]) for n in range(len(picks))]
    return check.worst(per)


def main(argv=None) -> int:
    from harness import spec

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **readings(cell, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
