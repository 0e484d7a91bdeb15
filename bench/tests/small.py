"""A cell resized for the CPU: 64 streams, 600 ticks, every other file
as committed."""
import argparse

import run as bench_run
from harness import spec

STREAMS, TICKS = 64, 600


def small_cell(workload: str) -> dict:
    cell = spec.cell(workload)
    cell["config"] = dict(cell["config"], streams=STREAMS, ticks=TICKS)
    return cell


def run_small(workload: str, seed: int, seconds: float = 0.3, trace: int = 0) -> dict:
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)
    return bench_run.run(args, cell=small_cell(workload),
                         require_accelerator=False)


def cells():
    return [w["name"] for w in spec.benchmark()["workloads"]]
