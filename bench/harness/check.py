"""The comparison that decides ``correct``: the program's answers against
the plain reference, number by number, each against its limit.

Three numbers, over every compared answer:

``flow_err``
    the widest relative gap of any per-stream flow (served, offloaded,
    dropped, expired, violating, still queued, arrived, accuracy mass,
    accuracy violations, attributed cost): ``|got - want| / max(|want|, 1)``;
``cost_err``
    the same over the ledger's totals (the cost of each tier and the
    provisioned, needed and idle chip-seconds);
``fleet_diff``
    how many final per-stream fleet sizes and in-flight launches of every
    tier (reserved, spot, harvest, remote) and active variants differ,
    plus the gaps in the preemption and swap counts.  Integers, compared
    exactly.

A reference job is one answer's ``arrivals`` ``[A, T]``, the
configuration (``cfg``), the float ``dtype`` and the ``sim_seed`` the
program ran that answer with, which a reference of a seeded deployment
(spot reclaims, the harvest signal) draws its streams from.  The
reference runs in NumPy-only worker processes, so the process that
holds the chip is never forked and the references of one run proceed
side by side.
"""
from __future__ import annotations

import importlib
import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor
from typing import Dict, List, Sequence

import numpy as np

NUMBERS = ("flow_err", "cost_err", "fleet_diff")
COST_KEYS = ("cost_reserved", "cost_spot", "cost_burst", "cost_harvest",
             "cost_remote", "chip_seconds", "chip_seconds_needed",
             "chip_seconds_over")


def _rel(got, want) -> float:
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    if got.shape != want.shape:
        return float("inf")
    err = np.abs(got - want) / np.maximum(np.abs(want), 1.0)
    return float(np.max(err)) if err.size else 0.0


def compare(got: dict, want: dict) -> Dict[str, float]:
    """The three numbers for one answer.  Both sides must state the same
    flows and the same fleet keys: a key that only one side states
    raises instead of passing unseen."""
    for part in ("flows", "fleet"):
        if set(got[part]) != set(want[part]):
            raise KeyError(f"{part} keys differ: program {sorted(got[part])}, "
                           f"reference {sorted(want[part])}")
    flow = max(_rel(got["flows"][k], want["flows"][k]) for k in want["flows"])
    cost = max(_rel(got["totals"][k], want["totals"][k]) for k in COST_KEYS)
    fleet = sum(int(np.sum(np.asarray(got["fleet"][k]) != np.asarray(want["fleet"][k])))
                for k in want["fleet"])
    fleet += abs(int(got["totals"]["preemptions"]) - int(want["totals"]["preemptions"]))
    fleet += abs(int(got["totals"]["variant_swaps"]) - int(want["totals"]["variant_swaps"]))
    return {"flow_err": flow, "cost_err": cost, "fleet_diff": fleet}


def sample(n_calls: int, n_cells: int, size: int, seed: int) -> List[tuple]:
    """Up to ``size`` distinct answers ``(call, cell)`` drawn from the
    seed, every cell index of a call covered before any is repeated."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    answers = [(j, i) for j in range(n_calls) for i in range(n_cells)]
    order = [answers[x] for x in rng.permutation(len(answers))]
    picked, cells = [], set()
    for a in order:
        if a[1] not in cells:
            picked.append(a)
            cells.add(a[1])
    picked += [a for a in order if a not in picked]
    return picked[:size]


def references(jobs: Sequence[dict]) -> List[dict]:
    """Run each job's reference, ``reference/<name>.py``'s ``run_cell``
    for the configuration's ``"reference"``, in NumPy-only worker
    processes."""
    runs = [importlib.import_module("reference." + j["cfg"]["reference"]).run_cell
            for j in jobs]
    workers = max(1, min(len(jobs), (os.cpu_count() or 2) - 1))
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(max_workers=workers, mp_context=ctx,
                             initializer=_numpy_only) as pool:
        futures = [pool.submit(run, job) for run, job in zip(runs, jobs)]
        return [f.result() for f in futures]


def _numpy_only() -> None:
    # a worker never needs the accelerator: keep it off the chip even if
    # something it imports pulls in JAX
    os.environ["JAX_PLATFORMS"] = "cpu"


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[k] <= limits[k] for k in NUMBERS)


def worst(per_answer: Sequence[Dict[str, float]]) -> Dict[str, float]:
    return {k: max(a[k] for a in per_answer) for k in NUMBERS}
