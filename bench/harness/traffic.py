"""The general traffic generator: a traffic file's scenarios, realized for
a configuration's pool from the run's seed.

Realization ``k`` of scenario ``i`` is re-rolled by a seed drawn from
``(seed, k, i)``, so ``--seed`` changes every arrival matrix and the
simulator seed beside it, while every seed runs the same shapes.
"""
from __future__ import annotations

import numpy as np

from harness.generators import build


def seeds(seed: int, k: int, i: int):
    """``(scenario_seed, sim_seed)`` for realization ``k`` of scenario
    ``i``, both below 2**31."""
    s = np.random.SeedSequence([int(seed), int(k), int(i)]).generate_state(2)
    return int(s[0] >> 1), int(s[1] >> 1)


def realize(traffic: dict, cfg: dict, seed: int, k: int) -> dict:
    """Realization ``k``: ``arrivals`` ``[B, A, T]`` (one row block per
    scenario), ``sim_seeds`` ``[B]`` and the scenario ``names``."""
    A, T = int(cfg["streams"]), int(cfg["ticks"])
    pool_rps = float(cfg["rps_per_stream"]) * A
    mats, sim_seeds = [], []
    for i, spec in enumerate(traffic["scenarios"]):
        sc_seed, sim_seed = seeds(seed, k, i)
        mats.append(build(spec, A, T, pool_rps, sc_seed))
        sim_seeds.append(sim_seed)
    return {"arrivals": np.stack(mats), "sim_seeds": sim_seeds,
            "names": [s["name"] for s in traffic["scenarios"]]}
