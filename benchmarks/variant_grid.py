"""BENCH: the joint model x resource decision space — variant-aware
schedulers across the workload-scenario zoo, dispatched through the
jitted vmapped grid.

Every stream carries a pool-wide accuracy SLO (``ACC_FLOOR``) and the
engine runs with a :class:`~repro.core.sim.VariantCatalog` over the
8-arch serving pool, so schedulers can trade accuracy against cost at
runtime (INFaaS / Cocktail: the decision prior work never makes
jointly with procurement).  Four points on the frontier per scenario:

  ``reactive``        — fixed-variant baseline: every arch pinned to its
                        base model; cheap procurement, but the accuracy
                        SLO is violated wherever the base model sits
                        below the floor.
  ``paragon``         — the paper's class-aware scheme, also pinned.
  ``accuracy_floor``  — cheapest variant meeting each stream's floor
                        (the runtime form of the paper's least-cost
                        selection) on Paragon procurement.
  ``infaas_variant``  — upgrade-on-slack / downgrade-on-pressure: spends
                        idle capacity on accuracy, sheds accuracy under
                        queue pressure.

Since the variant axis lives inside the ``lax.scan`` (PR 9) the whole
zoo runs as ONE :func:`~repro.core.sim.jax_engine.run_grid` vmapped
dispatch per policy — the per-cell summaries come out of the jitted
scan, and a NumPy-oracle cell pins the dispatch against the reference
engine at 1e-6 before any claim is read off it.

Artifact: ``BENCH_variant_grid.json``.

Claims:
  * both variant-aware schedulers are registered in VECTOR_SCHEDULERS
    AND in the scan-side JAX_POLICIES registry (CI fails if either
    form is ever dropped — the fleet-speed path must not silently
    regress to NumPy-only);
  * one (scenario, policy) cell re-run through the NumPy engine matches
    the vmapped dispatch at 1e-6 with exact swap counts;
  * request flow AND accuracy mass conserve in every cell;
  * ``accuracy_floor`` strictly dominates fixed-variant ``reactive`` on
    cost at equal-or-better delivered accuracy on >= 3 zoo scenarios
    (and eliminates its accuracy-SLO violations);
  * ``infaas_variant`` actually exercises the swap pipeline and
    delivers more accuracy than the fixed baseline;
  * the variant-aware scan at A=64 runs >= 5x the NumPy tick loop —
    same process, min-over-repeats on both sides (report-only under
    BENCH_SMALL: CI boxes vary too much for an absolute-ratio gate).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import numpy as np

from benchmarks.common import (
    BENCH_SMALL,
    Row,
    SERVING_POOL,
    STRICT_FRAC,
    print_rows,
    write_artifact,
)
from repro.core.schedulers import VECTOR_SCHEDULERS
from repro.core.sim import (
    ServingSim,
    VariantCatalog,
    replicate_pool,
    uniform_pool_workload,
)
from repro.core.workloads import SCENARIO_ZOO

DURATION_S = 600 if BENCH_SMALL else 3600
MEAN_RPS = 200.0 if BENCH_SMALL else 400.0
#: pool-wide accuracy SLO: above the cheap tier (whisper/qwen/rwkv/
#: minicpm sit below it -> a fixed-variant fleet must violate), below
#: the premium tier (several candidates satisfy it -> a real choice)
ACC_FLOOR = 0.55
POLICIES = ("reactive", "paragon", "infaas_variant", "accuracy_floor")
#: the cell the NumPy oracle re-runs (the scenario with the most swap
#: pressure under the slack-driven scheduler)
ORACLE_CELL = ("trending_hotswap", "infaas_variant")
# speedup section: variant-aware scan vs the NumPy tick loop at the
# INFaaS pool scale.  Full scan length always — a short scan
# under-amortizes dispatch overhead and misstates the claim.
SPEEDUP_ARCHS = 64
SPEEDUP_TICKS = 3600
SPEEDUP_REPEATS = 2 if BENCH_SMALL else 3
SPEEDUP_FLOOR = 5.0


def _numpy_run(arrivals: np.ndarray, wl, catalog, pol_name: str):
    sim = ServingSim(arrivals, wl, catalog=catalog)
    policy = VECTOR_SCHEDULERS[pol_name]()
    while not sim.done:
        sim.apply_pool(policy(sim.tick, sim.observe_pool()))
    return sim


def _cell_conserves(cell: dict, acc_lo: np.ndarray, acc_hi: np.ndarray) -> bool:
    """Flow + accuracy-mass conservation from one grid cell's per-arch
    arrays: admitted mass is fully accounted, and the delivered-accuracy
    mass sits inside the catalog's per-arch accuracy envelope (the scan
    must bill accuracy at an actually-deployable variant, every tick)."""
    pa = cell["per_arch"]
    accounted = (
        pa["served_vm"] + pa["served_burst"] + pa["dropped"]
        + pa["expired_end"] + pa["queued"]
    )
    answered = pa["served_vm"] + pa["served_burst"] + pa["dropped"]
    return bool(
        np.allclose(pa["arrived"], accounted, atol=1e-6, rtol=1e-9)
        and (pa["acc_weight"] <= answered * acc_hi + 1e-6).all()
        and (pa["acc_weight"] >= answered * acc_lo - 1e-6).all()
        and (pa["acc_violations"] <= answered + 1e-6).all()
    )


def _speedup_bench() -> dict:
    """Variant-aware scan vs NumPy tick loop, A=64, same process.

    Min over repeats on BOTH sides (single-core boxes jitter +-50%);
    the warm-scan wall isolates the jitted dispatch — host-side input
    build and compile are reported separately, exactly like the
    ``sim_throughput`` scan rows."""
    import jax

    from repro.core.sim import jax_engine as je

    wl = [
        dataclasses.replace(w, min_accuracy=ACC_FLOOR)
        for w in replicate_pool(SERVING_POOL, SPEEDUP_ARCHS,
                                strict_frac=STRICT_FRAC)
    ]
    catalog = VariantCatalog.for_workload(wl)
    arr = SCENARIO_ZOO["trending_hotswap"].build(
        SPEEDUP_ARCHS, duration_s=SPEEDUP_TICKS, mean_rps=MEAN_RPS
    )

    np_wall = float("inf")
    for _ in range(2):
        t = time.perf_counter()
        sim = _numpy_run(arr, wl, catalog, "infaas_variant")
        np_wall = min(np_wall, time.perf_counter() - t)
    res_np = sim.res

    pol = je.JAX_POLICIES["infaas_variant"]
    statics, state0, xs = je.build_sim_inputs(
        arr, wl, catalog=catalog, needs_stats=pol.needs_stats
    )
    statics["policy"] = pol.default_params()
    runner = je._get_runner("infaas_variant", variants=True)
    with jax.enable_x64(True):
        t = time.perf_counter()
        out = jax.block_until_ready(runner(statics, state0, xs))
        first = time.perf_counter() - t
        scan_wall = float("inf")
        for _ in range(SPEEDUP_REPEATS):
            t = time.perf_counter()
            out = jax.block_until_ready(runner(statics, state0, xs))
            scan_wall = min(scan_wall, time.perf_counter() - t)
    res_jx = je._assemble(
        jax.tree.map(np.asarray, out), np.asarray(arr, dtype=np.float64)
    )["summary"]
    # the timed pair IS a differential sample: both engines must agree
    # before the ratio means anything
    assert abs(res_jx["cost_total"] - res_np.cost_total) <= 1e-6 * max(
        abs(res_np.cost_total), 1.0
    ), "engines drifted on the speedup pair"
    assert res_jx["variant_swaps"] == res_np.variant_swaps, "swap-count drift"
    return {
        "archs": SPEEDUP_ARCHS,
        "ticks": SPEEDUP_TICKS,
        "policy": "infaas_variant",
        "scenario": "trending_hotswap",
        "variant_swaps": int(res_np.variant_swaps),
        "numpy_wall_s": np_wall,
        "numpy_ticks_per_s": SPEEDUP_TICKS / np_wall,
        "jax_first_s": first,               # compile + run
        "jax_scan_s": scan_wall,
        "jax_ticks_per_s": SPEEDUP_TICKS / scan_wall,
        "speedup": np_wall / scan_wall,
    }


def run() -> bool:
    from repro.core.sim import jax_engine as je

    t0 = time.perf_counter()
    wl = [
        dataclasses.replace(w, min_accuracy=ACC_FLOOR)
        for w in uniform_pool_workload(SERVING_POOL, strict_frac=STRICT_FRAC)
    ]
    catalog = VariantCatalog.for_workload(wl)
    # per-arch accuracy envelope for the mass-conservation check
    acc_lo = np.array([min(v.accuracy for v in catalog.variants(w.arch))
                       for w in wl])
    acc_hi = np.array([max(v.accuracy for v in catalog.variants(w.arch))
                       for w in wl])

    scenarios = list(SCENARIO_ZOO)
    arrs = np.stack([
        SCENARIO_ZOO[name].build(len(wl), duration_s=DURATION_S,
                                 mean_rps=MEAN_RPS)
        for name in scenarios
    ])

    payload: Dict[str, dict] = {
        "duration_s": DURATION_S,
        "mean_rps": MEAN_RPS,
        "accuracy_floor": ACC_FLOOR,
        "pool": SERVING_POOL,
        "variants_per_arch": {a: catalog.n_variants(a) for a in SERVING_POOL},
        "grid": {name: {"scenario": SCENARIO_ZOO[name].to_dict()}
                 for name in scenarios},
        "dispatch": {},
    }

    # -- the whole zoo per policy, ONE vmapped dispatch each ----------
    conserved = True
    for pol_name in POLICIES:
        t = time.perf_counter()
        cells = je.run_grid(arrs, wl, pol_name, catalog=catalog)
        payload["dispatch"][pol_name] = {
            "cells": len(cells), "wall_s": time.perf_counter() - t,
        }
        for name, cell in zip(scenarios, cells):
            ok = _cell_conserves(cell, acc_lo, acc_hi)
            conserved &= ok
            payload["grid"][name][pol_name] = {
                **cell["summary"], "conserved": ok,
            }

    # -- NumPy-oracle cell: the dispatch's numbers are the engine's ---
    oracle_scenario, oracle_policy = ORACLE_CELL
    sim = _numpy_run(arrs[scenarios.index(oracle_scenario)], wl, catalog,
                     oracle_policy)
    np_summary = sim.res.summary()
    jx_summary = payload["grid"][oracle_scenario][oracle_policy]
    oracle_ok = all(
        np.isclose(jx_summary[k], v, rtol=1e-6, atol=1e-6)
        for k, v in np_summary.items()
    ) and jx_summary["variant_swaps"] == np_summary["variant_swaps"]
    payload["oracle_cell"] = {
        "scenario": oracle_scenario, "policy": oracle_policy,
        "numpy": np_summary, "ok": oracle_ok,
    }

    # -- frontier claims off the per-cell summaries -------------------
    dominated, infaas_swapped, infaas_more_accurate = [], [], []
    for name in scenarios:
        cell = payload["grid"][name]
        r_fix, r_floor, r_inf = (
            cell["reactive"], cell["accuracy_floor"], cell["infaas_variant"]
        )
        dominated.append(
            r_floor["cost_total"] < r_fix["cost_total"]
            and r_floor["mean_accuracy"] >= r_fix["mean_accuracy"] - 1e-9
            and r_floor["acc_violation_rate"] <= r_fix["acc_violation_rate"]
        )
        infaas_swapped.append(r_inf["variant_swaps"] > 0)
        infaas_more_accurate.append(
            r_inf["mean_accuracy"] > r_fix["mean_accuracy"]
        )
        cell["accuracy_floor_dominates_reactive"] = dominated[-1]

    payload["speedup_a64"] = sp = _speedup_bench()

    registered = all(
        name in VECTOR_SCHEDULERS and name in je.JAX_POLICIES
        for name in ("infaas_variant", "accuracy_floor")
    )
    n_dom = int(np.sum(dominated))
    rows: List[Row] = [
        ("variant_schedulers_registered", float(registered),
         "infaas_variant + accuracy_floor present in VECTOR_SCHEDULERS "
         "and JAX_POLICIES (the scan-side registry)", registered),
        ("scenarios", float(len(scenarios)),
         "grid covers >= 4 zoo scenarios", len(scenarios) >= 4),
        ("oracle_cell_parity", float(oracle_ok),
         "vmapped-dispatch cell == NumPy engine at 1e-6, exact swaps",
         oracle_ok),
        ("conserved_all", float(conserved),
         "request flow + accuracy mass conserve in every cell", conserved),
        ("accuracy_floor_dominates", float(n_dom),
         "accuracy_floor beats fixed-variant reactive on cost at >= equal "
         "accuracy and <= acc violations on >= 3 scenarios", n_dom >= 3),
        ("infaas_swaps_all_scenarios", float(np.sum(infaas_swapped)),
         "infaas_variant exercises the swap pipeline on every scenario",
         all(infaas_swapped)),
        ("infaas_more_accurate", float(np.sum(infaas_more_accurate)),
         "upgrade-on-slack delivers more accuracy than the fixed baseline "
         "on every scenario", all(infaas_more_accurate)),
        ("variant_scan_speedup_a64", sp["speedup"],
         f"variant-aware jitted scan >= {SPEEDUP_FLOOR:g}x the NumPy tick "
         f"loop at A={SPEEDUP_ARCHS} ({SPEEDUP_TICKS} ticks, same process, "
         "min-over-repeats; report-only under BENCH_SMALL)",
         BENCH_SMALL or sp["speedup"] >= SPEEDUP_FLOOR),
    ]

    # persist the enforced claims into the artifact itself (same
    # convention as BENCH_tier_portfolio) so the committed JSON records
    # what was asserted, not just the measured inputs
    payload["claims"] = {
        metric: {"value": value, "claim": claim, "ok": bool(ok)}
        for metric, value, claim, ok in rows
    }
    write_artifact("BENCH_variant_grid", payload, t0)
    return print_rows("variant_grid", rows, t0)


if __name__ == "__main__":
    raise SystemExit(0 if run() else 1)
