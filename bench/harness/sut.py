"""The system under test: the program's fleet engine, called through its
public entry points exactly as a user calls them.

``entry`` comes from the traffic file: ``grid`` is one
``jax_engine.run_grid`` call over every scenario of a realization,
``single`` one ``jax_engine.run_scenario`` call per scenario.  A call
runs from the arrival matrices on the host to the result dicts on the
host.  :func:`extract` keeps, per answer, what the check compares.
"""
from __future__ import annotations

import dataclasses

import numpy as np


class Program:
    def __init__(self, cfg: dict, entry: str):
        from repro.core.sim import VariantCatalog, jax_engine, replicate_pool

        self.je = jax_engine
        self.entry = entry
        self.policy = cfg["policy"]
        if entry not in ("grid", "single"):
            raise ValueError(f"unknown entry {entry!r}")
        floor = float(cfg.get("accuracy_floor") or 0.0)
        self.workload = [
            dataclasses.replace(w, min_accuracy=floor)
            for w in replicate_pool(cfg["models"], int(cfg["streams"]),
                                    strict_frac=float(cfg["strict_frac"]))
        ]
        self.catalog = None
        if cfg.get("catalog"):
            # a stream's substitutes are the models of its own task
            per_arch, base_idx = {}, {}
            for models in cfg["tasks"].values():
                part = VariantCatalog.for_workload(
                    [w for w in self.workload if w.arch in models],
                    candidates=models)
                per_arch.update(part.per_arch)
                base_idx.update(part.base_idx)
            self.catalog = VariantCatalog(per_arch, base_idx)

    def call(self, real: dict) -> list:
        arr, seeds = real["arrivals"], real["sim_seeds"]
        if self.entry == "grid":
            return self.je.run_grid(arr, self.workload, self.policy,
                                    seeds=seeds, catalog=self.catalog)
        return [self.je.run_scenario(a, self.workload, self.policy, seed=s,
                                     catalog=self.catalog)
                for a, s in zip(arr, seeds)]


def extract(res: dict) -> dict:
    """The per-stream flows, ledger totals and final fleet of one
    answer, as host arrays (the names of the reference's output)."""
    pa, raw = res["per_arch"], res["raw"]
    tot, final = raw["totals"], raw["final"]
    A = len(pa["arrived"])
    f = lambda x: np.asarray(x, dtype=np.float64)
    flows = {k: f(pa[k]) for k in ("arrived", "served_vm", "served_burst",
                                   "dropped", "expired_end", "violations",
                                   "queued", "acc_weight", "acc_violations")}
    flows["cost_arch"] = f(tot["cost_arch"])
    totals = {
        "cost_reserved": float(tot["cost_res"]),
        "cost_spot": float(tot["cost_spot"]),
        "cost_burst": float(tot["cost_burst"]),
        "cost_harvest": float(tot["cost_harv"]),
        "cost_remote": float(tot["cost_rem"]),
        "chip_seconds": float(tot["chip"]),
        "chip_seconds_needed": float(tot["need"]),
        "chip_seconds_over": float(tot["over"]),
        "preemptions": int(tot["preempt"]),
        "variant_swaps": int(tot["swaps"]) if "swaps" in tot else 0,
    }
    var = final.var_cur if final.var_cur is not None else np.zeros(A)
    fleet = {"active": np.asarray(final.res_active, dtype=np.int64),
             "pending": np.asarray(final.res_cum - final.res_mat, dtype=np.int64),
             "variant": np.asarray(var, dtype=np.int64)}
    return {"flows": flows, "totals": totals, "fleet": fleet}
