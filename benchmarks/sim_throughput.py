"""BENCH: simulation-engine throughput (ticks/sec) vs the seed loop.

Pool sizes 4/16/64 over a full 86 400-tick (24 h) berkeley trace with the
vectorized engine, against the seed per-arch Python loop (kept as
``repro.core.sim.reference``) measured on a shorter slice of the same
trace and reported as ticks/sec.  Tracks the perf trajectory of the
engine from PR 1 onward; artifact: ``BENCH_sim_throughput.json``.

Also microbenchmarks the streaming per-arch load monitor at A=256: the
banded incremental order-statistic structure
(:class:`repro.core.load_monitor.PoolLoadMonitor`) vs the naive per-tick
window median/max recompute it replaced.

Claims: a 64-arch pool over a 24 h trace runs >= 10x faster than the
seed per-arch loop; the incremental monitor is >= 1.5x the naive
recompute at a 256-arch pool.

PR 6 adds the ``jax_engine`` section: the jitted ``lax.scan`` tick
pipeline (:mod:`repro.core.sim.jax_engine`) against the NumPy engine's
Python tick loop on the same scenario/policy — single-scenario scan
throughput at A=64/256 (claim: >= 4x at A=64 on the scan path, compile
reported separately), and a 64-cell vmapped (scenario x seed) grid
dispatched in ONE call against serial NumPy runs (claim: >= 20x;
the serial side is extrapolated from a timed sample of cells).

PR 7 adds the ``telemetry_overhead`` section: the engine with telemetry
*disabled* (the default) must stay within 3% of the committed
pre-telemetry pool-64 throughput — the zero-cost-when-off guarantee of
the observability subsystem — and the fully-enabled recorder+event-log
overhead is recorded informationally.  The disabled-vs-committed claim
is enforced on full runs only (CI machines vary too much for an
absolute-throughput gate under BENCH_SMALL).

PR 8 adds the ``fleet_scale`` section: the optimized scan construction
(accumulated totals, donated carry, lazy sliding-window-min rings)
against the pre-PR ``"legacy"`` runner flavor — the same build the
engine shipped with before the optimization, kept alive precisely so
this A/B runs in one process on one machine and is immune to
cross-box jitter.  Shapes A=256 and A=1024 at the full 3600-tick
scan; claim: >= 1.5x at both (measured ~2.9x / ~3.1x on the reference
box), with the two flavors' ledger totals asserted equivalent.  The
telemetry-overhead section also grows an A=256 pool so the
zero-cost-when-off ratchet holds at fleet scale, and ``--fleet-only``
runs just the fleet A/B for the ``fleet-scale-smoke`` CI step (no
artifact write).  Multi-device grid sharding rides the existing grid
rows transparently (``run_grid`` auto-shards when the host exposes
more than one device); exact sharded-vs-unsharded parity is pinned by
``tests/test_jax_engine.py`` under a forced multi-device host.
"""
from __future__ import annotations

import json
import os
import time
from typing import List, Optional

import numpy as np

from benchmarks.common import (
    ARTIFACTS,
    BENCH_SMALL,
    Row,
    SERVING_POOL,
    print_rows,
    write_artifact,
)
from repro.core.load_monitor import PoolLoadMonitor
from repro.core.schedulers import SCHEDULERS, VECTOR_SCHEDULERS
from repro.core.sim import replicate_pool, simulate, simulate_reference
from repro.core.traces import get_trace

POOL_SIZES = (4, 16, 64)
DAY_TICKS = 7_200 if BENCH_SMALL else 86_400
BASELINE_TICKS = 300 if BENCH_SMALL else 1_000
MEAN_RPS = 400.0
STRICT_FRAC = 0.25
MONITOR_ARCHS = 256
MONITOR_TICKS = 1_000 if BENCH_SMALL else 3_000
# jax_engine section: scan shapes, and the vmapped-grid shape.  The
# scan rows keep their full length even under BENCH_SMALL — a short
# scan under-amortizes the fixed dispatch overhead and misstates the
# steady-state throughput the claim is about; only the (much more
# expensive) grid shrinks.
JAX_SCAN_ARCHS = (64, 256)
SCAN_TICKS = 3_600
JAX_TICKS = 1_200 if BENCH_SMALL else 3_600
GRID_CELLS = 64
GRID_ARCHS = 16
GRID_SCENARIOS = ("shared_berkeley", "diurnal_phases", "mmpp_bursts",
                  "flash_correlated")
GRID_NUMPY_SAMPLE = 4 if BENCH_SMALL else 8
# fleet_scale section: opt-vs-legacy flavor A/B at fleet shapes.  Full
# scan length always (same rationale as the scan rows above); under
# BENCH_SMALL only A=256 runs — A=1024 compiles two flavors and is the
# single most expensive cell of the whole benchmark.
FLEET_ARCHS = (256,) if BENCH_SMALL else (256, 1024)
FLEET_REPEATS = 2 if BENCH_SMALL else 3
FLEET_SPEEDUP_FLOOR = 1.5


def _monitor_bench() -> dict:
    """Steady-state monitor ticks/s at A=256: incremental vs naive."""
    rng = np.random.default_rng(0)
    out = {"archs": MONITOR_ARCHS, "ticks": MONITOR_TICKS}
    for name, flag in (("incremental", True), ("naive", False)):
        mon = PoolLoadMonitor(MONITOR_ARCHS, incremental=flag)
        stream = rng.gamma(2.0, 50.0, (MONITOR_TICKS + mon.window_s, MONITOR_ARCHS))
        for t in range(mon.window_s):                 # fill outside the clock
            mon.observe(stream[t])
        t0 = time.perf_counter()
        for t in range(mon.window_s, mon.window_s + MONITOR_TICKS):
            mon.observe(stream[t])
            mon.stats()
        wall = time.perf_counter() - t0
        out[name] = {"wall_s": wall, "ticks_per_s": MONITOR_TICKS / wall}
    out["speedup"] = (
        out["incremental"]["ticks_per_s"] / out["naive"]["ticks_per_s"]
    )
    return out


def _numpy_portfolio_run(arrivals, wl, seed: int = 0):
    """The NumPy engine's full observe/apply tick loop (the comparator
    the differential tests pin the jitted scan against)."""
    from repro.core.sim import ServingSim

    sim = ServingSim(arrivals, wl, seed=seed)
    pol = VECTOR_SCHEDULERS["portfolio"]()
    while not sim.done:
        sim.apply_pool(pol(sim.tick, sim.observe_pool()))
    return sim.res


def _jax_bench() -> dict:
    """Jitted-scan vs NumPy-loop throughput, plus the vmapped grid."""
    import jax

    from repro.core.sim import jax_engine as je
    from repro.core.workloads import SCENARIO_ZOO

    out = {"scan_ticks": SCAN_TICKS, "grid_ticks": JAX_TICKS,
           "scan": {}, "grid": {}}

    # -- single-scenario scan at A = 64 / 256.  Zoo-default load: the
    # same configuration the differential-fuzz tests pin (high-rps
    # pools also lengthen the data-dependent binomial walk inside the
    # scan, which is a separate axis from tick throughput) ------------
    for A in JAX_SCAN_ARCHS:
        wl = replicate_pool(SERVING_POOL, A, strict_frac=STRICT_FRAC)
        arr = SCENARIO_ZOO["shared_berkeley"].build(A, duration_s=SCAN_TICKS)
        # min over repeats on both sides: a single-core box jitters
        # +-50%, and one noisy sample would mislabel the claim
        np_wall = float("inf")
        for _ in range(2):
            t = time.perf_counter()
            res_np = _numpy_portfolio_run(arr, wl)
            np_wall = min(np_wall, time.perf_counter() - t)

        t = time.perf_counter()
        res_jx = je.run_scenario(arr, wl, "portfolio")
        first_wall = time.perf_counter() - t
        # warm scan: same shape -> no retrace; host build excluded so
        # the row isolates the scan dispatch itself
        pol = je.JAX_POLICIES["portfolio"]
        statics, state0, xs = je.build_sim_inputs(
            arr, wl, needs_stats=pol.needs_stats
        )
        statics["policy"] = pol.default_params()
        runner = je._get_runner("portfolio")
        with jax.enable_x64(True):
            scan_wall = float("inf")
            for _ in range(3):
                t = time.perf_counter()
                jax.block_until_ready(runner(statics, state0, xs))
                scan_wall = min(scan_wall, time.perf_counter() - t)
        assert abs(
            res_jx["summary"]["cost_total"] - res_np.cost_total
        ) <= 1e-2 * max(abs(res_np.cost_total), 1.0), "engines drifted"
        out["scan"][str(A)] = {
            "numpy_wall_s": np_wall,
            "numpy_ticks_per_s": SCAN_TICKS / np_wall,
            "jax_first_s": first_wall,       # compile + host build + run
            "jax_scan_s": scan_wall,
            "jax_ticks_per_s": SCAN_TICKS / scan_wall,
            "speedup_scan": np_wall / scan_wall,
        }

    # -- 64-cell vmapped grid in one dispatch -------------------------
    wl = replicate_pool(SERVING_POOL, GRID_ARCHS, strict_frac=STRICT_FRAC)
    arrs = np.stack([
        SCENARIO_ZOO[GRID_SCENARIOS[i % len(GRID_SCENARIOS)]].build(
            GRID_ARCHS, duration_s=JAX_TICKS, mean_rps=MEAN_RPS,
            seed=100 + i // len(GRID_SCENARIOS),
        )
        for i in range(GRID_CELLS)
    ])
    seeds = [i // len(GRID_SCENARIOS) for i in range(GRID_CELLS)]

    t = time.perf_counter()
    je.run_grid(arrs, wl, "portfolio", seeds=seeds)
    grid_first = time.perf_counter() - t
    t = time.perf_counter()
    je.run_grid(arrs, wl, "portfolio", seeds=seeds)
    grid_warm = time.perf_counter() - t

    # serial NumPy side, extrapolated from a timed sample of cells
    t = time.perf_counter()
    for i in range(GRID_NUMPY_SAMPLE):
        _numpy_portfolio_run(arrs[i], wl, seed=seeds[i])
    np_serial = (time.perf_counter() - t) * GRID_CELLS / GRID_NUMPY_SAMPLE
    out["grid"] = {
        "cells": GRID_CELLS,
        "archs": GRID_ARCHS,
        "numpy_serial_est_s": np_serial,
        "numpy_sampled_cells": GRID_NUMPY_SAMPLE,
        "jax_first_s": grid_first,
        "jax_warm_s": grid_warm,
        "speedup_grid": np_serial / grid_warm,
    }
    return out


def _fleet_pair(A: int, repeats: int) -> dict:
    """One opt-vs-legacy scan A/B at pool size ``A`` (portfolio policy,
    shared_berkeley, full scan length).  Both flavors run warm in the
    same process with min-over-repeats, so the ratio is immune to the
    cross-box absolute-throughput jitter that keeps the NumPy-vs-JAX
    rows report-only; the two ledgers are asserted equivalent first."""
    import jax

    from repro.core.sim import jax_engine as je
    from repro.core.workloads import SCENARIO_ZOO

    wl = replicate_pool(SERVING_POOL, A, strict_frac=STRICT_FRAC)
    arr = SCENARIO_ZOO["shared_berkeley"].build(A, duration_s=SCAN_TICKS)
    pol = je.JAX_POLICIES["portfolio"]
    cell: dict = {"archs": A}
    totals = {}
    for flavor in ("legacy", "opt"):
        # each flavor gets its own build: the lazy rings change the
        # carry layout, legacy feeds the EWMA from the host, and the
        # opt runner donates its state0
        statics, state0, xs = je.build_sim_inputs(
            arr, wl, needs_stats=pol.needs_stats,
            lazy_rings=(flavor == "opt"),
            ewma_in_scan=None if flavor == "opt" else False,
        )
        statics["policy"] = pol.default_params()
        runner = je._get_runner("portfolio", flavor=flavor)
        with jax.enable_x64(True):
            t = time.perf_counter()
            out = jax.block_until_ready(runner(statics, state0, xs))
            first = time.perf_counter() - t
            wall = float("inf")
            for _ in range(repeats):
                t = time.perf_counter()
                out = jax.block_until_ready(runner(statics, state0, xs))
                wall = min(wall, time.perf_counter() - t)
        totals[flavor] = jax.tree.map(np.asarray, out["totals"])
        cell[flavor] = {
            "first_s": first,                # compile + run
            "wall_s": wall,
            "ticks_per_s": SCAN_TICKS / wall,
        }
    # the optimization is a pure reformulation: identical ledgers (the
    # liveness flags fold to booleans on the opt path, tick counts on
    # the stacked legacy path — only truthiness is ever consumed)
    for k, v in totals["legacy"].items():
        w = totals["opt"][k]
        if k in je._LIVE_KEYS:
            assert bool(v) == bool(w), f"flavor liveness drift: {k}"
        else:
            np.testing.assert_allclose(
                np.asarray(w), np.asarray(v), rtol=1e-9, atol=1e-9,
                err_msg=f"flavor ledger drift: {k}",
            )
    cell["speedup_opt"] = cell["legacy"]["wall_s"] / cell["opt"]["wall_s"]
    return cell


def _fleet_scale_bench() -> dict:
    """Fleet-shape scan A/B (A=256 / A=1024) + device inventory."""
    import jax

    out = {
        "ticks": SCAN_TICKS,
        "repeats": FLEET_REPEATS,
        "policy": "portfolio",
        "scenario": "shared_berkeley",
        "devices": jax.device_count(),
        "a1024_skipped_small": 1024 not in FLEET_ARCHS,
        "scan": {},
    }
    for A in FLEET_ARCHS:
        out["scan"][str(A)] = _fleet_pair(A, FLEET_REPEATS)
    return out


def _fleet_rows(fleet: dict) -> List[Row]:
    rows: List[Row] = []
    for A in FLEET_ARCHS:
        sc = fleet["scan"][str(A)]
        rows.append((
            f"fleet_opt_ticks_per_s_a{A}", sc["opt"]["ticks_per_s"],
            f"optimized scan, A={A}, {SCAN_TICKS} ticks", True,
        ))
        rows.append((
            f"fleet_opt_speedup_a{A}", sc["speedup_opt"],
            f"optimized scan >= {FLEET_SPEEDUP_FLOOR}x the pre-PR "
            "(legacy-flavor) scan, same run / same machine",
            sc["speedup_opt"] >= FLEET_SPEEDUP_FLOOR,
        ))
    return rows


def run_fleet_only() -> bool:
    """The ``fleet-scale-smoke`` CI entry: just the flavor A/B (with
    its embedded ledger-parity asserts), no artifact write."""
    t0 = time.perf_counter()
    fleet = _fleet_scale_bench()
    return print_rows("sim_throughput[fleet]", _fleet_rows(fleet), t0)


OVERHEAD_TICKS = 2_400 if BENCH_SMALL else 7_200
OVERHEAD_ARCHS = 64
OVERHEAD_FLEET_ARCHS = 256


def _prev_committed(*keys) -> Optional[float]:
    """A float from the *committed* artifact, read before this run
    overwrites it — e.g. the pre-telemetry pool-64 baseline, or the
    last full run's fleet-pool disabled throughput.  Always reads the
    full-run (non-``_small``) file; ``None`` when absent."""
    path = os.path.join(os.path.abspath(ARTIFACTS), "BENCH_sim_throughput.json")
    try:
        with open(path) as f:
            node = json.load(f)
        for k in keys:
            node = node[k]
        return float(node)
    except Exception:
        return None


def _telemetry_overhead_pool(A: int) -> dict:
    """Disabled-vs-enabled telemetry throughput on one trace/pool."""
    from repro.core.sim import Telemetry

    wl = replicate_pool(SERVING_POOL, A, strict_frac=STRICT_FRAC)
    trace = get_trace("berkeley", OVERHEAD_TICKS, mean_rps=MEAN_RPS)
    out = {"archs": A, "ticks": OVERHEAD_TICKS}
    # min over repeats on both sides — single-core boxes jitter
    for name, make_tel in (
        ("disabled", lambda: None),
        ("enabled", lambda: Telemetry(events=True, record=True)),
    ):
        wall = float("inf")
        n_events = 0
        for _ in range(2):
            tel = make_tel()
            t = time.perf_counter()
            simulate(trace, wl, VECTOR_SCHEDULERS["paragon"](), telemetry=tel)
            wall = min(wall, time.perf_counter() - t)
            if tel is not None:
                n_events = len(tel.events)
        out[name] = {"wall_s": wall, "ticks_per_s": OVERHEAD_TICKS / wall}
        if name == "enabled":
            out[name]["events"] = n_events
    out["enabled_overhead_pct"] = 100.0 * (
        out["disabled"]["ticks_per_s"] / out["enabled"]["ticks_per_s"] - 1.0
    )
    return out


def _telemetry_overhead_bench() -> dict:
    """The PR 7 pool-64 section plus the PR 8 fleet pool (A=256): the
    zero-cost-when-off guarantee must not erode as the pool widens.
    The A=64 pool ratchets against the committed *pre-telemetry*
    pool-64 day-run throughput; the A=256 pool ratchets against its own
    previous committed measurement (same-shape, same-trace)."""
    out = _telemetry_overhead_pool(OVERHEAD_ARCHS)
    out["a256"] = _telemetry_overhead_pool(OVERHEAD_FLEET_ARCHS)
    prev_256 = _prev_committed(
        "telemetry_overhead", "a256", "disabled", "ticks_per_s"
    )
    out["a256"]["prev_committed_ticks_per_s"] = prev_256
    out["a256"]["disabled_vs_committed_ratio"] = (
        out["a256"]["disabled"]["ticks_per_s"] / prev_256
        if prev_256 else None
    )
    return out


def run() -> bool:
    t0 = time.perf_counter()
    prev_tps = _prev_committed("pool_sizes", "64", "ticks_per_s")
    trace = get_trace("berkeley", DAY_TICKS, mean_rps=MEAN_RPS)
    payload = {"pool_sizes": {}, "baseline": {}}

    for n in POOL_SIZES:
        wl = replicate_pool(SERVING_POOL, n, strict_frac=STRICT_FRAC)
        t = time.perf_counter()
        res = simulate(trace, wl, VECTOR_SCHEDULERS["paragon"]())
        wall = time.perf_counter() - t
        payload["pool_sizes"][str(n)] = {
            "ticks": DAY_TICKS,
            "wall_s": wall,
            "ticks_per_s": DAY_TICKS / wall,
            "violation_rate": res.violation_rate,
            "cost_total": res.cost_total,
        }

    # seed baseline: the per-arch loop at the largest pool, short slice
    n = POOL_SIZES[-1]
    wl = replicate_pool(SERVING_POOL, n, strict_frac=STRICT_FRAC)
    t = time.perf_counter()
    simulate_reference(trace[:BASELINE_TICKS], wl, SCHEDULERS["paragon"]())
    wall = time.perf_counter() - t
    baseline_tps = BASELINE_TICKS / wall
    payload["baseline"] = {
        "pool_size": n,
        "ticks": BASELINE_TICKS,
        "wall_s": wall,
        "ticks_per_s": baseline_tps,
    }

    engine_tps = payload["pool_sizes"][str(n)]["ticks_per_s"]
    speedup = engine_tps / baseline_tps
    payload["speedup_64arch"] = speedup
    payload["monitor_a256"] = mon = _monitor_bench()
    payload["jax_engine"] = jx = _jax_bench()
    payload["fleet_scale"] = fleet = _fleet_scale_bench()
    payload["telemetry_overhead"] = ov = _telemetry_overhead_bench()
    # best observed disabled measurement vs the committed pre-telemetry
    # number; the day run above IS a telemetry-disabled run of the new
    # engine, so take whichever sample is cleaner
    off_tps = max(engine_tps, ov["disabled"]["ticks_per_s"])
    ov["prev_committed_pool64_ticks_per_s"] = prev_tps
    ov["disabled_vs_committed_ratio"] = (
        off_tps / prev_tps if prev_tps else None
    )

    rows: List[Row] = [
        (
            f"engine_ticks_per_s_{n}", payload["pool_sizes"][str(n)]["ticks_per_s"],
            f"vectorized engine, {DAY_TICKS}-tick trace", True,
        )
        for n in POOL_SIZES
    ]
    rows.append((
        "seed_loop_ticks_per_s_64", baseline_tps, "seed per-arch loop", True,
    ))
    rows.append((
        "speedup_64arch_day", speedup,
        f"64-arch {DAY_TICKS}-tick pool >= 10x faster than the seed loop",
        speedup >= 10.0,
    ))
    rows.append((
        "monitor_speedup_a256", mon["speedup"],
        "incremental banded monitor >= 1.5x naive window recompute at A=256",
        mon["speedup"] >= 1.5,
    ))
    for A in JAX_SCAN_ARCHS:
        sc = jx["scan"][str(A)]
        # the NumPy comparator's absolute speed swings tens of percent
        # across boxes, which moves a marginal ratio without either
        # engine changing (jax_ticks_per_s is the stable signal) — the
        # floor is 4x, and report-only under BENCH_SMALL
        rows.append((
            f"jax_scan_speedup_a{A}", sc["speedup_scan"],
            f"jitted scan >= 4x the NumPy tick loop at A=64 "
            f"({SCAN_TICKS} ticks; report-only under BENCH_SMALL)" if A == 64
            else f"jitted scan vs NumPy tick loop at A={A}",
            (BENCH_SMALL or sc["speedup_scan"] >= 4.0) if A == 64 else True,
        ))
    rows.append((
        "jax_grid_speedup_64cell", jx["grid"]["speedup_grid"],
        f"{GRID_CELLS}-cell vmapped grid >= 20x {GRID_CELLS} serial "
        "NumPy runs, one dispatch",
        jx["grid"]["speedup_grid"] >= 20.0,
    ))
    rows.extend(_fleet_rows(fleet))
    ratio = ov["disabled_vs_committed_ratio"]
    rows.append((
        "telemetry_disabled_ratio", ratio if ratio is not None else 0.0,
        "telemetry-disabled engine within 3% of committed pre-telemetry "
        "pool-64 throughput (report-only under BENCH_SMALL)",
        True if (BENCH_SMALL or ratio is None) else ratio >= 0.97,
    ))
    ratio256 = ov["a256"]["disabled_vs_committed_ratio"]
    rows.append((
        "telemetry_disabled_ratio_a256", ratio256 if ratio256 is not None else 0.0,
        "telemetry-disabled A=256 pool within 3% of its committed "
        "measurement (report-only under BENCH_SMALL)",
        True if (BENCH_SMALL or ratio256 is None) else ratio256 >= 0.97,
    ))
    rows.append((
        "telemetry_enabled_overhead_pct", ov["enabled_overhead_pct"],
        "recorder+event-log overhead when fully enabled (informational)",
        True,
    ))
    rows.append((
        "telemetry_enabled_overhead_pct_a256", ov["a256"]["enabled_overhead_pct"],
        "fully-enabled overhead at the A=256 fleet pool (informational)",
        True,
    ))

    write_artifact("BENCH_sim_throughput", payload, t0)
    return print_rows("sim_throughput", rows, t0)


if __name__ == "__main__":
    import sys

    if "--fleet-only" in sys.argv[1:]:
        raise SystemExit(0 if run_fleet_only() else 1)
    raise SystemExit(0 if run() else 1)
