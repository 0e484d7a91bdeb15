"""Differential tests: the jitted JAX engine vs the NumPy oracle.

The batched engine (``repro.core.sim.jax_engine``) re-expresses the
structure-of-arrays tick pipeline as a pure-functional ``lax.scan``;
the NumPy :class:`~repro.core.sim.ServingSim` stays the semantic
oracle.  These tests pin the two together:

* differential fuzz over zoo scenarios / seeds / policies — RAW
  (unrounded) ledger totals at 1e-6 relative tolerance plus
  summary-key-set equality (rounded values may differ by one rounding
  ulp from summation order, the raw comparison is the strict one);
* per-arch flow conservation (arrived == served + offloaded + dropped
  + expired + still-queued, per arch) and accuracy-mass consistency;
* ``SimState`` pytree round-trip;
* the jit-recompile guard — repeated same-shape runs must hit one
  trace per (A, T, policy) shape;
* the vmapped grid vs per-cell ``run_scenario`` parity;
* the building blocks the scan shares with the host path (binomial
  inverse-CDF, feature build).

Tests named ``*_smoke_*`` are the CI subset (``-k smoke``).
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.core.load_monitor import LoadMonitor, pool_stats_trajectory
from repro.core.rl.obs import pool_features, pool_features_arrays
from repro.core.schedulers import VECTOR_SCHEDULERS
from repro.core.sim import ServingSim
from repro.core.sim import jax_engine as je
from repro.core.sim.fleet import BINOMIAL_KMAX, binomial_from_uniform
from repro.core.sim.types import ArchLoad
from repro.core.workloads import SCENARIO_ZOO

ARCHS = ["llama3-8b", "minicpm-2b", "qwen1.5-0.5b"]

#: raw SimResult attribute -> how to read it off the jax raw totals
_LEDGER_KEYS = (
    "cost_reserved", "cost_spot", "cost_burst", "cost_harvest",
    "cost_remote", "violations", "violations_strict", "served_vm",
    "served_burst", "preemptions", "chip_seconds", "chip_seconds_needed",
    "chip_seconds_over", "accuracy_weighted", "accuracy_served",
    "acc_violations",
)


def _workload(A):
    return [
        ArchLoad(ARCHS[i % len(ARCHS)], 1.0 / A, 0.25, name=f"m@{i}")
        for i in range(A)
    ]


def _numpy_run(arrivals, workload, policy, seed=0, catalog=None):
    sim = ServingSim(arrivals, workload, seed=seed, catalog=catalog)
    if policy == "rl_pool":
        from repro.core.rl.policy import RLPoolPolicy
        pol = RLPoolPolicy(greedy=True)
    else:
        pol = VECTOR_SCHEDULERS[policy]()
    while not sim.done:
        sim.apply_pool(pol(sim.tick, sim.observe_pool()))
    return sim


def _raw_ledger_np(res):
    return {
        "cost_reserved": res.cost_reserved,
        "cost_spot": res.cost_spot,
        "cost_burst": res.cost_burst,
        "cost_harvest": res.cost_other.get("harvest", 0.0),
        "cost_remote": res.cost_other.get("remote", 0.0),
        "violations": res.violations,
        "violations_strict": res.violations_strict,
        "served_vm": res.served_vm,
        "served_burst": res.served_burst,
        "preemptions": float(res.preemptions),
        "chip_seconds": res.chip_seconds,
        "chip_seconds_needed": res.chip_seconds_needed,
        "chip_seconds_over": res.chip_seconds_over,
        "accuracy_weighted": res.accuracy_weighted,
        "accuracy_served": res.accuracy_served,
        "acc_violations": res.acc_violations,
    }


def _raw_ledger_jx(out):
    tot = out["raw"]["totals"]
    exp_s, exp_r = out["raw"]["expired_s"], out["raw"]["expired_r"]
    served = float(tot["served"].sum() + tot["dropped"].sum())
    burst = float(tot["burst"].sum())
    return {
        "cost_reserved": float(tot["cost_res"]),
        "cost_spot": float(tot["cost_spot"]),
        "cost_burst": float(tot["cost_burst"]),
        "cost_harvest": float(tot["cost_harv"]),
        "cost_remote": float(tot["cost_rem"]),
        "violations": float(tot["viol"].sum() + exp_s.sum() + exp_r.sum()),
        "violations_strict": float(tot["viol_strict"] + exp_s.sum()),
        "served_vm": served,
        "served_burst": burst,
        "preemptions": float(tot["preempt"]),
        "chip_seconds": float(tot["chip"]),
        "chip_seconds_needed": float(tot["need"]),
        "chip_seconds_over": float(tot["over"]),
        "accuracy_weighted": float(tot["acc_w"].sum()),
        "accuracy_served": served + burst,
        "acc_violations": float(tot["acc_viol"].sum()),
    }


def _assert_equivalent(arrivals, workload, policy, seed=0, catalog=None):
    sim = _numpy_run(arrivals, workload, policy, seed=seed, catalog=catalog)
    out = je.run_scenario(arrivals, workload, policy, seed=seed,
                          catalog=catalog)
    raw_np = _raw_ledger_np(sim.res)
    raw_jx = _raw_ledger_jx(out)
    for k in _LEDGER_KEYS:
        assert raw_jx[k] == pytest.approx(raw_np[k], rel=1e-6, abs=1e-6), (
            f"{policy}: raw ledger key {k!r} drifted "
            f"(np={raw_np[k]!r} jax={raw_jx[k]!r})"
        )
    # rounded summaries expose the same keys (values may sit one
    # rounding ulp apart from summation order — the raw check above is
    # the strict one)
    assert set(out["summary"]) == set(sim.res.summary())
    if catalog is not None:
        # swaps-in-flight accounting: the scan's popped-swap count is an
        # exact integer flow, so it must match the oracle exactly
        assert out["summary"]["variant_swaps"] == (
            sim.res.summary()["variant_swaps"]
        ), f"{policy}: variant_swaps drifted"
    # per-arch flow totals line up with the oracle's
    counts = sim.per_arch_counts()
    per = out["per_arch"]
    for k in ("served_vm", "served_burst", "dropped", "violations",
              "acc_weight", "acc_violations"):
        np.testing.assert_allclose(
            per[k], counts[k], rtol=1e-6, atol=1e-6, err_msg=f"per-arch {k}"
        )
    return out


# ---------------------------------------------------------------------------
# Differential fuzz.
# ---------------------------------------------------------------------------
def test_smoke_fuzz_zoo_portfolio_small():
    """CI subset: two zoo scenarios under the portfolio policy."""
    A, T = 4, 300
    wl = _workload(A)
    for scn in ("shared_berkeley", "mmpp_bursts"):
        arr = SCENARIO_ZOO[scn].build(A, duration_s=T)
        _assert_equivalent(arr, wl, "portfolio", seed=3)


def test_fuzz_all_zoo_scenarios_portfolio():
    """Every SCENARIO_ZOO preset matches under the portfolio policy
    (the policy that exercises all four procurement tiers)."""
    A, T = 4, 400
    wl = _workload(A)
    for i, scn in enumerate(sorted(SCENARIO_ZOO)):
        arr = SCENARIO_ZOO[scn].build(A, duration_s=T, seed=20 + i)
        _assert_equivalent(arr, wl, "portfolio", seed=i)


def test_fuzz_policies_and_shapes():
    """Random (scenario, seed, policy, shape) draws across the other
    in-scan policies."""
    rng = np.random.default_rng(7)
    names = sorted(SCENARIO_ZOO)
    cases = [("reactive", 4, 400), ("paragon", 4, 400),
             ("portfolio", 6, 600), ("reactive", 2, 250)]
    for policy, A, T in cases:
        scn = names[rng.integers(len(names))]
        seed = int(rng.integers(100))
        arr = SCENARIO_ZOO[scn].build(A, duration_s=T, seed=seed)
        _assert_equivalent(arr, _workload(A), policy, seed=seed)


def test_fuzz_fleet_scale_a256():
    """Fleet-scale differential fuzz: the lazy window-min rings, the
    in-carry EWMA and the in-carry totals accumulator must hold the
    ledger contract at A=256, not just at toy pool sizes."""
    A, T = 256, 150
    wl = _workload(A)
    arr = SCENARIO_ZOO["shared_berkeley"].build(
        A, duration_s=T, mean_rps=400.0, seed=9
    )
    _assert_equivalent(arr, wl, "portfolio", seed=9)


def test_fuzz_rl_pool_parity():
    """The in-scan rl_pool twin matches RLPoolPolicy(greedy=True)
    driving the NumPy engine — net forward, feature build, procurement
    decode and engine semantics all at once."""
    A, T = 4, 400
    arr = SCENARIO_ZOO["diurnal_phases"].build(A, duration_s=T)
    _assert_equivalent(arr, _workload(A), "rl_pool", seed=0)


def test_flow_conservation_per_arch():
    """arrived == served_vm + served_burst + dropped + expired + queued
    per arch (the invariant ``ServingSim.per_arch_counts`` documents),
    and the accuracy mass stays within the answered mass (weights are
    per-request accuracies in [0, 1])."""
    A, T = 6, 600
    wl = _workload(A)
    arr = SCENARIO_ZOO["flash_anti"].build(A, duration_s=T)
    out = je.run_scenario(arr, wl, "portfolio")
    per = out["per_arch"]
    answered = per["served_vm"] + per["served_burst"] + per["dropped"]
    np.testing.assert_allclose(
        per["arrived"],
        answered + per["expired_end"] + per["queued"],
        rtol=1e-9, atol=1e-6,
    )
    assert (per["acc_weight"] >= -1e-9).all()
    assert (per["acc_weight"] <= answered + 1e-6).all()
    assert (per["acc_violations"] <= answered + 1e-6).all()


# ---------------------------------------------------------------------------
# Variant axis: catalog-enabled differential fuzz + swap edge cases.
# ---------------------------------------------------------------------------
def _vworkload(floor=0.55):
    import dataclasses

    from repro.core.sim import uniform_pool_workload
    pool = ["llama3-8b", "qwen1.5-0.5b", "rwkv6-1.6b", "minicpm-2b"]
    return [
        dataclasses.replace(w, min_accuracy=floor)
        for w in uniform_pool_workload(pool, strict_frac=0.25)
    ]


@pytest.fixture(scope="module")
def vcatalog():
    from repro.core.sim import VariantCatalog
    return VariantCatalog.for_workload(_vworkload())


def test_smoke_fuzz_variant_catalog(vcatalog):
    """CI subset: both variant-aware schedulers on a catalog run must
    match the NumPy oracle — swaps, accuracy mass and money included."""
    wl = _vworkload()
    arr = SCENARIO_ZOO["diurnal_phases"].build(
        len(wl), duration_s=400, mean_rps=400.0, seed=3
    )
    for policy in ("infaas_variant", "accuracy_floor"):
        out = _assert_equivalent(arr, wl, policy, seed=0, catalog=vcatalog)
        assert out["summary"]["variant_swaps"] > 0, (
            f"{policy}: catalog run never swapped — edge not exercised"
        )


def test_fuzz_variant_zoo(vcatalog):
    """Every zoo scenario under both variant-aware schedulers, plus the
    RL policy's live variant head, at 1e-6 on the raw ledger."""
    wl = _vworkload()
    swapped = 0
    for i, scn in enumerate(sorted(SCENARIO_ZOO)):
        arr = SCENARIO_ZOO[scn].build(
            len(wl), duration_s=300, mean_rps=300.0, seed=40 + i
        )
        for policy in ("infaas_variant", "accuracy_floor"):
            out = _assert_equivalent(arr, wl, policy, seed=i,
                                     catalog=vcatalog)
            swapped += out["summary"]["variant_swaps"]
    assert swapped > 0
    arr = SCENARIO_ZOO["trending_hotswap"].build(
        len(wl), duration_s=400, mean_rps=300.0, seed=11
    )
    _assert_equivalent(arr, wl, "rl_pool", seed=1, catalog=vcatalog)


def test_variant_flow_and_accuracy_conservation(vcatalog):
    """Per-arch flow conservation and accuracy-mass bounds hold on a
    catalog run exactly as on the base engine."""
    wl = _vworkload()
    arr = SCENARIO_ZOO["flash_anti"].build(
        len(wl), duration_s=500, mean_rps=350.0, seed=5
    )
    out = je.run_scenario(arr, wl, "infaas_variant", catalog=vcatalog)
    per = out["per_arch"]
    answered = per["served_vm"] + per["served_burst"] + per["dropped"]
    np.testing.assert_allclose(
        per["arrived"],
        answered + per["expired_end"] + per["queued"],
        rtol=1e-9, atol=1e-6,
    )
    assert (per["acc_weight"] >= -1e-9).all()
    assert (per["acc_weight"] <= answered + 1e-6).all()
    assert (per["acc_violations"] <= answered + 1e-6).all()


def test_variant_policies_degrade_catalog_free():
    """Catalog-free, the in-scan variant-aware schedulers degrade to
    exactly Paragon (same guarantee the vector forms pin) — and the
    whole variant machinery stays untraced."""
    A = 4
    wl = _workload(A)
    arr = SCENARIO_ZOO["mmpp_bursts"].build(A, duration_s=300, seed=2)
    p = je.run_scenario(arr, wl, "paragon", seed=0)["summary"]
    for policy in ("infaas_variant", "accuracy_floor"):
        assert je.run_scenario(arr, wl, policy, seed=0)["summary"] == p


# --- scripted swap edge cases, pinned against the NumPy engine --------------
def _scripted_parity(arr, wl, catalog, np_policy, jax_apply, seed=0):
    """Run a scripted action sequence through BOTH engines and compare
    the raw ledgers at 1e-6 (the harness behind the swap edge tests)."""
    import jax.numpy as jnp  # noqa: F401  (closures use it)

    sim = ServingSim(arr, wl, seed=seed, catalog=catalog)
    while not sim.done:
        sim.apply_pool(np_policy(sim.tick, sim.observe_pool()))
    statics, state0, xs = je.build_sim_inputs(
        arr, wl, catalog=catalog, seed=seed, needs_stats=True,
        lazy_rings=False,
    )
    statics["policy"] = {}
    run = jax.jit(je.make_runner(jax_apply, "sum", variants=True,
                                 window_stats=True))
    with jax.enable_x64(True):
        out = jax.tree.map(np.asarray, run(statics, state0, xs))
    res = je._assemble(out, np.asarray(arr, dtype=np.float64))
    raw_np, raw_jx = _raw_ledger_np(sim.res), _raw_ledger_jx(res)
    for k in _LEDGER_KEYS:
        assert raw_jx[k] == pytest.approx(raw_np[k], rel=1e-6, abs=1e-6), (
            f"scripted: raw ledger key {k!r} drifted "
            f"(np={raw_np[k]!r} jax={raw_jx[k]!r})"
        )
    assert res["summary"]["variant_swaps"] == (
        sim.res.summary()["variant_swaps"]
    )
    return sim, res


def _scripted_pair(variant_script_np, spot=0, harvest=0):
    """Matching (NumPy policy, JAX apply) for a reactive-sized fleet
    with a tick-scripted variant request stream."""
    from repro.core.sim import PoolAction

    def np_policy(tick, obs):
        tgt = np.maximum(
            1, np.ceil(obs.ewma_rate / obs.throughput)
        ).astype(np.int64)
        A = len(obs.keys)
        act = PoolAction(target=tgt)
        act.variant_target = variant_script_np(tick, A)
        if spot:
            act.spot_target = np.full(A, spot, dtype=np.int64)
        if harvest:
            act.harvest_target = np.full(A, harvest, dtype=np.int64)
        return act

    def jax_apply(params, obs, key):
        import jax.numpy as jnp
        tgt = jnp.maximum(
            1, jnp.ceil(obs["ewma_rate"] / obs["throughput"])
        ).astype(jnp.int64)
        z = jnp.zeros_like(tgt)
        t = obs["tick"]
        A = tgt.shape[0]
        # trace the SAME script: variant_script_np is evaluated per tick
        # on the host into a [T, A] table is impossible in-scan, so the
        # scripts below are written as jnp expressions of t
        variant = variant_script_np(t, A, xp=jnp)
        return dict(
            target=tgt, offload=z,
            spot=jnp.full_like(tgt, spot) if spot else z,
            harvest=jnp.full_like(tgt, harvest) if harvest else z,
            remote=z, variant=variant,
        ), {}

    return np_policy, jax_apply


def test_swap_retarget_to_current_cancels(vcatalog):
    """Re-targeting the CURRENT variant while a swap is in flight
    cancels it (the in-flight swap never lands); a later re-request
    completes.  Scripted identically into both engines."""
    import jax.numpy as jnp

    wl = _vworkload()
    arr = SCENARIO_ZOO["shared_berkeley"].build(
        len(wl), duration_s=300, mean_rps=200.0, seed=7
    )
    base = vcatalog.as_arrays(wl)["base_idx"].astype(np.int64)

    def script(t, A, xp=np):
        # t=5: request variant 0 (a real move for archs whose base > 0);
        # t=10 (< 5+60 swap latency): re-target CURRENT -> cancel;
        # t=100: request variant 0 again -> completes at tick 160
        b = base if xp is np else jnp.asarray(base)
        zero = xp.zeros(A, dtype=xp.int64)
        hold = zero - 1
        return xp.where(
            t == 10, b,
            xp.where((t == 5) | (t == 100), zero, hold),
        ).astype(xp.int64)

    np_pol, jx_apply = _scripted_pair(script)
    sim, res = _scripted_parity(arr, wl, vcatalog, np_pol, jx_apply)
    # exactly one completed swap per arch whose base isn't variant 0:
    # the canceled first request must never land
    assert res["summary"]["variant_swaps"] == int((base != 0).sum())
    assert not sim.swap.in_flight.any()


def test_swap_lands_on_final_tick(vcatalog):
    """A swap maturing exactly on the last tick pops during that tick's
    step (the arch serves at the new rate through the end-of-trace
    expired sweep), and a request issued ON the final tick stays in
    flight forever — both engines agree on the resulting ledger."""
    import jax.numpy as jnp

    wl = _vworkload()
    T = 200
    arr = SCENARIO_ZOO["flash_correlated"].build(
        len(wl), duration_s=T, mean_rps=250.0, seed=13
    )
    va = vcatalog.as_arrays(wl)
    base = va["base_idx"].astype(np.int64)
    top = (va["n_variants"] - 1).astype(np.int64)
    land = T - 1 - 60    # ready_at == T-1: pops on the final tick

    def script(t, A, xp=np):
        to = top if xp is np else jnp.asarray(top)
        zero = xp.zeros(A, dtype=xp.int64)
        hold = zero - 1
        return xp.where(
            t == land, zero, xp.where(t == T - 1, to, hold)
        ).astype(xp.int64)

    np_pol, jx_apply = _scripted_pair(script)
    sim, res = _scripted_parity(arr, wl, vcatalog, np_pol, jx_apply)
    # the landing request popped (once per arch whose base != 0); the
    # final-tick request entered the pipeline AFTER the pop and is
    # still in flight at the sweep
    assert res["summary"]["variant_swaps"] == int((base != 0).sum())
    assert sim.swap.in_flight.any()


def test_swap_request_on_reclaim_tick(vcatalog):
    """Swap requests issued every tick while spot/harvest churn (reclaims
    and evictions co-occur with swap traffic): the two engines must
    stay ledger-identical through the interleaving."""
    wl = _vworkload()
    arr = SCENARIO_ZOO["mmpp_bursts"].build(
        len(wl), duration_s=400, mean_rps=300.0, seed=17
    )

    def script(t, A, xp=np):
        # oscillate requests: variant 0 on even phases, hold on odd —
        # guarantees requests coincide with whatever reclaim ticks the
        # seeded spot/harvest processes produce
        req = xp.where((t % 7) < 3, 0, -1)
        if xp is np:
            return np.full(A, int(req), dtype=np.int64)
        return xp.broadcast_to(req, (A,)).astype(xp.int64)

    np_pol, jx_apply = _scripted_pair(script, spot=3, harvest=2)
    sim, res = _scripted_parity(arr, wl, vcatalog, np_pol, jx_apply)
    assert sim.res.preemptions > 0, "no reclaim landed — edge not exercised"
    assert res["summary"]["variant_swaps"] > 0


# ---------------------------------------------------------------------------
# Pytree / jit machinery.
# ---------------------------------------------------------------------------
def test_simstate_pytree_roundtrip():
    A, T = 3, 50
    arr = SCENARIO_ZOO["shared_berkeley"].build(A, duration_s=T)
    # stats path: the EWMA arrives via xs, so the carry slot is an
    # empty (None) subtree and contributes no leaf
    _, state0, _ = je.build_sim_inputs(arr, _workload(A))
    assert state0.ewma is None
    # catalog-free runs also leave the 4 variant-swap slots as empty
    # (None) subtrees
    n_var = 4
    leaves, treedef = jax.tree.flatten(state0)
    assert len(leaves) == len(je.SimState._fields) - 1 - n_var
    rebuilt = jax.tree.unflatten(treedef, leaves)
    assert isinstance(rebuilt, je.SimState)
    for a, b in zip(jax.tree.leaves(rebuilt), leaves):
        np.testing.assert_array_equal(a, b)
    # non-stats path: the EWMA recurrence lives in the carry
    _, state0, xs = je.build_sim_inputs(arr, _workload(A), needs_stats=False)
    assert state0.ewma is not None and "ewma" not in xs
    leaves, _ = jax.tree.flatten(state0)
    assert len(leaves) == len(je.SimState._fields) - n_var
    # variant-catalog run: the swap pipeline fills every slot
    from repro.core.sim import VariantCatalog
    _, state0, _ = je.build_sim_inputs(
        arr, _workload(A), catalog=VariantCatalog.for_workload(_workload(A))
    )
    leaves, _ = jax.tree.flatten(state0)
    assert len(leaves) == len(je.SimState._fields) - 1
    assert state0.var_pending is not None and (state0.var_pending == -1).all()


def test_smoke_recompile_guard():
    """Repeated same-shape runs reuse one trace; a new (A, T) shape
    adds exactly one more."""
    wl4 = _workload(4)
    arr = SCENARIO_ZOO["shared_berkeley"].build(4, duration_s=120)
    je.run_scenario(arr, wl4, "reactive")
    n0 = je.runner_trace_count("reactive")
    for seed in (1, 2):
        je.run_scenario(arr, wl4, "reactive", seed=seed)
    assert je.runner_trace_count("reactive") == n0
    arr2 = SCENARIO_ZOO["shared_berkeley"].build(5, duration_s=120)
    je.run_scenario(arr2, _workload(5), "reactive")
    assert je.runner_trace_count("reactive") == n0 + 1


def test_donation_safety_and_flavor_parity():
    """The donated opt runner (a) is repeatable — two dispatches from
    the same host-side inputs return identical totals, proving donation
    aliases only the fresh device staging buffers, never the caller's
    NumPy arrays — and (b) does not drift from the legacy flavor
    (eager ring clips, host-fed EWMA, stacked post-scan reduction)."""

    A, T = 8, 300
    wl = _workload(A)
    arr = SCENARIO_ZOO["mmpp_bursts"].build(A, duration_s=T, seed=5)
    pol = je.JAX_POLICIES["portfolio"]
    with jax.enable_x64(True):
        statics, state0, xs = je.build_sim_inputs(
            arr, wl, seed=3, needs_stats=pol.needs_stats,
            needs_key=pol.needs_key,
        )
        statics = dict(statics)
        statics["policy"] = pol.default_params()
        state_snap = [np.array(x, copy=True) for x in jax.tree.leaves(state0)]
        xs_snap = [np.array(x, copy=True) for x in jax.tree.leaves(xs)]
        runner = je._get_runner("portfolio")
        out1 = jax.tree.map(np.asarray, runner(statics, state0, xs))
        out2 = jax.tree.map(np.asarray, runner(statics, state0, xs))
        for k in out1["totals"]:
            np.testing.assert_array_equal(
                out1["totals"][k], out2["totals"][k], err_msg=k
            )
        for got, want in zip(jax.tree.leaves(state0), state_snap):
            np.testing.assert_array_equal(np.asarray(got), want)
        for got, want in zip(jax.tree.leaves(xs), xs_snap):
            np.testing.assert_array_equal(np.asarray(got), want)

        statics_l, state0_l, xs_l = je.build_sim_inputs(
            arr, wl, seed=3, needs_stats=pol.needs_stats,
            needs_key=pol.needs_key, ewma_in_scan=False, lazy_rings=False,
        )
        statics_l = dict(statics_l)
        statics_l["policy"] = pol.default_params()
        out_l = jax.tree.map(
            np.asarray,
            je._get_runner("portfolio", flavor="legacy")(
                statics_l, state0_l, xs_l
            ),
        )
    for k in out1["totals"]:
        if k in je._LIVE_KEYS:
            # opt folds liveness with logical-or, legacy sums the per-
            # tick flags — only truthiness is consumed (_assemble)
            assert bool(out1["totals"][k]) == bool(out_l["totals"][k]), k
            continue
        np.testing.assert_allclose(
            out1["totals"][k], out_l["totals"][k], rtol=1e-9, atol=1e-9,
            err_msg=f"flavor drift in {k}",
        )


def test_smoke_grid_matches_run_scenario():
    """One vmapped dispatch over (scenario x seed) cells reproduces the
    per-cell scan summaries exactly."""
    A, T, B = 4, 200, 3
    wl = _workload(A)
    names = ("shared_berkeley", "mmpp_bursts", "flash_correlated")
    arrs = np.stack([
        SCENARIO_ZOO[n].build(A, duration_s=T, seed=30 + i)
        for i, n in enumerate(names)
    ])
    seeds = [5, 6, 7]
    cells = je.run_grid(arrs, wl, "portfolio", seeds=seeds)
    for i in range(B):
        single = je.run_scenario(arrs[i], wl, "portfolio", seed=seeds[i])
        assert cells[i]["summary"] == single["summary"], f"cell {i}"


# ---------------------------------------------------------------------------
# The monitor's order statistics on the device.
# ---------------------------------------------------------------------------
_W = LoadMonitor.window_s


def _host_p2m_construction(monkeypatch):
    """Put back the construction from before the order statistics ran on
    the device: ``p2m`` from the streaming monitor on the host, fed to a
    runner that reads it."""
    build, opts = je.build_sim_inputs, je._flavor_opts

    def host_p2m(arrivals, *args, **kw):
        statics, state0, xs = build(arrivals, *args, **kw)
        if "p2m" not in xs:
            xs["p2m"] = pool_stats_trajectory(arrivals)[2]
        return statics, state0, xs

    monkeypatch.setattr(je, "build_sim_inputs", host_p2m)
    monkeypatch.setattr(je, "_flavor_opts",
                        lambda *a: {**opts(*a), "window_stats": False})
    monkeypatch.setattr(je, "_RUNNERS", {})


def _assert_leaves_bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("policy", ["paragon", "infaas_variant", "rl_pool",
                                    "portfolio", "reactive"])
def test_device_order_stats_leave_entry_points_bitwise(policy, vcatalog, monkeypatch):
    """``run_grid`` and ``run_scenario`` return the same bits as with
    ``p2m`` made on the host; the xs of a stats policy carry no ``p2m``,
    and a policy that reads no order statistics keeps its placeholder
    and a runner without the device pass."""
    wl = _vworkload()
    A, T = len(wl), _W + 100
    # load enough for fleets of several instances, whose size the
    # bursty / flat headroom then moves
    arrs = np.stack([SCENARIO_ZOO[n].build(A, duration_s=T, seed=11 + i, mean_rps=2000.0)
                     for i, n in enumerate(("flash_correlated", "mmpp_bursts"))])
    catalog = vcatalog if policy == "infaas_variant" else None
    pol = je.JAX_POLICIES[policy]
    _, _, xs = je.build_sim_inputs(arrs[0], wl, catalog=catalog,
                                   needs_stats=pol.needs_stats)
    assert ("p2m" in xs) == (not pol.needs_stats)
    for mode in ("sum", "stack"):
        assert je._flavor_opts(policy, mode, "opt")["window_stats"] == pol.needs_stats
        assert "window_stats" not in je._flavor_opts(policy, mode, "legacy")
    if not pol.needs_stats:
        assert xs["p2m"].shape == (T, 1)

    def runs():
        monkeypatch.setattr(je, "_RUNNERS", {})
        out = je.run_grid(arrs, wl, policy, seeds=[3, 4], catalog=catalog)
        out.append(je.run_scenario(arrs[1], wl, policy, seed=4, catalog=catalog))
        return out

    device = runs()
    _host_p2m_construction(monkeypatch)
    host = runs()
    for a, b in zip(device, host):
        assert a["summary"] == b["summary"]
        _assert_leaves_bitwise(a["raw"], b["raw"])


@pytest.mark.parametrize("collector", ["collect_rollouts_jax", "collect_rollouts_jax_zoo"])
def test_device_order_stats_leave_rollouts_bitwise(collector, monkeypatch):
    """The PPO collectors (``rl_sample``) draw the same rollouts as with
    ``p2m`` made on the host."""
    from repro.core.rl import ppo
    from repro.core.rl.env import EnvConfig, PoolServingEnv

    A, T = 2, _W + 40
    zoo = [SCENARIO_ZOO[n] for n in ("shared_berkeley", "flash_correlated")]
    params = ppo.init_net(jax.random.key(0), ppo.PPOConfig())

    def collect():
        env = PoolServingEnv(_workload(A), EnvConfig(duration_s=T, mean_rps=40.0),
                             scenarios=zoo, scenario_seed=0)
        return getattr(ppo, collector)(env, params, jax.random.key(5))

    device = collect()
    _host_p2m_construction(monkeypatch)
    host = collect()
    assert set(device) == set(host)
    _assert_leaves_bitwise(device, host)


# ---------------------------------------------------------------------------
# Shared building blocks.
# ---------------------------------------------------------------------------
def test_binomial_jnp_matches_numpy():
    """The in-scan inverse-CDF binomial is the NumPy twin's, bit for
    bit, across the (n, p, u) grid both engines draw from."""

    rng = np.random.default_rng(0)
    n = rng.integers(0, BINOMIAL_KMAX + 10, size=200)
    u = rng.random(200)
    for p in (0.0, 1e-4, 0.01, 0.3, 1.0):
        want = binomial_from_uniform(n, p, u)
        with jax.enable_x64(True):      # the scan always runs in x64
            got = np.asarray(je.binomial_from_uniform_jnp(
                np.asarray(n), float(p), np.asarray(u)
            ))
        np.testing.assert_array_equal(got, want, err_msg=f"p={p}")


def test_pool_features_arrays_parity():
    """The backend-parametric feature build matches the deployed NumPy
    one elementwise on a materialized PoolObs."""
    A, T = 4, 60
    wl = _workload(A)
    arr = SCENARIO_ZOO["shared_berkeley"].build(A, duration_s=T)
    sim = ServingSim(arr, wl)
    pol = VECTOR_SCHEDULERS["portfolio"]()
    for _ in range(30):
        sim.apply_pool(pol(sim.tick, sim.observe_pool()))
    obs = sim.observe_pool()
    prev = obs.rate * 0.9
    want = pool_features(obs, prev, rate_scale=100.0, fleet_scale=10.0)
    o = {f: np.broadcast_to(np.asarray(getattr(obs, f)), (A,))
         for f in ("rate", "ewma_rate", "peak_to_median", "queue_strict",
                   "queue_relaxed", "n_active", "n_pending", "utilization",
                   "last_violations", "active_variant", "n_variants",
                   "accuracy", "accuracy_floor", "n_spot", "n_spot_pending",
                   "spot_reclaim_risk", "harvest_level")}
    got = pool_features_arrays(
        o, prev, rate_scale=100.0, fleet_scale=10.0, xp=np
    )
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Batched rollout collection.
# ---------------------------------------------------------------------------
def test_collect_rollouts_jax_buffers():
    """The in-scan collector returns the host loop's buffer layout,
    deterministically per key, with the episode-end reward carrying the
    finalize sweep."""
    from repro.core.rl.env import EnvConfig, PoolServingEnv
    from repro.core.rl.ppo import OBS_DIM, PPOConfig, collect_rollouts_jax, init_net

    A, T = 4, 200
    arr = SCENARIO_ZOO["shared_berkeley"].build(A, duration_s=T)
    env = PoolServingEnv(_workload(A), EnvConfig(duration_s=T), arrivals=arr)
    params = init_net(jax.random.key(0), PPOConfig())
    key = jax.random.key(11)
    buf = collect_rollouts_jax(env, params, key)
    assert buf["obs"].shape == (T, A, OBS_DIM)
    for k in ("actions", "logp", "values", "rewards"):
        assert buf[k].shape == (T, A), k
    assert buf["dones"].sum() == 1.0 and buf["dones"][-1] == 1.0
    assert np.isfinite(buf["rewards"]).all()
    assert (buf["logp"] <= 1e-6).all()
    buf2 = collect_rollouts_jax(env, params, key)
    for k in buf:
        np.testing.assert_array_equal(buf[k], buf2[k], err_msg=k)
    # a different key draws a different action stream
    buf3 = collect_rollouts_jax(env, params, jax.random.key(12))
    assert (buf3["actions"] != buf["actions"]).any()


def test_collect_rollouts_jax_zoo_matches_cells():
    """The full-zoo batched collector is bit-identical, cell by cell,
    to the unbatched collector run on the same (arrivals, seed, key)
    triples — the vmapped dispatch changes wall-clock, not rollouts."""
    from repro.core.rl.env import EnvConfig, PoolServingEnv
    from repro.core.rl.ppo import (
        OBS_DIM,
        PPOConfig,
        collect_rollouts_jax,
        collect_rollouts_jax_zoo,
        init_net,
    )

    A, T = 2, 200
    zoo = [SCENARIO_ZOO[n]
           for n in ("shared_berkeley", "mmpp_bursts", "flash_correlated")]
    S = len(zoo)
    cfg = EnvConfig(duration_s=T, mean_rps=40.0)
    wl = _workload(A)
    env = PoolServingEnv(wl, cfg, scenarios=zoo, scenario_seed=0)
    params = init_net(jax.random.key(0), PPOConfig())
    key = jax.random.key(7)
    buf = collect_rollouts_jax_zoo(env, params, key)
    assert buf["obs"].shape == (T, S * A, OBS_DIM)
    assert buf["dones"].sum() == 1.0 and buf["dones"][-1] == 1.0

    ep = env._episode
    keys = jax.random.split(key, S)
    env1 = PoolServingEnv(wl, cfg, arrivals=np.zeros((A, T)))
    for i, sc in enumerate(zoo):
        arr = sc.build(A, seed=sc.seed + ep, duration_s=T, mean_rps=40.0)
        cell = collect_rollouts_jax(
            env1, params, keys[i], arrivals=arr, seed=ep * S + i
        )
        for k in ("obs", "actions", "logp", "values", "rewards"):
            np.testing.assert_array_equal(
                buf[k][:, i * A:(i + 1) * A], cell[k],
                err_msg=f"cell {i} key {k}",
            )


# ---------------------------------------------------------------------------
# Multi-device grid sharding (forced multi-CPU subprocess).
# ---------------------------------------------------------------------------
def test_sharded_grid_parity_subprocess():
    """``run_grid(sharded=True)`` computes the same cells as the single
    vmapped dispatch.  Device count is a process-level XLA flag, so the
    2-device mesh runs in a subprocess."""
    import os
    import subprocess
    import sys

    script = r"""
import numpy as np, jax
assert len(jax.devices()) == 2, jax.devices()
from repro.core.sim import jax_engine as je
from repro.core.sim.types import ArchLoad
from repro.core.workloads import SCENARIO_ZOO
ARCHS = ["llama3-8b", "minicpm-2b", "qwen1.5-0.5b"]
A, T = 3, 120
wl = [ArchLoad(ARCHS[i % 3], 1.0 / A, 0.25, name=f"m@{i}") for i in range(A)]
names = ("shared_berkeley", "mmpp_bursts")
arrs = np.stack([SCENARIO_ZOO[n].build(A, duration_s=T, seed=30 + i)
                 for i, n in enumerate(names)])
seeds = [5, 6]
sh = je.run_grid(arrs, wl, "portfolio", seeds=seeds, sharded=True)
un = je.run_grid(arrs, wl, "portfolio", seeds=seeds, sharded=False)
for i in range(len(names)):
    assert sh[i]["summary"] == un[i]["summary"], (i, sh[i], un[i])
assert all(c["devices"] == 2 for c in sh) and all(c["devices"] == 1 for c in un)
# auto mode: 2 cells % 2 devices == 0 -> sharded path, same cells
auto = je.run_grid(arrs, wl, "portfolio", seeds=seeds)
for i in range(len(names)):
    assert auto[i]["summary"] == un[i]["summary"], i
# 3 cells on 2 devices: padded to 4, still sharded, padding dropped
arrs3 = np.concatenate([arrs, arrs[1:]])
seeds3 = seeds + [7]
sh3 = je.run_grid(arrs3, wl, "portfolio", seeds=seeds3)
un3 = je.run_grid(arrs3, wl, "portfolio", seeds=seeds3, sharded=False)
assert len(sh3) == 3 and all(c["devices"] == 2 for c in sh3)
for i in range(3):
    assert sh3[i]["summary"] == un3[i]["summary"], (i, sh3[i], un3[i])
print("SHARDED_PARITY_OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
    ).strip()
    # the subprocess must resolve the package the same way this one did
    src = os.path.dirname(os.path.dirname(os.path.abspath(je.__file__)))
    src = os.path.dirname(os.path.dirname(src))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=540,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHARDED_PARITY_OK" in proc.stdout


# ---------------------------------------------------------------------------
# Named tick stages: compile-time metadata only.
# ---------------------------------------------------------------------------
TICK_STAGES = ("scan.observe", "scan.variants", "scan.policy",
               "scan.provision", "scan.serve", "scan.account")


def _compiled_runner_text(policy, batched, catalog):
    """The optimized module of the runner ``run_grid`` (batched) or
    ``run_scenario`` uses, at a tiny size."""
    wl = _vworkload()
    pol = je.JAX_POLICIES[policy]
    cells = [
        je.build_sim_inputs(
            SCENARIO_ZOO["mmpp_bursts"].build(len(wl), duration_s=40, seed=i), wl,
            catalog=catalog, seed=i, needs_stats=pol.needs_stats,
            needs_key=pol.needs_key, lazy_rings=not batched)
        for i in range(2)
    ]
    statics = cells[0][0]
    runner = je._get_runner(policy, batched=batched, variants="var_smult" in statics)
    if batched:
        args = (statics, je._tree_stack([pol.default_params()] * 2),
                je._tree_stack([c[1] for c in cells]),
                je._tree_stack([c[2] for c in cells]))
    else:
        args = (dict(statics, policy=pol.default_params()), cells[0][1], cells[0][2])
    with jax.enable_x64(True):
        return runner.lower(*args).compile().as_text()


def _path_components(text):
    import re

    return {c for p in re.findall(r'op_name="([^"]*)"', text) for c in p.split("/")}


@pytest.fixture
def no_compile_cache():
    """A persistent compilation cache keys programs without their scope
    metadata, so a program cached without scopes would come back
    without them: compile afresh."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("batched", [False, True])
@pytest.mark.parametrize("with_catalog", [False, True])
def test_tick_stages_are_named_in_the_runner(batched, with_catalog, vcatalog,
                                             monkeypatch, no_compile_cache):
    """Every stage scope is a whole path component of some op's
    ``op_name`` in the compiled runner; ``scan.variants`` only where a
    catalog puts the variant axis in the tick."""
    monkeypatch.setattr(je, "_RUNNERS", {})
    comps = _path_components(_compiled_runner_text(
        "infaas_variant", batched, vcatalog if with_catalog else None))
    assert set(TICK_STAGES) - {"scan.variants"} <= comps
    assert ("scan.variants" in comps) == with_catalog
    # the monitor's order statistics, before the tick scan (vmap wraps
    # the scope's name in the grid)
    assert comps & {"sim.monitor", "vmap(sim.monitor)"}


def test_stages_and_spans_leave_results_bitwise(vcatalog, monkeypatch, no_compile_cache):
    """With the scopes and the program spans (profiler off) the runners
    compile to the same optimized module, metadata aside, and
    ``run_grid`` / ``run_scenario`` return the same bits as without."""
    import contextlib
    import re

    from repro.core.sim import telemetry

    wl = _vworkload()
    arrs = np.stack([SCENARIO_ZOO[n].build(len(wl), duration_s=120, seed=7 + i)
                     for i, n in enumerate(("shared_berkeley", "flash_correlated"))])

    def runs():
        monkeypatch.setattr(je, "_RUNNERS", {})
        out = je.run_grid(arrs, wl, "infaas_variant", seeds=[3, 4], catalog=vcatalog)
        out.append(je.run_scenario(arrs[0], wl, "paragon", seed=3))
        text = _compiled_runner_text("infaas_variant", True, vcatalog)
        return out, text

    def strip(text):
        text = text[text.index("\n%"):]          # the file / frame tables
        text = re.sub(r",? metadata=\{[^}]*\}", "", text)
        return re.sub(r"%[\w.\-]+", "%", text)       # names (scope-derived)

    scoped, scoped_text = runs()
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    monkeypatch.setattr(telemetry, "span", lambda name: contextlib.nullcontext())
    plain, plain_text = runs()
    assert "scan.policy" in scoped_text and "scan.policy" not in plain_text
    assert strip(scoped_text) == strip(plain_text)
    for a, b in zip(scoped, plain):
        assert a["summary"] == b["summary"]
        la, lb = jax.tree.leaves(a["raw"]), jax.tree.leaves(b["raw"])
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(x, y)
