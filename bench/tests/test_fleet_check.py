"""What the check hands the reference and what it compares: each job
carries the simulator seed of its answer, and the final fleet of every
tier is compared exactly."""
import numpy as np
import pytest

from small import cells, run_small

TIERS = {"spot": "spot", "harvest": "harv", "remote": "rem"}
FLEET_KEYS = ("active", "pending", "variant") + tuple(
    f"{name}_{k}" for name in TIERS for k in ("active", "pending"))


@pytest.mark.parametrize("workload", cells())
def test_each_job_carries_its_answers_sim_seed(workload, monkeypatch):
    from harness import check
    from repro.core.sim import jax_engine as je

    ran, jobs = [], []                  # (arrivals, seed) the program ran
    run_grid, run_scenario, references = je.run_grid, je.run_scenario, check.references

    def grid(arrivals, *args, seeds, **kw):
        ran.extend(zip(arrivals, seeds))
        return run_grid(arrivals, *args, seeds=seeds, **kw)

    def single(arrivals, *args, seed, **kw):
        ran.append((arrivals, seed))
        return run_scenario(arrivals, *args, seed=seed, **kw)

    def recorded(js):
        jobs.extend(js)
        return references(js)

    monkeypatch.setattr(je, "run_grid", grid)
    monkeypatch.setattr(je, "run_scenario", single)
    monkeypatch.setattr(check, "references", recorded)
    res = run_small(workload, 2**31 + 7)
    assert res["correct"] is True, res["checks"]
    assert jobs
    for job in jobs:
        seeds = {s for a, s in ran if np.array_equal(a, job["arrivals"])}
        assert seeds == {job["sim_seed"]}, (seeds, job["sim_seed"])


def _portfolio_answer():
    """One ``run_scenario`` under ``portfolio``, 8 streams x 400 ticks of
    ``flash_correlated``, whose final spot and harvest fleets are
    nonzero, launches in flight included."""
    from harness import generators, spec, sut

    cell = spec.cell(cells()[0])
    cfg = dict(cell["config"], streams=8, ticks=400, policy="portfolio")
    sc = next(s for s in cell["traffic"]["scenarios"]
              if s["name"] == "flash_correlated")
    arr = generators.build(sc, 8, 400, float(cfg["rps_per_stream"]) * 8, 4)
    return sut.Program(cfg, "single").call({"arrivals": arr[None], "sim_seeds": [4]})[0]


def test_extract_keeps_every_tiers_fleet():
    from harness import sut

    res = _portfolio_answer()
    final = res["raw"]["final"]
    fleet = sut.extract(res)["fleet"]
    assert set(fleet) == set(FLEET_KEYS)
    for name, tier in TIERS.items():
        np.testing.assert_array_equal(fleet[name + "_active"],
                                      getattr(final, tier + "_active"))
        np.testing.assert_array_equal(
            fleet[name + "_pending"],
            getattr(final, tier + "_cum") - getattr(final, tier + "_mat"))
    assert fleet["harvest_active"].any() and fleet["harvest_pending"].any()
    assert fleet["spot_pending"].any()
    for k in FLEET_KEYS:
        assert fleet[k].dtype == np.int64 and fleet[k].shape == (8,), k

    # a state that does not hold a tier reads as zeros
    dropped = final._replace(harv_active=None, harv_cum=None, harv_mat=None)
    res["raw"] = dict(res["raw"], final=dropped)
    fleet = sut.extract(res)["fleet"]
    assert not fleet["harvest_active"].any() and not fleet["harvest_pending"].any()
    assert fleet["spot_pending"].any()


def test_reference_gives_the_same_fleet_keys():
    from harness import spec
    from reference import fleet as ref

    cfg = dict(spec.cell(cells()[0])["config"], streams=8, ticks=20)
    out = ref.run_cell({"arrivals": np.full((8, 20), 30.0), "cfg": cfg,
                        "dtype": "float64", "sim_seed": 4})
    assert set(out["fleet"]) == set(FLEET_KEYS)
    for name in TIERS:
        assert not out["fleet"][name + "_active"].any()
        assert not out["fleet"][name + "_pending"].any()


def _answer(A=4):
    from harness.check import COST_KEYS

    return {"flows": {"served_vm": np.ones(A)},
            "totals": {**{k: 1.0 for k in COST_KEYS}, "preemptions": 0,
                       "variant_swaps": 0},
            "fleet": {k: np.zeros(A, dtype=np.int64) for k in FLEET_KEYS}}


@pytest.mark.parametrize("key", FLEET_KEYS)
def test_compare_counts_a_fleet_difference(key):
    from harness import check

    want, got = _answer(), _answer()
    assert check.compare(got, want)["fleet_diff"] == 0
    got["fleet"][key][[1, 3]] = [1, 2]
    assert check.compare(got, want) == {"flow_err": 0.0, "cost_err": 0.0,
                                        "fleet_diff": 2}


@pytest.mark.parametrize("side", ["program", "reference"])
@pytest.mark.parametrize("part,key", [("fleet", "harvest_pending"), ("flows", "served_vm")])
def test_compare_refuses_a_key_only_one_side_states(part, key, side):
    from harness import check

    want, got = _answer(), _answer()
    del (want if side == "program" else got)[part][key]
    with pytest.raises(KeyError, match=part):
        check.compare(got, want)
