"""The observability subsystem (PR 7): event-log <-> ledger
reconciliation, zero-cost-when-disabled goldens, the recorder, the
burn-rate monitors, the exporters, and the JAX trajectory surface.

The load-bearing property is **bit-exact reconciliation**: every ledger
delta the engine posts must be explained by the structured event log —
``reconcile_events`` replays the ledger's exact posting order from the
events alone and the totals compare ``==`` (not merely close) against
the run's :class:`SimResult`, per arch included.  The second hard
property is that a telemetry-less run is *bit-identical* to the
pre-telemetry engine (goldens hardcoded below from the PR 6 tree).
"""
from __future__ import annotations

import json
import warnings

import dataclasses
import numpy as np
import pytest

from repro.core.schedulers import VECTOR_SCHEDULERS
from repro.core.sim import (
    EVENT_TYPES,
    SUMMARY_KEY_DOCS,
    MonitorConfig,
    ServingSim,
    Telemetry,
    TimeSeriesRecorder,
    VariantCatalog,
    detect_incidents,
    incidents_table,
    reconcile_events,
    simulate,
    uniform_pool_workload,
)
from repro.core.sim.telemetry import (
    _mask_to_incidents,
    _rolling_sum,
    events_from_jsonl,
)
from repro.core.workloads import SCENARIO_ZOO

POOL = [
    "llama3-8b", "qwen1.5-0.5b", "rwkv6-1.6b", "minicpm-2b",
    "whisper-small", "llava-next-mistral-7b", "recurrentgemma-9b",
    "phi3.5-moe-42b-a6.6b",
]

LEDGER_SCALARS = (
    "total_requests", "served_vm", "served_burst", "violations",
    "violations_strict", "cost_reserved", "cost_spot", "cost_burst",
    "accuracy_weighted", "accuracy_served", "acc_violations",
    "chip_seconds", "chip_seconds_needed", "chip_seconds_over",
)


def _run(scenario: str, policy: str, ticks: int = 300, *,
         telemetry=None, catalog=None, wl=None, mean_rps: float = 300.0):
    wl = wl if wl is not None else uniform_pool_workload(POOL, strict_frac=0.25)
    arr = SCENARIO_ZOO[scenario].build(len(wl), duration_s=ticks,
                                       mean_rps=mean_rps)
    sim = ServingSim(arr, wl, seed=0, catalog=catalog, telemetry=telemetry)
    pol = VECTOR_SCHEDULERS[policy]()
    while not sim.done:
        sim.apply_pool(pol(sim.tick, sim.observe_pool()))
    return sim


def _assert_reconciles(sim, tel, ticks: int) -> None:
    rec = reconcile_events(tel.events, len(sim.keys), ticks)
    res = sim.res
    for k in LEDGER_SCALARS:
        assert rec[k] == getattr(res, k), (
            f"{k}: events rebuild {rec[k]!r} != ledger {getattr(res, k)!r}"
        )
    assert rec["preemptions"] == res.preemptions
    assert rec["variant_swaps"] == res.variant_swaps
    assert rec["cost_other"] == res.cost_other       # values AND key order
    assert list(rec["cost_other"]) == list(res.cost_other)
    assert rec["cost_total"] == res.cost_total
    counts = sim.per_arch_counts()
    for k, v in rec["per_arch"].items():
        if k == "violations":
            # the engine folds still-queued mass into its running per-arch
            # violations view only at finalize; both sides include it here
            pass
        np.testing.assert_array_equal(v, counts[k], err_msg=k)


# ---------------------------------------------------------------------------
# Tentpole property 1: the event log explains the ledger, bit-exactly,
# on every zoo scenario.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scenario", sorted(SCENARIO_ZOO))
def test_reconciliation_zoo_smoke(scenario):
    ticks = 300
    tel = Telemetry()
    sim = _run(scenario, "portfolio", ticks, telemetry=tel)
    assert len(tel.events) > ticks          # emitted something every tick
    _assert_reconciles(sim, tel, ticks)


@pytest.mark.parametrize("policy", ["spot_paragon", "reactive"])
def test_reconciliation_other_policies(policy):
    ticks = 240
    tel = Telemetry()
    sim = _run("mmpp_bursts", policy, ticks, telemetry=tel)
    _assert_reconciles(sim, tel, ticks)


def test_reconciliation_variant_catalog():
    """Accuracy mass, accuracy violations and swap events reconcile on a
    variant-aware run (the trending_hotswap scenario forces swaps)."""
    ticks = 240
    wl = [dataclasses.replace(w, min_accuracy=0.6)
          for w in uniform_pool_workload(POOL, strict_frac=0.25)]
    catalog = VariantCatalog.for_workload(wl)
    tel = Telemetry()
    sim = _run("trending_hotswap", "infaas_variant", ticks,
               telemetry=tel, catalog=catalog, wl=wl)
    assert sim.res.accuracy_served > 0
    _assert_reconciles(sim, tel, ticks)


# ---------------------------------------------------------------------------
# Tentpole property 2: telemetry off == the pre-telemetry engine, bit
# for bit.  Goldens recorded from the PR 6 tree (A=8 uniform pool,
# strict_frac=0.25, duration 600, mean_rps 300, default build seed).
# ---------------------------------------------------------------------------
GOLDENS = {
    ("flash_correlated", "portfolio"): dict(
        violations=4650.577013700305, cost_total=3.4240622414251773,
        served_vm=179963.98193845653, preemptions=1),
    ("mmpp_bursts", "paragon"): dict(
        violations=18461.562900661895, cost_total=3.609333333333281,
        served_vm=179995.47008110004, preemptions=0),
    ("diurnal_phases", "spot_paragon"): dict(
        violations=1223.2627715401238, cost_total=3.448999999999966,
        served_vm=179999.99999999994, preemptions=0),
}


@pytest.mark.parametrize("scenario,policy", sorted(GOLDENS))
def test_disabled_matches_pre_telemetry_goldens(scenario, policy):
    wl = uniform_pool_workload(POOL, strict_frac=0.25)
    arr = SCENARIO_ZOO[scenario].build(len(wl), duration_s=600, mean_rps=300.0)
    res = simulate(arr, wl, VECTOR_SCHEDULERS[policy]())
    g = GOLDENS[(scenario, policy)]
    assert res.violations == g["violations"]
    assert res.cost_total == g["cost_total"]
    assert res.served_vm == g["served_vm"]
    assert res.preemptions == g["preemptions"]


def test_enabled_equals_disabled_bitwise():
    """Attaching telemetry must not perturb a single ledger bit."""
    ticks = 300
    on = _run("flash_correlated", "portfolio", ticks, telemetry=Telemetry())
    off = _run("flash_correlated", "portfolio", ticks)
    for k in LEDGER_SCALARS:
        assert getattr(on.res, k) == getattr(off.res, k), k
    assert on.res.cost_other == off.res.cost_other
    assert on.res.preemptions == off.res.preemptions
    for k, v in on.per_arch_counts().items():
        np.testing.assert_array_equal(v, off.per_arch_counts()[k], err_msg=k)


# ---------------------------------------------------------------------------
# The recorder: stride semantics, flow conservation, gauges.
# ---------------------------------------------------------------------------
def test_recorder_stride_buckets():
    ticks = 120
    t1 = Telemetry(stride=1)
    _run("mmpp_bursts", "paragon", ticks, telemetry=t1)
    t10 = Telemetry(stride=10)
    _run("mmpp_bursts", "paragon", ticks, telemetry=t10)

    assert t1.recorder.n_rows == ticks
    assert t10.recorder.n_rows == ticks // 10
    # flows accumulate within a bucket: totals survive downsampling
    for name in TimeSeriesRecorder.FLOW_NAMES:
        np.testing.assert_allclose(
            t10.recorder.flows[name].sum(axis=0),
            t1.recorder.flows[name].sum(axis=0), rtol=1e-12)
    np.testing.assert_allclose(t10.recorder.tier_cost.sum(),
                               t1.recorder.tier_cost.sum(), rtol=1e-12)
    # gauges are last-write-wins: each bucket reports its final tick
    np.testing.assert_array_equal(
        t10.recorder.tick[:12], np.arange(12) * 10 + 9)
    np.testing.assert_array_equal(
        t10.recorder.tier_active["reserved"][:12],
        t1.recorder.tier_active["reserved"][9::10])


def test_recorder_allocation_stride_and_dtypes():
    """The recorder honors the stride at allocation (R = ceil(T/stride)
    rows, not T) and keeps gauge buffers narrow — observability series
    are float32/int32 while the reconciliation-bearing flow and cost
    series stay float64."""
    rec = TimeSeriesRecorder(256, ticks=3600, stride=60)
    assert rec.rows == 60
    for t in rec.tier_names:
        assert rec.tier_active[t].shape == (60, 256)
        assert rec.tier_active[t].dtype == np.int32
        assert rec.tier_pending[t].dtype == np.int32
    for c in ("strict", "relaxed"):
        assert rec.queue_depth[c].dtype == np.float32
        assert rec.queue_age_p99[c].dtype == np.int32
    assert rec.active_variant.dtype == np.int32
    assert rec.utilization.dtype == np.float32
    assert rec.harvest_level.dtype == np.float32
    # the exactness-bearing series keep full precision
    for name in TimeSeriesRecorder.FLOW_NAMES:
        assert rec.flows[name].dtype == np.float64
    assert rec.tier_cost.dtype == np.float64
    assert rec.tick.dtype == np.int64


def test_recorder_direct_flow_accumulation():
    rec = TimeSeriesRecorder(2, ticks=10, stride=5)
    rec.add_flow(0, "arrived", np.array([1.0, 2.0]))
    rec.add_flow(4, "arrived", np.array([3.0, 4.0]))
    rec.add_flow(5, "arrived", np.array([10.0, 0.0]))
    assert rec.rows == 2
    np.testing.assert_array_equal(rec.flows["arrived"][0], [4.0, 6.0])
    np.testing.assert_array_equal(rec.flows["arrived"][1], [10.0, 0.0])
    assert rec.n_rows == 2
    np.testing.assert_array_equal(rec.pool_flow("arrived"), [10.0, 10.0])
    assert set(rec.as_dict()) >= {"tick", "arrived", "tier_cost",
                                  "utilization", "harvest_level"}


def test_telemetry_rebinds_fresh_per_run():
    """RL envs reuse one Telemetry across episodes: bind() must reset."""
    tel = Telemetry()
    _run("mmpp_bursts", "paragon", 60, telemetry=tel)
    n1 = len(tel.events)
    sim = _run("mmpp_bursts", "paragon", 60, telemetry=tel)
    assert len(tel.events) == n1            # fresh log, not doubled
    _assert_reconciles(sim, tel, 60)


# ---------------------------------------------------------------------------
# Monitors.
# ---------------------------------------------------------------------------
def test_rolling_sum_matches_naive():
    rng = np.random.default_rng(0)
    x = rng.random(200)
    for w in (1, 7, 60, 500):
        naive = np.array([x[max(0, i - w + 1): i + 1].sum()
                          for i in range(len(x))])
        np.testing.assert_allclose(_rolling_sum(x, w), naive, atol=1e-9)


def test_mask_to_incidents_merges_runs():
    ticks = np.arange(10)
    mask = np.array([0, 1, 1, 0, 0, 1, 0, 0, 1, 1], dtype=bool)
    peak = np.arange(10, dtype=float)
    out = _mask_to_incidents(mask, ticks, peak, "slo_burn", "strict", "d")
    assert [(i.start_tick, i.end_tick, i.peak) for i in out] == [
        (1, 2, 2.0), (5, 5, 5.0), (8, 9, 9.0)]
    assert _mask_to_incidents(np.zeros(4, bool), ticks[:4], peak[:4],
                              "slo_burn", "strict", "d") == []


def _synthetic_recorder(ticks: int = 600) -> TimeSeriesRecorder:
    rec = TimeSeriesRecorder(2, ticks)
    rec.tick[:] = np.arange(ticks)
    rec._touched = ticks
    rec.flows["arrived"][:] = 50.0          # per arch, per tick
    rec.flows["served_vm"][:] = 50.0
    rec.tier_cost[:, 0] = 1.0               # $1/tick reserved baseline
    return rec


def test_monitor_detects_slo_burn():
    rec = _synthetic_recorder()
    rec.flows["viol_strict"][200:330, 0] = 60.0   # 60% of pool arrivals
    inc = detect_incidents(rec)
    burns = [i for i in inc if i.kind == "slo_burn"]
    assert burns and burns[0].label == "strict"
    # pages only once the slow window confirms, inside the burst
    assert 200 <= burns[0].start_tick <= 330
    assert burns[0].peak > MonitorConfig().burn_threshold
    # quiet series -> quiet monitors
    assert detect_incidents(_synthetic_recorder()) == []


def test_monitor_detects_queue_age():
    rec = _synthetic_recorder()
    rec.queue_age_p99["relaxed"][300:340, 1] = 99
    inc = [i for i in detect_incidents(rec) if i.kind == "queue_age"]
    assert len(inc) == 1 and inc[0].label == "relaxed"
    assert (inc[0].start_tick, inc[0].end_tick) == (300, 339)
    assert inc[0].peak == 99.0


def test_monitor_detects_cost_drift():
    rec = _synthetic_recorder()
    rec.tier_cost[400:, 0] = 30.0           # 30x the $/request baseline
    inc = [i for i in detect_incidents(rec) if i.kind == "cost_drift"]
    assert inc and inc[0].start_tick >= 400
    table = incidents_table(inc)
    assert "cost_drift" in table and "cost_per_request" in table
    assert incidents_table([]) == "no incidents detected\n"


def test_dashboard_scenario_yields_incident():
    """The acceptance path: a zoo scenario must page >= 1 incident with
    default monitor thresholds (what --require-incident exercises)."""
    tel = Telemetry()
    _run("flash_correlated", "portfolio", 600, telemetry=tel)
    assert len(detect_incidents(tel.recorder)) >= 1


# ---------------------------------------------------------------------------
# Exporters.
# ---------------------------------------------------------------------------
def test_jsonl_round_trip(tmp_path):
    tel = Telemetry()
    _run("mmpp_bursts", "paragon", 90, telemetry=tel)
    path = str(tmp_path / "events.jsonl")
    n = tel.to_jsonl(path)
    assert n == len(tel.events) > 0
    back = events_from_jsonl(path)
    assert back == tel.events               # NamedTuple equality, exact
    rec = reconcile_events(back, 8, 90)
    assert rec["total_requests"] > 0


def test_prometheus_text_format():
    tel = Telemetry()
    sim = _run("flash_correlated", "portfolio", 120, telemetry=tel)
    text = tel.prometheus_text(sim.res)
    lines = text.splitlines()
    assert any(l.startswith("# TYPE repro_sim_events_total") for l in lines)
    assert any(l.startswith('repro_sim_events_total{etype="arrival"}')
               for l in lines)
    assert any(l.startswith('repro_sim_result{metric="cost_total"}')
               for l in lines)
    # every sample line is "name{labels} value" with a float value
    for l in lines:
        if l and not l.startswith("#"):
            float(l.rsplit(" ", 1)[1])


def test_event_types_documented():
    tel = Telemetry()
    _run("flash_correlated", "portfolio", 200, telemetry=tel)
    seen = {e.etype for e in tel.events}
    assert seen <= set(EVENT_TYPES)
    assert all(isinstance(v, str) and v for v in EVENT_TYPES.values())
    d = tel.events_as_dicts()[0]
    assert set(d) == {"tick", "etype", "arch", "tier", "cls",
                      "magnitude", "cost"}


def test_summary_key_docs_cover_every_key():
    wl = [dataclasses.replace(w, min_accuracy=0.6)
          for w in uniform_pool_workload(POOL, strict_frac=0.25)]
    catalog = VariantCatalog.for_workload(wl)
    sim = _run("flash_correlated", "portfolio", 200, catalog=catalog, wl=wl)
    for key in sim.res.summary():
        doc_key = key if key in SUMMARY_KEY_DOCS else "cost_<tier>"
        assert doc_key in SUMMARY_KEY_DOCS, f"undocumented summary key {key}"
        assert key.startswith("cost_") or key in SUMMARY_KEY_DOCS


# ---------------------------------------------------------------------------
# JAX engine surface: trajectories + the retrace counter/warning.
# ---------------------------------------------------------------------------
def test_jax_trajectory_matches_sum_mode():
    from repro.core.sim import jax_engine as je
    from repro.core.sim.telemetry import global_counters

    wl = uniform_pool_workload(POOL[:4], strict_frac=0.25)
    arr = SCENARIO_ZOO["mmpp_bursts"].build(4, duration_s=200, mean_rps=120.0)
    base = je.run_scenario(arr, wl, "portfolio")
    traj = je.run_scenario(arr, wl, "portfolio", record_trajectory=True)

    assert set(base["summary"]) == set(traj["summary"])
    for k, v in base["summary"].items():
        np.testing.assert_allclose(traj["summary"][k], v, rtol=1e-6,
                                   err_msg=k)
    series = traj["trajectory"]
    for k in ("served", "viol", "cost_arch", "n_res", "queue_strict",
              "queue_relaxed"):
        assert series[k].shape[0] == 200, k
    # the per-tick fleet gauge is a level series, not all-zero
    assert np.asarray(series["n_res"]).sum() > 0
    # both runner modes surfaced their trace counts as global counters
    keys = [k for k in global_counters() if "jax_runner_traces_total" in k]
    assert any('mode="sum"' in k for k in keys)
    assert any('mode="stack"' in k for k in keys)


def test_retrace_warns_once_per_key():
    from repro.core.sim import jax_engine as je

    wl = uniform_pool_workload(POOL[:2], strict_frac=0.25)
    arr = SCENARIO_ZOO["mmpp_bursts"].build(2, duration_s=60, mean_rps=60.0)
    je.run_scenario(arr, wl, "reactive")
    key = ("reactive", "sum", False, "opt", False)
    n = je.runner_trace_count(*key)
    assert n >= 1
    # pretend the key was seen at a lower trace count: the next use must
    # warn exactly once, then stay quiet
    je._TRACE_SEEN[key] = n - 1
    je._TRACE_WARNED.discard(key)
    with pytest.warns(RuntimeWarning, match="retraced"):
        je.note_runner_use(*key)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert je.note_runner_use(*key) == n


def test_jax_trajectory_variant_gauges():
    """Variant-catalog trajectory runs expose the per-tick variant gauges
    (active index, swap-in-flight flag, delivered-accuracy rate) and stay
    summary-identical to sum mode — the gauge channels must not perturb
    the reduction."""
    from repro.core.sim import jax_engine as je

    wl = [dataclasses.replace(w, min_accuracy=0.55)
          for w in uniform_pool_workload(POOL[:4], strict_frac=0.25)]
    catalog = VariantCatalog.for_workload(wl)
    arr = SCENARIO_ZOO["trending_hotswap"].build(4, duration_s=300,
                                                 mean_rps=300.0)
    base = je.run_scenario(arr, wl, "infaas_variant", catalog=catalog)
    traj = je.run_scenario(arr, wl, "infaas_variant", catalog=catalog,
                           record_trajectory=True)

    assert set(base["summary"]) == set(traj["summary"])
    for k, v in base["summary"].items():
        np.testing.assert_allclose(traj["summary"][k], v, rtol=1e-6,
                                   err_msg=k)
    series = traj["trajectory"]
    for k in ("active_variant", "swap_in_flight", "acc_rate", "swaps"):
        assert k in series, k
    for k in ("active_variant", "swap_in_flight", "acc_rate"):
        assert np.asarray(series[k]).shape == (300, 4), k
    # flows still sum to the ledger; gauges describe states
    assert int(np.asarray(series["swaps"]).sum()) == base["summary"][
        "variant_swaps"]
    assert base["summary"]["variant_swaps"] > 0
    # the gauge channels are consistent with each other: while a swap is
    # in flight the delivered accuracy still reflects the OLD variant
    acc = np.asarray(series["acc_rate"])
    active = np.asarray(series["active_variant"])
    assert (acc > 0).all()
    vmax = max(len(vs) for vs in catalog.per_arch.values())
    assert active.min() >= 0 and (active < vmax).all()


def test_recorder_acc_rate_on_catalog_run():
    """The NumPy recorder's delivered-accuracy gauge mirrors the JAX
    ``acc_rate`` trajectory channel: populated on catalog runs and
    exported by ``as_dict``."""
    wl = [dataclasses.replace(w, min_accuracy=0.55)
          for w in uniform_pool_workload(POOL[:4], strict_frac=0.25)]
    catalog = VariantCatalog.for_workload(wl)
    tel = Telemetry(events=False)
    _run("trending_hotswap", "infaas_variant", 300, telemetry=tel,
         catalog=catalog, wl=wl)
    rec = tel.recorder
    d = rec.as_dict()
    assert "acc_rate" in d and "active_variant" in d
    assert d["acc_rate"].shape == (300, 4)
    assert (d["acc_rate"] > 0).all()
    # the gauge tracks the post-swap effective accuracy, so any tick
    # after a swap lands must show the new variant's accuracy
    assert d["active_variant"].shape == (300, 4)


# ---------------------------------------------------------------------------
# PPO training-curve stream.
# ---------------------------------------------------------------------------
def test_ppo_training_log(tmp_path):
    from repro.core.rl import EnvConfig, PPOConfig, PoolServingEnv, train_ppo_pool
    from repro.core.workloads import get_scenario

    wl = uniform_pool_workload(POOL[:2], strict_frac=0.25)
    env = PoolServingEnv(wl, EnvConfig(mean_rps=30, duration_s=60),
                         scenarios=[get_scenario("mmpp_bursts")])
    path = str(tmp_path / "curve.jsonl")
    state = train_ppo_pool(
        env, PPOConfig(iterations=2, rollout_len=60, hidden=16),
        log_path=path)
    rows = [json.loads(l) for l in open(path)]
    assert len(rows) == 2 == len(state.history)
    for row in rows:
        assert {"iter", "rollout_reward", "loss_mean", "pi_loss", "v_loss",
                "entropy_mean", "approx_kl"} <= set(row)
        assert np.isfinite([row["loss_mean"], row["entropy_mean"],
                            row["approx_kl"]]).all()
    assert rows == state.history            # the stream IS the history


# ---------------------------------------------------------------------------
# Program spans and counters of the JAX engine's entry points.
# ---------------------------------------------------------------------------
def test_runner_counter_labels_every_part_of_the_key():
    """A catalog and a catalog-free runner of one policy are counted
    under separate labels: neither overwrites the other's count."""
    from repro.core.sim import jax_engine as je
    from repro.core.sim.telemetry import global_counters

    wl = [dataclasses.replace(w, min_accuracy=0.55)
          for w in uniform_pool_workload(POOL[:3], strict_frac=0.25)]
    arr = SCENARIO_ZOO["mmpp_bursts"].build(3, duration_s=60, mean_rps=60.0)
    je.run_scenario(arr, wl, "infaas_variant")
    je.run_scenario(arr, wl, "infaas_variant",
                    catalog=VariantCatalog.for_workload(wl))
    keys = [k for k in global_counters()
            if k.startswith('jax_runner_traces_total{policy="infaas_variant",mode="sum",'
                            'batched="0"')]
    assert {k for k in keys if 'flavor="opt"' in k and 'sharded="0"' in k} == set(keys)
    assert sorted('variants="1"' in k for k in keys) == [False, True]
    for variants in (False, True):
        n = je.runner_trace_count("infaas_variant", variants=variants)
        assert n >= 1
        key = next(k for k in keys if f'variants="{int(variants)}"' in k)
        assert global_counters()[key] == n


def test_sharded_runner_is_counted_subprocess():
    """The sharded ``run_grid`` path notes its runner too, and
    ``runner_trace_count`` finds it by its device count (two host
    devices, so in a subprocess)."""
    import os
    import subprocess
    import sys

    from repro.core.sim import jax_engine as je

    script = r"""
import numpy as np, jax
assert len(jax.devices()) == 2, jax.devices()
from repro.core.sim import jax_engine as je
from repro.core.sim.telemetry import global_counters
from repro.core.sim.types import ArchLoad
from repro.core.workloads import SCENARIO_ZOO
wl = [ArchLoad("llama3-8b", 0.5, 0.25, name=f"m@{i}") for i in range(2)]
arrs = np.stack([SCENARIO_ZOO["mmpp_bursts"].build(2, duration_s=40, seed=i)
                 for i in range(2)])
je.run_grid(arrs, wl, "reactive", seeds=[1, 2], sharded=True)
assert je.runner_trace_count("reactive", batched=True, sharded=2) == 1
assert je.runner_trace_count("reactive", batched=True) == 0
key = ('jax_runner_traces_total{policy="reactive",mode="sum",batched="1",'
       'flavor="opt",variants="0",sharded="2"}')
assert global_counters()[key] == 1, global_counters()
print("SHARDED_COUNTED_OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
    ).strip()
    src = os.path.dirname(os.path.dirname(os.path.abspath(je.__file__)))
    src = os.path.dirname(os.path.dirname(src))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "SHARDED_COUNTED_OK" in proc.stdout


def test_program_spans_keep_self_time_per_call():
    import time

    from repro.core.sim import telemetry

    @telemetry.program_call()
    def call():
        with telemetry.span("t.outer"):
            time.sleep(0.02)
            with telemetry.span("t.inner"):
                time.sleep(0.03)
            telemetry.add_counter("t_bytes_total", 5)
        with telemetry.span("t.inner"):
            time.sleep(0.01)
        telemetry.add_counter("t_bytes_total", 2)

    before = telemetry.GLOBAL_COUNTERS.get("t_bytes_total", 0.0)
    call()
    rec = telemetry.CALLS[-1]
    assert set(rec) == {"t.outer", "t.inner", "t_bytes_total"}
    assert rec["t_bytes_total"] == 7
    assert telemetry.GLOBAL_COUNTERS["t_bytes_total"] == before + 7
    # the outer span's self time leaves its nested span out
    assert 0.02 <= rec["t.outer"] < 0.03 + 0.02
    assert 0.04 <= rec["t.inner"] < 0.04 + 0.03
    # outside a program call, spans and counters keep no record
    n = len(telemetry.CALLS)
    with telemetry.span("t.outer"):
        telemetry.add_counter("t_bytes_total", 1)
    assert len(telemetry.CALLS) == n


def _tree_bytes(tree):
    import jax

    return sum(np.asarray(leaf).nbytes for leaf in jax.tree.leaves(tree))


def test_h2d_counter_counts_the_runner_arguments():
    """Each call adds the bytes of every argument it hands the runner."""
    from repro.core.sim import jax_engine as je
    from repro.core.sim import telemetry

    wl = uniform_pool_workload(POOL[:3], strict_frac=0.25)
    arrs = np.stack([SCENARIO_ZOO[n].build(3, duration_s=80, seed=i)
                     for i, n in enumerate(("mmpp_bursts", "shared_berkeley"))])
    pol = je.JAX_POLICIES["paragon"]

    def counted(fn):
        c0 = telemetry.GLOBAL_COUNTERS.get("sim_h2d_bytes_total", 0.0)
        fn()
        rec = telemetry.CALLS[-1]
        assert telemetry.GLOBAL_COUNTERS["sim_h2d_bytes_total"] - c0 == rec["sim_h2d_bytes_total"]
        return rec

    rec = counted(lambda: je.run_scenario(arrs[0], wl, "paragon", seed=4))
    statics, state0, xs = je.build_sim_inputs(arrs[0], wl, seed=4)
    assert "p2m" not in xs          # made from xs["rate"] on the device
    statics["policy"] = pol.default_params()
    assert rec["sim_h2d_bytes_total"] == _tree_bytes((statics, state0, xs))
    assert rec["sim_arch_ticks_total"] == arrs[0].size

    rec = counted(lambda: je.run_grid(arrs, wl, "paragon", seeds=[4, 5]))
    cells = [je.build_sim_inputs(a, wl, seed=s, lazy_rings=False)
             for a, s in zip(arrs, (4, 5))]
    policy_b = je._tree_stack([pol.default_params()] * 2)
    want = _tree_bytes((cells[0][0], policy_b, je._tree_stack([c[1] for c in cells]),
                        je._tree_stack([c[2] for c in cells])))
    assert rec["sim_h2d_bytes_total"] == want
    assert rec["sim_arch_ticks_total"] == arrs.size
    # one record per call, each with every stage of the call
    assert {"sim.prep.template", "sim.prep.monitor", "sim.prep.inputs",
            "sim.prep.stack", "sim.dispatch", "sim.fetch", "sim.assemble"} <= set(rec)


@pytest.mark.parametrize("policy", ["paragon", "portfolio"])
def test_monitor_counter_says_where_the_order_statistics_ran(policy):
    """Every arch-tick of a stats policy's call has its order statistics
    computed in the runner; a policy that reads none computes none."""
    from repro.core.sim import jax_engine as je
    from repro.core.sim import telemetry

    wl = uniform_pool_workload(POOL[:3], strict_frac=0.25)
    arrs = np.stack([SCENARIO_ZOO[n].build(3, duration_s=60, seed=i)
                     for i, n in enumerate(("mmpp_bursts", "flash_anti"))])
    key = "sim_monitor_device_arch_ticks_total"
    for call in (lambda: je.run_grid(arrs, wl, policy, seeds=[1, 2]),
                 lambda: je.run_scenario(arrs[1], wl, policy, seed=2)):
        c0 = telemetry.GLOBAL_COUNTERS.get(key, 0.0)
        call()
        rec = telemetry.CALLS[-1]
        want = rec["sim_arch_ticks_total"] if je.JAX_POLICIES[policy].needs_stats else 0
        assert rec[key] == want
        assert telemetry.GLOBAL_COUNTERS[key] - c0 == want


def test_prometheus_text_prints_counters_exactly():
    from repro.core.sim import telemetry

    telemetry.set_global_counter("t_exact_bytes_total", 1064818176)
    text = Telemetry().prometheus_text()
    assert "repro_t_exact_bytes_total 1064818176\n" in text
