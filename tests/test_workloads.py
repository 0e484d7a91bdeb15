"""The workload scenario subsystem: generator determinism, Scenario
round-trips, the per-arch engine path (streaming monitor, per-arch
conservation), and backward equivalence — ``from_pool_trace`` arrivals
must reproduce the shared-trace engine."""
import json

import numpy as np
import pytest

from repro.core.load_monitor import LoadMonitor, PoolLoadMonitor, pool_stats_trajectory
from repro.core.schedulers import SCHEDULERS, VECTOR_SCHEDULERS
from repro.core.sim import ServingSim, shares, simulate, uniform_pool_workload
from repro.core.traces import get_trace
from repro.core.workloads import (
    GENERATORS,
    SCENARIO_ZOO,
    Scenario,
    from_pool_trace,
    get_scenario,
    save_replay,
)

SEED_ARCHS = ["llama3-8b", "qwen1.5-0.5b", "rwkv6-1.6b", "minicpm-2b"]


@pytest.fixture(scope="module")
def workload():
    return uniform_pool_workload(SEED_ARCHS, strict_frac=0.25)


# ---------------------------------------------------------------------------
# Generators.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", sorted(set(GENERATORS) - {"replay"}))
def test_generator_deterministic_and_normalized(kind):
    """Same seed -> bit-identical matrix; different seed -> different
    realization; pool mean lands on mean_rps; everything non-negative.
    (``replay`` is excluded: it is seed-invariant by design — literal
    playback of a capture — and has its own tests below.)"""
    gen = GENERATORS[kind]
    m1 = gen(6, 500, 80.0, 3)
    m2 = gen(6, 500, 80.0, 3)
    m3 = gen(6, 500, 80.0, 4)
    assert m1.shape == (6, 500)
    np.testing.assert_array_equal(m1, m2)
    assert not np.array_equal(m1, m3)
    assert (m1 >= 0).all()
    assert m1.sum(axis=0).mean() == pytest.approx(80.0, rel=0.05)


def test_from_pool_trace_is_exact_share_scaling():
    trace = get_trace("twitter", 300, mean_rps=50)
    share = np.array([0.5, 0.3, 0.2])
    mat = from_pool_trace(trace, share)
    # bit-identical to the engine's internal fan-out (trace[t] * share[a])
    for t in (0, 17, 299):
        np.testing.assert_array_equal(mat[:, t], trace[t] * share)


def test_flash_crowd_modes_differ():
    kw = dict(n_events=2, amplitude=4.0)
    corr = GENERATORS["flash_crowd"](4, 600, 100.0, 1, mode="correlated", **kw)
    anti = GENERATORS["flash_crowd"](4, 600, 100.0, 1, mode="anti", **kw)
    solo = GENERATORS["flash_crowd"](4, 600, 100.0, 1, mode="solo", **kw)
    assert not np.array_equal(corr, anti) and not np.array_equal(anti, solo)


def test_hotswap_shifts_popularity():
    """After a hotswap shift the per-arch share of pool demand moves:
    some arch's late-window share grows well beyond its early share."""
    mat = GENERATORS["hotswap"](4, 1200, 100.0, 5, n_shifts=2, boost=6.0)
    w_early = mat[:, :200].sum(axis=1) / mat[:, :200].sum()
    w_late = mat[:, -200:].sum(axis=1) / mat[:, -200:].sum()
    assert np.abs(w_late - w_early).max() > 0.1


# ---------------------------------------------------------------------------
# Scenario spec.
# ---------------------------------------------------------------------------
def test_scenario_json_roundtrip_rebuilds_identically():
    sc = get_scenario("mmpp_bursts")
    sc2 = Scenario.from_json(sc.to_json())
    assert sc2 == sc
    np.testing.assert_array_equal(sc.build(5), sc2.build(5))
    # the dict form is plain JSON (benchmark artifacts embed it)
    json.dumps(sc.to_dict())


def test_scenario_overrides_do_not_mutate_spec():
    sc = get_scenario("diurnal_phases")
    a = sc.build(3, seed=99, duration_s=200, mean_rps=10.0)
    assert a.shape == (3, 200)
    assert a.sum(axis=0).mean() == pytest.approx(10.0, rel=0.05)
    b = sc.build(3)
    assert b.shape == (3, sc.duration_s)   # spec unchanged


def test_unknown_scenario_kind_rejected():
    with pytest.raises(AssertionError):
        Scenario("bad", kind="nope")


# ---------------------------------------------------------------------------
# The streaming per-arch monitor.
# ---------------------------------------------------------------------------
def test_pool_monitor_matches_scalar_monitor_per_row():
    """PoolLoadMonitor == one LoadMonitor per arch, on arbitrary streams."""
    rng = np.random.default_rng(0)
    rates = rng.uniform(0, 50, size=(3, 700))   # longer than the window
    pool = PoolLoadMonitor(3)
    scalars = [LoadMonitor() for _ in range(3)]
    for t in range(rates.shape[1]):
        pool.observe(rates[:, t])
        for a, m in enumerate(scalars):
            m.observe(float(rates[a, t]))
        np.testing.assert_allclose(pool.rate, [m.rate for m in scalars], rtol=1e-12)
        np.testing.assert_allclose(pool.peak, [m.peak for m in scalars], rtol=1e-12)
        if t in (0, 5, 298, 299, 300, 699):     # window edges + steady state
            np.testing.assert_allclose(
                pool.median, [m.median for m in scalars], rtol=1e-12
            )
            np.testing.assert_allclose(
                pool.peak_to_median,
                [m.peak_to_median for m in scalars], rtol=1e-12,
            )


@pytest.mark.parametrize("stream", ["gamma", "duplicates", "constant", "walk"])
def test_pool_monitor_incremental_matches_naive(stream):
    """The banded incremental order-statistic structure must be
    bit-identical to the naive full-window recompute on every tick —
    continuous data, duplicate-heavy integer data, constant rows (zero
    arrivals), and drifting random walks (band re-centering)."""
    rng = np.random.default_rng(7)
    T, A, W = 700, 16, 300
    s = {
        "gamma": rng.gamma(2.0, 40.0, (T, A)),
        "duplicates": rng.integers(0, 5, (T, A)).astype(float),
        "constant": np.zeros((T, A)),
        "walk": np.abs(np.cumsum(rng.normal(0, 4.0, (T, A)), axis=0) + 200),
    }[stream]
    inc = PoolLoadMonitor(A, window_s=W)
    ref = PoolLoadMonitor(A, window_s=W, incremental=False)
    for t in range(T):
        inc.observe(s[t])
        ref.observe(s[t])
        np.testing.assert_array_equal(inc.peak, ref.peak)
        np.testing.assert_array_equal(inc.median, ref.median)
    for a, b in zip(inc.stats(), ref.stats()):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# Scenario composition.
# ---------------------------------------------------------------------------
def test_compose_splice_equals_children_segments():
    sc = get_scenario("diurnal_flash_splice")
    m = sc.build(6)
    kids = [Scenario.from_dict(c) for c in sc.params["children"]]
    built = [k.build(6, duration_s=sc.duration_s, mean_rps=sc.mean_rps)
             for k in kids]
    half = sc.duration_s // 2
    np.testing.assert_array_equal(m[:, :half], built[0][:, :half])
    np.testing.assert_array_equal(m[:, half:], built[1][:, half:])


def test_compose_roundtrip_and_seed_delta():
    sc = get_scenario("diurnal_flash_splice")
    sc2 = Scenario.from_json(sc.to_json())
    assert sc2 == sc
    np.testing.assert_array_equal(sc.build(4), sc2.build(4))
    json.dumps(sc.to_dict())        # artifacts embed the spec
    # a seed override re-rolls every child coherently and deterministically
    a = sc.build(4, seed=sc.seed + 9)
    b = sc.build(4, seed=sc.seed + 9)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, sc.build(4))


def test_compose_sum_preserves_pool_mean():
    kids = [
        Scenario("a", kind="diurnal").to_dict(),
        Scenario("b", kind="mmpp", seed=2).to_dict(),
    ]
    sc = Scenario("mix", kind="compose",
                  params={"op": "sum", "weights": [0.7, 0.3], "children": kids})
    m = sc.build(5, duration_s=600, mean_rps=90.0)
    assert m.shape == (5, 600)
    assert (m >= 0).all()
    assert m.sum(axis=0).mean() == pytest.approx(90.0, rel=0.05)


def test_compose_rejects_bad_specs():
    kid = Scenario("a", kind="diurnal").to_dict()
    with pytest.raises(AssertionError):
        Scenario("x", kind="compose", params={"children": [kid]})     # 1 child
    with pytest.raises(AssertionError):
        Scenario("x", kind="compose",
                 params={"op": "nope", "children": [kid, kid]})
    with pytest.raises(AssertionError):
        Scenario("x", kind="compose",
                 params={"op": "splice", "splits": [1.5],
                         "children": [kid, kid]})


# ---------------------------------------------------------------------------
# Backward equivalence: the per-arch path reproduces the shared path.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", ["reactive", "exascale", "mixed", "paragon"])
def test_from_pool_trace_matches_shared_engine(workload, policy):
    """Driving the engine with the from_pool_trace matrix must reproduce
    the shared-trace run exactly at summary level — the adapter IS
    today's behavior, through the new per-arch monitor path."""
    trace = get_trace("berkeley", 400, mean_rps=120)
    mat = from_pool_trace(trace, shares(workload))
    a = simulate(trace, workload, SCHEDULERS[policy]()).summary()
    b = simulate(mat, workload, SCHEDULERS[policy]()).summary()
    assert a == b


def test_from_pool_trace_matches_shared_engine_vectorized(workload):
    trace = get_trace("wits", 500, mean_rps=90)
    mat = from_pool_trace(trace, shares(workload))
    a = simulate(trace, workload, VECTOR_SCHEDULERS["paragon"]()).summary()
    b = simulate(mat, workload, VECTOR_SCHEDULERS["paragon"]()).summary()
    assert a == b


# ---------------------------------------------------------------------------
# Per-arch conservation through the matrix path.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(SCENARIO_ZOO))
def test_per_arch_conservation_every_tick(workload, name):
    """admitted == served_vm + served_burst + dropped + queued, per arch,
    after every tick, for every zoo scenario."""
    sc = get_scenario(name)
    arrivals = sc.build(len(workload), duration_s=300, mean_rps=60.0)
    sim = ServingSim(arrivals, workload)
    pol = VECTOR_SCHEDULERS["paragon"]()
    while not sim.done:
        sim.apply_pool(pol(sim.tick, sim.observe_pool()))
        c = sim.per_arch_counts()
        accounted = (
            c["served_vm"] + c["served_burst"] + c["dropped"]
            + c["expired_end"] + c["queued"]
        )
        np.testing.assert_allclose(c["arrived"], accounted, atol=1e-6)
    # and the per-arch totals agree with the pool ledger
    c = sim.per_arch_counts()
    assert sim.res.total_requests == pytest.approx(float(c["arrived"].sum()))
    assert sim.res.served_burst == pytest.approx(float(c["served_burst"].sum()))


def test_heterogeneous_monitor_sees_per_arch_bursts(workload):
    """One arch bursts, the rest stay flat: only the bursting arch's
    peak-to-median should blow up — exactly what share-scaling of a pool
    monitor can never express."""
    n, T = len(workload), 900
    arrivals = np.full((n, T), 20.0)
    arrivals[2, 450:480] = 200.0           # one flash crowd on arch 2
    sim = ServingSim(arrivals, workload)
    pol = VECTOR_SCHEDULERS["reactive"]()
    p2m_at_burst = None
    while not sim.done:
        obs = sim.observe_pool()
        if sim.tick == 500:
            p2m_at_burst = obs.peak_to_median.copy()
        sim.apply_pool(pol(sim.tick, obs))
    flat = [a for a in range(n) if a != 2]
    assert p2m_at_burst[2] > 5.0
    assert np.all(p2m_at_burst[flat] < 1.5)


def test_matrix_shape_mismatch_rejected(workload):
    with pytest.raises(AssertionError):
        ServingSim(np.ones((2, 100)), workload)   # 2 rows for 4 archs


# ---------------------------------------------------------------------------
# Trace replay: captured [A, T] matrices as first-class scenarios.
# ---------------------------------------------------------------------------
def test_replay_roundtrips_capture_exactly(tmp_path):
    """save_replay -> Scenario(kind="replay") -> build returns the
    captured matrix verbatim, and the spec JSON-round-trips."""
    captured = get_scenario("mmpp_bursts").build(4, duration_s=300,
                                                 mean_rps=70)
    path = str(tmp_path / "capture.npz")
    save_replay(path, captured)
    sc = Scenario("replayed", kind="replay", duration_s=300, mean_rps=70,
                  params={"path": path})
    np.testing.assert_array_equal(sc.build(4), captured)
    # replay is literal: a re-rolled episode seed replays the capture
    np.testing.assert_array_equal(sc.build(4, seed=sc.seed + 5), captured)
    sc2 = Scenario.from_json(sc.to_json())
    assert sc2 == sc
    np.testing.assert_array_equal(sc2.build(4), captured)


def test_replay_truncates_never_invents(tmp_path):
    captured = get_scenario("diurnal_phases").build(3, duration_s=200,
                                                    mean_rps=50)
    path = str(tmp_path / "cap.npz")
    save_replay(path, captured)
    short = Scenario("cut", kind="replay", duration_s=120,
                     params={"path": path}).build(3)
    np.testing.assert_array_equal(short, captured[:, :120])
    with pytest.raises(AssertionError):    # longer than the capture
        Scenario("long", kind="replay", duration_s=500,
                 params={"path": path}).build(3)
    with pytest.raises(AssertionError):    # wrong pool size
        Scenario("rows", kind="replay", duration_s=100,
                 params={"path": path}).build(5)


def test_replay_renormalizes_pool_mean(tmp_path):
    captured = get_scenario("flash_anti").build(4, duration_s=240,
                                                mean_rps=30)
    path = str(tmp_path / "cap.npz")
    save_replay(path, captured)
    mat = Scenario("scaled", kind="replay", duration_s=240, mean_rps=90,
                   params={"path": path, "renormalize": True}).build(4)
    assert mat.sum(axis=0).mean() == pytest.approx(90.0)
    # shape preserved up to one global scale
    np.testing.assert_allclose(
        mat / max(mat.max(), 1e-12), captured / max(captured.max(), 1e-12),
        atol=1e-12,
    )


def test_replay_drives_engine_and_env(workload, tmp_path):
    """A replayed scenario is a drop-in engine/RL-env workload source —
    closes the ROADMAP trace-replay item end to end."""
    from repro.core.rl import EnvConfig, PoolServingEnv

    captured = get_scenario("flash_correlated").build(
        len(workload), duration_s=150, mean_rps=60
    )
    path = str(tmp_path / "cap.npz")
    save_replay(path, captured)
    sc = Scenario("rp", kind="replay", duration_s=150, mean_rps=60,
                  params={"path": path})
    res = simulate(sc.build(len(workload)), workload,
                   VECTOR_SCHEDULERS["paragon"]())
    assert res.total_requests == pytest.approx(float(captured.sum()))
    env = PoolServingEnv(workload, EnvConfig(mean_rps=60, duration_s=150),
                         scenarios=[sc])
    env.reset()
    np.testing.assert_array_equal(env.sim.arrivals, captured)


# ---------------------------------------------------------------------------
# The monitor's order statistics on the device (the JAX engine's runner).
# ---------------------------------------------------------------------------
W_MON = LoadMonitor.window_s


def _monitor_streams(kind):
    """``[A, T]`` arrival streams that stress the sorted window."""
    rng = np.random.default_rng(17)
    if kind == "zoo_rows":
        return np.concatenate([
            SCENARIO_ZOO[n].build(4, duration_s=W_MON + 80, seed=i)
            for i, n in enumerate(("shared_berkeley", "flash_correlated",
                                   "mmpp_bursts"))
        ])
    if kind == "heavy_ties":
        return np.round(rng.gamma(0.5, 2.0, size=(8, W_MON + 150)))
    if kind == "all_zero":
        return np.zeros((3, W_MON + 20))
    if kind == "zero_median":
        # mostly zeros with spikes: the median sits at 0 while the peak does not
        x = np.zeros((4, W_MON + 40))
        x[:, ::7] = rng.integers(1, 9, size=x[:, ::7].shape)
        return x
    if kind.startswith("T="):
        T = W_MON + int(kind[2:])
        return np.round(rng.poisson(6.0, size=(5, T)) * rng.uniform(0.5, 2.0, size=(5, 1)))
    assert kind == "A=1"
    return rng.poisson(3.0, size=(1, W_MON + 60)).astype(np.float64)


@pytest.mark.parametrize("kind", ["zoo_rows", "heavy_ties", "all_zero", "zero_median",
                                  "T=-100", "T=0", "T=1", "A=1"])
def test_window_p2m_matches_the_monitor_bitwise(kind):
    """The runner's sorted-window pass gives the streaming monitor's
    peak-to-median ratio to the bit: growing windows, the full window,
    the first tick a sample leaves, ties, zero medians, one arch."""
    import jax

    from repro.core.sim import jax_engine as je

    arr = _monitor_streams(kind)
    _, _, want = pool_stats_trajectory(arr)
    with jax.enable_x64(True):
        got = np.asarray(jax.jit(je._window_p2m)(np.ascontiguousarray(arr.T)))
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got.view(np.int64), want.view(np.int64))
