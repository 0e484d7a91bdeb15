"""Plain reference of the serving-fleet simulation, for the cells'
correctness check.

An independent implementation of what the program's fleet engine
computes for a pool of model streams under a reserved tier plus a
serverless burst tier: one-second ticks of admit -> policy -> variant
swap -> provision -> serve -> burst offload -> abandon -> account, then
the end-of-trace sweep.  It reads every constant from the
configuration file (the profiled service table, the prices, the variant
catalog) and imports nothing but NumPy, so it can run in worker
processes that never touch the accelerator.

State is kept in the plainest form: queues and the provisioning
pipeline are age-ordered ``[A, W]`` arrays that shift left once a tick
(column 0 the oldest).  Serving oldest-first is a cumulative sum.

``dtype`` selects the float precision of every array and accumulator;
the configuration states float64, and float32 is the check's control.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

#: the load monitor's sliding window (ticks) and EWMA smoothing
MONITOR_WINDOW_S = 300
EWMA_ALPHA = 0.3
#: offloaded mass at or below this is cumsum residue, not requests
OFFLOAD_EPS = 1e-9

POLICY_DEFAULTS = {
    "paragon": dict(bursty_threshold=1.5, flat_cushion=1.1,
                    drain_horizon_s=5.0),
    "infaas_variant": dict(bursty_threshold=1.5, flat_cushion=1.1,
                           drain_horizon_s=5.0, up_util=0.55, down_util=0.9,
                           post_swap_util=0.75, queue_pressure_s=2.0,
                           cooldown_s=120),
}


# ---------------------------------------------------------------------------
# Load monitor: EWMA and the windowed peak / median, per stream.
# ---------------------------------------------------------------------------
def monitor_stats(arrivals: np.ndarray, window: int = MONITOR_WINDOW_S,
                  alpha: float = EWMA_ALPHA, chunk: int = 32):
    """``(ewma, p2m)``, each ``[T, A]``: the smoothed rate, and the peak
    over the median of the last ``window`` ticks (growing windows over
    the first ticks; 1 where the median is 0)."""
    A, T = arrivals.shape
    dt = arrivals.dtype
    ewma = np.empty((T, A), dtype=dt)
    e = arrivals[:, 0].copy()
    ewma[0] = e
    for t in range(1, T):
        e = alpha * arrivals[:, t] + (1 - alpha) * e
        ewma[t] = e
    peak = np.empty((A, T), dtype=dt)
    med = np.empty((A, T), dtype=dt)
    for t in range(min(window - 1, T)):
        peak[:, t] = arrivals[:, : t + 1].max(axis=1)
        med[:, t] = np.median(arrivals[:, : t + 1], axis=1)
    if T >= window:
        sw = np.lib.stride_tricks.sliding_window_view(arrivals, window, axis=1)
        for s in range(0, sw.shape[1], chunk):
            blk = sw[:, s: s + chunk]
            peak[:, window - 1 + s: window - 1 + s + blk.shape[1]] = blk.max(axis=2)
            med[:, window - 1 + s: window - 1 + s + blk.shape[1]] = np.median(blk, axis=2)
    p2m = np.where(med > 0, peak / np.where(med > 0, med, 1), 1).astype(dt)
    return ewma, np.ascontiguousarray(p2m.T)


# ---------------------------------------------------------------------------
# Age-ordered queues and pipelines.
# ---------------------------------------------------------------------------
def _shift(buf: np.ndarray) -> np.ndarray:
    """Everything one tick older: drop column 0, open an empty newest."""
    out = np.zeros_like(buf)
    out[:, :-1] = buf[:, 1:]
    return out


def _serve(q, capacity, late_mask):
    """Serve ``capacity[a]`` oldest-first; returns ``(q, served, late)``."""
    before = np.cumsum(q, axis=1) - q
    take = np.minimum(q, np.clip(capacity[:, None] - before, 0.0, None))
    return q - take, take.sum(axis=1), (take * late_mask).sum(axis=1)


def _cancel_newest(pipe, counts):
    """Remove up to ``counts[a]`` launches, newest (last column) first."""
    rev = pipe[:, ::-1]
    before = np.cumsum(rev, axis=1) - rev
    take = np.minimum(rev, np.clip(counts[:, None] - before, 0, None))
    return (rev - take)[:, ::-1]


# ---------------------------------------------------------------------------
# Policies.
# ---------------------------------------------------------------------------
def _paragon_target(o, p, thr):
    bursty = o["p2m"] >= p["bursty_threshold"]
    headroom = np.where(bursty, 1.0, p["flat_cushion"])
    demand = o["ewma"] + o["queue_len"] / p["drain_horizon_s"]
    return np.maximum(1, np.ceil(demand * headroom / thr)).astype(np.int64)


def _infaas_move(o, p, tick, last_move):
    cap = np.maximum(o["n_active"], 1) * o["thr"]
    backlog = o["queue_len"] - o["rate"]
    pressure = (o["util"] >= p["down_util"]) | (
        backlog > p["queue_pressure_s"] * cap)
    slack = (o["util"] <= p["up_util"]) & (backlog <= 1e-6)
    ready = (~o["in_flight"]) & (tick - last_move >= p["cooldown_s"])
    down = (pressure & ready & (o["cur"] > o["var_lo"])
            & (o["down_ratio"] > 1.0 + 1e-9))
    up = (slack & ~pressure & ready & (o["cur"] < o["var_n"] - 1)
          & (o["util"] / o["up_ratio"] <= p["post_swap_util"]))
    tgt = np.where(down, o["cur"] - 1, np.where(up, o["cur"] + 1, -1))
    return tgt.astype(np.int64), np.where(down | up, tick, last_move)


# ---------------------------------------------------------------------------
# The pool, from the configuration file.
# ---------------------------------------------------------------------------
def pool_tables(cfg: dict, dtype=np.float64) -> Dict[str, np.ndarray]:
    """Per-stream ``[A]`` / ``[A, V]`` tables for ``cfg["streams"]``
    streams cycling through ``cfg["models"]`` (stream ``i`` serves
    model ``i mod len(models)``)."""
    models = cfg["models"]
    A = int(cfg["streams"])
    prof = cfg["profiles"]
    idx = [models[i % len(models)] for i in range(A)]

    def col(key):
        return np.array([prof[m][key] for m in idx], dtype=np.float64)

    tab = {
        "thr": col("throughput_rps"), "chips": col("chips"),
        "lat_b1": col("latency_b1_s"), "cold_start": col("cold_start_s"),
        "quality": col("accuracy"),
        "strict_frac": np.full(A, float(cfg["strict_frac"])),
        "acc_floor": np.full(A, float(cfg.get("accuracy_floor") or 0.0)),
    }
    cat = cfg.get("catalog")
    if cat:
        vmax = max(len(cat[m]["variants"]) for m in models)
        for key, field in (("var_acc", "accuracy"), ("var_smult", "service_mult"),
                           ("var_cmult", "cost_mult"), ("var_lmult", "lat_mult")):
            rows = []
            for m in idx:
                vals = [v[field] for v in cat[m]["variants"]]
                rows.append(vals + [vals[-1]] * (vmax - len(vals)))
            tab[key] = np.array(rows, dtype=np.float64)
        for key, field in (("var_n", None), ("var_base", "base"),
                           ("var_lo", "floor_lo"),
                           ("var_cheapest", "floor_cheapest")):
            tab[key] = np.array(
                [len(cat[m]["variants"]) if field is None else cat[m][field]
                 for m in idx], dtype=np.int64)
    else:
        tab["var_acc"] = tab["quality"][:, None]
        tab["var_smult"] = np.ones((A, 1))
        tab["var_cmult"] = np.ones((A, 1))
        tab["var_lmult"] = np.ones((A, 1))
        tab["var_n"] = np.ones(A, dtype=np.int64)
        for key in ("var_base", "var_lo", "var_cheapest"):
            tab[key] = np.zeros(A, dtype=np.int64)
    return {k: (v.astype(dtype) if v.dtype.kind == "f" else v)
            for k, v in tab.items()}


# ---------------------------------------------------------------------------
# The simulation.
# ---------------------------------------------------------------------------
def simulate(arrivals: np.ndarray, cfg: dict, dtype=np.float64) -> dict:
    """Run one ``[A, T]`` arrival matrix under ``cfg``'s policy; returns
    per-stream flows, ledger totals and the final fleet."""
    fdt = np.dtype(dtype)
    f = fdt.type
    arr = np.asarray(arrivals, dtype=np.float64).astype(fdt)
    A, T = arr.shape
    tab = pool_tables(cfg, fdt)
    if len(tab["thr"]) != A:
        raise ValueError(f"{A} arrival rows for {len(tab['thr'])} streams")
    pr = cfg["pricing"]
    policy = cfg["policy"]
    params = dict(POLICY_DEFAULTS[policy], **cfg.get("policy_params", {}))
    variants = bool(cfg.get("catalog"))
    if policy == "infaas_variant" and not variants:
        raise ValueError("infaas_variant needs a catalog")

    res_chip_s = f(pr["reserved_chip_hour"] / 3600.0)
    burst_chip_s = f(pr["reserved_chip_hour"] / 3600.0 * pr["burst_premium"])
    fee = f(pr["burst_invocation_fee"])
    spinup = f(pr["burst_spinup_s"])
    idle_timeout = f(pr["burst_idle_timeout_s"])
    prov_lat = max(int(pr["reserved_provision_s"]), 1)
    swap_lat = max(int(pr["variant_swap_s"]), 1)
    slo = {"strict": float(cfg["slo_s"]["strict"]),
           "relaxed": float(cfg["slo_s"]["relaxed"])}

    ewma, p2m = monitor_stats(arr)

    # queues: window = abandon age (3 x SLO) + 2; column c has age W-1-c
    queues = {}
    for cls in ("strict", "relaxed"):
        W = int(3 * slo[cls]) + 2
        slack = np.maximum(0, (slo[cls] - tab["lat_b1"].astype(np.float64))
                           .astype(np.int64))
        ages = np.arange(W - 1, -1, -1)
        queues[cls] = {"q": np.zeros((A, W), dtype=fdt),
                       "late": (ages[None, :] > slack[:, None]).astype(fdt)}

    def variant_state(cur):
        g = lambda tab_key: np.take_along_axis(tab[tab_key], cur[:, None], 1)[:, 0]
        smult = g("var_smult")
        thr = tab["thr"] * smult
        chips = tab["chips"] * g("var_cmult")
        return {"acc": g("var_acc"), "smult": smult, "thr": thr, "chips": chips,
                "lat_b1": tab["lat_b1"] * g("var_lmult"),
                "cpr": (chips / thr) * burst_chip_s + fee}

    cur = tab["var_base"].copy()
    pending = np.full(A, -1, dtype=np.int64)
    ready_at = np.zeros(A, dtype=np.int64)
    last_move = np.full(A, -(10 ** 9), dtype=np.int64)
    vs = variant_state(cur)

    active = np.maximum(1, np.ceil(arr[:, 0] / vs["thr"])).astype(np.int64)
    pipe = np.zeros((A, prov_lat), dtype=np.int64)
    last_used = np.zeros(A, dtype=fdt)          # the burst pool starts warm
    last_util = np.zeros(A, dtype=fdt)

    zeros = lambda: np.zeros(A, dtype=fdt)
    flows = {k: zeros() for k in ("served_vm", "served_burst", "dropped",
                                  "expired_end", "violations", "acc_weight",
                                  "acc_violations", "cost_arch")}
    tot = {k: f(0) for k in ("cost_reserved", "cost_burst", "chip_seconds",
                             "chip_seconds_needed", "chip_seconds_over")}
    swaps = 0
    acc_floor_live = bool((tab["acc_floor"] > 0).any())

    for t in range(T):
        rate = arr[:, t]
        # -- admit
        n_strict = rate * tab["strict_frac"]
        for cls, n in (("strict", n_strict), ("relaxed", rate - n_strict)):
            q = _shift(queues[cls]["q"])
            q[:, -1] += n
            queues[cls]["q"] = q
        queue_len = queues["strict"]["q"].sum(axis=1) + queues["relaxed"]["q"].sum(axis=1)

        # -- observe (before due swaps land) and decide
        up = np.minimum(cur + 1, tab["var_n"] - 1)
        dn = np.maximum(cur - 1, 0)
        gs = lambda i: np.take_along_axis(tab["var_smult"], i[:, None], 1)[:, 0]
        obs = {
            "rate": rate, "ewma": ewma[t], "p2m": p2m[t],
            "queue_len": queue_len, "n_active": active, "thr": vs["thr"],
            "util": last_util, "cur": cur, "var_n": tab["var_n"],
            "var_lo": tab["var_lo"], "in_flight": pending >= 0,
            "up_ratio": gs(up) / vs["smult"], "down_ratio": gs(dn) / vs["smult"],
            "pending_ratio": np.where(
                pending >= 0, gs(np.maximum(pending, 0)) / vs["smult"], 1.0),
        }
        variant_target = None
        if policy == "paragon":
            target = _paragon_target(obs, params, obs["thr"])
        else:
            target = _paragon_target(
                obs, params, obs["thr"] * np.minimum(1.0, obs["pending_ratio"]))
            variant_target, last_move = _infaas_move(obs, params, t, last_move)

        # -- variant swaps: due ones land for this tick's serving, then
        # requests enter the one-deep pipeline
        if variants:
            done = (pending >= 0) & (ready_at <= t)
            if done.any():
                cur = np.where(done, pending, cur)
                pending = np.where(done, -1, pending)
                swaps += int(done.sum())
                vs = variant_state(cur)
            if variant_target is not None:
                req = np.minimum(variant_target, tab["var_n"] - 1)
                pending = np.where((req >= 0) & (req == cur), -1, pending)
                start = (req >= 0) & (req != cur) & (req != pending)
                pending = np.where(start, req, pending)
                ready_at = np.where(start, t + swap_lat, ready_at)

        # -- provision: launches from prov_lat ticks ago come online, then
        # grow or shrink toward the target (cancel newest launches first)
        active = active + pipe[:, 0]
        pipe = _shift(pipe)
        in_flight = active + pipe.sum(axis=1)
        grow = np.maximum(target - in_flight, 0)
        pipe[:, -1] += grow
        shrink = in_flight - target
        if (shrink > 0).any():
            cancel = np.clip(np.minimum(pipe.sum(axis=1), shrink), 0, None)
            pipe = _cancel_newest(pipe, cancel)
            active = np.where(shrink > 0,
                              np.minimum(active, np.maximum(target, 0)), active)

        # -- serve, strict first
        capacity = active * vs["thr"]
        qs, served_s, late_s = _serve(queues["strict"]["q"], capacity,
                                      queues["strict"]["late"])
        qr, served_r, late_r = _serve(queues["relaxed"]["q"], capacity - served_s,
                                      queues["relaxed"]["late"])
        queues["strict"]["q"], queues["relaxed"]["q"] = qs, qr
        served = served_s + served_r
        answered = served.copy()
        flows["served_vm"] += served
        flows["violations"] += late_s + late_r
        last_util = np.where(capacity > 0,
                             served / np.where(capacity > 0, capacity, 1.0),
                             1.0).astype(fdt)

        # -- burst offload: strict queues drain to the burst pool (both
        # policies offload strict traffic only)
        q = queues["strict"]["q"]
        counts = q.sum(axis=1)
        queues["strict"]["q"] = np.zeros_like(q)
        counts = np.where(counts <= OFFLOAD_EPS, 0.0, counts).astype(fdt)
        if counts.any():
            cold = (t - last_used) > idle_timeout
            lat_first = spinup + vs["lat_b1"] + cold * tab["cold_start"]
            lat_warm = spinup + vs["lat_b1"]
            first = np.minimum(counts, 1.0)
            viol = (first * (lat_first > slo["strict"])
                    + (counts - first) * (lat_warm > slo["strict"]))
            cost = vs["cpr"] * counts
            tot["cost_burst"] += cost.sum(dtype=fdt)
            flows["served_burst"] += counts
            flows["violations"] += viol
            flows["cost_arch"] += cost
            answered += counts
            last_used = np.where(counts > 0, t, last_used).astype(fdt)

        # -- abandon what aged past 3 x SLO unserved (answered, late)
        for cls in ("strict", "relaxed"):
            q = queues[cls]["q"]
            dropped = q[:, 0].copy()
            q[:, 0] = 0
            flows["dropped"] += dropped
            flows["violations"] += dropped
            answered += dropped

        # -- delivered accuracy
        flows["acc_weight"] += answered * vs["acc"]
        if acc_floor_live:
            flows["acc_violations"] += answered * (vs["acc"] < tab["acc_floor"] - 1e-12)

        # -- account
        chip_s = active * vs["chips"]
        tot["cost_reserved"] += chip_s.sum(dtype=fdt) * res_chip_s
        flows["cost_arch"] += chip_s * res_chip_s
        need = np.ceil(rate / vs["thr"]) * vs["chips"]
        tot["chip_seconds"] += chip_s.sum(dtype=fdt)
        tot["chip_seconds_needed"] += need.sum(dtype=fdt)
        tot["chip_seconds_over"] += np.maximum(chip_s - need, 0.0).sum(dtype=fdt)

    # end of trace: one tick later, whatever waits past its slack violates
    queued = np.zeros(A, dtype=fdt)
    for cls in ("strict", "relaxed"):
        q = _shift(queues[cls]["q"])
        late = (q * queues[cls]["late"]).sum(axis=1)
        flows["expired_end"] += late
        flows["violations"] += late
        queued += q.sum(axis=1) - late

    return {
        "flows": {**flows, "queued": queued, "arrived": arr.sum(axis=1)},
        "totals": {**tot, "cost_spot": f(0), "cost_harvest": f(0),
                   "cost_remote": f(0), "preemptions": 0,
                   "variant_swaps": swaps},
        "fleet": {"active": active, "pending": pipe.sum(axis=1),
                  "variant": cur},
    }


def run_cell(job: dict) -> dict:
    """Worker entry: ``job`` holds ``arrivals``, ``cfg`` and ``dtype``
    (a NumPy dtype name)."""
    return simulate(job["arrivals"], job["cfg"], np.dtype(job["dtype"]))
