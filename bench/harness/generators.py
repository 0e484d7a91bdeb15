"""Arrival generators of the benchmark: seeded ``[A, T]`` per-stream
request-rate matrices.

A frozen copy of the program's workload generators
(``repro.core.workloads.generators`` and the ``repro.core.traces``
twins they call) and of the ``Scenario.build`` composition rule, so
that a change to the program's workload subsystem cannot move the
benchmark's traffic.  The program receives only the matrices built
here.  NumPy only.

Every generator returns float64 rates; each row is scaled so that the
pool mean is ``mean_rps`` (the traces are scaled as a whole).
"""
from __future__ import annotations

from typing import Callable, Dict, Mapping, Optional, Sequence

import numpy as np


# ---------------------------------------------------------------------------
# Pool-trace twins (one shared [T] rate curve).
# ---------------------------------------------------------------------------
def _normalize(rate: np.ndarray, mean_rps: float) -> np.ndarray:
    rate = np.maximum(rate, 0.0)
    return rate * (mean_rps / max(rate.mean(), 1e-9))


def berkeley(duration_s: int, mean_rps: float, seed: int) -> np.ndarray:
    """Home-IP dial-up: a diurnal swell plus two evening flash crowds."""
    rng = np.random.default_rng(seed + 101)
    t = np.arange(duration_s)
    base = 1.0 + 0.55 * np.sin(2 * np.pi * t / duration_s - 0.7)
    for start, scale, tau in ((duration_s * 0.35, 1.7, 180.0),
                              (duration_s * 0.7, 1.3, 140.0)):
        base += scale * np.exp(-np.maximum(t - start, 0) / tau) * (t >= start)
    noise = rng.gamma(shape=24.0, scale=1 / 24.0, size=duration_s)
    return _normalize(base * noise, mean_rps)


def wiki(duration_s: int, mean_rps: float, seed: int) -> np.ndarray:
    """Wikipedia: a smooth, low-variance diurnal."""
    rng = np.random.default_rng(seed + 202)
    t = np.arange(duration_s)
    base = 1.0 + 0.18 * np.sin(2 * np.pi * t / duration_s) + 0.06 * np.sin(
        6 * np.pi * t / duration_s + 1.1
    )
    noise = rng.gamma(shape=120.0, scale=1 / 120.0, size=duration_s)
    return _normalize(base * noise, mean_rps)


TRACES: Dict[str, Callable] = {"berkeley": berkeley, "wiki": wiki}


# ---------------------------------------------------------------------------
# Per-stream generators.
# ---------------------------------------------------------------------------
def _weights(n_archs: int, weights: Optional[Sequence[float]]) -> np.ndarray:
    if weights is None:
        return np.full(n_archs, 1.0 / n_archs)
    w = np.asarray(weights, dtype=np.float64)
    return w / max(w.sum(), 1e-12)


def _normalize_pool(mat: np.ndarray, mean_rps: float,
                    weights: np.ndarray) -> np.ndarray:
    mat = np.maximum(mat, 0.0)
    row_mean = np.maximum(mat.mean(axis=1), 1e-9)
    return mat * (mean_rps * weights / row_mean)[:, None]


def pool_trace(n_archs, duration_s, mean_rps, seed, *, trace="berkeley",
               weights=None):
    """One shared trace fanned out by a static share."""
    share = _weights(n_archs, weights)
    tr = TRACES[trace](duration_s, mean_rps, seed)
    return share[:, None] * tr[None, :]


def diurnal(n_archs, duration_s, mean_rps, seed, *, amplitude=0.45,
            amp_jitter=0.4, phase_jitter=1.0, cycles=1.0, noise_shape=40.0,
            weights=None):
    """Per-stream diurnals with phase and amplitude jitter."""
    rng = np.random.default_rng(seed)
    w = _weights(n_archs, weights)
    t = np.arange(duration_s)
    phase = phase_jitter * rng.uniform(-np.pi, np.pi, n_archs)
    amp = amplitude * (1.0 + amp_jitter * rng.uniform(-1.0, 1.0, n_archs))
    base = 1.0 + amp[:, None] * np.sin(
        2 * np.pi * cycles * t[None, :] / duration_s + phase[:, None]
    )
    noise = rng.gamma(noise_shape, 1.0 / noise_shape, (n_archs, duration_s))
    return _normalize_pool(base * noise, mean_rps, w)


def flash_crowd(n_archs, duration_s, mean_rps, seed, *, mode="correlated",
                n_events=2, amplitude=3.0, tau_s=150.0, dip=0.6,
                noise_shape=30.0, weights=None):
    """Flash crowds: ``correlated`` hits a random half of the pool at
    once, ``anti`` spikes one stream while the others dip, ``solo``
    spikes one stream alone."""
    if mode not in ("correlated", "anti", "solo"):
        raise ValueError(f"unknown flash_crowd mode {mode!r}")
    rng = np.random.default_rng(seed)
    w = _weights(n_archs, weights)
    t = np.arange(duration_s, dtype=np.float64)
    mat = np.ones((n_archs, duration_s))
    for _ in range(n_events):
        start = float(rng.uniform(0.1, 0.8) * duration_s)
        amp = amplitude * (0.5 + rng.pareto(2.5))
        profile = np.exp(-np.maximum(t - start, 0.0) / tau_s) * (t >= start)
        if mode == "correlated":
            hit = rng.random(n_archs) < 0.5
            if not hit.any():
                hit[rng.integers(n_archs)] = True
            jitter = rng.uniform(0.6, 1.4, n_archs)
            mat += hit[:, None] * (amp * jitter)[:, None] * profile[None, :]
        else:
            a = int(rng.integers(n_archs))
            mat[a] += amp * profile
            if mode == "anti":
                others = np.arange(n_archs) != a
                mat[others] *= 1.0 - dip * profile[None, :]
    noise = rng.gamma(noise_shape, 1.0 / noise_shape, (n_archs, duration_s))
    return _normalize_pool(mat * noise, mean_rps, w)


def mmpp(n_archs, duration_s, mean_rps, seed, *, burst_mult=4.0,
         pareto_alpha=2.0, mean_quiet_s=400.0, mean_burst_s=60.0,
         noise_shape=25.0, weights=None):
    """Two-state Markov-modulated bursts with Pareto amplitudes."""
    rng = np.random.default_rng(seed)
    w = _weights(n_archs, weights)
    mat = np.ones((n_archs, duration_s))
    for a in range(n_archs):
        pos, bursting = 0, bool(rng.random() < 0.2)
        while pos < duration_s:
            mean_len = mean_burst_s if bursting else mean_quiet_s
            length = 1 + int(rng.geometric(1.0 / mean_len))
            if bursting:
                amp = 1.0 + min(burst_mult * rng.pareto(pareto_alpha),
                                6.0 * burst_mult)
                mat[a, pos: pos + length] = amp
            pos += length
            bursting = not bursting
    noise = rng.gamma(noise_shape, 1.0 / noise_shape, (n_archs, duration_s))
    return _normalize_pool(mat * noise, mean_rps, w)


def hotswap(n_archs, duration_s, mean_rps, seed, *, n_shifts=2,
            ramp_s=300.0, boost=4.0, pool_trace="wiki", weights=None):
    """Trending-model popularity shifts over a smooth pool trace."""
    rng = np.random.default_rng(seed)
    w0 = _weights(n_archs, weights)
    t = np.arange(duration_s, dtype=np.float64)
    logw = np.broadcast_to(np.log(np.maximum(w0, 1e-12))[:, None],
                           (n_archs, duration_s)).copy()
    for k in range(n_shifts):
        a = int(rng.integers(n_archs))
        t_k = (k + 1) / (n_shifts + 1) * duration_s * rng.uniform(0.8, 1.2)
        ramp = 1.0 / (1.0 + np.exp(-(t - t_k) / ramp_s))
        logw[a] += np.log(boost) * ramp
    wt = np.exp(logw)
    wt /= wt.sum(axis=0, keepdims=True)
    pool = TRACES[pool_trace](duration_s, mean_rps, seed)
    return wt * pool[None, :]


GENERATORS: Dict[str, Callable] = {
    "pool_trace": pool_trace,
    "diurnal": diurnal,
    "flash_crowd": flash_crowd,
    "mmpp": mmpp,
    "hotswap": hotswap,
}


def build(spec: Mapping, n_archs: int, duration_s: int, mean_rps: float,
          seed: int) -> np.ndarray:
    """One ``[n_archs, duration_s]`` realization of a scenario spec
    (``{"kind", "params", "seed"}``), re-rolled by ``seed``.

    ``kind == "compose"`` splices (``op="splice"``) or mixes
    (``op="sum"``) its children, each re-rolled by the same seed delta
    against the parent's spec seed."""
    if spec["kind"] != "compose":
        return GENERATORS[spec["kind"]](n_archs, duration_s, mean_rps, seed,
                                        **dict(spec.get("params", {})))
    params = spec["params"]
    delta = seed - int(spec.get("seed", 0))
    kids = params["children"]
    mats = [build(k, n_archs, duration_s, mean_rps, int(k.get("seed", 0)) + delta)
            for k in kids]
    if params.get("op", "sum") == "sum":
        w = params.get("weights")
        w = (np.full(len(kids), 1.0 / len(kids)) if w is None
             else np.asarray(w, dtype=np.float64))
        w = w / w.sum()
        return sum(wk * m for wk, m in zip(w, mats))
    splits = params.get("splits")
    if splits is None:
        splits = [(i + 1) / len(kids) for i in range(len(kids) - 1)]
    bounds = [0] + [int(round(s * duration_s)) for s in splits] + [duration_s]
    out = np.empty((n_archs, duration_s))
    for m, lo, hi in zip(mats, bounds[:-1], bounds[1:]):
        out[:, lo:hi] = m[:, lo:hi]
    return out
