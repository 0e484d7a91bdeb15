"""Batched pure-functional JAX twin of the tick engine.

The NumPy engine (:mod:`repro.core.sim.engine`) advances one tick per
Python call: admit -> provision -> serve -> offload -> drop -> account.
This module re-expresses that pipeline as a pure function over one flat
pytree of arrays (:class:`SimState`) so a whole trajectory compiles to a
single ``jax.lax.scan`` — and, with ``jax.vmap`` over a leading batch
axis, a whole (scenario x seed x policy-params) evaluation grid runs as
ONE dispatch instead of B Python tick loops.

Semantics are pinned to the NumPy engine, which stays the oracle
(``tests/test_jax_engine.py`` differential-fuzzes the two over the
scenario zoo).  Three representation changes make the port pure AND
fast without changing results:

* **Prefix-sum age buffers.**  The NumPy queues/pipelines are
  tick-indexed ring buffers of per-age counts; here every age buffer
  is stored as its *running prefix sum* along the age axis.  Queues
  are oldest-first (``S[:, j]`` = total mass in the ``j+1`` oldest
  buckets, so the last column is the queue total), pipelines
  newest-first (``P[:, j]`` = launches in the ``j+1`` newest cohorts).
  The payoff is that every order-dependent operation collapses to a
  rank-1 broadcast:

  - serving ``c`` oldest-first: the cumulative take through bucket
    ``j`` is ``min(S_j, c)``, so ``S' = max(S - c, 0)`` and ``served =
    min(S_last, c)``;
  - SLO lateness: the late buckets are exactly the oldest ``m`` (an
    age-contiguous prefix), so the late mass served is ``min(S[m-1],
    c)`` — a single gather;
  - aging is a column shift, grow / drop / drain are broadcast add /
    subtract / row-zero, and totals are the last column.

  No cumulative sum survives into the compiled tick — XLA's CPU scan
  kernel costs several times a copy over the same elements, and the
  naive count-space port spent most of its wall-clock there; in prefix
  form a queue tick is a handful of fused elementwise passes.

* **Cumulative-counter pipeline rings.**  Tier provisioning pipelines
  (up to 300 ticks deep for the remote tier) would pay an O(A*L) shift
  per tick even in prefix form, and shifting them was the ported
  tick's dominant cost.  Instead each pipeline stores a ring of
  *cumulative granted* counters: slot ``t mod L`` holds ``G_t``, the
  clipped running total of instances granted through tick ``t``.  A
  cohort launched at ``t`` matures at ``t + L``, exactly when its slot
  comes around again, so ready = ``G_{t-L} - G_{t-L-1}`` (the slot
  read minus last tick's), pending = ``G - G_{t-L}``, and the push is
  a single-slot write — all O(A).  Cancelling ``c`` newest-first
  clips the cumulative curve from the top: ``ring = min(ring, G - c)``
  — and because every stored value is ``<= G``, the same clip is a
  numeric no-op on cancel-free ticks, so it runs unconditionally as
  the only O(A*L) pass a pipeline pays per tick.

* **Everything-runs-every-tick.**  The NumPy engine lazily skips idle
  tiers and empty offloads; here every tier provisions, serves and
  accounts unconditionally — a 0-active tier contributes exact zeros,
  so the branchless form is identical (down to summary key presence,
  which per-tick liveness flags reconstruct).

* **Precomputed inputs.**  Every stochastic or stream-derived input is
  a pure function of ``(seed, tick)`` or of the arrival matrix alone.
  The harvest signal
  (:func:`~repro.core.sim.fleet.harvest_level_trajectory`), the spot
  reclaim uniforms (:func:`~repro.core.sim.fleet.spot_reclaim_uniforms`)
  and, for policies that read the order statistics, the monitor's EWMA
  are materialized host-side, bit-identical to the streams the NumPy
  engine consumes, and fed to the scan as per-tick inputs.  The
  monitor's windowed peak-to-median ratio is computed inside the
  runner, on the device, by a sorted-window pass over the rates before
  the tick scan (:func:`_window_p2m`) — bit-identical to
  :func:`~repro.core.load_monitor.pool_stats_trajectory`.

Everything runs under ``jax.enable_x64(True)`` (float64, like
the NumPy engine) without flipping the global flag — the float32 PPO
training stack is untouched.  Policies are in-scan twins of the
vectorized schedulers (:data:`JAX_POLICIES`); their parameters ride in
the traced statics pytree, so a parameter sweep vmaps without
recompiling and one trace serves every workload of the same shape.
"""
from __future__ import annotations

import warnings
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.hardware import PRICING, FleetPricing
from repro.core.load_monitor import LoadMonitor
from repro.core.rl.obs import (
    pool_features_arrays,
    procurement_targets_arrays,
    variant_targets_arrays,
)
from repro.core.schedulers import (
    accuracy_floor_move_arrays,
    infaas_variant_move_arrays,
    swap_aware_target_arrays,
)
from repro.core.rl.policy import (
    load_policy_checkpoint,
    _fallback_params,
    policy_logits,
)
from repro.core.sim import telemetry
from repro.core.sim.engine import ServingSim
from repro.core.sim.fleet import (
    BINOMIAL_KMAX,
    harvest_level_trajectory,
    spot_reclaim_uniforms,
)
from repro.core.sim.types import ArchLoad

__all__ = [
    "SimState",
    "JAX_POLICIES",
    "binomial_from_uniform_jnp",
    "build_sim_inputs",
    "make_runner",
    "note_runner_use",
    "run_scenario",
    "run_grid",
    "runner_trace_count",
]


# ---------------------------------------------------------------------------
# State pytree.
# ---------------------------------------------------------------------------
class SimState(NamedTuple):
    """All engine / tier / queue / monitor state for one tick, flat.

    ``*_buf`` are ``[A, W]`` oldest-first queue *prefix sums* (column
    ``j`` totals the ``j+1`` oldest age buckets; the last column is the
    queue total).  Each tier pipeline is a cumulative-counter ring
    (see the module docstring): ``*_ring [A, L]`` holds the clipped
    cumulative granted count by launch slot, ``*_cum [A]`` the current
    cumulative total and ``*_mat [A]`` the cumulative matured total.
    """

    qs_buf: Any          # [A, Ws] strict queue prefix mass (f64)
    qr_buf: Any          # [A, Wr] relaxed queue prefix mass (f64)
    res_active: Any      # [A]     reserved instances (i64)
    res_ring: Any        # [A, Lr] cumulative grants by launch slot (i32)
    res_cum: Any         # [A]     cumulative granted (i32)
    res_mat: Any         # [A]     cumulative matured (i32)
    spot_active: Any
    spot_ring: Any
    spot_cum: Any
    spot_mat: Any
    harv_active: Any
    harv_ring: Any
    harv_cum: Any
    harv_mat: Any
    rem_active: Any
    rem_ring: Any
    rem_cum: Any
    rem_mat: Any
    burst_last_used: Any  # [A] last tick the burst pool saw each arch
    last_util: Any        # [A] previous tick's utilization (policy obs)
    last_viol: Any        # [A] previous tick's violation delta
    prev_rate: Any        # [A] previous tick's arrivals (RL trend feature)
    ewma: Any = None      # [A] in-carry EWMA (None when fed via xs)
    # lazy-ring sliding-window-min state, per tier (None on the eager
    # path): [A, L] per-tick event minima, [A, L] previous-block suffix
    # minima, [A] current-block running min — see _tier_set_target_lazy
    res_ehist: Any = None
    res_sufmin: Any = None
    res_bmin: Any = None
    spot_ehist: Any = None
    spot_sufmin: Any = None
    spot_bmin: Any = None
    harv_ehist: Any = None
    harv_sufmin: Any = None
    harv_bmin: Any = None
    rem_ehist: Any = None
    rem_sufmin: Any = None
    rem_bmin: Any = None
    # model-variant swap pipeline (None on catalog-free runs): the NumPy
    # SwapPipeline's (current, pending, ready_at) triple — at most one
    # swap per arch is ever in flight, so the ISSUE's "ring" collapses
    # to a depth-1 slot and every op is O(A)
    var_cur: Any = None       # [A] active variant index (i64)
    var_pending: Any = None   # [A] in-flight swap target, -1 = none (i64)
    var_ready: Any = None     # [A] tick the in-flight swap matures (i64)
    var_last_move: Any = None  # [A] variant-policy cooldown state (i64)


# ---------------------------------------------------------------------------
# Primitive ops (exact twins of the NumPy engine's steps).
# ---------------------------------------------------------------------------
def binomial_from_uniform_jnp(n, p, u):
    """Traceable twin of :func:`repro.core.sim.fleet.binomial_from_uniform`.

    Identical inverse-CDF walk, identical :data:`BINOMIAL_KMAX` cap —
    the early exit of the NumPy loop never changes the count (the
    ``u >= cdf`` indicator is monotone in the walk), so a bounded
    ``lax.while_loop`` reproduces it exactly.
    """
    n = jnp.asarray(n)
    u = jnp.asarray(u, dtype=jnp.float64)
    p = jnp.asarray(p, dtype=jnp.float64)
    pc = jnp.clip(p, 1e-12, 1.0 - 1e-12)   # walk-safe; edges handled below
    q = 1.0 - pc
    nf = n.astype(jnp.float64)
    pmf0 = q ** nf
    k0 = (u >= pmf0).astype(n.dtype)

    def cond(c):
        j, _, cdf, _ = c
        return (j <= BINOMIAL_KMAX) & (u >= cdf).any()

    def body(c):
        j, pmf, cdf, k = c
        jf = j.astype(jnp.float64)
        pmf = jnp.maximum(pmf * ((nf - (jf - 1.0)) / jf) * (pc / q), 0.0)
        cdf = cdf + pmf
        k = k + (u >= cdf).astype(n.dtype)
        return j + 1, pmf, cdf, k

    j0 = jnp.asarray(1, dtype=jnp.int64)
    _, _, _, k = lax.while_loop(cond, body, (j0, pmf0, pmf0, k0))
    k = jnp.minimum(k, n)
    zero = jnp.zeros_like(n)
    return jnp.where(p <= 0.0, zero, jnp.where(p >= 1.0, n, k))


def _age_queue(S):
    """One tick of queue aging: every bucket gets one tick older.  In
    prefix form that is a left shift — the falling-off oldest bucket is
    empty by construction (the drop step subtracted its prefix last
    tick, zeroing column 0 exactly), so every prefix already excludes
    it and the total (last column, duplicated) is preserved."""
    return jnp.concatenate([S[:, 1:], S[:, -1:]], axis=1)


def _serve(S, capacity, n_late):
    """Oldest-first serve from a prefix queue.  ``n_late[a]`` counts how
    many of the oldest buckets are past arch ``a``'s slack (lateness is
    always an age-contiguous prefix, so one gather scores it).
    Returns ``(S, served, late)``."""
    served = jnp.minimum(S[:, -1], capacity)
    late = jnp.minimum(_late_mass(S, n_late), capacity)
    S = jnp.maximum(S - capacity[:, None], 0.0)
    return S, served, late


def _late_mass(S, n_late):
    """Mass in the ``n_late[a]`` oldest buckets of a prefix queue (also
    the end-of-trace expired sweep)."""
    idx = jnp.clip(n_late - 1, 0, S.shape[1] - 1)
    picked = jnp.take_along_axis(S, idx[:, None], axis=1)[:, 0]
    return jnp.where(n_late > 0, picked, 0.0)


class _Pipe(NamedTuple):
    """A tier's cumulative-counter pipeline ring (module docstring)."""

    ring: Any   # [A, L] clipped cumulative grants by launch slot (i32)
    cum: Any    # [A]    cumulative granted, post-cancel (i32)
    mat: Any    # [A]    cumulative matured (i32)


class _LazyPipe(NamedTuple):
    """A pipeline ring with *lazy* cancel clips.

    The eager :class:`_Pipe` keeps every slot ``<= cum`` by running a
    full ``min(ring, cum)`` pass on every cancel-capable tick — an
    O(A*L) read+write that dominates the whole scan at fleet scale
    (the remote ring alone is 300 columns).  This variant stores the
    raw slot writes and reconstructs the clip at read time: the value
    a read needs is ``min(G_s, min of every cum the tier passed
    through between write and read)`` — a sliding-window minimum over
    the cum event stream with window L, maintained with the standard
    two-block decomposition:

    * ``ehist [A, L]``: each tick's event minimum (entry cum ∧ exit
      cum), written at its slot — O(A) per tick;
    * ``bmin [A]``: running minimum of the current block's events,
      reset when the slot wraps to 0;
    * ``sufmin [A, L]``: suffix minima of the *previous* block's
      events, recomputed once per L ticks (a ``lax.cond`` whose branch
      runs O(A*L·logL) — amortized O(A·logL) per tick).

    At a read of slot ``p`` the window ``(t-L, t)`` splits exactly into
    the previous block's suffix from ``p+1`` plus the current block —
    ``min(sufmin[p+1], bmin)`` — so the read is O(A) and the per-tick
    ring cost collapses to two single-slot writes.  Counters are
    integers, so the lazy and eager forms are bit-identical; the lazy
    form is only wired into non-batched runners (under ``vmap`` the
    block-boundary ``cond`` would decay to ``select`` and pay the
    suffix recompute every tick)."""

    ring: Any     # [A, L] RAW cumulative grants by launch slot (i32)
    cum: Any      # [A]    cumulative granted, post-cancel (i32)
    mat: Any      # [A]    cumulative matured (i32)
    ehist: Any    # [A, L] per-tick event minima by slot (i32)
    sufmin: Any   # [A, L] previous block's suffix minima (i32)
    bmin: Any     # [A]    current block's running minimum (i32)


_I32_MAX = np.int32(np.iinfo(np.int32).max)


def _pipe_cancel(p, counts):
    """Cancel up to ``counts[a]`` in-flight launches, newest first.

    Eagerly: clipping the cumulative curve from the top eats the most
    recent cohorts first; every stored slot is ``<= cum``, so on
    cancel-free rows the clip is a numeric no-op — the op runs
    unconditionally.  Lazily: the cum drop alone records the cancel;
    reads recover the clip from the window minimum."""
    cancel = jnp.minimum(counts, p.cum - p.mat).astype(p.cum.dtype)
    cum = p.cum - cancel
    if isinstance(p, _LazyPipe):
        return p._replace(cum=cum)
    return _Pipe(jnp.minimum(p.ring, cum[:, None]), cum, p.mat)


def _tier_set_target(active, p, target, slot):
    """One tier tick on a pipeline ring: admit the cohort maturing at
    this tick's slot, then grow or shrink toward ``target`` (cancel
    in-flight newest-first before releasing active) —
    ``ResourceTier.set_target`` branchless and O(A) except the cancel
    clip.  ``slot`` is ``t mod L`` (a traced scalar): the slot written
    L ticks ago (or the initial 0) is exactly the cohort maturing now,
    and the write at the end stores this tick's cumulative total for
    tick ``t + L``."""
    if isinstance(p, _LazyPipe):
        return _tier_set_target_lazy(active, p, target, slot)
    v = lax.dynamic_slice_in_dim(p.ring, slot, 1, axis=1)[:, 0]
    ready = (v - p.mat).astype(active.dtype)
    active = active + ready
    pending = (p.cum - v).astype(active.dtype)
    in_flight = active + pending
    grow = jnp.maximum(target - in_flight, 0)
    shrink = in_flight - target
    cancel = jnp.where(shrink > 0, jnp.minimum(pending, shrink), 0)
    cum = p.cum + grow.astype(p.cum.dtype) - cancel.astype(p.cum.dtype)
    ring = jnp.minimum(p.ring, cum[:, None])
    ring = lax.dynamic_update_slice_in_dim(ring, cum[:, None], slot, axis=1)
    active = jnp.where(
        shrink > 0, jnp.minimum(active, jnp.maximum(target, 0)), active
    )
    return active, _Pipe(ring, cum, v)


def _tier_set_target_lazy(active, p: _LazyPipe, target, slot):
    """:func:`_tier_set_target` against a :class:`_LazyPipe` — same
    integer results, O(A) per tick (see the class docstring)."""
    L = p.ring.shape[1]
    # block boundary: the just-completed block becomes "previous" —
    # recompute its suffix minima, reset the running block min
    sufmin, bmin = lax.cond(
        slot == 0,
        lambda: (
            lax.associative_scan(jnp.minimum, p.ehist, reverse=True, axis=1),
            jnp.full_like(p.bmin, _I32_MAX),
        ),
        lambda: (p.sufmin, p.bmin),
    )
    nxt = jnp.minimum(slot + 1, L - 1)
    suf = lax.dynamic_slice_in_dim(sufmin, nxt, 1, axis=1)[:, 0]
    window = jnp.minimum(jnp.where(slot + 1 < L, suf, _I32_MAX), bmin)
    raw = lax.dynamic_slice_in_dim(p.ring, slot, 1, axis=1)[:, 0]
    v = jnp.minimum(jnp.minimum(raw, window), p.cum)
    ready = (v - p.mat).astype(active.dtype)
    active = active + ready
    pending = (p.cum - v).astype(active.dtype)
    in_flight = active + pending
    grow = jnp.maximum(target - in_flight, 0)
    shrink = in_flight - target
    cancel = jnp.where(shrink > 0, jnp.minimum(pending, shrink), 0)
    entry_cum = p.cum
    cum = entry_cum + grow.astype(entry_cum.dtype) - cancel.astype(
        entry_cum.dtype
    )
    ring = lax.dynamic_update_slice_in_dim(p.ring, cum[:, None], slot, axis=1)
    # this tick's event minimum: the lowest cum any later read's window
    # must see from this tick (entry covers the begin-tick cancel)
    e = jnp.minimum(entry_cum, cum)
    ehist = lax.dynamic_update_slice_in_dim(p.ehist, e[:, None], slot, axis=1)
    bmin = jnp.minimum(bmin, e)
    active = jnp.where(
        shrink > 0, jnp.minimum(active, jnp.maximum(target, 0)), active
    )
    return active, _LazyPipe(ring, cum, v, ehist, sufmin, bmin)


def _spot_begin(active, p: _Pipe, u, p_reclaim):
    """``SpotTier.begin_tick``: i.i.d. reclaims on active instances and
    in-flight launches (cancelled newest-first), from this tick's
    precomputed uniform pair."""
    reclaimed = binomial_from_uniform_jnp(active, p_reclaim, u[0])
    active = active - reclaimed
    lost = binomial_from_uniform_jnp(p.cum - p.mat, p_reclaim, u[1])
    p = _pipe_cancel(p, lost)
    return active, p, reclaimed.sum() + lost.sum()


def _harvest_begin(active, p: _Pipe, ceiling):
    """``HarvestVMTier.begin_tick``: evict active above the granted
    ceiling (correlated across the pool), cancel in-flight overflow
    newest-first."""
    evicted = jnp.maximum(active - ceiling, 0)
    active = active - evicted
    over = jnp.maximum(active + (p.cum - p.mat) - ceiling, 0)
    p = _pipe_cancel(p, over)
    return active, p, evicted.sum()


def _offload(S, mask, last_used, t, slo_s, st):
    """``BurstTier.offload`` of one class's drained queues: drain the
    masked archs, zero sub-epsilon cumsum residue in the offload counts
    (the queue rows are emptied regardless), score first-invocation
    cold starts, bill per request."""
    counts = S[:, -1] * mask
    counts = jnp.where(counts <= 1e-9, 0.0, counts)
    S = S * (~mask)[:, None]
    cold = (t - last_used) > st["idle_timeout"]
    lat_first = st["spinup"] + st["lat_b1"] + cold * st["cold_start"]
    lat_warm = st["spinup"] + st["lat_b1"]
    first = jnp.minimum(counts, 1.0)
    viol = first * (lat_first > slo_s) + (counts - first) * (lat_warm > slo_s)
    cost_arch = st["burst_cpr"] * counts
    last_used = jnp.where(counts > 0, t.astype(last_used.dtype), last_used)
    return S, counts, viol, cost_arch, last_used


# ---------------------------------------------------------------------------
# In-scan policies: twins of the vectorized schedulers.  Each maps
# ``(params, obs, key) -> (action dict, extras dict)`` where obs is a
# dict of [A] arrays (the traced PoolObs) and the action dict carries
# ``target / offload / spot / harvest / remote`` integer arrays.
# ---------------------------------------------------------------------------
_OFFLOAD_SLACK_AWARE = 2


def _scale_target(throughput, demand, headroom=1.0):
    return jnp.maximum(1, jnp.ceil(demand * headroom / throughput)).astype(
        jnp.int64
    )


def _no_action(like):
    return jnp.zeros_like(like)


def _pol_reactive(params, obs, key):
    tgt = _scale_target(obs["throughput"], obs["ewma_rate"])
    z = _no_action(tgt)
    return dict(target=tgt, offload=z, spot=z, harvest=z, remote=z), {}


def _pol_paragon(params, obs, key):
    bursty = obs["peak_to_median"] >= params["bursty_threshold"]
    headroom = jnp.where(bursty, 1.0, params["flat_cushion"])
    demand = obs["ewma_rate"] + obs["queue_len"] / params["drain_horizon_s"]
    tgt = _scale_target(obs["throughput"], demand, headroom)
    z = _no_action(tgt)
    off = jnp.full_like(tgt, _OFFLOAD_SLACK_AWARE)
    return dict(target=tgt, offload=off, spot=z, harvest=z, remote=z), {}


def _pol_portfolio(params, obs, key):
    thr = obs["throughput"]
    demand = obs["ewma_rate"] + obs["queue_len"] / params["drain_horizon_s"]
    floor = _scale_target(thr, demand, params["strict_share"])
    remote = (
        params["remote_frac"] * (1 - params["strict_share"])
        * obs["ewma_rate"] / thr
    ).astype(jnp.int64)
    residual = jnp.maximum(0.0, demand - (floor + remote) * thr)
    h_frac = jnp.minimum(
        jnp.maximum(obs["harvest_level"] - params["harvest_margin"], 0.0),
        params["harvest_max_frac"],
    )
    h_want = jnp.ceil(residual * h_frac * params["harvest_buffer"] / thr)
    harvest = jnp.minimum(h_want, obs["harvest_ceiling"]).astype(jnp.int64)
    spot_resid = jnp.maximum(0.0, residual - harvest * thr)
    spot = jnp.ceil(spot_resid * params["spot_buffer"] / thr).astype(jnp.int64)
    off = jnp.full_like(floor, _OFFLOAD_SLACK_AWARE)
    return dict(
        target=floor, offload=off, spot=spot, harvest=harvest, remote=remote
    ), {}


def _net_forward(net, feats):
    """The PPO net's forward pass (policy head via the shared
    :func:`policy_logits` expression, value head alongside)."""
    h = jnp.tanh(feats @ net["torso1"]["w"] + net["torso1"]["b"])
    h = jnp.tanh(h @ net["torso2"]["w"] + net["torso2"]["b"])
    logits = h @ net["pi"]["w"] + net["pi"]["b"]
    value = (h @ net["v"]["w"] + net["v"]["b"])[..., 0]
    return logits, value


def _rl_action(params, obs, actions):
    target, offload, spot, vmove = procurement_targets_arrays(
        actions,
        ewma_rate=obs["ewma_rate"],
        queue_strict=obs["queue_strict"],
        queue_relaxed=obs["queue_relaxed"],
        throughput=obs["throughput"],
        n_spot=obs["n_spot"],
        n_spot_pending=obs["n_spot_pending"],
        xp=jnp,
    )
    z = _no_action(target)
    # the 3-way variant head, decoded exactly like the host env path
    # (procurement_action): on catalog-free runs _tick never reads the
    # "variant" entry and every step clips to the hold code anyway
    variant = variant_targets_arrays(
        obs["active_variant"], obs["n_variants"], vmove, xp=jnp
    )
    return dict(target=target, offload=offload, spot=spot, harvest=z,
                remote=z, variant=variant)


def _pol_rl_greedy(params, obs, key):
    """``RLPoolPolicy(greedy=True)`` inside the scan: deterministic
    argmax over the checkpoint net's logits (the parity-testable form —
    the stochastic form needs a key stream and lives in the rollout
    collector's ``rl_sample``)."""
    feats = pool_features_arrays(
        obs, obs["prev_rate"],
        rate_scale=params["rate_scale"], fleet_scale=params["fleet_scale"],
        xp=jnp,
    )
    logits = policy_logits(params["net"], feats, xp=jnp)
    actions = jnp.argmax(logits, axis=-1)
    return _rl_action(params, obs, actions), {}


def _pol_rl_sample(params, obs, key):
    """Stochastic PPO policy with rollout extras — what
    ``collect_rollouts_jax`` scans: sampled actions, logp, value and the
    feature matrix come back per tick, exactly the buffers the host
    rollout loop fills."""
    feats = pool_features_arrays(
        obs, obs["prev_rate"],
        rate_scale=params["rate_scale"], fleet_scale=params["fleet_scale"],
        xp=jnp,
    )
    logits, value = _net_forward(params["net"], feats)
    actions = jax.random.categorical(key, logits)
    logp = jnp.take_along_axis(
        jax.nn.log_softmax(logits), actions[:, None], axis=1
    )[:, 0]
    extras = {"obs": feats, "action": actions, "logp": logp, "value": value}
    return _rl_action(params, obs, actions), extras


def _pol_infaas_variant(params, obs, key):
    """In-scan twin of ``VectorInfaasVariantPolicy``: Paragon offload +
    swap-aware sizing + the INFaaS up/down move, all through the shared
    ``*_arrays`` expressions (``core/schedulers.py``) so the dict,
    vector and scan forms cannot drift.  The per-arch cooldown state
    (``_last_move``) rides in the scan carry (``SimState.var_last_move``)
    and comes back through the action dict."""
    tgt = swap_aware_target_arrays(
        obs, bursty_threshold=params["bursty_threshold"],
        flat_cushion=params["flat_cushion"],
        drain_horizon_s=params["drain_horizon_s"], xp=jnp,
    )
    variant, last_move = infaas_variant_move_arrays(
        obs, obs["tick"], obs["variant_last_move"],
        up_util=params["up_util"], down_util=params["down_util"],
        post_swap_util=params["post_swap_util"],
        queue_pressure_s=params["queue_pressure_s"],
        cooldown_s=params["cooldown_s"], xp=jnp,
    )
    z = _no_action(tgt)
    off = jnp.full_like(tgt, _OFFLOAD_SLACK_AWARE)
    return dict(target=tgt, offload=off, spot=z, harvest=z, remote=z,
                variant=variant, variant_last_move=last_move), {}


def _pol_accuracy_floor(params, obs, key):
    """In-scan twin of ``VectorAccuracyFloorPolicy``: swap-aware sizing
    + move to the cheapest floor-satisfying variant."""
    tgt = swap_aware_target_arrays(
        obs, bursty_threshold=params["bursty_threshold"],
        flat_cushion=params["flat_cushion"],
        drain_horizon_s=params["drain_horizon_s"], xp=jnp,
    )
    z = _no_action(tgt)
    off = jnp.full_like(tgt, _OFFLOAD_SLACK_AWARE)
    return dict(target=tgt, offload=off, spot=z, harvest=z, remote=z,
                variant=accuracy_floor_move_arrays(obs, xp=jnp)), {}


class JaxPolicy(NamedTuple):
    apply: Callable            # (params, obs, key) -> (actions, extras)
    needs_stats: bool          # True: policy reads peak_to_median
    needs_key: bool            # True: per-tick PRNG keys enter the scan
    default_params: Callable   # () -> params pytree


def _rl_default_params() -> dict:
    params, meta = load_policy_checkpoint()
    if params is None:
        params = _fallback_params(0)
    return {
        "net": params,
        "rate_scale": float(meta.get("rate_scale", 100.0)),
        "fleet_scale": float(meta.get("fleet_scale", 10.0)),
    }


#: in-scan twins of the vectorized schedulers, by registry name
JAX_POLICIES: Dict[str, JaxPolicy] = {
    "reactive": JaxPolicy(_pol_reactive, False, False, lambda: {}),
    "paragon": JaxPolicy(
        _pol_paragon, True, False,
        lambda: dict(bursty_threshold=1.5, flat_cushion=1.1,
                     drain_horizon_s=5.0),
    ),
    "portfolio": JaxPolicy(
        _pol_portfolio, False, False,
        lambda: dict(drain_horizon_s=5.0, strict_share=0.25, remote_frac=0.3,
                     harvest_margin=0.15, harvest_max_frac=0.8,
                     harvest_buffer=1.1, spot_buffer=1.25),
    ),
    "rl_pool": JaxPolicy(_pol_rl_greedy, True, False, _rl_default_params),
    "rl_sample": JaxPolicy(_pol_rl_sample, True, True, _rl_default_params),
    "infaas_variant": JaxPolicy(
        _pol_infaas_variant, True, False,
        lambda: dict(bursty_threshold=1.5, flat_cushion=1.1,
                     drain_horizon_s=5.0, up_util=0.55, down_util=0.9,
                     post_swap_util=0.75, queue_pressure_s=2.0,
                     cooldown_s=120),
    ),
    "accuracy_floor": JaxPolicy(
        _pol_accuracy_floor, True, False,
        lambda: dict(bursty_threshold=1.5, flat_cushion=1.1,
                     drain_horizon_s=5.0),
    ),
}


# ---------------------------------------------------------------------------
# The tick function.
# ---------------------------------------------------------------------------
#: the monitor's smoothing constant, hoisted once (a Python float is a
#: trace-time constant — no statics traffic)
_EWMA_ALPHA = float(LoadMonitor.ewma_alpha)


def _pipe_of(state: SimState, pre: str, lazy: bool):
    """A tier's pipeline view over the flat state, eager or lazy."""
    ring = getattr(state, pre + "_ring")
    cum = getattr(state, pre + "_cum")
    mat = getattr(state, pre + "_mat")
    if lazy:
        return _LazyPipe(
            ring, cum, mat,
            getattr(state, pre + "_ehist"),
            getattr(state, pre + "_sufmin"),
            getattr(state, pre + "_bmin"),
        )
    return _Pipe(ring, cum, mat)


def _window_p2m(rate):
    """The monitor's windowed peak-to-median ratio, ``[T, A] -> [T, A]``,
    bit-identical to the ``p2m`` of
    :func:`~repro.core.load_monitor.pool_stats_trajectory`.

    A ``lax.scan`` over ticks carries each arch's window sorted along
    its major axis, ``[W, A]``, padded with ``+inf`` while it fills.
    A tick deletes the leaving sample ``rate[t - W]`` (``+inf`` before
    the window is full: one of the pads) and inserts the arriving one;
    both positions are compare-and-count reductions over ``W`` and the
    move is three selects against the window shifted by one row, so
    nothing depends on the data but the values.  Peak and median are
    rows of the first ``f = min(t + 1, W)``: ``f - 1``, and the mean of
    ``(f - 1) // 2`` and ``f // 2`` as ``np.median`` takes it."""
    W = LoadMonitor.window_s
    A = rate.shape[1]
    with jax.named_scope("sim.monitor"):
        pad = jnp.full((1, A), jnp.inf, rate.dtype)
        row = jnp.arange(W, dtype=jnp.int32)[:, None]

        def step(win, x):
            t, new = x
            out = jnp.where(
                t >= W,
                lax.dynamic_index_in_dim(rate, jnp.maximum(t - W, 0),
                                         keepdims=False),
                jnp.inf,
            )
            # delete at the first copy of `out`; insert where `new` goes
            # once `out` has left (it was counted iff out < new)
            p = jnp.sum(win < out, axis=0, dtype=jnp.int32)
            q = jnp.sum(win < new, axis=0, dtype=jnp.int32) - (out < new)
            up = jnp.concatenate([win[1:], pad])       # row j holds j + 1
            down = jnp.concatenate([pad, win[:-1]])    # row j holds j - 1
            win = jnp.where(
                row == q, new,
                jnp.where((row >= p) & (row < q), up,
                          jnp.where((row > q) & (row <= p), down, win)),
            )
            f = jnp.minimum(t + 1, W)
            peak = lax.dynamic_index_in_dim(win, f - 1, keepdims=False)
            med = 0.5 * (lax.dynamic_index_in_dim(win, (f - 1) // 2, keepdims=False)
                         + lax.dynamic_index_in_dim(win, f // 2, keepdims=False))
            return win, jnp.where(med > 0, peak / jnp.where(med > 0, med, 1.0), 1.0)

        win0 = jnp.full((W, A), jnp.inf, rate.dtype)
        _, p2m = lax.scan(step, win0, (jnp.arange(rate.shape[0]), rate))
    return p2m


def _gather_v(table, idx):
    """Row-wise gather from a padded ``[A, V]`` catalog table at ``[A]``
    indices (the scan form of ``np.take_along_axis(table, idx[:, None],
    1)[:, 0]``)."""
    return jnp.take_along_axis(table, idx[:, None], axis=1)[:, 0]


def _tick(state: SimState, xs: dict, st: dict, policy_apply,
          ewma_in_carry: bool = False, lazy_rings: bool = False,
          variants: bool = False):
    """One engine tick, pure: ``(state, inputs) -> (state, metrics)``.

    Mirrors ``ServingSim.observe_pool`` + ``_step`` operation for
    operation; see the module docstring for why the branchless form is
    exact.  With ``ewma_in_carry`` the monitor's EWMA recurrence runs
    inside the scan (same float64 expression, same operation order as
    :func:`_ewma_trajectory` — bit-identical) instead of arriving as a
    host-precomputed ``[T, A]`` input.

    ``variants`` is a trace-time switch for the model-variant axis: when
    False (catalog-free) none of the swap machinery is traced, so the
    compiled graph is IDENTICAL to the variant-blind engine's — base
    runs stay bit-for-bit what they were.  When True the tick follows
    the NumPy ordering exactly: the observation gathers at the PRE-pop
    active variant, due swaps land before serving (the arch serves this
    tick at the NEW rate), new requests enter the depth-1 pipeline after
    the pop, and serving / burst billing / accuracy / chip accounting
    all gather at the POST-pop variant."""
    with jax.named_scope("scan.observe"):
        t = xs["t"]
        rate = xs["rate"]
        A = rate.shape[0]
        if ewma_in_carry:
            # first observe seeds the EWMA with the raw rates (seen == 0)
            ewma = jnp.where(
                t == 0, rate,
                _EWMA_ALPHA * rate + (1.0 - _EWMA_ALPHA) * state.ewma,
            )
        else:
            ewma = xs["ewma"]

        # ---- admit (observe_pool): age the queues, push this tick (new
        # arrivals land in the newest bucket: only the total prefix) -------
        qs_buf = _age_queue(state.qs_buf)
        qr_buf = _age_queue(state.qr_buf)
        n_strict = rate * st["strict_frac"]
        n_relaxed = rate - n_strict
        qs_buf = qs_buf.at[:, -1].add(n_strict)
        qr_buf = qr_buf.at[:, -1].add(n_relaxed)
        qs_tot = qs_buf[:, -1]
        qr_tot = qr_buf[:, -1]

    # ---- variant observation (pre-pop, like the NumPy observe_pool:
    # due swaps have NOT landed yet, so ratios and throughput gather at
    # the carried active variant; catalog-free every entry aliases a
    # read-only static and no gather is traced) ------------------------
    if variants:
        with jax.named_scope("scan.variants"):
            v_cur = state.var_cur
            v_pend = state.var_pending
            smult_cur = _gather_v(st["var_smult"], v_cur)
            v_up = jnp.minimum(v_cur + 1, st["var_n"] - 1)
            v_dn = jnp.maximum(v_cur - 1, 0)
            vobs = {
                "throughput": st["thr"] * smult_cur,
                "active_variant": v_cur,
                "n_variants": st["var_n"],
                "accuracy": _gather_v(st["var_acc"], v_cur),
                "accuracy_floor": st["acc_floor"],
                "variant_lo": st["var_lo"],
                "variant_cheapest": st["var_cheapest"],
                "variant_in_flight": v_pend >= 0,
                "variant_up_ratio": _gather_v(st["var_smult"], v_up) / smult_cur,
                "variant_down_ratio": _gather_v(st["var_smult"], v_dn) / smult_cur,
                "variant_pending_ratio": jnp.where(
                    v_pend >= 0,
                    _gather_v(st["var_smult"], jnp.maximum(v_pend, 0)) / smult_cur,
                    1.0,
                ),
                "variant_last_move": state.var_last_move,
            }
    else:
        vobs = {
            "throughput": st["thr"],
            "active_variant": st["zeros_i"],
            "n_variants": st["ones_i"],
            "accuracy": st["cur_acc"],
            "accuracy_floor": st["acc_floor"],
            "variant_lo": st["zeros_i"],
            "variant_cheapest": st["zeros_i"],
            "variant_in_flight": st["false_b"],
            "variant_up_ratio": st["ones_f"],
            "variant_down_ratio": st["ones_f"],
            "variant_pending_ratio": st["ones_f"],
            "variant_last_move": st["neg_i"],
        }

    # ---- observe: the traced PoolObs (pre-provision state, like the
    # NumPy observe_pool; idle-tier fields equal the static zeros the
    # NumPy engine serves because a dead tier's state IS zero) ---------
    with jax.named_scope("scan.observe"):
        obs = {
            "rate": rate,
            "ewma_rate": ewma,
            "peak_to_median": xs["p2m"],
            "queue_len": qs_tot + qr_tot,
            "queue_strict": qs_tot,
            "queue_relaxed": qr_tot,
            "n_active": state.res_active,
            "n_pending": (state.res_cum - state.res_mat).astype(jnp.int64),
            "n_spot": state.spot_active,
            "n_spot_pending": (state.spot_cum - state.spot_mat).astype(jnp.int64),
            "n_harvest": state.harv_active,
            "n_harvest_pending": (state.harv_cum - state.harv_mat).astype(jnp.int64),
            "n_remote": state.rem_active,
            "n_remote_pending": (state.rem_cum - state.rem_mat).astype(jnp.int64),
            "utilization": state.last_util,
            "last_violations": state.last_viol,
            "harvest_level": jnp.broadcast_to(xs["h_lev_obs"], (A,)),
            "harvest_ceiling": jnp.broadcast_to(xs["h_ceil_obs"], (A,)),
            "spot_reclaim_risk": st["risk"],
            "tick": t,
            "prev_rate": state.prev_rate,
            **vobs,
        }
    with jax.named_scope("scan.policy"):
        acts, extras = policy_apply(st["policy"], obs, xs.get("key"))

    # ---- variant swaps (ServingSim._step order): pop matured swaps
    # BEFORE provisioning/serving — the arch serves this tick at the new
    # rate — then enqueue this tick's requests into the depth-1 slot
    # (cancel-newest = one overwrite, exactly SwapPipeline.request) ----
    if variants:
        with jax.named_scope("scan.variants"):
            done = (v_pend >= 0) & (state.var_ready <= t)
            v_cur = jnp.where(done, v_pend, v_cur)
            v_pend = jnp.where(done, -1, v_pend)
            swaps = done.sum()
            # POST-pop effective serving state (what _refresh_variant_state
            # caches on the NumPy engine): serve, bill burst invocations and
            # account chips at the NEW variant from this tick on
            cur_acc = _gather_v(st["var_acc"], v_cur)
            thr = st["thr"] * _gather_v(st["var_smult"], v_cur)
            chips = st["chips"] * _gather_v(st["var_cmult"], v_cur)
            st_off = dict(
                st,
                lat_b1=st["lat_b1"] * _gather_v(st["var_lmult"], v_cur),
                burst_cpr=(chips / thr) * st["burst_chip_s"] + st["inv_fee"],
            )
            # request: re-targeting the current variant cancels the in-flight
            # swap; re-requesting the in-flight target leaves its clock
            # alone; anything else (re)starts the slot
            req = jnp.minimum(acts.get("variant", st["neg_i"]), st["var_n"] - 1)
            cancel = (req >= 0) & (req == v_cur)
            v_pend = jnp.where(cancel, -1, v_pend)
            start = (req >= 0) & (req != v_cur) & (req != v_pend)
            v_pend = jnp.where(start, req, v_pend)
            v_ready = jnp.where(start, t + st["swap_lat"], state.var_ready)
            v_last_move = acts.get("variant_last_move", state.var_last_move)
    else:
        thr = st["thr"]
        chips = st["chips"]
        cur_acc = st["cur_acc"]
        st_off = st

    with jax.named_scope("scan.provision"):
        # ---- provision (reserved, then aux in registration order).  Each
        # tier's ring slot for this tick is t mod L (L static per tier) ----
        res_active, res_pipe = _tier_set_target(
            state.res_active,
            _pipe_of(state, "res", lazy_rings),
            acts["target"], t % state.res_ring.shape[1],
        )
        spot_active, spot_pipe, reclaimed = _spot_begin(
            state.spot_active,
            _pipe_of(state, "spot", lazy_rings),
            xs["spot_u"], st["p_reclaim"],
        )
        spot_active, spot_pipe = _tier_set_target(
            spot_active, spot_pipe, acts["spot"],
            t % state.spot_ring.shape[1],
        )
        harv_active, harv_pipe, evicted = _harvest_begin(
            state.harv_active,
            _pipe_of(state, "harv", lazy_rings),
            xs["h_ceil"],
        )
        harv_active, harv_pipe = _tier_set_target(
            harv_active, harv_pipe, jnp.minimum(acts["harvest"], xs["h_ceil"]),
            t % state.harv_ring.shape[1],
        )
        rem_active, rem_pipe = _tier_set_target(
            state.rem_active,
            _pipe_of(state, "rem", lazy_rings),
            acts["remote"], t % state.rem_ring.shape[1],
        )
        preempt = reclaimed + evicted

    # ---- serve: local capacity first (strict priority), then the
    # remote group against its egress-tightened lateness prefixes ------
    with jax.named_scope("scan.serve"):
        cap_local = (res_active + spot_active + harv_active) * thr
        qs_buf, served_s, late_s = _serve(qs_buf, cap_local, st["late_s"])
        rem_cap = rem_active * thr
        qs_buf, srs, lrs = _serve(qs_buf, rem_cap, st["rlate_s"])
        qr_buf, served_r, late_r = _serve(
            qr_buf, cap_local - served_s, st["late_r"]
        )
        qr_buf, srr, lrr = _serve(qr_buf, rem_cap - srs, st["rlate_r"])
        served_s, late_s = served_s + srs, late_s + lrs
        served_r, late_r = served_r + srr, late_r + lrr
        served = served_s + served_r
        cap_total = cap_local + rem_cap
        util = jnp.where(
            cap_total > 0,
            served / jnp.where(cap_total > 0, cap_total, 1.0),
            1.0,
        )
        viol_arch = late_s + late_r
        viol_strict = late_s.sum()

        # ---- offload to burst (strict: any offload mode; relaxed: blind
        # only), sequential so the relaxed batch sees a warmed pool --------
        offload = acts["offload"]
        qs_buf, counts_s, bviol_s, bcost_s, last_used = _offload(
            qs_buf, offload >= 1, state.burst_last_used, t, st["slo_strict"],
            st_off,
        )
        qr_buf, counts_r, bviol_r, bcost_r, last_used = _offload(
            qr_buf, offload == 1, last_used, t, st["slo_relaxed"], st_off,
        )
        viol_arch = viol_arch + bviol_s + bviol_r
        viol_strict = viol_strict + bviol_s.sum()

        # ---- drop the bucket that aged past the abandon window (the
        # oldest; subtracting its prefix zeroes column 0 exactly) ----------
        dropped_s = qs_buf[:, 0]
        qs_buf = jnp.maximum(qs_buf - dropped_s[:, None], 0.0)
        dropped_r = qr_buf[:, 0]
        qr_buf = jnp.maximum(qr_buf - dropped_r[:, None], 0.0)
        dropped = dropped_s + dropped_r
        viol_arch = viol_arch + dropped
        viol_strict = viol_strict + dropped_s.sum()

    with jax.named_scope("scan.account"):
        # ---- delivered accuracy ------------------------------------------
        answered = served + counts_s + counts_r + dropped
        acc_w = answered * cur_acc
        acc_viol = answered * (cur_acc < st["acc_floor"] - 1e-12)

        # ---- account ------------------------------------------------------
        ch_res = res_active * chips
        ch_spot = spot_active * chips
        ch_harv = harv_active * chips
        ch_rem = rem_active * chips
        cost_arch = (
            bcost_s + bcost_r
            + ch_res * st["p_res"] + ch_spot * st["p_spot"]
            + ch_harv * st["p_harv"] + ch_rem * st["p_rem"]
        )
        chip_all = ch_res + ch_spot + ch_harv + ch_rem
        need = jnp.ceil(rate / thr) * chips

        # summary key presence: a tier posts (even $0) only on live ticks
        harv_live = (
            harv_active.sum() + (harv_pipe.cum - harv_pipe.mat).sum()
        ) > 0
        rem_live = (
            rem_active.sum() + (rem_pipe.cum - rem_pipe.mat).sum()
        ) > 0

        lazy_kw = {}
        if lazy_rings:
            for pre, pipe in (("res", res_pipe), ("spot", spot_pipe),
                              ("harv", harv_pipe), ("rem", rem_pipe)):
                lazy_kw[pre + "_ehist"] = pipe.ehist
                lazy_kw[pre + "_sufmin"] = pipe.sufmin
                lazy_kw[pre + "_bmin"] = pipe.bmin
        var_ys = {}
        if variants:
            lazy_kw.update(var_cur=v_cur, var_pending=v_pend,
                           var_ready=v_ready, var_last_move=v_last_move)
            var_ys = {
                # "swaps" is a flow (summed into the ledger); the rest are
                # per-tick gauges matching the NumPy recorder's end_tick
                # sampling points: active variant post-pop (swap.current),
                # in-flight post-request (swap.in_flight), delivered
                # accuracy at the serving variant (cur_acc)
                "swaps": swaps,
                "active_variant": v_cur,
                "swap_in_flight": v_pend >= 0,
                "acc_rate": cur_acc,
            }
        new_state = SimState(
            qs_buf=qs_buf, qr_buf=qr_buf,
            res_active=res_active,
            res_ring=res_pipe.ring, res_cum=res_pipe.cum, res_mat=res_pipe.mat,
            spot_active=spot_active,
            spot_ring=spot_pipe.ring, spot_cum=spot_pipe.cum,
            spot_mat=spot_pipe.mat,
            harv_active=harv_active,
            harv_ring=harv_pipe.ring, harv_cum=harv_pipe.cum,
            harv_mat=harv_pipe.mat,
            rem_active=rem_active,
            rem_ring=rem_pipe.ring, rem_cum=rem_pipe.cum, rem_mat=rem_pipe.mat,
            burst_last_used=last_used, last_util=util, last_viol=viol_arch,
            prev_rate=rate,
            ewma=ewma if ewma_in_carry else None,
            **lazy_kw,
        )
        ys = {
            "served": served,
            "burst": counts_s + counts_r,
            "dropped": dropped,
            "viol": viol_arch,
            "viol_strict": viol_strict,
            "acc_w": acc_w,
            "acc_viol": acc_viol,
            "cost_arch": cost_arch,
            "cost_res": ch_res.sum() * st["p_res"],
            "cost_spot": ch_spot.sum() * st["p_spot"],
            "cost_harv": ch_harv.sum() * st["p_harv"],
            "cost_rem": ch_rem.sum() * st["p_rem"],
            "cost_burst": bcost_s.sum() + bcost_r.sum(),
            "preempt": preempt,
            "chip": chip_all.sum(),
            "need": need.sum(),
            "over": jnp.maximum(chip_all - need, 0.0).sum(),
            "harv_live": harv_live,
            "rem_live": rem_live,
            # fleet / queue gauges for the telemetry trajectory (exact zeros
            # contribute nothing in "sum" mode; "stack" mode exposes the
            # per-tick series run_scenario(record_trajectory=True) returns)
            "n_res": res_active,
            "n_spot": spot_active,
            "n_harv": harv_active,
            "n_rem": rem_active,
            "queue_strict": qs_buf[:, -1],
            "queue_relaxed": qr_buf[:, -1],
            **var_ys,
            **extras,
        }
    return new_state, ys


# ---------------------------------------------------------------------------
# Host-side input builder.
# ---------------------------------------------------------------------------
def _ewma_trajectory(arrivals: np.ndarray,
                     alpha: float = LoadMonitor.ewma_alpha) -> np.ndarray:
    """The monitor's EWMA alone, ``[A, T] -> [T, A]``, bit-identical to
    :class:`~repro.core.load_monitor.PoolLoadMonitor`'s (the runner
    computes the order statistics on the device)."""
    A, T = arrivals.shape
    out = np.empty((T, A), dtype=np.float64)
    e = arrivals[:, 0].astype(np.float64).copy()
    out[0] = e
    for t in range(1, T):
        e = alpha * arrivals[:, t] + (1 - alpha) * e
        out[t] = e
    return out


#: memoized harvest availability signals — pure functions of
#: ``(seed, T)``, shared across the cells of a grid
_HARV_CACHE: Dict[tuple, np.ndarray] = {}


def _harvest_traj(seed: int, ticks: int) -> np.ndarray:
    k = (seed, ticks)
    if k not in _HARV_CACHE:
        if len(_HARV_CACHE) > 256:
            _HARV_CACHE.clear()
        _HARV_CACHE[k] = harvest_level_trajectory(seed, ticks)
    return _HARV_CACHE[k]


@telemetry.span("sim.prep.inputs")
def build_sim_inputs(
    arrivals: np.ndarray,
    workload: List[ArchLoad],
    *,
    pricing: FleetPricing = PRICING,
    catalog=None,
    seed: int = 0,
    prewarm: bool = True,
    warm_start: bool = True,
    needs_stats: bool = True,
    needs_key: bool = False,
    key=None,
    ewma: Optional[np.ndarray] = None,
    ewma_in_scan: Optional[bool] = None,
    lazy_rings: bool = True,
    _sim: Optional[ServingSim] = None,
):
    """Materialize ``(statics, state0, xs)`` for one scan — NumPy host
    arrays throughout (device transfer happens at the jit boundary).

    ``statics`` is the traced per-run constant pytree (slip the policy
    parameters in under ``statics["policy"]``); ``xs`` holds the
    per-tick inputs with leading time axis.  All derived quantities are
    read off a throwaway :class:`ServingSim` so the two engines share
    one construction path and cannot drift — ``_sim`` lets
    :func:`run_grid` amortize that construction over cells sharing a
    workload (every sim-derived quantity is arrival- and
    seed-independent except the warm-start fleet, recomputed here), and
    ``ewma`` likewise injects a precomputed ``[T, A]`` EWMA trajectory
    (the grid batches the monitor across cells).

    ``needs_stats`` policies get the host EWMA as ``xs["ewma"]`` and no
    order statistics: the runner computes ``p2m`` from ``xs["rate"]``
    on the device (:func:`_window_p2m`; the ``"legacy"`` flavor reads a
    host ``xs["p2m"]`` its caller adds).  On the non-stats path the EWMA
    recurrence runs *inside* the scan by default (``ewma_in_scan=None``
    resolves to ``not needs_stats``): ``state0.ewma`` seeds the carry
    and no ``[T, A]`` smoothing input is materialized.  Pass
    ``ewma_in_scan=False`` for the legacy host-precomputed input; the
    runner flavor must match (:func:`_get_runner` ``flavor``).

    Runs inside the program span ``sim.prep.inputs``; the template sim
    and the host EWMA pass have spans of their own.
    """
    arrivals = np.asarray(arrivals, dtype=np.float64)
    assert arrivals.ndim == 2, "the JAX engine needs an [A, T] matrix"
    A, T = arrivals.shape
    sim = _sim
    if sim is None:
        with telemetry.span("sim.prep.template"):
            sim = ServingSim(
                arrivals, workload, pricing=pricing, prewarm=prewarm,
                warm_start=warm_start, seed=seed, catalog=catalog,
            )
    variants = sim._variants_live

    if ewma_in_scan is None:
        ewma_in_scan = not needs_stats
    assert not (needs_stats and ewma_in_scan), "stats policies read the monitor stream"
    if not ewma_in_scan and ewma is None:
        with telemetry.span("sim.prep.monitor"):
            ewma = _ewma_trajectory(arrivals)

    cap = pricing.harvest_cap_per_arch
    lev = _harvest_traj(seed, T)
    h_lev_obs = np.concatenate([[1.0], lev[:-1]])   # level BEFORE the advance
    statics = {
        "strict_frac": sim.strict_frac.astype(np.float64),
        "thr": sim.eff_throughput,
        "chips": sim.eff_chips,
        "cur_acc": sim.cur_acc,
        "acc_floor": sim.acc_floor.astype(np.float64),
        # lateness as prefix lengths: how many of the oldest buckets
        # violate each arch's slack (masks are age-contiguous)
        "late_s": _n_late(sim.q_strict._late_mask),
        "late_r": _n_late(sim.q_relaxed._late_mask),
        "rlate_s": _n_late(sim._remote_late_strict),
        "rlate_r": _n_late(sim._remote_late_relaxed),
        # finalize prefixes: buffer age + 1 (the sweep runs at tick T)
        "fin_s": _n_late(_finalize_mask(sim.q_strict)),
        "fin_r": _n_late(_finalize_mask(sim.q_relaxed)),
        "lat_b1": sim.burst.lat_b1,
        "cold_start": sim.burst.cold_start_s,
        "burst_cpr": sim.burst.cost_per_request,
        "spinup": float(pricing.burst_spinup_s),
        "idle_timeout": float(pricing.burst_idle_timeout_s),
        "slo_strict": sim.q_strict.slo_s,
        "slo_relaxed": sim.q_relaxed.slo_s,
        "p_res": sim.reserved.price_per_chip_s(),
        "p_spot": sim.spot.price_per_chip_s(),
        "p_harv": sim.harvest.price_per_chip_s(),
        "p_rem": sim.remote.price_per_chip_s(),
        "p_reclaim": sim.spot.reclaim_probability(),
        "risk": np.full(A, sim.spot.reclaim_probability()),
        "zeros_i": np.zeros(A, dtype=np.int64),
        "ones_i": np.ones(A, dtype=np.int64),
        "false_b": np.zeros(A, dtype=bool),
        "ones_f": np.ones(A, dtype=np.float64),
        # the hold sentinel for variant requests / cooldown clocks (any
        # value far below tick 0 works; matches the vector schedulers)
        "neg_i": np.full(A, -(10 ** 9), dtype=np.int64),
        "policy": {},            # caller / run_scenario fills this in
    }
    if variants:
        # the scan gathers effective quantities per tick, so the serving
        # statics revert to BASE values and the padded catalog rides in
        statics.update(
            thr=sim.throughput,
            chips=sim.chips,
            lat_b1=sim.lat_b1,
            var_acc=sim.var_acc,
            var_smult=sim.var_smult,
            var_cmult=sim.var_cmult,
            var_lmult=sim.var_lmult,
            var_n=sim.var_n,
            var_lo=sim.var_lo,
            var_cheapest=sim.var_cheapest,
            swap_lat=np.int64(sim.swap.lat),
            burst_chip_s=float(pricing.burst_chip_s),
            inv_fee=float(pricing.burst_invocation_fee),
        )
    if warm_start:
        # the sim's own warm-start rule, recomputed so a reused _sim
        # still yields THIS cell's t=0 fleet
        res_active0 = np.maximum(
            1, np.ceil(arrivals[:, 0] / sim.eff_throughput)
        ).astype(np.int64)
    else:
        res_active0 = sim.reserved.active.copy()
    state0 = SimState(
        qs_buf=np.zeros((A, sim.q_strict.window), dtype=np.float64),
        qr_buf=np.zeros((A, sim.q_relaxed.window), dtype=np.float64),
        res_active=res_active0,
        res_ring=np.zeros((A, sim.reserved.pipeline.lat), dtype=np.int32),
        res_cum=np.zeros(A, dtype=np.int32),
        res_mat=np.zeros(A, dtype=np.int32),
        spot_active=np.zeros(A, dtype=np.int64),
        spot_ring=np.zeros((A, sim.spot.pipeline.lat), dtype=np.int32),
        spot_cum=np.zeros(A, dtype=np.int32),
        spot_mat=np.zeros(A, dtype=np.int32),
        harv_active=np.zeros(A, dtype=np.int64),
        harv_ring=np.zeros((A, sim.harvest.pipeline.lat), dtype=np.int32),
        harv_cum=np.zeros(A, dtype=np.int32),
        harv_mat=np.zeros(A, dtype=np.int32),
        rem_active=np.zeros(A, dtype=np.int64),
        rem_ring=np.zeros((A, sim.remote.pipeline.lat), dtype=np.int32),
        rem_cum=np.zeros(A, dtype=np.int32),
        rem_mat=np.zeros(A, dtype=np.int32),
        burst_last_used=sim.burst.last_used.copy(),
        last_util=np.zeros(A, dtype=np.float64),
        last_viol=np.zeros(A, dtype=np.float64),
        prev_rate=arrivals[:, 0].copy(),         # trend feature = 0 at t=0
        # the t=0 value is recomputed in-scan; this seeds dtype/shape
        ewma=arrivals[:, 0].copy() if ewma_in_scan else None,
        # variant axis: start at the base variant with an empty swap
        # slot and a cooldown clock that never blocks the first move
        **(
            dict(
                var_cur=sim.swap.current.astype(np.int64),
                var_pending=np.full(A, -1, dtype=np.int64),
                var_ready=np.zeros(A, dtype=np.int64),
                var_last_move=np.full(A, -(10 ** 9), dtype=np.int64),
            )
            if variants else {}
        ),
        # lazy-ring window-min state: "no events yet" is +inf everywhere
        **(
            {
                pre + suf: (
                    np.full(A, _I32_MAX, dtype=np.int32) if suf == "_bmin"
                    else np.full(
                        (A, getattr(sim, tier).pipeline.lat), _I32_MAX,
                        dtype=np.int32,
                    )
                )
                for pre, tier in (("res", "reserved"), ("spot", "spot"),
                                  ("harv", "harvest"), ("rem", "remote"))
                for suf in ("_ehist", "_sufmin", "_bmin")
            }
            if lazy_rings else {}
        ),
    )
    xs = {
        "t": np.arange(T, dtype=np.int64),
        "rate": np.ascontiguousarray(arrivals.T),
        "spot_u": spot_reclaim_uniforms(seed, T, A),
        "h_ceil": (lev * cap).astype(np.int64),
        "h_lev_obs": h_lev_obs,
        "h_ceil_obs": (h_lev_obs * cap).astype(np.int64),
    }
    if not ewma_in_scan:
        xs["ewma"] = ewma
    if not needs_stats:
        # no policy on this path reads peak_to_median: a broadcastable
        # placeholder keeps it out of the grid's host->device traffic
        xs["p2m"] = np.ones((T, 1), dtype=np.float64)
    if needs_key:
        if key is None:
            key = jax.random.PRNGKey(seed)
        xs["key"] = _split_keys(key, T)
    return statics, state0, xs


def _finalize_mask(q) -> np.ndarray:
    """Lateness mask for the end-of-trace sweep: the sweep runs one tick
    after the last shift, so every bucket is one tick older than its
    column says."""
    ages = np.arange(q.window - 1, -1, -1) + 1
    return ages[None, :] > q.slack[:, None]


def _n_late(mask: np.ndarray) -> np.ndarray:
    """An oldest-first lateness mask is always age-contiguous from
    bucket 0, so its per-arch count fully describes it — the gather
    index the prefix queues score lateness with."""
    n = mask.sum(axis=1).astype(np.int64)
    w = mask.shape[1]
    assert (mask == (np.arange(w)[None, :] < n[:, None])).all()
    return n


@jax.jit
def _split_chain(key, length):
    """The host rollout loop's split sequence (``key, k_t = split(key)``
    each tick) as ONE device scan — bit-identical keys, one dispatch
    instead of ``n`` host round-trips."""
    def f(k, _):
        k, kt = jax.random.split(k)
        return k, jax.random.key_data(kt)

    _, keys = lax.scan(f, key, length)
    return keys


def _split_keys(key, n: int) -> np.ndarray:
    return np.asarray(
        _split_chain(key, np.zeros(n, dtype=np.int8)), dtype=np.uint32
    )


# ---------------------------------------------------------------------------
# Runners: jitted scan (optionally vmapped), cached per policy so
# repeated calls of the same (A, T, policy) shape never re-trace.
# ---------------------------------------------------------------------------
_RUNNERS: Dict[tuple, Any] = {}


#: per-tick *level* series (fleet sizes, queue depths) exposed only by
#: the ``mode="stack"`` trajectory path — excluded from the in-graph
#: "sum" reduction, where their totals would be meaningless tick-seconds
GAUGE_KEYS = frozenset(
    ("n_res", "n_spot", "n_harv", "n_rem", "queue_strict", "queue_relaxed",
     "active_variant", "swap_in_flight", "acc_rate")
)

#: metric keys reduced by the in-carry accumulator ("sum" mode); the
#: per-tick liveness flags fold with logical-or instead of ``+``.
#: "swaps" only exists on variant-catalog runs — the accumulator keys
#: are filtered by presence in the tick's output shape
_SUM_KEYS = (
    "served", "burst", "dropped", "viol", "viol_strict", "acc_w",
    "acc_viol", "cost_arch", "cost_res", "cost_spot", "cost_harv",
    "cost_rem", "cost_burst", "preempt", "chip", "need", "over", "swaps",
)
_LIVE_KEYS = ("harv_live", "rem_live")

#: default chunked-scan unroll for the optimized runner flavor.  The
#: option exists (``make_runner(unroll=...)`` chunks the scan body so
#: XLA amortizes loop overhead), but on CPU unrolling forces the
#: single-slot ring writes to materialize full copies per unrolled
#: step — measured strictly slower at A>=256 — so the default stays 1
SCAN_UNROLL = 1


def make_runner(policy_apply, mode: str = "sum", *, unroll: int = 1,
                ewma_in_carry: bool = False, accumulate: bool = False,
                lazy_rings: bool = False, variants: bool = False,
                window_stats: bool = False):
    """Build ``run(statics, state0, xs) -> out`` around one policy.

    ``mode="sum"`` reduces the per-tick metrics (scenario evaluation);
    ``mode="stack"`` returns them per tick (rollout collection).
    ``accumulate`` (sum mode only) folds the totals into the scan carry
    as running sums instead of stacking ``[T, ...]`` outputs and
    reducing post-scan — at fleet scale the stacked form writes and
    re-reads hundreds of MB per run, the in-carry form touches only
    ``[A]`` accumulators.  ``unroll`` is passed through to ``lax.scan``
    (the chunked/unrolled option); ``ewma_in_carry`` moves the monitor
    EWMA into the scan (see :func:`_tick`); ``window_stats`` computes
    the monitor's ``p2m`` input from ``xs["rate"]`` in the program
    (:func:`_window_p2m`) instead of reading a host ``xs["p2m"]``.  Not
    jitted or cached — see :func:`_get_runner`.
    """
    assert not (accumulate and mode != "sum")

    def run(statics, state0, xs):
        if window_stats:
            xs = {**xs, "p2m": _window_p2m(xs["rate"])}
        if accumulate:
            x0 = jax.tree.map(lambda a: a[0], xs)
            ys_shape = jax.eval_shape(
                lambda s, x: _tick(s, x, statics, policy_apply,
                                   ewma_in_carry, lazy_rings, variants)[1],
                state0, x0,
            )
            acc0 = {
                k: jnp.zeros(ys_shape[k].shape, ys_shape[k].dtype)
                for k in _SUM_KEYS + _LIVE_KEYS if k in ys_shape
            }

            def f(carry, x):
                state, acc = carry
                state, ys = _tick(state, x, statics, policy_apply,
                                  ewma_in_carry, lazy_rings, variants)
                with jax.named_scope("scan.account"):
                    acc = {
                        k: (acc[k] | ys[k]) if k in _LIVE_KEYS
                        else acc[k] + ys[k]
                        for k in acc
                    }
                return (state, acc), None

            (final, tot), _ = lax.scan(f, (state0, acc0), xs, unroll=unroll)
            return {
                "final": final,
                "expired_s": _late_mass(final.qs_buf, statics["fin_s"]),
                "expired_r": _late_mass(final.qr_buf, statics["fin_r"]),
                "totals": tot,
            }

        def f(carry, x):
            return _tick(carry, x, statics, policy_apply, ewma_in_carry,
                         lazy_rings, variants)

        final, ys = lax.scan(f, state0, xs, unroll=unroll)
        out = {
            "final": final,
            "expired_s": _late_mass(final.qs_buf, statics["fin_s"]),
            "expired_r": _late_mass(final.qr_buf, statics["fin_r"]),
        }
        if mode == "sum":
            # summing the telemetry gauges is meaningless (they are
            # levels, not flows) — dropping them here lets XLA dead-code
            # the per-tick stacking, keeping scenario evaluation at its
            # pre-telemetry throughput
            out["totals"] = jax.tree.map(
                lambda a: a.sum(axis=0),
                {k: v for k, v in ys.items() if k not in GAUGE_KEYS},
            )
        else:
            out["ys"] = ys
        return out

    return run


def _flavor_opts(policy: str, mode: str, flavor: str) -> dict:
    """Resolve a runner flavor to concrete :func:`make_runner` options.

    ``"opt"`` (default everywhere) carries the totals and — for
    policies that never read the order statistics — the EWMA in the
    scan carry, computes the order statistics on the device for those
    that do, and unrolls the scan; ``"legacy"`` reproduces the
    pre-optimization construction (stacked per-tick outputs, host-fed
    EWMA and — for stats policies, added by the caller from
    :func:`~repro.core.load_monitor.pool_stats_trajectory` — host-fed
    ``xs["p2m"]``, unroll=1, no donation) and exists so the throughput
    benchmark can A/B the two in one run on one machine."""
    if flavor == "legacy":
        return dict(unroll=1, ewma_in_carry=False, accumulate=False,
                    lazy_rings=False)
    assert flavor == "opt", flavor
    needs_stats = JAX_POLICIES[policy].needs_stats
    return dict(
        unroll=SCAN_UNROLL,
        ewma_in_carry=not needs_stats,
        window_stats=needs_stats,
        accumulate=(mode == "sum"),
        # under vmap the lazy rings' block-boundary cond decays to
        # select (both branches execute) — batched runners keep the
        # eager clip; _get_runner strips this flag for them
        lazy_rings=True,
    )


def _get_sharded_runner(policy: str, mesh, mode: str = "sum",
                        flavor: str = "opt", variants: bool = False):
    """The batched grid runner wrapped in ``shard_map``: the leading
    cell axis splits across ``mesh``'s devices (pure data parallelism —
    cells never communicate), statics stay replicated.  The logical
    "cells" axis maps onto the mesh axis through the standard
    :mod:`repro.distributed.sharding` rules so the spec derivation is
    the same one model code uses."""
    from repro.distributed.sharding import AxisRules, logical_to_spec

    ndev = mesh.devices.size
    key = _runner_key(policy, mode, True, flavor, variants, ndev)
    if key not in _RUNNERS:
        opts = _flavor_opts(policy, mode, flavor)
        opts["lazy_rings"] = False          # vmapped inside shard_map
        base = make_runner(JAX_POLICIES[policy].apply, mode,
                           variants=variants, **opts)

        def grid(statics, policy_params, state0, xs):
            return base({**statics, "policy": policy_params}, state0, xs)

        inner = jax.vmap(grid, in_axes=(None, 0, 0, 0))
        rules = AxisRules(mesh, {"cells": mesh.axis_names[0]})
        cell = logical_to_spec(("cells",), rules)
        rep = logical_to_spec((), rules)
        # check_vma=False: the binomial inverse-CDF lax.while_loop has no
        # shard_map replication rule; every input/output spec is explicit
        # here so the check adds nothing.
        fn = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(rep, cell, cell, cell), out_specs=cell,
            check_vma=False,
        )
        _RUNNERS[key] = jax.jit(fn)
    return _RUNNERS[key]


def _runner_key(policy: str, mode: str, batched: bool, flavor: str,
                variants: bool, sharded: int = 0) -> tuple:
    """The :data:`_RUNNERS` key of a runner; ``sharded`` is the device
    count of a ``shard_map`` grid runner, 0 for one device."""
    if sharded:
        return (policy, mode, "sharded", sharded, flavor, variants)
    return (policy, mode, batched, flavor, variants)


def _get_runner(policy: str, mode: str = "sum", batched: bool = False,
                flavor: str = "opt", variants: bool = False):
    key = _runner_key(policy, mode, batched, flavor, variants)
    if key not in _RUNNERS:
        opts = _flavor_opts(policy, mode, flavor)
        if batched:
            opts["lazy_rings"] = False
        base = make_runner(JAX_POLICIES[policy].apply, mode,
                           variants=variants, **opts)
        if batched:
            # one statics pytree serves every cell (grid cells share a
            # workload); only policy params, state and per-tick inputs
            # carry the batch axis
            def grid(statics, policy_params, state0, xs):
                return base({**statics, "policy": policy_params}, state0, xs)

            fn = jax.vmap(grid, in_axes=(None, 0, 0, 0))
            donate = (2,)
        else:
            fn = base
            donate = (1,)
        if flavor == "opt":
            # donate the scan carry's initial state — jit converts the
            # host state0 to a fresh device buffer per call, so XLA may
            # alias it into the carry without copying
            _RUNNERS[key] = jax.jit(fn, donate_argnums=donate)
        else:
            _RUNNERS[key] = jax.jit(fn)
    return _RUNNERS[key]


def runner_trace_count(policy: str, mode: str = "sum",
                       batched: bool = False, flavor: str = "opt",
                       variants: bool = False, sharded: int = 0) -> int:
    """How many distinct shapes the cached runner has traced (the
    recompile guard: repeated same-shape runs must report 1);
    ``sharded`` names the grid runner sharded over that many devices."""
    fn = _RUNNERS.get(_runner_key(policy, mode, batched, flavor, variants, sharded))
    return 0 if fn is None else fn._cache_size()


# trace counts last observed per runner key, and the keys already warned
# about — a runner retracing for a key we've seen is a silent recompile
# (a perf bug), surfaced once per key and counted in the telemetry
# counters (`repro_jax_runner_traces_total{...}` in the Prometheus dump)
_TRACE_SEEN: Dict[tuple, int] = {}
_TRACE_WARNED: set = set()


def note_runner_use(policy: str, mode: str = "sum",
                    batched: bool = False, flavor: str = "opt",
                    variants: bool = False, sharded: int = 0) -> int:
    """Record a runner dispatch: export its trace count as a telemetry
    counter, labelled with every part of the runner's key, and warn
    (once per key) if it retraced for an already-seen key.  Returns the
    current trace count."""
    key = _runner_key(policy, mode, batched, flavor, variants, sharded)
    n = runner_trace_count(policy, mode, batched, flavor, variants, sharded)
    telemetry.set_global_counter(
        f'jax_runner_traces_total{{policy="{policy}",mode="{mode}",'
        f'batched="{int(batched)}",flavor="{flavor}",'
        f'variants="{int(variants)}",sharded="{sharded}"}}', n)
    prev = _TRACE_SEEN.get(key)
    if prev is not None and n > prev and key not in _TRACE_WARNED:
        _TRACE_WARNED.add(key)
        warnings.warn(
            f"jax_engine runner retraced for already-seen key {key}: "
            f"{n} traces cached (was {prev}) — same-shape runs should "
            "hit the jit cache; check for dtype/shape drift in inputs",
            RuntimeWarning, stacklevel=3,
        )
    _TRACE_SEEN[key] = max(n, prev or 0)
    return n


# ---------------------------------------------------------------------------
# Result assembly (mirrors SimResult.summary / per_arch_counts).
# ---------------------------------------------------------------------------
def _assemble(out: dict, arrivals: np.ndarray) -> dict:
    tot = out["totals"]
    exp_s, exp_r = out["expired_s"], out["expired_r"]
    expired = exp_s + exp_r
    total_requests = float(arrivals.sum())
    viol_total = float(tot["viol"].sum() + expired.sum())
    viol_strict = float(tot["viol_strict"] + exp_s.sum())
    served_vm = float(tot["served"].sum() + tot["dropped"].sum())
    served_burst = float(tot["burst"].sum())
    answered = served_vm + served_burst
    cost_res = float(tot["cost_res"])
    cost_spot = float(tot["cost_spot"])
    cost_burst = float(tot["cost_burst"])
    cost_harv = float(tot["cost_harv"])
    cost_rem = float(tot["cost_rem"])
    chip = float(tot["chip"])
    need = float(tot["need"])
    over = float(tot["over"])

    summary = {
        "cost_total": round(
            cost_res + cost_spot + cost_burst + cost_harv + cost_rem, 4
        ),
        "cost_reserved": round(cost_res, 4),
        "cost_spot": round(cost_spot, 4),
        "cost_burst": round(cost_burst, 4),
    }
    # tier keys appear iff the tier was ever live (it posts $0 entries
    # on pipeline-only ticks) — same rule as the lazy NumPy accounting
    if bool(tot["harv_live"]):
        summary["cost_harvest"] = round(cost_harv, 4)
    if bool(tot["rem_live"]):
        summary["cost_remote"] = round(cost_rem, 4)
    summary.update({
        "preemptions": int(tot["preempt"]),
        "violation_rate": round(viol_total / max(total_requests, 1e-9), 5),
        "violations_strict": round(viol_strict, 1),
        "served_vm": round(served_vm, 1),
        "served_burst": round(served_burst, 1),
        "overprovision_ratio": round(over / max(need, 1e-9), 4),
        "chip_seconds": round(chip, 1),
    })
    if answered > 0:
        acc_w = float(tot["acc_w"].sum())
        summary["mean_accuracy"] = round(acc_w / max(answered, 1e-9), 5)
        summary["acc_violation_rate"] = round(
            float(tot["acc_viol"].sum()) / max(answered, 1e-9), 5
        )
        summary["variant_swaps"] = (
            int(tot["swaps"]) if "swaps" in tot else 0
        )

    final: SimState = out["final"]
    per_arch = {
        "arrived": arrivals.sum(axis=1),
        "served_vm": tot["served"],
        "served_burst": tot["burst"],
        "dropped": tot["dropped"],
        "expired_end": expired,
        "violations": tot["viol"] + expired,
        "queued": (final.qs_buf[:, -1] - exp_s) + (final.qr_buf[:, -1] - exp_r),
        "acc_weight": tot["acc_w"],
        "acc_violations": tot["acc_viol"],
    }
    return {"summary": summary, "per_arch": per_arch, "raw": out}


def _tree_to_host(out):
    return jax.tree.map(np.asarray, out)


def _tree_stack(trees):
    return jax.tree.map(lambda *leaves: np.stack(leaves), *trees)


def _tree_index(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _tree_nbytes(tree) -> int:
    """Bytes in a pytree's leaves: what a jit call copies to the device
    for arguments given as host values."""
    return sum(leaf.nbytes if hasattr(leaf, "nbytes") else np.asarray(leaf).nbytes
               for leaf in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# Public entry points.  Each call is one telemetry.program_call; its
# stages are program spans (docs/TELEMETRY.md): sim.prep.template,
# sim.prep.monitor, sim.prep.inputs, sim.prep.stack, sim.dispatch (the
# jit call, which returns once the arguments' copies to the device are
# under way), sim.fetch (waits for those copies and the device, copies
# the results back) and sim.assemble.
# ---------------------------------------------------------------------------
@telemetry.program_call()
def run_scenario(
    arrivals: np.ndarray,
    workload: List[ArchLoad],
    policy: str = "portfolio",
    params: Optional[dict] = None,
    *,
    pricing: FleetPricing = PRICING,
    catalog=None,
    seed: int = 0,
    prewarm: bool = True,
    warm_start: bool = True,
    record_trajectory: bool = False,
) -> dict:
    """One scenario through the jitted scan; returns ``{"summary",
    "per_arch", "raw"}`` with the summary shaped exactly like
    ``SimResult.summary()`` from the NumPy engine.

    ``catalog`` switches on the model-variant axis: the scan carries the
    per-arch swap pipeline and gathers effective serving state from the
    padded catalog tables every tick (see :func:`_tick`); without one
    the compiled graph is the variant-blind engine's, unchanged.

    ``record_trajectory=True`` runs the ``mode="stack"`` runner instead
    and adds a ``"trajectory"`` entry: the per-tick ``[T, ...]`` series
    of every scan output (served / burst / violation flows, per-tier
    cost and fleet gauges, queue totals, and — on catalog runs — the
    variant gauges ``active_variant`` / ``swap_in_flight`` /
    ``acc_rate``) — the JAX-side counterpart of the NumPy engine's
    telemetry recorder."""
    pol = JAX_POLICIES[policy]
    statics, state0, xs = build_sim_inputs(
        arrivals, workload, pricing=pricing, catalog=catalog, seed=seed,
        prewarm=prewarm, warm_start=warm_start, needs_stats=pol.needs_stats,
        needs_key=pol.needs_key,
    )
    telemetry.add_counter("sim_arch_ticks_total", xs["rate"].size)
    telemetry.add_counter("sim_monitor_device_arch_ticks_total",
                          xs["rate"].size if pol.needs_stats else 0)
    variants = "var_smult" in statics
    statics["policy"] = pol.default_params() if params is None else params
    mode = "stack" if record_trajectory else "sum"
    runner = _get_runner(policy, mode=mode, variants=variants)
    telemetry.add_counter("sim_h2d_bytes_total", _tree_nbytes((statics, state0, xs)))
    with jax.enable_x64(True):
        with telemetry.span("sim.dispatch"):
            out = runner(statics, state0, xs)
        with telemetry.span("sim.fetch"):
            out = _tree_to_host(out)
    note_runner_use(policy, mode, variants=variants)
    with telemetry.span("sim.assemble"):
        trajectory = None
        if record_trajectory:
            trajectory = out.pop("ys")
            # reduce the stacked series host-side so _assemble sees the
            # same shape the in-graph "sum" reduction produces
            out["totals"] = {k: v.sum(axis=0) for k, v in trajectory.items()}
        result = _assemble(out, np.asarray(arrivals, dtype=np.float64))
        if record_trajectory:
            result["trajectory"] = trajectory
    return result


@telemetry.program_call()
def run_grid(
    arrivals_batch: np.ndarray,              # [B, A, T]
    workload: List[ArchLoad],
    policy: str = "portfolio",
    params_batch: Optional[List[dict]] = None,
    seeds: Optional[List[int]] = None,
    *,
    pricing: FleetPricing = PRICING,
    catalog=None,
    prewarm: bool = True,
    warm_start: bool = True,
    sharded: Optional[bool] = None,
) -> List[dict]:
    """A whole (scenario x seed x policy-params) grid in ONE vmapped
    dispatch: cell ``i`` runs ``arrivals_batch[i]`` under
    ``params_batch[i]`` with spot/harvest realizations from
    ``seeds[i]``.  Returns one :func:`run_scenario`-shaped dict per
    cell.

    With more than one device the cell axis is sharded across them via
    ``shard_map`` (``sharded=None`` shards whenever there are several
    devices; ``True`` requires them, ``False`` forces the single
    dispatch).  A cell count that the device count does not divide is
    padded with copies of cell 0, which are dropped from the result.
    Cells never communicate, so the sharded and unsharded paths compute
    identical cells.  Each cell's dict also says over how many
    ``"devices"`` the grid's output was spread."""
    from repro.distributed.sharding import device_mesh

    arrivals_batch = np.asarray(arrivals_batch, dtype=np.float64)
    B, A, T = arrivals_batch.shape
    telemetry.add_counter("sim_arch_ticks_total", B * A * T)
    pol = JAX_POLICIES[policy]
    telemetry.add_counter("sim_monitor_device_arch_ticks_total",
                          B * A * T if pol.needs_stats else 0)
    seeds = list(seeds) if seeds is not None else [0] * B
    assert len(seeds) == B
    # one template sim serves the whole grid (cells share the
    # workload); per-cell monitor EWMAs run as ONE batched recurrence
    # over the stacked [B*A, T] arrival matrix (rows are independent,
    # so the batched pass is bit-identical to B per-cell passes)
    with telemetry.span("sim.prep.template"):
        sim = ServingSim(
            arrivals_batch[0], workload, pricing=pricing, prewarm=prewarm,
            warm_start=warm_start, seed=seeds[0], catalog=catalog,
        )
    variants = sim._variants_live
    if pol.needs_stats:
        # the order statistics run in the runner, on the device
        with telemetry.span("sim.prep.monitor"):
            ew = _ewma_trajectory(arrivals_batch.reshape(B * A, T))
        ewmas = [ew[:, i * A:(i + 1) * A] for i in range(B)]
    else:
        ewmas = [None] * B       # EWMA runs in the scan carry
    cells = [
        build_sim_inputs(
            arrivals_batch[i], workload, pricing=pricing, seed=seeds[i],
            prewarm=prewarm, warm_start=warm_start,
            needs_stats=pol.needs_stats, needs_key=pol.needs_key,
            key=jax.random.PRNGKey(seeds[i]) if pol.needs_key else None,
            ewma=ewmas[i], lazy_rings=False, _sim=sim,
        )
        for i in range(B)
    ]
    statics = cells[0][0]
    if params_batch is None:
        params_batch = [pol.default_params() for _ in range(B)]
    mesh = device_mesh()
    use_shard = mesh is not None if sharded is None else sharded
    if use_shard:
        assert mesh is not None, "sharded run_grid needs more than one device"
    ndev = mesh.devices.size if use_shard else 0
    with telemetry.span("sim.prep.stack"):
        state0_b = _tree_stack([c[1] for c in cells])
        xs_b = _tree_stack([c[2] for c in cells])
        policy_b = _tree_stack(list(params_batch))
        if use_shard:
            pad = -B % ndev
            state0_b, xs_b, policy_b = (
                jax.tree.map(
                    lambda a: np.concatenate([a, np.repeat(a[:1], pad, axis=0)]),
                    tree,
                )
                for tree in (state0_b, xs_b, policy_b)
            )
    if use_shard:
        runner = _get_sharded_runner(policy, mesh, variants=variants)
    else:
        runner = _get_runner(policy, batched=True, variants=variants)
    telemetry.add_counter("sim_h2d_bytes_total",
                          _tree_nbytes((statics, policy_b, state0_b, xs_b)))
    with jax.enable_x64(True):
        with telemetry.span("sim.dispatch"):
            out = runner(statics, policy_b, state0_b, xs_b)
        devices = min(len(leaf.sharding.device_set) for leaf in jax.tree.leaves(out))
        with telemetry.span("sim.fetch"):
            out = _tree_to_host(out)
    note_runner_use(policy, batched=True, variants=variants, sharded=ndev)
    with telemetry.span("sim.assemble"):
        return [
            {**_assemble(_tree_index(out, i), arrivals_batch[i]), "devices": devices}
            for i in range(B)
        ]
