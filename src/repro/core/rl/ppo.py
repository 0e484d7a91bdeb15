"""Proximal Policy Optimization in pure JAX (paper §V), pool-wide.

The paper sketches a PPO controller with the clipped surrogate
L(theta) = E_t[min(r_t A_t, clip(r_t, 1-eps, 1+eps) A_t)] over scheduling
decisions; we implement the full loop over the *whole serving pool*:

* a shared MLP torso with policy+value heads, applied **per arch row**
  (the factored action space of :mod:`repro.core.rl.obs`) — the same
  parameters control any pool size, and one forward pass over the
  ``[A, OBS_DIM]`` observation matrix prices every arch's action;
* batched rollouts: buffers are ``[T, A, ...]`` arrays filled by the
  vectorized :class:`~repro.core.rl.env.PoolServingEnv`;
* GAE(lambda) computed over ``[T, A]`` reward/value arrays with
  *per-arch credit assignment* — each arch's advantage stream sees its
  own decomposed reward (engine cost attribution + violation counts),
  not the pool average;
* jitted minibatched clipped updates with Adam over the flattened
  ``[T*A, OBS_DIM]`` batch, entropy bonus included.

The single-arch ``train_ppo`` entry point survives as a thin shim: a
legacy :class:`~repro.core.rl.env.ServingEnv` is just the A=1 view of
the pool path.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.rl.env import (
    N_ACTIONS,
    OBS_DIM,
    PoolServingEnv,
    ServingEnv,
)
from repro.core.sim import jax_engine
from repro.core.sim.telemetry import JsonlWriter


@dataclass(frozen=True)
class PPOConfig:
    hidden: int = 64
    lr: float = 5e-4
    gamma: float = 0.97
    gae_lambda: float = 0.95
    clip_eps: float = 0.2
    entropy_coef: float = 0.01
    value_coef: float = 0.5
    epochs: int = 4
    minibatches: int = 8
    rollout_len: int = 1200        # cover a full episode -> every update
                                   # sees flash-crowd segments
    iterations: int = 60
    max_grad_norm: float = 0.5
    seed: int = 0


# ---------------------------------------------------------------------------
# Networks.  The torso maps one arch's feature row to logits/value; JAX
# broadcasting applies it to [A, F] (a pool tick) and [N, F] (an update
# minibatch) alike — the per-arch head is vmap-free by construction.
# ---------------------------------------------------------------------------
def init_net(key, cfg: PPOConfig) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    h = cfg.hidden

    def lin(k, i, o, scale):
        return {
            "w": scale * jax.random.normal(k, (i, o)) / jnp.sqrt(i),
            "b": jnp.zeros((o,)),
        }

    return {
        "torso1": lin(k1, OBS_DIM, h, 1.0),
        "torso2": lin(k2, h, h, 1.0),
        "pi": lin(k3, h, N_ACTIONS, 0.01),
        "v": lin(k4, h, 1, 1.0),
    }


def _apply(p, x):
    h = jnp.tanh(x @ p["torso1"]["w"] + p["torso1"]["b"])
    h = jnp.tanh(h @ p["torso2"]["w"] + p["torso2"]["b"])
    logits = h @ p["pi"]["w"] + p["pi"]["b"]
    value = (h @ p["v"]["w"] + p["v"]["b"])[..., 0]
    return logits, value


@jax.jit
def policy_logits_value(params, obs):
    return _apply(params, obs)


@jax.jit
def _pool_action(params, obs, key):
    """Sample per-arch actions for one pool tick: obs [A, F] -> [A]."""
    logits, values = _apply(params, obs)
    actions = jax.random.categorical(key, logits)
    logp = jnp.take_along_axis(
        jax.nn.log_softmax(logits), actions[:, None], axis=1
    )[:, 0]
    return actions, logp, values


def pool_policy_action(params, obs: np.ndarray, key) -> Tuple[np.ndarray, ...]:
    a, logp, v = _pool_action(params, jnp.asarray(obs), key)
    return np.asarray(a), np.asarray(logp), np.asarray(v)


def policy_action(params, obs: np.ndarray, key) -> Tuple[int, float, float]:
    """Single-arch convenience form (seed interface)."""
    a, logp, v = pool_policy_action(params, np.asarray(obs)[None, :], key)
    return int(a[0]), float(logp[0]), float(v[0])


# ---------------------------------------------------------------------------
# GAE.
# ---------------------------------------------------------------------------
def compute_gae_pool(rewards, values, dones, last_value, gamma, lam):
    """GAE over ``[T, A]`` per-arch reward/value streams.

    ``dones[t]`` is the shared episode boundary (the whole pool resets
    together); advantages are otherwise accumulated independently per
    arch, which is the credit-assignment half of the factored action
    space.
    """
    T, A = rewards.shape
    adv = np.zeros((T, A), dtype=np.float32)
    lastgaelam = np.zeros(A, dtype=np.float32)
    for t in reversed(range(T)):
        nonterminal = 1.0 - float(dones[t])
        next_v = last_value if t == T - 1 else values[t + 1]
        delta = rewards[t] + gamma * next_v * nonterminal - values[t]
        lastgaelam = delta + gamma * lam * nonterminal * lastgaelam
        adv[t] = lastgaelam
    returns = adv + values
    return adv, returns


def compute_gae(rewards, values, dones, last_value, gamma, lam):
    """Single-stream GAE (seed interface): the A=1 column of the pool form."""
    adv, ret = compute_gae_pool(
        np.asarray(rewards, np.float32)[:, None],
        np.asarray(values, np.float32)[:, None],
        dones,
        np.float32(last_value),
        gamma,
        lam,
    )
    return adv[:, 0], ret[:, 0]


# ---------------------------------------------------------------------------
# Batched rollout collection: one whole episode inside the jitted engine
# scan instead of T host round-trips through env.step.
# ---------------------------------------------------------------------------
def _rewards_from_ys(cfg, ys, expired) -> np.ndarray:
    """Per-tick ``[..., T, A]`` rewards rebuilt from the engine's per-arch
    attribution, with the end-of-trace expired sweep booked on the last
    tick exactly as ``env.step`` does."""
    viol = np.array(ys["viol"], dtype=np.float64)    # owned: last tick edited
    viol[..., -1, :] += expired
    return -cfg.reward_scale * (
        ys["cost_arch"]
        + cfg.violation_penalty * viol
        - cfg.accuracy_bonus * ys["acc_w"]
    )


def collect_rollouts_jax(env: PoolServingEnv, params, key, *,
                         arrivals=None, seed: int = 0) -> dict:
    """Collect one full-episode ``[T, A]`` rollout in a single dispatch.

    Drives the batched engine (:mod:`repro.core.sim.jax_engine`) with
    the stochastic ``rl_sample`` policy: the net's forward pass, the
    categorical draw and the procurement decode all run *inside*
    ``lax.scan``, and the per-tick extras come back as exactly the
    buffers the host rollout loop fills — observation features,
    sampled actions, log-probs, values — plus rewards rebuilt from the
    engine's per-arch cost/violation/accuracy attribution under the
    env's :class:`~repro.core.rl.env.EnvConfig` weights (the end-of-
    trace expired sweep lands on the last tick, as ``env.step`` books
    it).  The per-tick key sequence is the host loop's own
    ``key, k_t = split(key)`` chain, so the sampling stream is shared
    with the step-wise collector, not merely analogous.

    Arrival precedence matches ``env.reset``: an explicit ``arrivals``
    matrix, else a fresh draw from the env's scenario pool, else the
    fixed matrix the env was built with.  Episodes are done-terminated
    only at the trace end, so ``dones`` is a one-hot tail and
    ``last_value`` is irrelevant to GAE (returned as zeros).
    """
    cfg = env.cfg
    if arrivals is not None:
        tr = arrivals
    elif env.scenarios:
        tr = env._sample_arrivals()
        seed = env._episode          # the per-episode sim seed env.reset uses
    else:
        tr = env.base_arrivals
    tr = np.asarray(tr, dtype=np.float64)
    A, T = tr.shape
    pol = jax_engine.JAX_POLICIES["rl_sample"]
    # the env's variant catalog rides into the scan, so the sampled
    # variant head EXECUTES during collection (swaps change served
    # accuracy and cost) instead of decaying to a no-op
    statics, state0, xs = jax_engine.build_sim_inputs(
        tr, env.workload, pricing=cfg.pricing, catalog=env.catalog,
        seed=seed, needs_stats=pol.needs_stats, needs_key=True, key=key,
    )
    variants = "var_smult" in statics
    statics["policy"] = {
        "net": params,
        "rate_scale": cfg.rate_scale,
        "fleet_scale": cfg.fleet_scale,
    }
    with jax.enable_x64(True):
        out = jax.tree.map(
            np.asarray,
            jax_engine._get_runner("rl_sample", mode="stack",
                                   variants=variants)(
                statics, state0, xs
            ),
        )
    ys = out["ys"]
    rewards = _rewards_from_ys(
        cfg, ys, out["expired_s"] + out["expired_r"]
    )
    dones = np.zeros(T, dtype=np.float32)
    dones[-1] = 1.0
    return {
        "obs": np.asarray(ys["obs"], dtype=np.float32),
        "actions": np.asarray(ys["action"], dtype=np.int32),
        "logp": np.asarray(ys["logp"], dtype=np.float32),
        "values": np.asarray(ys["value"], dtype=np.float32),
        "rewards": rewards.astype(np.float32),
        "dones": dones,
        "last_value": np.zeros(A, dtype=np.float32),
    }


def collect_rollouts_jax_zoo(env: PoolServingEnv, params, key) -> dict:
    """Collect ``[S, T, A]`` rollouts over the env's WHOLE scenario pool
    in one vmapped dispatch — the full-zoo form of
    :func:`collect_rollouts_jax`.

    Instead of sampling one scenario per iteration, every scenario in
    ``env.scenarios`` becomes a cell of the batched engine runner (the
    same ``vmap`` grid dispatch :func:`~repro.core.sim.jax_engine.run_grid`
    uses): per-cell arrival realizations, sim seeds and per-tick key
    streams are all distinct, the net's parameters are shared across
    cells, and the per-cell monitor EWMAs run as one batched
    recurrence over the stacked ``[S*A, T]`` arrival matrix (rows are
    independent, so this is bit-identical to S per-cell passes; the
    order statistics run in the runner, on the device).

    The returned buffers merge the cell axis into the arch axis —
    ``[T, S*A, ...]`` — so GAE and the PPO update treat the zoo batch
    exactly like a wider pool: ``dones`` is the shared one-hot tail
    (every cell ends at the trace end), per-column advantage streams
    never mix cells, and the flattened update batch has ``T*S*A`` rows.
    One PPO iteration therefore trains on every load shape in the zoo
    at once instead of memorizing this episode's draw.
    """
    cfg = env.cfg
    assert env.scenarios, "full-zoo collection needs a scenario pool"
    S, A = len(env.scenarios), env.n_archs
    env._episode += 1              # one zoo sweep advances the episode clock
    ep = env._episode
    arrs = np.stack([
        np.asarray(
            sc.build(A, seed=sc.seed + ep, duration_s=cfg.duration_s,
                     mean_rps=cfg.mean_rps),
            dtype=np.float64,
        )
        for sc in env.scenarios
    ])                             # [S, A, T]
    T = arrs.shape[2]
    # distinct per-cell sim seeds across cells AND iterations (tier
    # noise must not replay), distinct per-cell key streams
    seeds = [ep * S + i for i in range(S)]
    keys = jax.random.split(key, S)
    sim_tmpl = jax_engine.ServingSim(
        arrs[0], env.workload, pricing=cfg.pricing, seed=seeds[0],
        catalog=env.catalog,
    )
    variants = sim_tmpl._variants_live
    ew = jax_engine._ewma_trajectory(arrs.reshape(S * A, T))
    cells = [
        jax_engine.build_sim_inputs(
            arrs[i], env.workload, pricing=cfg.pricing, seed=seeds[i],
            needs_stats=True, needs_key=True, key=keys[i],
            ewma=ew[:, i * A:(i + 1) * A],
            lazy_rings=False, _sim=sim_tmpl,
        )
        for i in range(S)
    ]
    statics = cells[0][0]
    state0_b = jax_engine._tree_stack([c[1] for c in cells])
    xs_b = jax_engine._tree_stack([c[2] for c in cells])
    policy_b = jax_engine._tree_stack([{
        "net": params,
        "rate_scale": cfg.rate_scale,
        "fleet_scale": cfg.fleet_scale,
    }] * S)
    with jax.enable_x64(True):
        out = jax.tree.map(
            np.asarray,
            jax_engine._get_runner("rl_sample", mode="stack", batched=True,
                                   variants=variants)(
                statics, policy_b, state0_b, xs_b
            ),
        )
    ys = out["ys"]                 # leaves [S, T, A, ...]
    rewards = _rewards_from_ys(
        cfg, ys, out["expired_s"] + out["expired_r"]
    )

    def merge(x, dtype):           # [S, T, A, ...] -> [T, S*A, ...]
        x = np.asarray(x)
        return np.swapaxes(x, 0, 1).reshape(
            (T, S * A) + x.shape[3:]
        ).astype(dtype)

    dones = np.zeros(T, dtype=np.float32)
    dones[-1] = 1.0
    return {
        "obs": merge(ys["obs"], np.float32),
        "actions": merge(ys["action"], np.int32),
        "logp": merge(ys["logp"], np.float32),
        "values": merge(ys["value"], np.float32),
        "rewards": merge(rewards, np.float32),
        "dones": dones,
        "last_value": np.zeros(S * A, dtype=np.float32),
        "n_cells": S,
    }


# ---------------------------------------------------------------------------
# Update.
# ---------------------------------------------------------------------------
def _loss(params, batch, clip_eps, entropy_coef, value_coef):
    logits, values = _apply(params, batch["obs"])
    logp_all = jax.nn.log_softmax(logits)
    logp = jnp.take_along_axis(logp_all, batch["actions"][:, None], axis=1)[:, 0]
    ratio = jnp.exp(logp - batch["logp_old"])
    adv = batch["adv"]
    unclipped = ratio * adv
    clipped = jnp.clip(ratio, 1 - clip_eps, 1 + clip_eps) * adv
    pi_loss = -jnp.mean(jnp.minimum(unclipped, clipped))
    v_loss = jnp.mean((values - batch["returns"]) ** 2)
    entropy = -jnp.mean(jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1))
    # the standard sampled KL(old || new) estimator over the batch — the
    # health signal telemetry tracks per iteration (a spike means the
    # clipped surrogate stopped trusting the rollout distribution)
    approx_kl = jnp.mean(batch["logp_old"] - logp)
    total = pi_loss + value_coef * v_loss - entropy_coef * entropy
    return total, {"pi_loss": pi_loss, "v_loss": v_loss, "entropy": entropy,
                   "approx_kl": approx_kl}


@partial(jax.jit, static_argnames=("cfg",))
def ppo_update(params, opt_state, batch, cfg: PPOConfig):
    (loss, aux), grads = jax.value_and_grad(
        _loss, has_aux=True
    )(params, batch, cfg.clip_eps, cfg.entropy_coef, cfg.value_coef)
    # global-norm clip + Adam
    gnorm = jnp.sqrt(
        sum(jnp.sum(g**2) for g in jax.tree.leaves(grads))
    )
    scale = jnp.minimum(1.0, cfg.max_grad_norm / (gnorm + 1e-8))
    grads = jax.tree.map(lambda g: g * scale, grads)

    step, m, v = opt_state
    step = step + 1
    b1, b2, eps = 0.9, 0.999, 1e-8
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    mhat = jax.tree.map(lambda m_: m_ / (1 - b1**step), m)
    vhat = jax.tree.map(lambda v_: v_ / (1 - b2**step), v)
    params = jax.tree.map(
        lambda p, mh, vh: p - cfg.lr * mh / (jnp.sqrt(vh) + eps), params, mhat, vhat
    )
    return params, (step, m, v), loss, aux


@dataclass
class PPOState:
    params: dict                 # best-seen policy (by rollout reward)
    final_params: dict           # last-iteration policy
    opt_state: tuple
    history: List[dict]
    best_reward: float = float("-inf")


def train_ppo_pool(
    env: Union[PoolServingEnv, ServingEnv],
    cfg: PPOConfig = PPOConfig(),
    *,
    verbose: bool = False,
    jax_rollouts: bool = False,
    full_zoo: bool = False,
    log_path: Optional[str] = None,
) -> PPOState:
    """Train the pool controller with batched ``[T, A]`` rollouts.

    ``jax_rollouts=True`` swaps the step-wise env loop for
    :func:`collect_rollouts_jax`: each iteration collects exactly one
    full episode in a single jitted dispatch (``cfg.rollout_len`` is
    superseded by the episode length on that path); the update math is
    identical.

    ``full_zoo=True`` (requires ``jax_rollouts`` and a scenario pool)
    swaps the per-iteration scenario *sample* for the whole pool:
    :func:`collect_rollouts_jax_zoo` runs every scenario as a cell of
    one vmapped engine dispatch and each update trains on the merged
    ``[T, S*A]`` batch.

    ``log_path`` streams the per-iteration training curve (reward,
    loss components, entropy, approx-KL — the fields ``history`` keeps)
    to a JSONL file as it trains, e.g.
    ``artifacts/rl/training_log.jsonl``.
    """
    if isinstance(env, ServingEnv):
        env = env.pool
    assert not full_zoo or (jax_rollouts and env.scenarios), (
        "full_zoo needs jax_rollouts=True and a scenario pool"
    )
    A = env.n_archs
    key = jax.random.key(cfg.seed)
    key, knet = jax.random.split(key)
    params = init_net(knet, cfg)
    opt_state = (jnp.zeros((), jnp.int32),
                 jax.tree.map(jnp.zeros_like, params),
                 jax.tree.map(jnp.zeros_like, params))

    obs = env.reset()
    history: List[dict] = []
    ep_reward, ep_rewards = 0.0, []
    best_reward, best_params = float("-inf"), params
    log = JsonlWriter(log_path) if log_path else None

    for it in range(cfg.iterations):
        if jax_rollouts:
            key, kroll = jax.random.split(key)
            buf = (collect_rollouts_jax_zoo(env, params, kroll) if full_zoo
                   else collect_rollouts_jax(env, params, kroll))
            obs_buf, act_buf = buf["obs"], buf["actions"]
            logp_buf, val_buf = buf["logp"], buf["values"]
            rew_buf, done_buf = buf["rewards"], buf["dones"]
            T = rew_buf.shape[0]
            last_v = buf["last_value"]
            ep_rewards.append(float(rew_buf.sum()))
        else:
            T = cfg.rollout_len
            obs_buf = np.zeros((T, A, OBS_DIM), np.float32)
            act_buf = np.zeros((T, A), np.int32)
            logp_buf = np.zeros((T, A), np.float32)
            val_buf = np.zeros((T, A), np.float32)
            rew_buf = np.zeros((T, A), np.float32)
            done_buf = np.zeros((T,), np.float32)

            for t in range(T):
                key, kact = jax.random.split(key)
                a, logp, v = pool_policy_action(params, obs, kact)
                obs_buf[t], act_buf[t], logp_buf[t], val_buf[t] = (
                    obs, a, logp, v
                )
                obs, r_arch, done, _ = env.step(a)
                rew_buf[t], done_buf[t] = r_arch, float(done)
                ep_reward += float(r_arch.sum())
                if done:
                    ep_rewards.append(ep_reward)
                    ep_reward = 0.0
                    obs = env.reset()

            _, last_v = policy_logits_value(params, jnp.asarray(obs))
        adv, rets = compute_gae_pool(
            rew_buf, val_buf, done_buf, np.asarray(last_v, np.float32),
            cfg.gamma, cfg.gae_lambda,
        )
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)

        # flatten [T, W] -> [T*W] and update on shuffled minibatches
        # (W = A, or S*A when a full-zoo batch merged the cell axis)
        W = obs_buf.shape[1]
        flat = {
            "obs": obs_buf.reshape(T * W, OBS_DIM),
            "actions": act_buf.reshape(T * W),
            "logp_old": logp_buf.reshape(T * W),
            "adv": adv.reshape(T * W),
            "returns": rets.reshape(T * W),
        }
        idx = np.arange(T * W)
        rng = np.random.default_rng(cfg.seed + it)
        mb_stats = []          # device scalars; one host sync per iteration
        for _ in range(cfg.epochs):
            rng.shuffle(idx)
            for mb in np.array_split(idx, cfg.minibatches):
                batch = {k: jnp.asarray(v[mb]) for k, v in flat.items()}
                params, opt_state, loss, aux = ppo_update(
                    params, opt_state, batch, cfg
                )
                mb_stats.append(jnp.stack([
                    loss, aux["pi_loss"], aux["v_loss"], aux["entropy"],
                    aux["approx_kl"],
                ]))
        it_mean = np.asarray(jnp.stack(mb_stats)).mean(axis=0)

        roll_r = float(rew_buf.sum())
        if roll_r > best_reward:
            # PPO can catastrophically forget a good procurement policy on a
            # later unlucky rollout; keep the best-seen snapshot.
            best_reward = roll_r
            best_params = jax.tree.map(lambda x: x, params)

        mean_ep = float(np.mean(ep_rewards[-5:])) if ep_rewards else float("nan")
        history.append(
            {
                "iter": it,
                "rollout_reward": roll_r,
                "mean_episode_reward": mean_ep,
                # last-minibatch values (seed-era fields), plus the
                # iteration means the telemetry curve tracks
                "loss": float(loss),
                "entropy": float(aux["entropy"]),
                "loss_mean": float(it_mean[0]),
                "pi_loss": float(it_mean[1]),
                "v_loss": float(it_mean[2]),
                "entropy_mean": float(it_mean[3]),
                "approx_kl": float(it_mean[4]),
            }
        )
        if log is not None:
            log.write(history[-1])
        if verbose and it % 5 == 0:
            print(
                f"[ppo] it={it:3d} rollout_r={roll_r:9.4f} "
                f"ep_r={mean_ep:9.3f} H={history[-1]['entropy']:.3f}",
                flush=True,
            )
    if log is not None:
        log.close()
    return PPOState(
        params=best_params,
        final_params=params,
        opt_state=opt_state,
        history=history,
        best_reward=best_reward,
    )


def train_ppo(env: ServingEnv, cfg: PPOConfig = PPOConfig(), *,
              verbose: bool = False) -> PPOState:
    """Seed entry point: single-arch training is the A=1 pool path."""
    return train_ppo_pool(env, cfg, verbose=verbose)


def evaluate_pool_policy(env: PoolServingEnv, params, *,
                         arrivals=None, greedy: bool = False, seed: int = 1):
    """Run one full pool episode; return the SimResult.

    Stochastic evaluation (the default) is the trained object: the policy
    hedges between procurement modes tick-by-tick, and argmax-collapsing
    it discards the offload behaviour it actually learned."""
    key = jax.random.key(seed)
    obs = env.reset(arrivals)
    done = False
    while not done:
        logits, _ = policy_logits_value(params, jnp.asarray(obs))
        if greedy:
            a = np.asarray(jnp.argmax(logits, axis=-1))
        else:
            key, k = jax.random.split(key)
            a = np.asarray(jax.random.categorical(k, logits))
        obs, _, done, _ = env.step(a)
    return env.episode_result()


def evaluate_policy(env: ServingEnv, params, *, greedy: bool = False, seed: int = 1):
    """Single-arch evaluation (seed interface)."""
    return evaluate_pool_policy(env.pool, params, greedy=greedy, seed=seed)
