"""Smoke run of the main path on a TPU, through the entry points a user calls.

  python chip_smoke.py               # one chip: kernels, serving, control plane
  python chip_smoke.py --four-chips  # only the sharded run_grid over four chips

One chip runs, in one process and in this order:

1. the device check (fails unless JAX's first device is a TPU);
2. each Pallas kernel once at real widths, against its ``kernels/ref.py``
   oracle at highest matmul precision;
3. the serving engine: full-width qwen1.5-0.5b (f32 weights from
   ``--seed``) behind ``ContinuousBatcher``, 16 requests of 128 prompt
   tokens, with one request's logits checked against ``model.forward``;
4. the control plane: the portfolio scan at A=1024, T=3600 against the
   NumPy ``ServingSim``; ``run_grid`` over the 7 zoo scenarios against
   per-cell ``run_scenario``; one full-zoo PPO iteration.

Times printed on the way are informal (host clock, compile included
where said).  The last line of standard output is one JSON object naming
the device; it is printed only after every phase passed.  Any failure
raises, and the exit code is non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# --- tolerances, each with its reason ---------------------------------------
# The largest errors one v5e chip showed are in brackets.
# Attention kernels: the kernels and the oracle both run their matmuls at
# f32 (HIGHEST) precision and accumulate in f32; what is left is the
# order of the online-softmax sums over 512 keys on O(1) outputs.
# [flash 1.2e-6, decode 1.2e-7]
ATTN_ATOL = 1e-5
# RWKV6: the chunked form sums in a different order from the sequential
# oracle over 512 steps, and its within-chunk cumulative decay is a
# matmul; relative to the output and state scale.  [2.8e-5]
RWKV_RTOL = 2e-4
# Serving logits: the engine runs at the default TPU matmul precision (one
# bf16 pass per f32 matmul, 8 mantissa bits) through 24 layers, while the
# reference runs at HIGHEST; relative to the largest reference logit.
# [7.3e-3]
LOGITS_RTOL = 3e-2
# Control plane: the scan runs in float64, which the TPU emulates, and the
# NumPy engine in IEEE float64.  Flow counts are fractional request masses
# (arrivals are rates), summed over 3600 ticks; costs are sums of
# products.  Relative to max(|value|, 1).  [flows 3.3e-12, costs 1.0e-13]
FLOW_RTOL = 1e-10
COST_RTOL = 1e-11
# run_grid and run_scenario are the same scan, vmapped or not.  [0.0]
GRID_RTOL = 1e-12

ARCH = "qwen1.5-0.5b"
SLOTS, CACHE_LEN, REQUESTS, PROMPT_LEN, NEW_TOKENS = 8, 512, 16, 128, 32
SCAN_A, GRID_A, T = 1024, 64, 3600
PPO_DURATION_S = 900

_FLOW_KEYS = ("served_vm", "served_burst", "dropped", "violations",
              "expired_end", "queued", "acc_violations")


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1.0)))


def check(name: str, err: float, tol: float) -> float:
    log(f"[check] {name}: max error {err!r} (tolerance {tol!r})")
    assert err <= tol, f"{name}: error {err!r} above tolerance {tol!r}"
    return err


def timed(label: str, fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    log(f"[time] {label}: {time.perf_counter() - t0:.3f} s (informal)")
    return out


# ---------------------------------------------------------------------------
def device_check(count: int) -> dict:
    devices = jax.devices()
    dev = devices[0]
    log(f"[device] platform={dev.platform} kind={dev.device_kind} count={len(devices)}")
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {dev.platform!r}")
    if len(devices) != count:
        raise SystemExit(f"expected {count} TPU devices, found {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


# ---------------------------------------------------------------------------
def phase_kernels(interpret: bool = False, *, sq: int = 512, heads: int = 16,
                  hd: int = 64, slots: int = SLOTS, cache: int = CACHE_LEN,
                  rwkv_heads: int = 32) -> None:
    """Each Pallas kernel once, at qwen1.5-0.5b / rwkv6-1.6b widths."""
    from repro.kernels import ref
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.rwkv6_scan import rwkv6_chunked

    ks = jax.random.split(jax.random.key(0), 12)
    q, k, v = (jax.random.normal(ks[i], (1, sq, heads, hd)) for i in range(3))
    fa = jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=interpret))
    out = timed("flash_attention compile+run", fa, q, k, v)
    with jax.default_matmul_precision("highest"):
        want = ref.mha_reference(q, k, v, causal=True)
    check("flash_attention", float(jnp.max(jnp.abs(out - want))), ATTN_ATOL)

    qd = jax.random.normal(ks[3], (slots, heads, hd))
    kc, vc = (jax.random.normal(ks[4 + i], (slots, cache, heads, hd)) for i in range(2))
    valid = (jax.random.uniform(ks[6], (slots, cache)) < 0.7).at[:, 0].set(True)
    da = jax.jit(lambda *a: decode_attention(*a, interpret=interpret))
    out = timed("decode_attention compile+run", da, qd, kc, vc, valid)
    with jax.default_matmul_precision("highest"):
        want = ref.decode_attention_reference(qd, kc, vc, valid)
    check("decode_attention", float(jnp.max(jnp.abs(out - want))), ATTN_ATOL)

    shape = (1, sq, rwkv_heads, hd)
    r = jax.random.normal(ks[7], shape) * 0.5
    kr = jax.random.normal(ks[8], shape) * 0.5
    vr = jax.random.normal(ks[9], shape)
    w = jax.nn.sigmoid(jax.random.normal(ks[10], shape) * 2 - 1) * 0.5 + 0.45
    u = jax.random.normal(ks[11], (rwkv_heads, hd)) * 0.3
    rw = jax.jit(lambda *a: rwkv6_chunked(*a, interpret=interpret))
    out, s_t = timed("rwkv6_chunked compile+run", rw, r, kr, vr, w, u)
    with jax.default_matmul_precision("highest"):
        want, want_s = ref.rwkv6_reference(r, kr, vr, w, u)
    scale = max(float(jnp.max(jnp.abs(want))), float(jnp.max(jnp.abs(want_s))), 1.0)
    err = max(float(jnp.max(jnp.abs(out - want))),
              float(jnp.max(jnp.abs(s_t - want_s)))) / scale
    check("rwkv6_chunked (relative)", err, RWKV_RTOL)


# ---------------------------------------------------------------------------
def phase_serving(cfg, seed: int, *, slots: int = SLOTS, cache_len: int = CACHE_LEN,
                  requests: int = REQUESTS, prompt_len: int = PROMPT_LEN,
                  new_tokens: int = NEW_TOKENS):
    """Engine + ContinuousBatcher as ``repro.launch.serve`` drives them;
    returns the engine."""
    from repro.models import model as model_lib
    from repro.serving import ContinuousBatcher, Engine, EngineConfig, Request

    t0 = time.perf_counter()
    params = model_lib.init_params(cfg, jax.random.key(seed))
    jax.block_until_ready(params)
    log(f"[time] init_params {cfg.name} (L={cfg.num_layers} d={cfg.d_model} "
        f"vocab={cfg.vocab_size}): {time.perf_counter() - t0:.3f} s (informal)")
    engine = Engine(cfg, params, EngineConfig(
        slots=slots, cache_len=cache_len, max_new_tokens=new_tokens))

    # record what the engine's own jitted steps return for request 0
    # (admitted first, so it holds slot 0 until it finishes)
    seen = {"prefill": None, "decode": []}
    prefill_one, decode = engine._prefill_one, engine._decode

    def prefill_rec(params, tokens, cache1):
        logits, cache1 = prefill_one(params, tokens, cache1)
        if seen["prefill"] is None:
            seen["prefill"] = logits[0]
        return logits, cache1

    def decode_rec(params, tokens, cache):
        logits, cache = decode(params, tokens, cache)
        if len(seen["decode"]) < new_tokens:
            seen["decode"].append(logits[0])
        return logits, cache

    engine._prefill_one, engine._decode = prefill_rec, decode_rec

    batcher = ContinuousBatcher(engine)
    rng = np.random.default_rng(seed)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=prompt_len)
                .astype(np.int32), max_new_tokens=new_tokens)
        for i in range(requests)
    ]
    for req in reqs:
        batcher.submit(req)
    t0 = time.perf_counter()
    batcher.run_step()
    log(f"[time] first batcher step ({slots} prefills + 1 decode, compile "
        f"included): {time.perf_counter() - t0:.3f} s (informal)")
    t0 = time.perf_counter()
    stats = batcher.run_until_idle()
    log(f"[time] rest of the run: {time.perf_counter() - t0:.3f} s (informal)")
    log(f"[serving] {stats.summary()}")
    assert stats.finished == requests, stats.summary()
    assert all(r.finished and len(r.output) == new_tokens + 1 for r in reqs)

    # the reference: one full forward over the prompt and the tokens the
    # engine fed back, at highest precision on the jnp path
    first = reqs[0]
    tokens = np.concatenate([first.prompt, np.asarray(first.output[:-1], np.int32)])
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, x: model_lib.forward(cfg, p, x, impl="xla")[0])
        want = timed("reference forward compile+run", fwd, params,
                     jnp.asarray(tokens)[None])[0, prompt_len - 1:]
    got = jnp.stack([seen["prefill"]] + seen["decode"])
    assert got.shape == want.shape, (got.shape, want.shape)
    assert bool(jnp.all(jnp.isfinite(got)))
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    log(f"[serving] logits scale {float(jnp.max(jnp.abs(want)))!r}")
    check("serving logits vs forward (relative)", err, LOGITS_RTOL)
    engine._prefill_one, engine._decode = prefill_one, decode
    return engine


def assert_kernels_compiled_in(engine) -> None:
    """The engine's jitted prefill and decode both hold Pallas kernels."""
    from repro.models import model as model_lib

    ecfg = engine.ecfg
    cache1 = model_lib.init_cache(engine.cfg, 1, ecfg.cache_len,
                                  window=ecfg.window, dtype=ecfg.dtype)
    prompt = jnp.zeros((1, PROMPT_LEN), jnp.int32)
    tokens = jnp.zeros((ecfg.slots,), jnp.int32)
    for name, lowered in (
        ("prefill", engine._prefill_one.lower(engine.params, prompt, cache1)),
        ("decode", engine._decode.lower(engine.params, tokens, engine.cache)),
    ):
        assert "tpu_custom_call" in lowered.as_text(), f"no kernel in {name}"
    log("[serving] prefill and decode run the Pallas kernels")


# ---------------------------------------------------------------------------
def _pool(A: int):
    from benchmarks.common import MEAN_RPS, SERVING_POOL, STRICT_FRAC
    from repro.core.sim import replicate_pool

    # per-arch demand held at the benchmarks' 8-arch level
    return (replicate_pool(SERVING_POOL, A, strict_frac=STRICT_FRAC),
            MEAN_RPS * A / len(SERVING_POOL))


def _costs(totals) -> dict:
    return {k: float(totals[k]) for k in
            ("cost_res", "cost_spot", "cost_burst", "cost_harv", "cost_rem",
             "chip", "need", "over")}


def phase_scan(seed: int, *, A: int = SCAN_A, T: int = T) -> None:
    """``run_scenario("portfolio")`` against the NumPy engine."""
    from repro.core.schedulers import VECTOR_SCHEDULERS
    from repro.core.sim import ServingSim
    from repro.core.sim import jax_engine as je
    from repro.core.workloads import SCENARIO_ZOO

    wl, rps = _pool(A)
    arr = SCENARIO_ZOO["shared_berkeley"].build(A, duration_s=T, mean_rps=rps)
    out = timed(f"run_scenario portfolio A={A} T={T} (compile included)",
                je.run_scenario, arr, wl, "portfolio", seed=seed)
    t0 = time.perf_counter()
    sim = ServingSim(arr, wl, seed=seed)
    pol = VECTOR_SCHEDULERS["portfolio"]()
    while not sim.done:
        sim.apply_pool(pol(sim.tick, sim.observe_pool()))
    log(f"[time] NumPy ServingSim A={A} T={T}: {time.perf_counter() - t0:.3f} s (informal)")
    res, counts = sim.res, sim.per_arch_counts()
    log(f"[scan] jax   {out['summary']}")
    log(f"[scan] numpy {res.summary()}")
    assert set(out["summary"]) == set(res.summary())
    flow = max(rel_err(out["per_arch"][k], counts[k]) for k in _FLOW_KEYS)
    flow = max(flow, rel_err(out["raw"]["totals"]["preempt"], res.preemptions))
    check("scan flow counts vs NumPy", flow, FLOW_RTOL)
    got = _costs(out["raw"]["totals"])
    want = {
        "cost_res": res.cost_reserved, "cost_spot": res.cost_spot,
        "cost_burst": res.cost_burst,
        "cost_harv": res.cost_other.get("harvest", 0.0),
        "cost_rem": res.cost_other.get("remote", 0.0),
        "chip": res.chip_seconds, "need": res.chip_seconds_needed,
        "over": res.chip_seconds_over,
    }
    check("scan costs vs NumPy", max(rel_err(got[k], want[k]) for k in want), COST_RTOL)


def phase_grid(seed: int, *, A: int = GRID_A, T: int = T) -> None:
    """``run_grid`` over the zoo against ``run_scenario`` per cell."""
    from repro.core.sim import jax_engine as je
    from repro.core.workloads import SCENARIO_ZOO

    wl, rps = _pool(A)
    zoo = list(SCENARIO_ZOO.values())
    arrs = np.stack([sc.build(A, duration_s=T, mean_rps=rps) for sc in zoo])
    seeds = [seed + i for i in range(len(zoo))]
    cells = timed(f"run_grid {len(zoo)} cells A={A} T={T} (compile included)",
                  je.run_grid, arrs, wl, "portfolio", seeds=seeds)
    t0 = time.perf_counter()
    singles = [je.run_scenario(arrs[i], wl, "portfolio", seed=seeds[i])
               for i in range(len(zoo))]
    log(f"[time] {len(zoo)} run_scenario cells: {time.perf_counter() - t0:.3f} s (informal)")
    err = 0.0
    for sc, cell, one in zip(zoo, cells, singles):
        assert set(cell["summary"]) == set(one["summary"]), sc.name
        a, b = cell["raw"]["totals"], one["raw"]["totals"]
        err = max(err, max(rel_err(a[k], b[k]) for k in a))
    check("run_grid cells vs run_scenario", err, GRID_RTOL)


def phase_ppo(seed: int, *, A: int = GRID_A, duration_s: int = PPO_DURATION_S) -> None:
    """One full-zoo PPO iteration: ``collect_rollouts_jax_zoo``, then
    ``ppo_update`` on the whole merged batch."""
    from repro.core.rl.env import OBS_DIM, EnvConfig, PoolServingEnv
    from repro.core.rl.ppo import (
        PPOConfig, collect_rollouts_jax_zoo, compute_gae_pool, init_net, ppo_update,
    )
    from repro.core.workloads import SCENARIO_ZOO

    wl, rps = _pool(A)
    env = PoolServingEnv(wl, EnvConfig(mean_rps=rps, duration_s=duration_s),
                         scenarios=list(SCENARIO_ZOO.values()), scenario_seed=seed)
    cfg = PPOConfig(seed=seed)
    key, knet = jax.random.split(jax.random.key(seed))
    params = init_net(knet, cfg)
    buf = timed("collect_rollouts_jax_zoo (compile included)",
                collect_rollouts_jax_zoo, env, params, key)
    S = len(SCENARIO_ZOO)
    W = S * A
    shapes = {"obs": (duration_s, W, OBS_DIM), "actions": (duration_s, W),
              "logp": (duration_s, W), "values": (duration_s, W),
              "rewards": (duration_s, W), "dones": (duration_s,),
              "last_value": (W,)}
    for k, shape in shapes.items():
        assert buf[k].shape == shape, (k, buf[k].shape, shape)
        assert np.all(np.isfinite(buf[k])), k
    adv, rets = compute_gae_pool(buf["rewards"], buf["values"], buf["dones"],
                                 buf["last_value"], cfg.gamma, cfg.gae_lambda)
    adv = (adv - adv.mean()) / (adv.std() + 1e-8)
    n = duration_s * W
    batch = {"obs": buf["obs"].reshape(n, OBS_DIM),
             "actions": buf["actions"].reshape(n),
             "logp_old": buf["logp"].reshape(n),
             "adv": adv.reshape(n), "returns": rets.reshape(n)}
    opt_state = (jnp.zeros((), jnp.int32), jax.tree.map(jnp.zeros_like, params),
                 jax.tree.map(jnp.zeros_like, params))
    _, _, loss, aux = timed("ppo_update (compile included)", ppo_update,
                            params, opt_state, batch, cfg)
    losses = {"loss": float(loss), **{k: float(v) for k, v in aux.items()}}
    log(f"[ppo] buffers [T={duration_s}, S*A={W}] {losses}")
    assert all(np.isfinite(v) for v in losses.values()), losses


# ---------------------------------------------------------------------------
def phase_four_chips(seed: int, *, A: int = GRID_A, T: int = T, cells: int = 8) -> None:
    """An 8-cell ``run_grid`` sharded over every device against the same
    grid in one unsharded dispatch."""
    from repro.core.sim import jax_engine as je
    from repro.core.workloads import SCENARIO_ZOO

    wl, rps = _pool(A)
    zoo = list(SCENARIO_ZOO.values())
    scs = [zoo[i % len(zoo)] for i in range(cells)]
    arrs = np.stack([sc.build(A, seed=sc.seed + i, duration_s=T, mean_rps=rps)
                     for i, sc in enumerate(scs)])
    seeds = [seed + i for i in range(cells)]
    sh = timed(f"run_grid sharded {cells} cells A={A} T={T} (compile included)",
               je.run_grid, arrs, wl, "portfolio", seeds=seeds, sharded=True)
    un = timed(f"run_grid unsharded {cells} cells (compile included)",
               je.run_grid, arrs, wl, "portfolio", seeds=seeds, sharded=False)
    ndev = len(jax.devices())
    assert all(c["devices"] == ndev for c in sh), [c["devices"] for c in sh]
    assert all(c["devices"] == 1 for c in un), [c["devices"] for c in un]
    log(f"[grid4] sharded output spread over {sh[0]['devices']} devices")
    for i, (a, b) in enumerate(zip(sh, un)):
        assert a["summary"] == b["summary"], (i, a["summary"], b["summary"])
    log(f"[grid4] {cells} cell summaries identical sharded and unsharded")


# ---------------------------------------------------------------------------
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the run_grid sharded over four chips")
    args = ap.parse_args()

    device = device_check(4 if args.four_chips else 1)
    from repro.launch.compile_cache import enable_compile_cache

    log(f"[cache] compilation cache at {enable_compile_cache()}")
    t_all = time.perf_counter()
    if args.four_chips:
        phase_four_chips(args.seed)
    else:
        from repro.configs import get_config
        from repro.kernels import ops

        assert ops.default_impl() == "pallas", ops.default_impl()
        phase_kernels()
        engine = phase_serving(get_config(ARCH), args.seed)
        assert_kernels_compiled_in(engine)
        del engine
        phase_scan(args.seed)
        phase_grid(args.seed)
        phase_ppo(args.seed)
    log(f"[time] all phases: {time.perf_counter() - t_all:.3f} s (informal)")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
