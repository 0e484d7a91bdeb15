"""Serving driver.

Runs the continuous-batching engine for any registered architecture on
the first device JAX finds.  On a TPU the attention kernels are the
Pallas ones; elsewhere their jnp references run.  ``--reduced`` serves
the small smoke variant, the size for a CPU; the full config of a model
that fits one chip (qwen1.5-0.5b, as in ``chip_smoke.py``) serves
unsharded on one v5e.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --reduced \
      --requests 16 --slots 4 --max-new 8
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import model as model_lib
from repro.serving import ContinuousBatcher, Engine, EngineConfig, Request


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", default=False)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.frontend == "vision":
        raise SystemExit(
            "vision archs serve via embeddings; see examples/quickstart.py"
        )

    print(f"[serve] {cfg.name}: L={cfg.num_layers} d={cfg.d_model} "
          f"params={cfg.params_total/1e6:.1f}M", flush=True)
    params = model_lib.init_params(cfg, jax.random.key(args.seed))
    engine = Engine(cfg, params, EngineConfig(
        slots=args.slots, cache_len=args.cache_len, max_new_tokens=args.max_new
    ))
    batcher = ContinuousBatcher(engine)

    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        plen = int(rng.integers(max(2, args.prompt_len // 2), args.prompt_len + 1))
        prompt = rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
        batcher.submit(Request(rid=i, prompt=prompt, max_new_tokens=args.max_new))

    t0 = time.perf_counter()
    stats = batcher.run_until_idle()
    wall = time.perf_counter() - t0
    s = stats.summary()
    toks = s["finished"] * args.max_new
    print(f"[serve] {s}")
    print(f"[serve] {toks} tokens in {wall:.2f}s = {toks / wall:.1f} tok/s "
          f"({s['decode_steps']} decode steps)")


if __name__ == "__main__":
    main()
