"""Prefill + decode against full-sequence forward — the serving-engine
correctness contract, including sliding-window ring caches and enc-dec."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import model as model_lib

DECODE_ARCHS = [
    "llama3-8b", "qwen1.5-0.5b", "qwen2-72b", "minicpm-2b",
    "phi3.5-moe-42b-a6.6b", "rwkv6-1.6b", "recurrentgemma-9b",
    "whisper-small", "kimi-k2-1t-a32b",
]


def _setup(arch, b=2, s=20, seed=0):
    cfg = get_config(arch).reduced()
    params = model_lib.init_params(cfg, jax.random.key(seed))
    toks = jax.random.randint(jax.random.key(seed + 1), (b, s), 0, cfg.vocab_size)
    enc = None
    if cfg.is_encoder_decoder:
        enc = jax.random.normal(
            jax.random.key(seed + 2), (b, cfg.encoder_seq, cfg.d_model)
        )
    return cfg, params, toks, enc


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_decode_matches_forward(arch):
    cfg, params, toks, enc = _setup(arch)
    b, s = toks.shape
    cache = model_lib.init_cache(cfg, b, 32)
    last, cache = model_lib.prefill(cfg, params, toks, cache, enc_inputs=enc)
    nxt = jnp.argmax(last, -1).astype(jnp.int32)
    dl, cache = model_lib.decode_step(cfg, params, nxt, cache)
    ext = jnp.concatenate([toks, nxt[:, None]], 1)
    full, _ = model_lib.forward(cfg, params, ext, enc_inputs=enc)
    err = float(jnp.max(jnp.abs(dl - full[:, -1])))
    assert err < 5e-3, (arch, err)


def test_multi_token_decode_chain():
    cfg, params, toks, _ = _setup("qwen1.5-0.5b")
    b, s = toks.shape
    cache = model_lib.init_cache(cfg, b, 40)
    last, cache = model_lib.prefill(cfg, params, toks, cache)
    seq = [jnp.argmax(last, -1).astype(jnp.int32)]
    for _ in range(4):
        dl, cache = model_lib.decode_step(cfg, params, seq[-1], cache)
        seq.append(jnp.argmax(dl, -1).astype(jnp.int32))
    # greedy rollout with full forward must agree
    cur = toks
    for i in range(5):
        full, _ = model_lib.forward(cfg, params, cur)
        nxt = jnp.argmax(full[:, -1], -1).astype(jnp.int32)
        assert bool(jnp.all(nxt == seq[i])), f"divergence at step {i}"
        cur = jnp.concatenate([cur, nxt[:, None]], 1)


def test_sliding_window_ring_cache():
    cfg, params, toks, _ = _setup("llama3-8b")
    W = 8
    b, s = toks.shape
    ref, _ = model_lib.forward(cfg, params, toks, window=W)

    # ring cache exactly the window size, smaller than the prompt
    cache = model_lib.init_cache(cfg, b, W, window=W)
    last, cache = model_lib.prefill(cfg, params, toks, cache, window=W)
    err = float(jnp.max(jnp.abs(last - ref[:, -1])))
    assert err < 5e-3, err

    nxt = jnp.argmax(last, -1).astype(jnp.int32)
    dl, cache = model_lib.decode_step(cfg, params, nxt, cache, window=W)
    ext = jnp.concatenate([toks, nxt[:, None]], 1)
    ref2, _ = model_lib.forward(cfg, params, ext, window=W)
    err2 = float(jnp.max(jnp.abs(dl - ref2[:, -1])))
    assert err2 < 5e-3, err2


def test_long_context_window_decode_rgemma():
    """Hybrid arch: RG-LRU state + local-attention ring must chain."""
    cfg, params, toks, _ = _setup("recurrentgemma-9b", s=24)
    b = toks.shape[0]
    cache = model_lib.init_cache(cfg, b, 16)
    last, cache = model_lib.prefill(cfg, params, toks, cache)
    nxt = jnp.argmax(last, -1).astype(jnp.int32)
    for _ in range(3):
        dl, cache = model_lib.decode_step(cfg, params, nxt, cache)
        nxt = jnp.argmax(dl, -1).astype(jnp.int32)
        assert not bool(jnp.any(jnp.isnan(dl)))


def test_whisper_cross_attention_cache():
    cfg, params, toks, enc = _setup("whisper-small", s=12)
    b = toks.shape[0]
    cache = model_lib.init_cache(cfg, b, 24)
    last, cache = model_lib.prefill(cfg, params, toks, cache, enc_inputs=enc)
    assert "cross" in cache
    nxt = jnp.argmax(last, -1).astype(jnp.int32)
    dl, cache = model_lib.decode_step(cfg, params, nxt, cache)
    ext = jnp.concatenate([toks, nxt[:, None]], 1)
    full, _ = model_lib.forward(cfg, params, ext, enc_inputs=enc)
    err = float(jnp.max(jnp.abs(dl - full[:, -1])))
    assert err < 5e-3, err
