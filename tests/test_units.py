"""Small-unit coverage: load monitor, ops dispatch, pricing, frontends."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hardware import PRICING, V5E
from repro.core.load_monitor import LoadMonitor
from repro.kernels import ops, ref
from repro.models import frontends
from repro.configs import get_config


# ---------------------------------------------------------------------------
# LoadMonitor.
# ---------------------------------------------------------------------------
def test_monitor_flat_stream_not_bursty():
    m = LoadMonitor(window_s=50)
    for _ in range(100):
        m.observe(10.0)
    assert m.peak_to_median == pytest.approx(1.0)
    assert not m.bursty()
    assert m.rate == pytest.approx(10.0)


def test_monitor_spike_detected():
    m = LoadMonitor(window_s=100)
    for _ in range(80):
        m.observe(10.0)
    for _ in range(5):
        m.observe(50.0)
    assert m.peak_to_median > 1.5
    assert m.bursty()


def test_monitor_window_slides():
    m = LoadMonitor(window_s=10)
    for _ in range(20):
        m.observe(100.0)
    for _ in range(10):
        m.observe(1.0)
    # the spike has left the window entirely
    assert m.peak == pytest.approx(1.0)


# (test_monitor_peak_bounds_median moved to test_properties.py)


# ---------------------------------------------------------------------------
# ops dispatch.
# ---------------------------------------------------------------------------
def test_default_impl_switch():
    """The platform picks the path: the jnp reference off the chip, and
    the kernel whenever a caller names it."""
    assert ops.default_impl() == "xla"
    q = jax.random.normal(jax.random.key(0), (1, 32, 2, 16))
    out = ops.flash_attention(q, q, q, causal=True, impl="pallas_interpret")
    exp = ref.mha_reference(q, q, q, causal=True)
    assert float(jnp.max(jnp.abs(out - exp))) < 1e-4


def test_invalid_impl_rejected():
    q = jnp.zeros((1, 8, 2, 16))
    with pytest.raises(AssertionError):
        ops.flash_attention(q, q, q, impl="cuda")


def test_blocked_dispatch_only_when_profitable():
    """window >= S/2 (not profitable) must use the plain masked path and
    still be exact."""
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, 32, 2, 16))
    k = jax.random.normal(ks[1], (1, 32, 2, 16))
    v = jax.random.normal(ks[2], (1, 32, 2, 16))
    got = ops.flash_attention(q, k, v, causal=True, window=24)
    exp = ref.mha_reference(q, k, v, causal=True, window=24)
    assert float(jnp.max(jnp.abs(got - exp))) < 1e-5


# ---------------------------------------------------------------------------
# Pricing sanity.
# ---------------------------------------------------------------------------
def test_pricing_relationships():
    assert PRICING.burst_chip_s > PRICING.reserved_chip_s
    assert PRICING.spot_discount < 1.0
    assert PRICING.burst_spinup_s < PRICING.reserved_provision_s
    assert V5E.peak_flops_bf16 / V5E.hbm_bandwidth > 100  # ops:byte ridge


# ---------------------------------------------------------------------------
# Frontends.
# ---------------------------------------------------------------------------
def test_vision_embeddings_deterministic_and_scaled():
    cfg = get_config("llava-next-mistral-7b").reduced()
    a = frontends.vision_embeddings(cfg, 2, tiles=2, seed=5)
    b = frontends.vision_embeddings(cfg, 2, tiles=2, seed=5)
    assert np.array_equal(a, b)
    assert a.shape == (2, 2 * frontends.VLM_BASE_PATCHES, cfg.d_model)
    # unit-RMS rows
    rms = np.sqrt((a ** 2).sum(-1).mean())
    assert 0.8 < rms < 1.2


def test_frontend_type_guards():
    lm = get_config("llama3-8b").reduced()
    with pytest.raises(AssertionError):
        frontends.vision_embeddings(lm, 1)
    with pytest.raises(AssertionError):
        frontends.audio_frames(lm, 1)
