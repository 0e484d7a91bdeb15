"""Compile the chip's hot paths for a described TPU v5e, without a chip.

The TPU compiler refuses what interpret mode accepts: block shapes off
the (8, 128) tiling, 1-D or scalar VMEM scratch, too much fast memory.
These tests compile the three Pallas kernels at real widths, the
portfolio scan runner and the paragon grid runner (with its device
monitor pass) for one chip of a described ``v5e:2x2`` topology,
so such a refusal shows up here rather than on the chip.  The topology
is described inside a fixture only: describing it loads the TPU library,
which one process at a time may hold.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rwkv6_scan import rwkv6_chunked


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


# (kernel, argument shapes): qwen1.5-0.5b widths (16 heads of 64, 512
# tokens; 8 decode slots over a 512-token cache) and rwkv6-1.6b widths
# (32 heads of 64)
KERNELS = {
    "flash_attention": (
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        [(1, 512, 16, 64)] * 3,
    ),
    "decode_attention": (
        decode_attention,
        [(8, 16, 64), (8, 512, 16, 64), (8, 512, 16, 64), ((8, 512), jnp.bool_)],
    ),
    "rwkv6_chunked": (
        rwkv6_chunked,
        [(1, 512, 32, 64)] * 4 + [(32, 64), (1, 32, 64, 64)],
    ),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    args = [
        _spec(one_chip, *s) if isinstance(s[0], tuple) else _spec(one_chip, s)
        for s in shapes
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_portfolio_scan_compiles_for_v5e(one_chip):
    """The float64 portfolio scan at A=64, T=600: the chip emulates f64,
    and its compiler must still take the whole runner."""
    from repro.core.sim import jax_engine as je
    from repro.core.sim.types import replicate_pool
    from repro.core.workloads import SCENARIO_ZOO

    A, T = 64, 600
    wl = replicate_pool(["llama3-8b", "qwen1.5-0.5b", "rwkv6-1.6b"], A, 0.25)
    arr = SCENARIO_ZOO["shared_berkeley"].build(A, duration_s=T)
    pol = je.JAX_POLICIES["portfolio"]
    statics, state0, xs = je.build_sim_inputs(arr, wl, needs_stats=pol.needs_stats)
    statics["policy"] = pol.default_params()
    with jax.enable_x64(True):
        shapes = jax.tree.map(
            lambda a: _spec(one_chip, np.shape(a), jnp.asarray(a).dtype),
            (statics, state0, xs),
        )
        compiled = je._get_runner("portfolio").lower(*shapes).compile()
    assert compiled.memory_analysis() is not None


def test_stats_grid_runner_compiles_for_v5e(one_chip):
    """The float64 ``paragon`` grid runner, two cells at A=64, T=600:
    its monitor pass (a scan over a sorted ``[W, A]`` window per cell)
    and the tick scan must both go through the chip's compiler."""
    from repro.core.sim import jax_engine as je
    from repro.core.sim.types import replicate_pool
    from repro.core.workloads import SCENARIO_ZOO

    A, T, B = 64, 600, 2
    wl = replicate_pool(["llama3-8b", "qwen1.5-0.5b", "rwkv6-1.6b"], A, 0.25)
    arr = SCENARIO_ZOO["mmpp_bursts"].build(A, duration_s=T)
    pol = je.JAX_POLICIES["paragon"]
    statics, state0, xs = je.build_sim_inputs(arr, wl, needs_stats=True, lazy_rings=False)
    assert "p2m" not in xs
    cells = lambda tree: jax.tree.map(
        lambda a: _spec(one_chip, (B,) + np.shape(a), jnp.asarray(a).dtype), tree)
    with jax.enable_x64(True):
        args = (
            jax.tree.map(lambda a: _spec(one_chip, np.shape(a), jnp.asarray(a).dtype), statics),
            cells(pol.default_params()), cells(state0), cells(xs),
        )
        compiled = je._get_runner("paragon", batched=True).lower(*args).compile()
    assert "sim.monitor" in compiled.as_text()
    assert compiled.memory_analysis() is not None
