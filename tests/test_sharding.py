"""Distribution layer: logical-axis rules, divisibility, spec building,
and an end-to-end lower+compile of the sharded steps on a tiny mesh."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.configs.registry import InputShape
from repro.distributed.sharding import AxisRules, axis_rules, logical_to_spec
from repro.launch.mesh import make_mesh, make_rules
from repro.launch.specs import build_step

PROD_MESH = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
POD_MESH = jax.sharding.AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_logical_to_spec_basic():
    rules = AxisRules(mesh=PROD_MESH, rules={"batch": ("data",), "ff": "model"})
    assert logical_to_spec(("batch", None, "ff"), rules) == P("data", None, "model")


def test_logical_to_spec_consumes_axis_once():
    rules = AxisRules(mesh=PROD_MESH, rules={"a": "model", "b": "model"})
    # the second dimension must NOT reuse the already-consumed mesh axis
    assert logical_to_spec(("a", "b"), rules) == P("model")


def test_rules_divisibility_minicpm():
    """minicpm: 36 heads don't divide 16 -> heads replicated; ff 5760 does."""
    cfg = get_config("minicpm-2b")
    rules = make_rules(cfg, PROD_MESH, "train", batch_size=256).rules
    assert rules["heads"] is None
    assert rules["kv_heads"] is None
    assert rules["ff"] == "model"          # 5760 % 16 == 0
    assert rules["vocab"] is None          # 122753 is odd


def test_rules_divisibility_llama():
    cfg = get_config("llama3-8b")
    rules = make_rules(cfg, PROD_MESH, "train", batch_size=256).rules
    assert rules["heads"] == "model"       # 32 % 16
    assert rules["kv_heads"] is None       # 8 < 16
    assert rules["vocab"] == "model"       # 128256 % 16
    assert rules["batch"] == ("data",)


def test_rules_multipod_batch():
    cfg = get_config("llama3-8b")
    rules = make_rules(cfg, POD_MESH, "train", batch_size=256).rules
    assert rules["batch"] == ("pod", "data")


def test_rules_decode_kv_split():
    cfg = get_config("llama3-8b")
    rules = make_rules(cfg, PROD_MESH, "decode", batch_size=128,
                       cache_len=32768).rules
    assert rules["kv_seq"] == "model"      # flash-decode split-K
    rules2 = make_rules(cfg, PROD_MESH, "prefill", batch_size=32).rules
    assert rules2["kv_seq"] is None


def test_batch_not_divisible_stays_replicated():
    cfg = get_config("llama3-8b")
    rules = make_rules(cfg, PROD_MESH, "decode", batch_size=1, cache_len=4096).rules
    assert rules["batch"] is None          # long_500k batch=1


# ---------------------------------------------------------------------------
# End-to-end: lower + compile the production step builders on a 1x1 mesh
# with REDUCED configs and small shapes (the real 512-device dry-run is
# launch/dryrun.py; this guards the plumbing in CI).
# ---------------------------------------------------------------------------
SMALL_SHAPES = {
    "train": InputShape("train_4k", 64, 4, "train"),
    "prefill": InputShape("prefill_32k", 64, 2, "prefill"),
    "decode": InputShape("decode_32k", 64, 4, "decode"),
    "long": InputShape("long_500k", 256, 1, "decode"),
}


@pytest.mark.parametrize("arch", ["llama3-8b", "rwkv6-1.6b", "phi3.5-moe-42b-a6.6b",
                                  "recurrentgemma-9b", "whisper-small"])
@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_lower_compile_small_mesh(arch, kind):
    cfg = get_config(arch).reduced()
    mesh = make_mesh()
    shape = SMALL_SHAPES[kind]
    step, args, in_shardings, rules, _donate = build_step(
        cfg, shape, mesh, param_dtype=jnp.float32)
    with mesh, axis_rules(rules):
        compiled = jax.jit(step, in_shardings=in_shardings).lower(*args).compile()
    assert compiled.cost_analysis() is not None


def test_lower_long_context_window(arch="llama3-8b"):
    """long_500k on a dense arch must lower through the sliding-window
    variant (ring cache shorter than the sequence)."""
    cfg = get_config(arch).reduced()
    mesh = make_mesh()
    step, args, in_shardings, rules, _donate = build_step(
        cfg, SMALL_SHAPES["long"], mesh, param_dtype=jnp.float32
    )
    # the cache spec must be window-sized, not seq-sized
    cache = args[2]
    k_shapes = [l.shape for l in jax.tree.leaves(cache) if hasattr(l, "shape")]
    assert all(s[2] <= cfg.long_context_window or len(s) < 3 for s in k_shapes if len(s) >= 3)
    with mesh, axis_rules(rules):
        compiled = jax.jit(step, in_shardings=in_shardings).lower(*args).compile()
    assert compiled is not None
