"""Flash-decode kernel (Pallas, TPU target).

The decode hot loop: ONE query token per sequence attending to a long KV
cache.  Grid = (batch, S/BK) with the KV axis innermost (sequential), so
the running softmax statistics live in VMEM scratch and the cache streams
HBM->VMEM in (BK, nkv*hd) tiles — this kernel is pure memory traffic,
which is exactly what the ``decode_32k`` / ``long_500k`` roofline says
dominates.

Every block meets the TPU block rule (last two dims a multiple of
(8, 128) or the array's own) without relaying out the cache: the cache
is read as its free (B, S, nkv*hd) view, and all query heads of a
sequence go through the MXU at once against a block-diagonal query,
``qbd[h, j*hd:(j+1)*hd] = q[h] if j == h // group else 0``.  Scores are
then one (nq, BK) matmul, and the (nq, nkv*hd) output keeps each head's
own KV block, which the wrapper picks out.  The MXU does ``nkv`` times
the needed work, which a memory-bound kernel can afford.

Invalid cache slots (ring-buffer holes, beyond-horizon positions) are
masked via the ``valid`` (B, S) boolean the engine derives from
``slot_pos``.  Validated with ``interpret=True`` against
``ref.decode_attention_reference``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
HIGHEST = jax.lax.Precision.HIGHEST


def _decode_kernel(
    q_ref, k_ref, v_ref, valid_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *,
    scale: float,
):
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32)                # (nq, nkv*hd) block-diag
    k = k_ref[...].astype(jnp.float32)                # (BK, nkv*hd)
    v = v_ref[...].astype(jnp.float32)                # (BK, nkv*hd)
    valid = valid_ref[...] > 0                        # (1, BK)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32,
    ) * scale                                         # (nq, BK)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                               # (nq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)     # (nq, BK)

    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32,
    )                                                 # (nq, nkv*hd)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[...] = (acc_ref[...] / safe).astype(o_ref.dtype)


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(
    q: jax.Array,                    # (B, nq, hd) — one token per sequence
    k_cache: jax.Array,              # (B, S, nkv, hd)
    v_cache: jax.Array,              # (B, S, nkv, hd)
    valid: jax.Array,                # (B, S) bool
    *,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, nq, hd = q.shape
    s, nkv = k_cache.shape[1], k_cache.shape[2]
    assert nq % nkv == 0
    group = nq // nkv
    scale = hd ** -0.5

    bk = min(block_k, _ceil_to(s, 8))
    s_p = _ceil_to(s, bk)
    if s_p != s:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, s_p - s), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, s_p - s), (0, 0), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, s_p - s)))

    kv_of = jnp.arange(nq) // group                   # (nq,) KV head per q head
    own = (jnp.arange(nkv)[None, :] == kv_of[:, None]).astype(q.dtype)
    qbd = (own[None, :, :, None] * q[:, :, None, :]).reshape(b, nq, nkv * hd)
    k_flat = k_cache.reshape(b, s_p, nkv * hd)
    v_flat = v_cache.reshape(b, s_p, nkv * hd)
    valid_i = valid.astype(jnp.int32)[:, None, :]     # (B, 1, S)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale),
        grid=(b, s_p // bk),
        in_specs=[
            pl.BlockSpec((None, nq, nkv * hd), lambda b_, ik: (b_, 0, 0)),
            pl.BlockSpec((None, bk, nkv * hd), lambda b_, ik: (b_, ik, 0)),
            pl.BlockSpec((None, bk, nkv * hd), lambda b_, ik: (b_, ik, 0)),
            pl.BlockSpec((None, 1, bk), lambda b_, ik: (b_, 0, ik)),
        ],
        out_specs=pl.BlockSpec((None, nq, nkv * hd), lambda b_, ik: (b_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nq, nkv * hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((nq, nkv * hd), jnp.float32),  # acc
            pltpu.VMEM((nq, 1), jnp.float32),         # m (running max)
            pltpu.VMEM((nq, 1), jnp.float32),         # l (running sum)
        ],
        interpret=interpret,
    )(qbd, k_flat, v_flat, valid_i)
    # head h keeps the output of its own KV head's block
    return out.reshape(b, nq, nkv, hd)[:, jnp.arange(nq), kv_of]
