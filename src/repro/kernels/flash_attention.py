"""FlashAttention-2 prefill kernel (Pallas, TPU target).

Tiling: grid = (batch, q_heads, Sq/BQ, Sk/BK); the KV axis is the
innermost (sequential on TPU) grid dimension, so the online-softmax
running statistics (m, l) and the f32 accumulator live in VMEM scratch
carried across KV steps.  Blocks are MXU-aligned (128x128 by default).
The wrapper lays q/k/v out heads-major, (B, heads, S, hd), so every block
is a (rows, hd) tile whose last two dimensions meet the TPU block rule
(rows a multiple of 8, hd the full head dim).  GQA is handled in the
index maps (query head h reads KV head h // group); causal and
sliding-window masks are applied from block-relative position
arithmetic, so no (Sq, Sk) mask tensor ever materializes.

Validated on CPU with ``interpret=True`` against ``ref.mha_reference``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
HIGHEST = jax.lax.Precision.HIGHEST


def _fa_kernel(
    q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *,
    scale: float,
    causal: bool,
    window: int,
    q_offset: int,
    bq: int,
    bk: int,
    sk_actual: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32)                # (BQ, hd)
    k = k_ref[...].astype(jnp.float32)                # (BK, hd)
    v = v_ref[...].astype(jnp.float32)                # (BK, hd)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32,
    ) * scale                                         # (BQ, BK)

    qpos = q_offset + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < sk_actual
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                               # (BQ, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)

    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[...] = (acc_ref[...] / safe).astype(o_ref.dtype)


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,                    # (B, Sq, nq, hd)
    k: jax.Array,                    # (B, Sk, nkv, hd)
    v: jax.Array,                    # (B, Sk, nkv, hd)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, sq, nq, hd = q.shape
    sk, nkv = k.shape[1], k.shape[2]
    assert nq % nkv == 0, (nq, nkv)
    group = nq // nkv
    scale = hd ** -0.5

    bq = min(block_q, _ceil_to(sq, 8))
    bk = min(block_k, _ceil_to(sk, 8))
    sq_p, sk_p = _ceil_to(sq, bq), _ceil_to(sk, bk)
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))

    # heads-major: each block is a (rows, hd) tile of one (batch, head)
    q, k, v = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
    grid = (b, nq, sq_p // bq, sk_p // bk)

    out = pl.pallas_call(
        functools.partial(
            _fa_kernel,
            scale=scale, causal=causal, window=window,
            q_offset=q_offset, bq=bq, bk=bk, sk_actual=sk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, bq, hd), lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec(
                (None, None, bk, hd),
                lambda b_, h, iq, ik, g=group: (b_, h // g, ik, 0),
            ),
            pl.BlockSpec(
                (None, None, bk, hd),
                lambda b_, h, iq, ik, g=group: (b_, h // g, ik, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, None, bq, hd), lambda b_, h, iq, ik: (b_, h, iq, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, nq, sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),    # m (running max)
            pltpu.VMEM((bq, 1), jnp.float32),    # l (running sum)
        ],
        interpret=interpret,
    )(q, k, v)
    return jnp.swapaxes(out, 1, 2)[:, :sq]
