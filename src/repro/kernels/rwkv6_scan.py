"""RWKV-6 chunkwise recurrence kernel (Pallas, TPU target).

The sequential oracle is O(T) steps of rank-1 state updates — hopeless on
a systolic machine.  This kernel processes the sequence in chunks of C
tokens per grid step with the (hd, hd) state carried in VMEM scratch:

  within a chunk (log-space cumulative decay  la_t = sum_{s<=t} log w_s):
    o_t  = (r_t * exp(la_{t-1})) . S0            (carry-in state term)
         + sum_{s<t} [ sum_i r_ti k_si e^{la_{t-1,i}-la_{s,i}} ] v_s
         + ((r_t * u) . k_t) v_t                 (bonus diagonal)
    S_C  = diag(e^{la_C}) S0 + sum_s (k_s * e^{la_C - la_s}) v_s^T

The intra-chunk pair term keeps the decay ratio INSIDE the reduction over
the head dim rather than factorizing it into k / a_s — the factorized
form overflows when the data-dependent decay is strong (exp(+la) with
la ~ -50/token), the in-reduction form is always bounded by 1.  That
trades MXU matmuls for VPU work: one (C, hd) tile per query row t, so
C * C * hd multiply-adds per chunk.

TPU layout: the wrapper lays r/k/v/w out heads-major, (B, H, T, hd), so
each block is a (C, hd) tile (C a multiple of 8, hd the full head dim),
and ``u`` as (H, 1, hd).  Every in-kernel value stays 2-D: the
within-chunk cumulative sum is a matmul with a lower-triangular ones
matrix, and the pair scores are built one query row at a time.

Validated with ``interpret=True`` against ``ref.rwkv6_reference``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(a, b, contract):
    """f32 matmul contracting ``a``'s dim ``contract[0]`` with ``b``'s
    ``contract[1]``."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )


def _rwkv_kernel(
    r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
    o_ref, sT_ref,
    state_ref,
    *,
    chunk: int,
):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = s0_ref[...].astype(jnp.float32)

    r = r_ref[...].astype(jnp.float32)                # (C, hd)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)                # (1, hd)
    S = state_ref[...]                                # (hd, hd) f32

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    logw = jnp.log(jnp.maximum(w, 1e-38))             # (C, hd) <= 0
    # la_t = sum_{s<=t} log w_s, as a lower-triangular matmul
    la = _dot((row >= col).astype(jnp.float32), logw, (1, 0))
    la_prev = la - logw                               # la_{t-1} (la_0 = 0)

    # carry-in state term: (r_t * e^{la_{t-1}}) @ S
    o_state = _dot(r * jnp.exp(la_prev), S, (1, 0))   # (C, hd_v)

    # intra-chunk pair scores, transposed: AT[s, t] = A[t, s]
    #   = sum_i r_ti k_si e^{la_{t-1,i}-la_{s,i}}  for s < t
    # (the exponent is <= 0 there; clipping it keeps the unused s >= t
    # entries finite)
    AT = jnp.zeros((chunk, chunk), jnp.float32)
    for t in range(1, chunk):
        ratio = jnp.exp(jnp.minimum(la_prev[t:t + 1] - la, 0.0))     # (C, hd)
        a_t = jnp.sum(r[t:t + 1] * k * ratio, axis=1, keepdims=True)  # (C, 1)
        AT = jnp.where(col == t, a_t, AT)
    AT = jnp.where(row < col, AT, 0.0)                # strictly s < t
    o_intra = _dot(AT, v, (0, 0))

    # bonus diagonal: ((r_t * u) . k_t) v_t
    bonus = jnp.sum(r * u * k, axis=1, keepdims=True)  # (C, 1)
    o_ref[...] = (o_state + o_intra + bonus * v).astype(o_ref.dtype)

    # state update: S_C = diag(e^{la_C}) S + sum_s (k_s e^{la_C - la_s}) v_s^T
    la_C = la[chunk - 1:chunk]                        # (1, hd)
    la_C_col = _dot(logw, jnp.ones((chunk, 1), jnp.float32), (0, 0))  # (hd, 1)
    k_dec = k * jnp.exp(la_C - la)                    # (C, hd), bounded
    S_new = jnp.exp(la_C_col) * S + _dot(k_dec, v, (0, 0))
    state_ref[...] = S_new

    @pl.when(ic == nc - 1)
    def _finalize():
        sT_ref[...] = S_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_chunked(
    r: jax.Array,                    # (B, T, H, hd)
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,                    # decay in (0, 1)
    u: jax.Array,                    # (H, hd)
    state=None,                      # (B, H, hd, hd) f32
    *,
    chunk: int = 32,
    interpret: bool = False,
):
    """Returns (out (B,T,H,hd), final_state (B,H,hd,hd) f32)."""
    b, t, h, hd = r.shape
    if state is None:
        state = jnp.zeros((b, h, hd, hd), jnp.float32)

    c = min(chunk, t)
    t_p = -(-t // c) * c
    if t_p != t:
        pad = ((0, 0), (0, t_p - t), (0, 0), (0, 0))
        r, k, v = jnp.pad(r, pad), jnp.pad(k, pad), jnp.pad(v, pad)
        w = jnp.pad(w, pad, constant_values=1.0)   # decay 1 = no-op steps

    # heads-major: each block is a (C, hd) tile of one (batch, head)
    r, k, v, w = (jnp.swapaxes(a, 1, 2) for a in (r, k, v, w))
    grid = (b, h, t_p // c)
    seq_spec = pl.BlockSpec((None, None, c, hd), lambda b_, h_, ic: (b_, h_, ic, 0))
    state_spec = pl.BlockSpec(
        (None, None, hd, hd), lambda b_, h_, ic: (b_, h_, 0, 0)
    )

    out, s_final = pl.pallas_call(
        functools.partial(_rwkv_kernel, chunk=c),
        grid=grid,
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((None, 1, hd), lambda b_, h_, ic: (h_, 0, 0)),
            state_spec,
        ],
        out_specs=[seq_spec, state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t_p, hd), r.dtype),
            jax.ShapeDtypeStruct((b, h, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u[:, None, :], state)
    return jnp.swapaxes(out, 1, 2)[:, :t], s_final
