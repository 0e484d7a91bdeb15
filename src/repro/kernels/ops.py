"""Jit'd dispatch wrappers around the compute hot-spots.

``impl`` selects the execution path:
  * ``"pallas"``            — the Pallas TPU kernel; the default on a TPU.
  * ``"xla"``               — pure-jnp (ref.py); the default everywhere
                               else (CPU tests, the dry-run lowering).
  * ``"pallas_interpret"``  — the same kernel body interpreted on CPU
                               (correctness validation off the chip).

The default is read from the platform at trace time.  A kernel that the
chip's compiler refuses is an error: nothing falls back to the reference.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref

IMPLS = ("xla", "pallas", "pallas_interpret")


def default_impl() -> str:
    """The kernel path this process runs when a caller names none."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _resolve(impl: Optional[str]) -> str:
    impl = impl or default_impl()
    assert impl in IMPLS, impl
    return impl


# ---------------------------------------------------------------------------
def flash_attention(
    q, k, v, *, causal=True, window=0, q_offset=0, impl=None
):
    """Full-sequence attention (B,Sq,nq,hd)x(B,Sk,nkv,hd)->(B,Sq,nq,hd)."""
    impl = _resolve(impl)
    if impl == "xla":
        if (
            causal and window > 0 and q.shape[1] == k.shape[1]
            and q.shape[1] > 2 * window and q_offset == 0
        ):
            # sliding window pays for itself only computed block-locally:
            # O(S*2W) logits instead of masked O(S^2) (§Perf iteration)
            return ref.local_attention_blocked(
                q, k, v, window=window, q_offset=q_offset
            )
        return ref.mha_reference(q, k, v, causal=causal, window=window, q_offset=q_offset)
    from repro.kernels import flash_attention as fa

    return fa.flash_attention(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        interpret=(impl == "pallas_interpret"),
    )


def decode_attention(q, k_cache, v_cache, valid, *, impl=None):
    """Single-token decode attention (B,nq,hd) vs (B,S,nkv,hd)."""
    impl = _resolve(impl)
    if impl == "xla":
        return ref.decode_attention_reference(q, k_cache, v_cache, valid)
    from repro.kernels import decode_attention as da

    return da.decode_attention(
        q, k_cache, v_cache, valid, interpret=(impl == "pallas_interpret")
    )


def rwkv6(r, k, v, w, u, state=None, *, impl=None):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.rwkv6_reference(r, k, v, w, u, state)
    from repro.kernels import rwkv6_scan

    return rwkv6_scan.rwkv6_chunked(
        r, k, v, w, u, state, interpret=(impl == "pallas_interpret")
    )


def rglru(x, a, h0=None, *, impl=None):
    impl = _resolve(impl)
    # RG-LRU is elementwise; the XLA associative_scan path is already
    # TPU-optimal (log-depth, no matmul) — used for every impl. Kept as an
    # ops entry point so the serving engine has a single dispatch surface.
    del impl
    return _rglru_assoc(x, a, h0)


def _rglru_assoc(x, a, h0=None):
    """Associative-scan RG-LRU: h_t = a_t h_{t-1} + b_t with log-depth scan."""
    f32 = jnp.float32
    b_term = jnp.sqrt(jnp.maximum(1.0 - a.astype(f32) ** 2, 0.0)) * x.astype(f32)
    a32 = a.astype(f32)
    if h0 is not None:
        # fold the carried state into the first step's additive term
        b_term = b_term.at[:, 0].add(a32[:, 0] * h0.astype(f32))

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    aa, hh = jax.lax.associative_scan(combine, (a32, b_term), axis=1)
    return hh.astype(x.dtype), hh[:, -1]
