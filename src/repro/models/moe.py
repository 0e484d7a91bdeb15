"""Mixture-of-Experts layer.

Three execution paths, all computing the same routing semantics
(top-k, softmax-over-selected, capacity-factor token dropping):

1. ``moe_dense_oracle``  — O(E·T·d·ff) one-hot einsum.  Exact, tiny shapes
   only; the correctness oracle for the other two paths.
2. ``moe_sort_local``    — sort-based capacity dispatch in global-view jnp.
   O(T log T + E·C·d·ff).  XLA's SPMD partitioner chooses the collectives.
   This is the paper-faithful baseline path.
3. ``moe_ep_a2a``        — explicit expert parallelism: ``shard_map`` over the
   mesh, tokens exchanged to expert-owner shards with ``all_to_all``.  The
   beyond-paper optimized path for train/prefill (§Perf).

Routing: logits -> top-k -> softmax over the selected k logits (Mixtral
convention).  Aux output is the load-balance loss (Switch-style
E · Σ_e f_e·p_e) used by the training substrate.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.registry import ModelConfig
from repro.distributed.sharding import current_rules, shard
from repro.models.params import boxed_normal


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    d = cfg.d_model
    e_ff = cfg.expert_d_ff or cfg.d_ff
    e = cfg.num_experts
    kr, kg, ku, ko = jax.random.split(key, 4)
    s_in, s_out = d ** -0.5, e_ff ** -0.5
    return {
        "router": boxed_normal(kr, (d, e), ("embed", None), s_in, jnp.float32),
        "wi_gate": boxed_normal(kg, (e, d, e_ff), ("experts", "embed", "ff"), s_in, dtype),
        "wi_up": boxed_normal(ku, (e, d, e_ff), ("experts", "embed", "ff"), s_in, dtype),
        "wo": boxed_normal(ko, (e, e_ff, d), ("experts", "ff", "embed"), s_out, dtype),
    }


def _route(cfg: ModelConfig, router_w: jax.Array, xf: jax.Array):
    """xf (T, d) -> (gates (T,k) fp32, expert_idx (T,k) int32, aux_loss)."""
    k = cfg.num_experts_per_tok
    e = cfg.num_experts
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router_w.astype(jnp.float32))
    topv, topi = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(topv, axis=-1)                     # (T, k)
    # Switch-style load balance: E * sum_e fraction_e * prob_e  (== 1 when
    # perfectly balanced)
    probs = jax.nn.softmax(logits, axis=-1)                   # (T, E)
    onehot = jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32)  # top-1 assignment share
    aux = e * jnp.sum(jnp.mean(onehot, axis=0) * jnp.mean(probs, axis=0))
    return gates, topi.astype(jnp.int32), aux


def _expert_ffn(cfg: ModelConfig, p: dict, buf: jax.Array) -> jax.Array:
    """buf (E, C, d) -> (E, C, d); batched per-expert SwiGLU."""
    g = jnp.einsum("ecd,edf->ecf", buf, p["wi_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["wi_up"])
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, p["wo"])


def _capacity(cfg: ModelConfig, tokens: int) -> int:
    c = int(tokens * cfg.num_experts_per_tok * cfg.moe_capacity_factor
            / cfg.num_experts) + 1
    # MXU-friendly multiple of 8 (128 when big enough)
    return max(8, -(-c // 8) * 8)


# ---------------------------------------------------------------------------
# 1. Dense oracle.
# ---------------------------------------------------------------------------
def moe_dense_oracle(cfg: ModelConfig, p: dict, x: jax.Array):
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    gates, topi, aux = _route(cfg, p["router"], xf)
    y = jnp.zeros_like(xf, dtype=jnp.float32)
    for e in range(cfg.num_experts):
        pe = {
            "wi_gate": p["wi_gate"][e][None, :, :],
            "wi_up": p["wi_up"][e][None, :, :],
            "wo": p["wo"][e][None, :, :],
        }
        out_e = _expert_ffn(cfg, pe, xf[None, :, :])[0]        # (T, d)
        w_e = jnp.sum(jnp.where(topi == e, gates, 0.0), axis=-1)  # (T,)
        y = y + w_e[:, None] * out_e.astype(jnp.float32)
    return y.reshape(b, s, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# 2. Sort-based capacity dispatch (global view).
# ---------------------------------------------------------------------------
def moe_sort_local(cfg: ModelConfig, p: dict, x: jax.Array,
                   capacity: Optional[int] = None):
    b, s, d = x.shape
    t = b * s
    k = cfg.num_experts_per_tok
    e = cfg.num_experts
    c = capacity or _capacity(cfg, t)

    xf = x.reshape(t, d)
    gates, topi, aux = _route(cfg, p["router"], xf)

    flat_e = topi.reshape(t * k)                               # (T·k,)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_gate = gates.reshape(t * k)

    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_tok[order], flat_gate[order]
    counts = jnp.bincount(se, length=e)                        # (E,)
    offsets = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * k, dtype=jnp.int32) - offsets[se]     # rank within expert
    keep = pos < c
    # out-of-range rows scatter with mode='drop'
    se_k = jnp.where(keep, se, e)
    pos_k = jnp.where(keep, pos, 0)

    buf = jnp.zeros((e, c, d), dtype=x.dtype)
    buf = buf.at[se_k, pos_k].set(xf[st], mode="drop")
    buf = shard(buf, "experts", None, None)
    out = _expert_ffn(cfg, p, buf)                             # (E, C, d)
    out = shard(out, "experts", None, None)

    rows = jnp.where(
        keep[:, None], out.at[(se_k, pos_k)].get(mode="fill", fill_value=0.0), 0.0
    )
    y = jnp.zeros((t, d), dtype=jnp.float32)
    y = y.at[st].add(sg[:, None] * rows.astype(jnp.float32))
    return y.reshape(b, s, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# 3. Explicit expert parallelism with all_to_all (shard_map).
# ---------------------------------------------------------------------------
def moe_ep_a2a(cfg: ModelConfig, p: dict, x: jax.Array):
    """Expert-parallel MoE. Requires active axis rules with an ``experts``
    mapping to a mesh axis, tokens divisible by that axis size."""
    rules = current_rules()
    if rules is None:
        return moe_sort_local(cfg, p, x)
    ep_axis = rules.mesh_axes("experts")
    if ep_axis is None:
        return moe_sort_local(cfg, p, x)
    if isinstance(ep_axis, tuple):
        ep_axis = ep_axis[0]
    mesh = rules.mesh
    n_ep = mesh.shape[ep_axis]
    if cfg.num_experts % n_ep or x.shape[1] % n_ep:
        return moe_sort_local(cfg, p, x)

    batch_axes = rules.mesh_axes("batch") or ()
    if isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    batch_axes = tuple(a for a in batch_axes if a in mesh.axis_names)

    e_loc = cfg.num_experts // n_ep
    d = x.shape[-1]
    k = cfg.num_experts_per_tok

    def local_fn(xs, router_w, wg, wu, wo):
        # xs: (B_loc, S_loc, d) — batch split over data axes, seq over ep axis
        b_loc, s_loc, _ = xs.shape
        t_loc = b_loc * s_loc
        c = _capacity(cfg, t_loc)
        xf = xs.reshape(t_loc, d)
        gates, topi, aux = _route(cfg, router_w, xf)

        flat_e = topi.reshape(t_loc * k)
        flat_tok = jnp.repeat(jnp.arange(t_loc, dtype=jnp.int32), k)
        flat_gate = gates.reshape(t_loc * k)
        order = jnp.argsort(flat_e, stable=True)
        se, st, sg = flat_e[order], flat_tok[order], flat_gate[order]
        counts = jnp.bincount(se, length=cfg.num_experts)
        offsets = jnp.cumsum(counts) - counts
        pos = jnp.arange(t_loc * k, dtype=jnp.int32) - offsets[se]
        keep = pos < c
        se_k = jnp.where(keep, se, cfg.num_experts)
        pos_k = jnp.where(keep, pos, 0)

        # dispatch buffer grouped by destination shard: (E, C, d) == (n_ep·e_loc, C, d)
        buf = jnp.zeros((cfg.num_experts, c, d), dtype=xs.dtype)
        buf = buf.at[se_k, pos_k].set(xf[st], mode="drop")
        buf = buf.reshape(n_ep, e_loc, c, d)
        # exchange: dim0 = destination shard -> after a2a dim0 = source shard
        recv = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0, tiled=True)
        recv = recv.reshape(n_ep, e_loc, c, d).transpose(1, 0, 2, 3)  # (e_loc, n_src, C, d)
        recv = recv.reshape(e_loc, n_ep * c, d)
        p_loc = {"wi_gate": wg, "wi_up": wu, "wo": wo}
        out = _expert_ffn(cfg, p_loc, recv)                   # (e_loc, n_src·C, d)
        out = out.reshape(e_loc, n_ep, c, d).transpose(1, 0, 2, 3).reshape(n_ep * e_loc, c, d)
        back = jax.lax.all_to_all(
            out.reshape(n_ep, e_loc, c, d), ep_axis, split_axis=0, concat_axis=0, tiled=True
        ).reshape(cfg.num_experts, c, d)

        rows = jnp.where(
            keep[:, None], back.at[(se_k, pos_k)].get(mode="fill", fill_value=0.0), 0.0
        )
        y = jnp.zeros((t_loc, d), dtype=jnp.float32)
        y = y.at[st].add(sg[:, None] * rows.astype(jnp.float32))
        # aux is a local mean; average across shards
        aux = jax.lax.pmean(aux, ep_axis)
        for ax in batch_axes:
            aux = jax.lax.pmean(aux, ax)
        return y.reshape(b_loc, s_loc, d).astype(xs.dtype), aux

    x_spec = P(batch_axes if batch_axes else None, ep_axis, None)
    w_spec = P(ep_axis, None, None)
    out_specs = (x_spec, P())
    y, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, w_spec),
        out_specs=out_specs,
        check_vma=False,
    )(x, p["router"], p["wi_gate"], p["wi_up"], p["wo"])
    return y, aux


def moe_apply(cfg: ModelConfig, p: dict, x: jax.Array, path: str = "local"):
    if path == "dense":
        return moe_dense_oracle(cfg, p, x)
    if path == "ep_a2a":
        return moe_ep_a2a(cfg, p, x)
    return moe_sort_local(cfg, p, x)
