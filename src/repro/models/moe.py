"""Mixture-of-Experts layer: top-k router + sort-based capacity dispatch.

Dispatch is done megablocks-style rather than with a GShard one-hot tensor:
tokens are sorted by their assigned expert, packed into a fixed-capacity
``[E, C, d]`` buffer (scatter), batch-matmul'd through the experts and
scattered back with the router weights.  The ``[E, C, d]`` buffer is what
gets sharded on the expert axis for expert parallelism — under the `shard`
plan the scatter/gather lowers to the all-to-all the paper's Alpa plans use.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import tracing
from repro.configs.base import ModelConfig
from repro.models.layers import dense_init


def init_moe(rng, cfg: ModelConfig):
    m, d = cfg.moe, cfg.d_model
    eff = m.expert_d_ff or cfg.d_ff
    r = jax.random.split(rng, 5)
    p = {
        "router": dense_init(r[0], (d, m.n_experts), d),
        "w_gate": dense_init(r[1], (m.n_experts, d, eff), d),
        "w_up": dense_init(r[2], (m.n_experts, d, eff), d),
        "w_down": dense_init(r[3], (m.n_experts, eff, d), eff),
    }
    if m.n_shared_experts:
        ns = m.n_shared_experts
        rs = jax.random.split(r[4], 3)
        p["shared_gate"] = dense_init(rs[0], (d, ns * eff), d)
        p["shared_up"] = dense_init(rs[1], (d, ns * eff), d)
        p["shared_down"] = dense_init(rs[2], (ns * eff, d), ns * eff)
    return p


def _expert_ffn(buf, params):
    """buf: [E, C, d] -> [E, C, d] through per-expert SwiGLU."""
    with jax.named_scope(tracing.MLP):
        dt = buf.dtype
        g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"].astype(dt))
        u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"].astype(dt))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
        return jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(dt))


def moe_forward(x, params, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Dispatch wrapper: ``cfg.moe_dispatch_axes`` (set by the step
    builders under SPMD plans) switches to per-data-shard local routing.

    The global path sorts ALL tokens jointly — on a 256-chip mesh that
    lowers to an all-gather of the full [T, d] token matrix per MoE layer
    (measured 2.5e6 ms of collective time for deepseek-v2 prefill_32k,
    EXPERIMENTS.md §Perf H1).  The sharded path routes each data shard's
    tokens locally inside a partial-manual shard_map; expert weights stay
    model-axis sharded in auto-SPMD, so the only cross-device traffic left
    is the token/expert all-to-all XLA inserts for the expert einsum."""
    axes = getattr(cfg, "moe_dispatch_axes", None) or ()
    if not axes:
        return _moe_forward_impl(x, params, cfg)
    axes = tuple(axes)
    dt = x.dtype

    @partial(jax.shard_map, axis_names=set(axes),
             in_specs=(P(axes if len(axes) > 1 else axes[0]), P()),
             out_specs=(P(axes if len(axes) > 1 else axes[0]), P()),
             check_vma=False)
    def run(x_loc, p):
        # fp32 at every shard_map boundary (activations AND param/cotangent
        # leaves): the XLA CPU SPMD partitioner CHECK-fails transposing
        # bf16 through partial-manual shard_map (same bug + workaround as
        # core/pipeline.py's carriers).
        out, aux = _moe_forward_impl(x_loc, p, cfg)
        return out.astype(jnp.float32), \
            jax.lax.pmean(aux, axes if len(axes) > 1 else axes[0])

    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    out, aux = run(x.astype(jnp.float32), p32)
    return out.astype(dt), aux


def _moe_forward_impl(x, params, cfg: ModelConfig
                      ) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d].  Returns (out, aux_loss).

    aux_loss is the standard load-balance loss  E * sum_e f_e * p_e  where
    f_e = fraction of tokens routed to e, p_e = mean router prob of e.
    """
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    dt = x.dtype
    xf = x.reshape(T, d)

    # router matmul in the model dtype (casting xf to fp32 here doubles
    # the bytes of every activation gather XLA schedules around it);
    # only the softmax runs in fp32
    logits = jnp.einsum("td,de->te", xf, params["router"].astype(dt))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [T, E]
    gate_vals, choices = jax.lax.top_k(probs, m.top_k)           # [T, k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # ---- load balance auxiliary ------------------------------------- #
    assign_onehot = jax.nn.one_hot(choices, m.n_experts, dtype=jnp.float32)
    f_e = jnp.mean(jnp.sum(assign_onehot, axis=1), axis=0)       # [E]
    p_e = jnp.mean(probs, axis=0)
    aux = m.n_experts * jnp.sum(f_e * p_e) * m.router_aux_coef

    # ---- sort-based dispatch ----------------------------------------- #
    E = m.n_experts
    # capacity floor keeps tiny decode batches drop-free
    cap = min(max(int(m.capacity_factor * T * m.top_k / E) + 1,
                  min(T, 16)), T)
    flat_expert = choices.reshape(-1)                            # [T*k]
    flat_token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), m.top_k)
    flat_gate = gate_vals.reshape(-1)

    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    sorted_token = flat_token[order]
    sorted_gate = flat_gate[order]
    # rank within expert = running index - offset of this expert's first slot
    counts = jnp.bincount(sorted_expert, length=E)
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(T * m.top_k, dtype=jnp.int32) - offsets[sorted_expert]
    keep = rank < cap
    slot = sorted_expert * cap + jnp.where(keep, rank, 0)        # [T*k]

    buf = jnp.zeros((E * cap, d), dt)
    gathered = jnp.where(keep[:, None], xf[sorted_token], 0).astype(dt)
    buf = buf.at[slot].add(gathered)                              # scatter
    buf = buf.reshape(E, cap, d)
    # pin the buffer expert-sharded: otherwise XLA replicates the full
    # [E, cap, d] buffer across the model axis before the expert einsum
    # (~16x the necessary traffic; EXPERIMENTS.md §Perf H1 iter 2)
    expert_axis = getattr(cfg, "moe_expert_axis", "")
    if expert_axis:
        buf = jax.lax.with_sharding_constraint(buf, P(expert_axis))
    out_buf = _expert_ffn(buf, params)
    if expert_axis:
        out_buf = jax.lax.with_sharding_constraint(out_buf, P(expert_axis))
    out_buf = out_buf.reshape(E * cap, d)

    contrib = out_buf[slot] * (sorted_gate * keep)[:, None].astype(dt)
    out = jnp.zeros((T, d), dt).at[sorted_token].add(contrib)

    # ---- shared (always-on) experts ----------------------------------- #
    if m.n_shared_experts:
        g = jnp.einsum("td,df->tf", xf, params["shared_gate"].astype(dt))
        u = jnp.einsum("td,df->tf", xf, params["shared_up"].astype(dt))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
        out = out + jnp.einsum("tf,fd->td", h, params["shared_down"].astype(dt))

    return out.reshape(B, S, d), aux
