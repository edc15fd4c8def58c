"""Plan-aware step builders: the glue between the paper's execution plans
(core/plans.py), the model zoo, and pjit.

``build_train_step`` realizes each technique:
  * data      — replicated params, batch split, XLA inserts the grad
                all-reduce;
  * zero2     — gradients are pinned to the ZeRO shardings (XLA lowers the
                pin to a reduce-scatter), AdamW updates the local shard, and
                the new params are pinned back to replicated (all-gather);
  * shard     — tensor-parallel param shardings from the rule engine;
  * pipeshard — loss comes from core/pipeline.py (stage axis + microbatch
                ppermute pipeline), Shard rules inside each stage.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import tracing
from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.core.pipeline import make_pipeline_loss, pipeline_mesh
from repro.core.plans import Plan
from repro.models.model import Model
from repro.optim import AdamWState, adamw_update, init_adamw, lr_at


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def opt_state_specs(opt_shapes: AdamWState, param_specs) -> AdamWState:
    return AdamWState(step=P(), m=param_specs, v=param_specs)


def _set_moe_dispatch(model: Model, plan: Plan, mesh: Mesh,
                      global_batch: int) -> None:
    """Per-data-shard local MoE routing (H1, EXPERIMENTS.md §Perf): the
    global token sort otherwise all-gathers [T, d] per MoE layer.  Not
    under Pipeshard (the stage axis is already manual there)."""
    import dataclasses
    if model.cfg.family != "moe":
        return
    axes = () if plan.pipeline else plan.batch_axes(mesh, global_batch)
    e_axis = ""
    if plan.shards_weights and not plan.pipeline and "model" in mesh.shape \
            and model.cfg.moe.n_experts % mesh.shape["model"] == 0:
        e_axis = "model"
    model.cfg = dataclasses.replace(model.cfg, moe_dispatch_axes=tuple(axes),
                                    moe_expert_axis=e_axis)


def _set_logits_spec(model: Model, plan: Plan, mesh: Mesh,
                     global_batch: int) -> None:
    """Keep [*, *, vocab] logits (and fp32 softmax temporaries) sharded on
    the model axis under weight-sharding plans — without this pin the loss
    all-gathers the full-vocab logits per device (95 GB/device for a 3B
    model at 128k vocab)."""
    cfg = model.cfg
    if plan.shards_weights and "model" in mesh.shape \
            and cfg.vocab_size % mesh.shape["model"] == 0:
        axes = plan.batch_axes(mesh, global_batch)
        b_ax = axes if len(axes) > 1 else (axes[0] if axes else None)
        model.logits_pspec = P(b_ax, None, "model")
    else:
        model.logits_pspec = None


def build_train_step(model: Model, plan: Plan, mesh: Mesh,
                     tcfg: TrainConfig, *, params_shapes,
                     batch_shapes, stage_layers=None,
                     schedule: str = "gpipe"
                     ) -> Tuple[Callable, Dict[str, Any]]:
    """Returns (jitted step, shardings dict).

    step(params, opt_state, batch) -> (params, opt_state, metrics)

    ``stage_layers``: pipeline plans only — per-stage (per-chunk under
    an interleaved schedule) layer counts from a searched
    ``core.plans.Placement`` (uneven splits run pad-and-masked, see
    ``core.pipeline.make_pipeline_loss``).

    ``schedule``: pipeline plans only — the tick-order schedule
    (``core.costmodel.SCHEDULES``, docs/schedules.md) the pipeline
    executes; reordering only, the loss/grads are schedule-invariant.
    """
    cfg = model.cfg
    _set_logits_spec(model, plan, mesh, batch_shapes["tokens"].shape[0])
    _set_moe_dispatch(model, plan, mesh, batch_shapes["tokens"].shape[0])
    if plan.fsdp and "model" in mesh.shape \
            and cfg.d_model % mesh.shape["model"] == 0:
        axes = plan.batch_axes(mesh, batch_shapes["tokens"].shape[0])
        b_ax = axes if len(axes) > 1 else (axes[0] if axes else None)
        model.resid_pspec = P(b_ax, None, "model")
    else:
        model.resid_pspec = None
    if plan.pipeline:
        loss_fn = make_pipeline_loss(model, mesh, tcfg.microbatches,
                                     remat=tcfg.remat,
                                     stage_layers=stage_layers,
                                     schedule=schedule)
    else:
        loss_fn = partial(model.loss, remat=tcfg.remat)

    p_specs = plan.param_specs(params_shapes, cfg, mesh)
    o_specs_p = plan.opt_specs(params_shapes, cfg, mesh)   # zero or mirror
    opt_specs = AdamWState(step=P(), m=o_specs_p, v=o_specs_p)
    b_specs = plan.batch_spec(batch_shapes, mesh)
    metric_specs = P()

    def grad_fn(params, batch):
        if tcfg.grad_accum <= 1:
            return jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        # sequential microbatching: activations exist for one microbatch at
        # a time; grads accumulate in fp32 on the optimizer shards
        # (EXPERIMENTS.md §Perf H2 iter 4)
        A = tcfg.grad_accum
        batch_m = jax.tree.map(
            lambda x: x.reshape(A, x.shape[0] // A, *x.shape[1:]), batch)
        batch_m = jax.lax.with_sharding_constraint(
            batch_m, jax.tree.map(
                lambda s: NamedSharding(mesh, P(None, *s)),
                plan.batch_spec(batch_shapes, mesh),
                is_leaf=lambda x: isinstance(x, P)))
        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                          params)
        g0 = jax.lax.with_sharding_constraint(g0, _ns(mesh, o_specs_p))

        def acc(carry, mb):
            g_acc, loss_acc, metrics_acc = carry
            (loss, metrics), g = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb)
            g_acc = jax.tree.map(lambda a, gg: a + gg.astype(jnp.float32),
                                 g_acc, g)
            g_acc = jax.lax.with_sharding_constraint(
                g_acc, _ns(mesh, o_specs_p))
            loss_acc = loss_acc + loss
            metrics_acc = jax.tree.map(lambda a, m: a + m, metrics_acc,
                                       metrics)
            return (g_acc, loss_acc, metrics_acc), None

        m0 = {"ce": 0.0, "aux": 0.0, "zloss": 0.0, "accuracy": 0.0,
              "tokens": 0.0}
        m0 = jax.tree.map(jnp.float32, m0)
        (g_sum, loss_sum, m_sum), _ = jax.lax.scan(
            acc, (g0, jnp.float32(0), m0), batch_m)
        grads = jax.tree.map(lambda g: g / A, g_sum)
        metrics = jax.tree.map(lambda m: m / A, m_sum)
        metrics["tokens"] = metrics["tokens"] * A
        return (loss_sum / A, metrics), grads

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = grad_fn(params, batch)
        if plan.zero_sharding:
            # pin grads to the ZeRO shards => XLA reduce-scatters them
            grads = jax.lax.with_sharding_constraint(
                grads, _ns(mesh, o_specs_p))
        with jax.named_scope(tracing.OPTIMIZER):
            lr = lr_at(opt_state.step, tcfg)
            new_params, new_opt, stats = adamw_update(
                grads, opt_state, params, tcfg, lr)
        if plan.zero_sharding:
            # updated shards all-gather back to the plan's param placement
            new_params = jax.lax.with_sharding_constraint(
                new_params, _ns(mesh, p_specs))
        metrics = dict(metrics, loss=loss, **stats)
        return new_params, new_opt, metrics

    shardings = {
        "params": _ns(mesh, p_specs),
        "opt": _ns(mesh, opt_specs),
        "batch": _ns(mesh, b_specs),
        "param_specs": p_specs,
        "opt_specs": opt_specs,
        "batch_specs": b_specs,
    }
    metric_sh = jax.tree.map(
        lambda _: NamedSharding(mesh, metric_specs), {"_": 0})["_"]
    step = jax.jit(
        train_step,
        in_shardings=(shardings["params"], shardings["opt"],
                      shardings["batch"]),
        out_shardings=(shardings["params"], shardings["opt"], None),
        donate_argnums=(0, 1),
    )
    return step, shardings


def build_prefill_step(model: Model, plan: Plan, mesh: Mesh, *,
                       params_shapes, batch_shapes, cache_shapes,
                       batch_size: int, window: int = 0,
                       gather_last: bool = False):
    """``gather_last`` (continuous batching): the returned step takes an
    extra traced ``last_pos`` scalar and reads logits at that position —
    one compile per prompt-length *bucket* instead of per prompt length
    (the pad tail past ``last_pos`` is causally invisible)."""
    cfg = model.cfg
    _set_logits_spec(model, plan, mesh, batch_size)
    _set_moe_dispatch(model, plan, mesh, batch_size)
    p_sh = _ns(mesh, plan.param_specs(params_shapes, cfg, mesh))
    b_sh = _ns(mesh, plan.batch_spec(batch_shapes, mesh))
    c_sh = plan.cache_shardings(cache_shapes, cfg, mesh, batch_size)

    if gather_last:
        def prefill_at(params, batch, cache, last_pos):
            return model.prefill(params, batch, cache, window=window,
                                 last_pos=last_pos)

        return jax.jit(prefill_at,
                       in_shardings=(p_sh, b_sh, c_sh,
                                     NamedSharding(mesh, P())),
                       out_shardings=(None, c_sh)), {
                           "params": p_sh, "batch": b_sh, "cache": c_sh}

    def prefill(params, batch, cache):
        return model.prefill(params, batch, cache, window=window)

    return jax.jit(prefill,
                   in_shardings=(p_sh, b_sh, c_sh),
                   out_shardings=(None, c_sh)), {
                       "params": p_sh, "batch": b_sh, "cache": c_sh}


def build_serve_step(model: Model, plan: Plan, mesh: Mesh, *,
                     params_shapes, cache_shapes, batch_size: int,
                     window: int = 0):
    """ONE new token against a KV/state cache — what decode shapes lower."""
    cfg = model.cfg
    _set_logits_spec(model, plan, mesh, batch_size)
    _set_moe_dispatch(model, plan, mesh, batch_size)
    p_sh = _ns(mesh, plan.param_specs(params_shapes, cfg, mesh))
    c_sh = plan.cache_shardings(cache_shapes, cfg, mesh, batch_size)
    axes = plan.batch_axes(mesh, batch_size)
    tok_sh = NamedSharding(
        mesh, P(axes if len(axes) > 1 else (axes[0] if axes else None)))

    def serve_step(params, cache, tokens):
        logits, new_cache = model.decode_step(params, cache, tokens,
                                              window=window)
        next_tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        return logits, next_tok, new_cache

    return jax.jit(serve_step,
                   in_shardings=(p_sh, c_sh, tok_sh),
                   out_shardings=(None, tok_sh, c_sh),
                   donate_argnums=(1,)), {
                       "params": p_sh, "cache": c_sh, "tokens": tok_sh}


def _is_index_path(path) -> bool:
    return any(getattr(p, "name", "") == "index" for p in path)


def build_insert_step(model: Model, plan: Plan, mesh: Mesh, *,
                      cache_shapes, src_cache_shapes, batch_size: int):
    """Prefill-insert for continuous batching: scatter one freshly
    prefilled request (a batch-1 cache from ``build_prefill_step``) into
    slot ``slot`` of the live per-slot decode cache
    (``Model.init_slot_cache``).

    ``length`` is the request's true prompt length: it overwrites the
    slot's ``index`` entries (the prefill cache holds the padded bucket
    length there), so the pad tail past it stays masked out of attention
    and the next decode append overwrites the first pad position.  The
    destination cache is donated — the scatter is in-place.
    """
    cfg = model.cfg
    dst_sh = plan.cache_shardings(cache_shapes, cfg, mesh, batch_size)
    src_sh = plan.cache_shardings(src_cache_shapes, cfg, mesh, 1)
    scalar_sh = NamedSharding(mesh, P())

    def insert(dst, src, slot, length):
        def leaf(path, d, s):
            if _is_index_path(path):
                # dst: [layers..., B] per-slot indices; the src cache's
                # shared per-layer index is replaced by the true length
                return d.at[..., slot].set(jnp.asarray(length, d.dtype))
            # batch dim: where dst (B) and src (1) disagree; equal-shape
            # leaves fall back to the cache_spec size convention
            b_dim = next((i for i, (a, b) in enumerate(zip(d.shape, s.shape))
                          if a != b), None)
            if b_dim is None:
                b_dim = next((i for i, n in enumerate(d.shape)
                              if n == batch_size), None)
            if b_dim is None:       # batch-free leaf (shared state)
                return d
            return jax.lax.dynamic_update_slice_in_dim(
                d, s.astype(d.dtype), slot, b_dim)

        return jax.tree_util.tree_map_with_path(leaf, dst, src)

    return jax.jit(insert,
                   in_shardings=(dst_sh, src_sh, scalar_sh, scalar_sh),
                   out_shardings=dst_sh,
                   donate_argnums=(0,)), {"cache": dst_sh, "src": src_sh}


def build_decode_slots_step(model: Model, plan: Plan, mesh: Mesh, *,
                            params_shapes, cache_shapes, batch_size: int,
                            window: int = 0, pad_id: int = 0):
    """One decode step over the persistent slot cache (continuous
    batching).  Beyond ``build_serve_step`` it takes a ``live`` [B] bool
    mask: dead (evicted, not yet backfilled) slots emit ``pad_id`` and
    their per-slot cache indices are frozen, so an evicted slot's ring
    state cannot drift between eviction and the insert that recycles it.
    """
    cfg = model.cfg
    _set_logits_spec(model, plan, mesh, batch_size)
    _set_moe_dispatch(model, plan, mesh, batch_size)
    p_sh = _ns(mesh, plan.param_specs(params_shapes, cfg, mesh))
    c_sh = plan.cache_shardings(cache_shapes, cfg, mesh, batch_size)
    axes = plan.batch_axes(mesh, batch_size)
    b_ax = axes if len(axes) > 1 else (axes[0] if axes else None)
    tok_sh = NamedSharding(mesh, P(b_ax))
    live_sh = NamedSharding(mesh, P(b_ax))

    def decode_slots(params, cache, tokens, live):
        logits, new_cache = model.decode_step(params, cache, tokens,
                                              window=window)

        def freeze(path, new, old):
            if _is_index_path(path):
                return jnp.where(live, new, old)   # [..., B] broadcast
            return new

        new_cache = jax.tree_util.tree_map_with_path(freeze, new_cache,
                                                     cache)
        next_tok = jnp.where(live[:, None],
                             jnp.argmax(logits, axis=-1)[:, None],
                             pad_id).astype(jnp.int32)
        return logits, next_tok, new_cache

    return jax.jit(decode_slots,
                   in_shardings=(p_sh, c_sh, tok_sh, live_sh),
                   out_shardings=(None, tok_sh, c_sh),
                   donate_argnums=(1,)), {
                       "params": p_sh, "cache": c_sh, "tokens": tok_sh}
