"""Training loop: plan-aware pretraining driver.

Mirrors the paper's measurement methodology (§III-B): wall-clock per epoch
and average achieved TFLOP/s (model FLOPs 6·N·D / step time), which is what
Algorithm 1 probes when choosing a technique.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro import tracing
from repro.configs.base import ModelConfig, TrainConfig
from repro.core.plans import Plan
from repro.core.steps import build_train_step
from repro.models.model import Model
from repro.models.registry import abstractify
from repro.optim import init_adamw
from repro.train.checkpoint import save_checkpoint


@dataclass
class TrainResult:
    losses: List[float] = field(default_factory=list)
    step_times: List[float] = field(default_factory=list)
    metrics_last: Dict[str, float] = field(default_factory=dict)
    compile_s: float = 0.0          # lowering + compiling the step
    # device bytes the compiled step needs per device (arguments +
    # outputs + temporaries - donated aliases); None if not reported
    step_bytes: Optional[int] = None
    params: Any = None              # the trained state, laid out by the plan
    opt_state: Any = None

    @property
    def avg_step_time(self) -> float:
        times = self.step_times[1:] or self.step_times  # drop warm-up step
        return float(np.mean(times)) if times else float("nan")

    def tflops(self, model_flops_per_step: float) -> float:
        t = self.avg_step_time
        return model_flops_per_step / t / 1e12 if t > 0 else 0.0


def model_flops_per_step(cfg: ModelConfig, tokens_per_step: int) -> float:
    """6·N_active·D — the paper's 'training performance' denominator."""
    return 6.0 * cfg.active_param_count() * tokens_per_step


def train(model: Model, plan: Plan, mesh, tcfg: TrainConfig, loader, *,
          steps: int, params=None, opt_state=None,
          log_every: int = 10, ckpt_dir: Optional[str] = None,
          ckpt_every: int = 0, stage_layers=None,
          schedule: str = "gpipe", start_step: int = 0,
          on_step_failure: Optional[Callable[[int], None]] = None,
          log_fn: Callable[[str], None] = print) -> TrainResult:
    """Plan-aware training driver; ``stage_layers`` and ``schedule``
    thread a searched pipeline ``Placement``'s per-stage layer split and
    tick-order schedule into the step builder (uneven splits run
    pad-and-masked, alternative schedules via the scheduled runner —
    core/pipeline.py, docs/schedules.md).

    ``start_step`` resumes mid-run: steps ``start_step..steps-1`` are
    executed against the same deterministic batch sequence
    (``loader.batch_at(i)``) and absolute step numbers, so a restored
    checkpoint continues exactly where the original run would have been
    — the elastic-recovery resume path (``repro.train.replan``,
    docs/elasticity.md).

    ``on_step_failure`` is the fault-injection hook: called with the
    absolute step index before each step executes; raising from it
    (e.g. ``repro.train.replan.SiteFailure``, via ``kill_site_at``)
    kills the run deterministically mid-epoch — the exception leaves
    ``train`` with the partial ``TrainResult`` attached as its
    ``result`` attribute, so the chaos benchmark can account for
    steps-lost and pre-failure step times.
    """
    cfg = model.cfg
    with jax.set_mesh(mesh):
        key = jax.random.key(tcfg.seed)
        first = loader.batch_at(start_step)
        p_shapes = abstractify(params) if params is not None \
            else jax.eval_shape(model.init, key)
        b_shapes = abstractify(first)
        step_fn, sh = build_train_step(model, plan, mesh, tcfg,
                                       params_shapes=p_shapes,
                                       batch_shapes=b_shapes,
                                       stage_layers=stage_layers,
                                       schedule=schedule)
        # fresh state is created in place, already laid out by the plan,
        # so no device ever holds a whole unsharded copy first
        if params is None:
            params = jax.jit(model.init, out_shardings=sh["params"])(key)
        else:
            params = jax.device_put(params, sh["params"])
        if opt_state is None:
            opt_state = jax.jit(init_adamw,
                                out_shardings=sh["opt"])(params)
        else:
            opt_state = jax.device_put(opt_state, sh["opt"])

        result = TrainResult()
        # compile ahead of the loop, so that no step time includes it
        t0 = time.perf_counter()
        with tracing.lowering() as noted:
            step_fn = step_fn.lower(params, opt_state,
                                    jax.device_put(first, sh["batch"]))
        step_fn = step_fn.compile()
        result.compile_s = time.perf_counter() - t0
        tracing.register("train_step", step_fn, compile_s=result.compile_s,
                         notes=noted)
        mem = step_fn.memory_analysis()
        if mem is not None:
            result.step_bytes = (mem.argument_size_in_bytes
                                 + mem.output_size_in_bytes
                                 + mem.temp_size_in_bytes
                                 - mem.alias_size_in_bytes)
        metrics: Dict[str, Any] = {}
        flops = model_flops_per_step(
            cfg, first["tokens"].shape[0] * first["tokens"].shape[1]
            * loader.n_shards)
        for i in range(start_step, steps):
            if on_step_failure is not None:
                try:
                    on_step_failure(i)
                except BaseException as e:
                    e.result = result        # partial losses/step times
                    raise
            with jax.profiler.StepTraceAnnotation("train", step_num=i):
                with tracing.span("train.batch"):
                    batch = jax.device_put(loader.batch_at(i), sh["batch"])
                t0 = time.perf_counter()
                params, opt_state, metrics = step_fn(params, opt_state,
                                                     batch)
                with tracing.span("train.sync"):
                    loss = float(metrics["loss"])  # blocks on completion
                dt = time.perf_counter() - t0
                result.losses.append(loss)
                result.step_times.append(dt)
                if log_every and (i % log_every == 0 or i == steps - 1):
                    log_fn(f"step {i:5d} loss {loss:8.4f} "
                           f"ce {float(metrics['ce']):8.4f} "
                           f"gnorm {float(metrics['grad_norm']):7.3f} "
                           f"{dt * 1e3:8.1f} ms "
                           f"{flops / max(dt, 1e-9) / 1e12:6.2f} TFLOP/s")
                if ckpt_dir and ckpt_every and (i + 1) % ckpt_every == 0:
                    save_checkpoint(ckpt_dir, i + 1, params, opt_state)
        result.metrics_last = {k: float(v) for k, v in metrics.items()}
        if ckpt_dir:
            save_checkpoint(ckpt_dir, steps, params, opt_state)
    result.params, result.opt_state = params, opt_state
    return result
