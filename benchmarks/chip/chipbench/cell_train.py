"""Training cells: one ``repro.train.train`` call runs set-up, the checked
steps and the measured window.

``train`` initialises, compiles and then calls ``on_step_failure(i)``
before each step ``i``.  The benchmark hands it a ``StepClock``: steps
0 .. check_steps-1 are the checked steps (their losses, the first
gradient as AdamW's first moment holds it, and the parameters' change
over them are read from the loop's own state); the window opens before
step ``check_steps`` and closes at the first step boundary at least
``seconds`` later, by raising ``WindowClosed``.  So the window is whole
steps of the program's own loop, host gaps included, and nothing of the
loop is copied here.
"""
from __future__ import annotations

import gc
import math
import sys
import time
from typing import Any, Dict, Optional

from chipbench import check, counts, gen, harness, reference, tracefile
from chipbench.spec import Cell


class WindowClosed(Exception):
    pass


def _state(frame_locals: Dict[str, Any], treedef):
    """The parameters and optimizer state held by the training loop: the
    local whose tree matches the model's parameters, and the one whose
    first moment ``m`` does."""
    import jax
    params = opt = None
    for v in frame_locals.values():
        if params is None and jax.tree.structure(v) == treedef:
            params = v
        elif opt is None and hasattr(v, "m") and hasattr(v, "step") and \
                jax.tree.structure(v.m) == treedef:
            opt = v
    if params is None or opt is None:
        raise RuntimeError("the training loop's parameters and optimizer "
                           "state were not found at the step boundary")
    return params, opt


class StepClock:
    """The ``on_step_failure`` hook: reads the checked steps, opens and
    closes the window, and in a traced run spans each step."""

    def __init__(self, treedef, check_steps: int, seconds: float,
                 beta1: float, profile: harness.Profile,
                 trace_seconds: float):
        import jax
        self.treedef, self.n_check = treedef, check_steps
        self.seconds, self.beta1 = seconds, beta1
        self.profile, self.trace_seconds = profile, trace_seconds
        self.p0 = None
        self.grad: Optional[Dict[str, float]] = None
        self.change: Optional[Dict[str, float]] = None
        self.t_open = self.t_close = None
        self.steps = 0
        self._span = None
        self._norms = jax.jit(lambda t: jax.tree.map(jnp_norm, t))

    def __call__(self, i: int) -> None:
        import jax
        frame = sys._getframe(1)
        try:
            if i <= self.n_check:
                self._read(i, frame.f_locals)
            if i == self.n_check:
                jax.block_until_ready(_state(frame.f_locals,
                                             self.treedef))
                self.t_open = time.perf_counter()
                self.profile.start()
            elif i > self.n_check:
                self._close_span()
                now = time.perf_counter()
                if self.profile.active and \
                        now - self.t_open >= self.trace_seconds:
                    self.profile.stop()
                if now - self.t_open >= self.seconds:
                    jax.block_until_ready(_state(frame.f_locals,
                                                 self.treedef))
                    self.t_close = time.perf_counter()
                    self.steps = i - self.n_check
                    self.profile.stop()
                    raise WindowClosed(i)
            if i >= self.n_check and self.profile.active:
                self._span = self.profile.span("train.step")
                self._span.__enter__()
        finally:
            del frame

    def _close_span(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def _read(self, i: int, frame_locals) -> None:
        import jax
        params, opt = _state(frame_locals, self.treedef)
        if i == 0:
            self.p0 = jax.device_get(params)
        if i == 1:
            m = jax.tree_util.tree_flatten_with_path(
                self._norms(opt.m))[0]
            self.grad = {jax.tree_util.keystr(p): float(v) / (1 - self.beta1)
                         for p, v in m}
        if i == self.n_check:
            self.change = reference.change_norms(jax.device_get(params),
                                                  self.p0)
            self.p0 = None


def jnp_norm(x):
    import jax.numpy as jnp
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


class SpannedBatches:
    """The traffic generator's loader, with a host span around each batch
    while the profiler runs."""

    def __init__(self, batches: gen.TrainBatches, profile: harness.Profile):
        self.batches, self.profile = batches, profile
        self.n_shards = batches.n_shards

    def batch_at(self, step: int):
        with self.profile.span("loader.batch_at"):
            return self.batches.batch_at(step)


def run(cell: Cell, seed: int, seconds: float, trace: bool, t0: float, *,
        require_tpu: bool = True) -> Dict[str, Any]:
    """One run of a training cell; returns the result line's fields."""
    import jax

    from repro.configs.base import TrainConfig
    from repro.train import train

    cfg, traffic = cell.config, cell.traffic
    devices = harness.chips_for(cell, require_tpu)
    opt = traffic["optimizer"]
    tcfg = TrainConfig(
        learning_rate=opt["learning_rate"], weight_decay=opt["weight_decay"],
        beta1=opt["beta1"], beta2=opt["beta2"], eps=opt["eps"],
        grad_clip=opt["grad_clip"], warmup_steps=opt["warmup_steps"],
        total_steps=opt["total_steps"], schedule=opt["schedule"],
        seed=seed, microbatches=traffic["microbatches"], remat=True)
    plan, mesh = harness.make_mesh(traffic, devices)
    model = harness.program_model(cell)
    treedef = jax.tree.structure(jax.eval_shape(model.init,
                                                jax.random.key(0)))
    batches = gen.TrainBatches(traffic, cfg["vocab_size"], seed)
    profile = harness.Profile(cell.name, trace)
    clock = StepClock(treedef, traffic["check_steps"], seconds,
                      opt["beta1"], profile, traffic["trace_seconds"])
    try:
        train(model, plan, mesh, tcfg, SpannedBatches(batches, profile),
              steps=sys.maxsize, log_every=0, on_step_failure=clock,
              schedule=traffic["schedule"])
    except WindowClosed as closed:
        losses = list(closed.result.losses)
    finally:
        profile.stop()
    del model
    device = harness.device_record(devices)
    gc.collect()                      # the loop's state goes with its frame

    tokens_per_step = traffic["batch"] * traffic["seq"]
    window_losses = losses[clock.n_check:clock.n_check + clock.steps]
    out: Dict[str, Any] = {
        "attempted": clock.steps,
        "failed": sum(not math.isfinite(l) for l in window_losses),
        "device": device,
        "setup_s": clock.t_open - t0,
        "train_tokens_per_s": clock.steps * tokens_per_step
        / (clock.t_close - clock.t_open),
    }
    if trace and profile.path:
        ctx = harness.trace_context(
            cell, profile.path, devices, "train.step",
            kind="train", tokens_per_step=tokens_per_step,
            flops_per_token=counts.train_flops_per_token(
                cell.family, cfg, traffic["seq"]))
        out["layer_metrics"] = harness.layer_metrics(cell, ctx)
        out["device"].update(harness.traced_device(ctx))
        out["breakdown"] = tracefile.breakdown(ctx.trace, ctx.lo, ctx.hi)

    prog = {"losses": losses[:clock.n_check], "grad": clock.grad,
            "change": clock.change}
    ref = reference.train_steps(
        cell.family, cfg, opt, traffic["z_loss"], seed,
        [batches.batch_at(i) for i in range(clock.n_check)],
        rows=traffic["reference_rows"])
    out["readings"] = check.train_readings(prog, ref)
    out["program"], out["reference"] = prog, ref
    return out
