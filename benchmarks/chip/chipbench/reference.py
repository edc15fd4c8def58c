"""The plain float32 reference that decides ``correct``, for any family.

A family's mathematics (``references/<family>.py``, on ``Cell.family``
as ``family.math``) gives ``init(cfg, key)``, ``logits(cfg, params,
tokens, mm, remat=False)`` and ``loss_sums(cfg, params, tokens, labels,
mm)``.  This module drives it: AdamW steps over blocks of rows, the
norms the checks compare, the logit gaps of served tokens, the control
and the planted faults.  So every family is held to the same rules.

It imports nothing of the program and takes nothing the program made.
Every matrix product goes through ``mm`` at ``Precision.HIGHEST``.
``precision="float8"`` is the control: every matrix product takes its
operands rounded to float8 e4m3 with one absmax scale per tensor, as a
change to fp8 matmuls would.  Gradients pass the rounding straight
through.
"""
from __future__ import annotations

import json
import math
from functools import lru_cache, partial
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn


def _f8(x):
    scale = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = jax.lax.stop_gradient(scale)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(precision: str, spec: str, a, b):
    if precision == "float8":
        a, b = _f8(a), _f8(b)
    elif precision != "float32":
        raise ValueError(f"unknown reference precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _decays(path) -> bool:
    """AdamW's weight decay skips the layer-norm scales and biases."""
    names = [str(getattr(p, "key", "")) for p in path]
    return not any("norm" in n for n in names)


def leaf_norms(tree) -> Dict[str, float]:
    """Per-leaf L2 norms, summed in float64 on the host."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(np.sqrt(np.sum(np.square(
        np.asarray(x, np.float64))))) for p, x in flat}


def change_norms(now, before) -> Dict[str, float]:
    """Per-leaf L2 norms of ``now - before`` (host trees of one layout),
    one leaf at a time in float64."""
    flat = jax.tree_util.tree_flatten_with_path(now)[0]
    return {jax.tree_util.keystr(p): float(np.sqrt(np.sum(np.square(
        np.asarray(a, np.float64) - np.asarray(b, np.float64)))))
        for (p, a), b in zip(flat, jax.tree.leaves(before))}


def grad_accumulator(maths, cfg: Dict[str, Any], z_loss: float,
                     precision: str = "float32"):
    """``acc, params, tokens, labels -> acc`` with the gradient of a block
    of rows' summed loss added to ``acc = (grads, nll, z)`` in place."""
    mm = partial(_mm, precision)

    def block_loss(p, tokens, labels):
        nll, zsq = maths.loss_sums(cfg, p, tokens, labels, mm)
        return nll + z_loss * zsq, (nll, zsq)

    @partial(jax.jit, donate_argnums=(0,))
    def accumulate(acc, params, tokens, labels):
        (_, (nll, zsq)), g = jax.value_and_grad(
            block_loss, has_aux=True)(params, tokens, labels)
        acc_g, acc_nll, acc_z = acc
        return jax.tree.map(jnp.add, acc_g, g), acc_nll + nll, acc_z + zsq

    return accumulate


def adamw_step(opt: Dict[str, Any]):
    """AdamW as the configuration states it: the mean gradient clipped to
    a global norm, bias-corrected moments, decoupled weight decay on all
    but the layer norms.  Returns (params, clipped grads, m, v)."""
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["eps"]
    wd, clip = opt["weight_decay"], opt["grad_clip"]

    @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def adamw(params, grads, m, v, t, lr, n_tokens):
        grads = jax.tree.map(lambda g: g / n_tokens, grads)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree.leaves(grads)))
        grads = jax.tree.map(
            lambda g: g * jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12)),
            grads)
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t

        def upd(path, p, m, v):
            delta = (m / c1) / (jnp.sqrt(v / c2) + eps)
            if _decays(path):
                delta = delta + wd * p
            return p - lr * delta

        return (jax.tree_util.tree_map_with_path(upd, params, m, v),
                grads, m, v)

    return adamw


_zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
_copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
_negate = jax.jit(lambda new, old: jax.tree.map(lambda a, b: 2 * b - a,
                                                new, old))


@lru_cache(maxsize=None)
def _step_programs(maths, cfg_json: str, opt_json: str, z_loss: float,
                   precision: str):
    """The jitted init, gradient and AdamW programs of one configuration,
    kept for the process: ``calibrate.py`` runs many seeds and variants
    through them and compiles each once."""
    cfg = json.loads(cfg_json)
    return (jax.jit(partial(maths.init, cfg)),
            grad_accumulator(maths, cfg, z_loss, precision),
            adamw_step(json.loads(opt_json)))


FAULTS = ("", "half_batch", "state_unchanged", "moments_not_carried",
          "update_negated")


def train_steps(family, cfg: Dict[str, Any], opt: Dict[str, Any],
                z_loss: float, seed: int,
                batches: Sequence[Dict[str, np.ndarray]], *,
                precision: str = "float32", rows: int = 1,
                fault: str = "") -> Dict[str, Any]:
    """AdamW steps of ``family``'s mathematics over ``batches`` from the
    seed's weights: each step's loss, every leaf's norm of the first
    (clipped) gradient, and every leaf's norm of the parameters' change
    over all the steps.

    The gradient of a step is summed over blocks of ``rows`` rows, so the
    reference fits beside nothing else on one chip.  ``fault`` plants one
    in the steps, to read what it does to the numbers compared:
    ``half_batch`` counts half of each batch's rows, the mean taken over
    those; ``state_unchanged`` returns the parameters and moments it was
    given; ``moments_not_carried`` starts every step from zero moments at
    step count 1; ``update_negated`` applies each step's update with its
    sign turned.
    """
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    init_fn, accumulate, adamw = _step_programs(
        family.math, json.dumps(cfg, sort_keys=True),
        json.dumps(opt, sort_keys=True), z_loss, precision)
    params = init_fn(jax.random.key(seed))
    p0 = jax.device_get(params)
    m, v = _zeros(params), _zeros(params)
    losses, grad0 = [], None
    for t, batch in enumerate(batches):
        tokens = np.asarray(batch["tokens"])
        labels = np.asarray(batch["labels"])
        keep = len(tokens) // 2 if fault == "half_batch" else len(tokens)
        tokens, labels = tokens[:keep], labels[:keep]
        n_tokens = int(np.sum(labels[:, 1:] >= 0))
        acc = (_zeros(params), jnp.float32(0), jnp.float32(0))
        for r in range(0, keep, rows):
            acc = accumulate(acc, params, tokens[r:r + rows],
                             labels[r:r + rows])
        grads, nll, zsq = acc
        losses.append((float(nll) + z_loss * float(zsq)) / n_tokens)
        if fault == "moments_not_carried":
            m, v, count = _zeros(params), _zeros(params), 1
        else:
            count = t + 1
        keep_old = fault in ("state_unchanged", "update_negated")
        new, grads, new_m, new_v = adamw(
            _copy(params) if keep_old else params, grads, m, v,
            jnp.float32(count), jnp.float32(lr_at(opt, t)),
            jnp.float32(n_tokens))
        if t == 0:
            grad0 = leaf_norms(grads)
        del grads
        if fault == "state_unchanged":
            m, v = _zeros(params), _zeros(params)
        elif fault == "update_negated":
            params, m, v = _negate(new, params), new_m, new_v
        else:
            params, m, v = new, new_m, new_v
        del new, new_m, new_v
    p_end = jax.device_get(params)
    del params, m, v
    return {"losses": losses, "grad": grad0,
            "change": change_norms(p_end, p0)}


def lr_at(opt: Dict[str, Any], step: int) -> float:
    """Learning rate of step ``step`` (0-based): linear warm-up, then
    constant or cosine decay to a tenth."""
    warm = min(1.0, (step + 1) / max(opt["warmup_steps"], 1))
    if opt["schedule"] == "constant":
        decay = 1.0
    elif opt["schedule"] == "cosine":
        frac = min(max((step - opt["warmup_steps"])
                       / max(opt["total_steps"] - opt["warmup_steps"], 1),
                       0.0), 1.0)
        decay = 0.1 + 0.45 * (1 + math.cos(math.pi * frac))
    else:
        raise ValueError(f"unknown schedule {opt['schedule']!r}")
    return opt["learning_rate"] * warm * decay


def served_gaps(family, cfg: Dict[str, Any], seed: int, weight_dtype: str,
                seqs: Sequence[Tuple[np.ndarray, np.ndarray]], *,
                control: bool = False, rows: int = 4) -> List[float]:
    """For each (prompt, served tokens): the widest gap by which a served
    token's logit lies below the reference's best at its position.

    ``control``: instead of the served token, the token that the float8
    reference puts first at each of those positions.
    """
    maths = family.math
    params = jax.jit(partial(maths.init, cfg))(jax.random.key(seed))
    if weight_dtype != "float32":
        params = jax.jit(lambda t: jax.tree.map(
            lambda x: x.astype(weight_dtype).astype(jnp.float32), t))(params)
    S = family.program.positions(cfg)

    @jax.jit
    def gaps(params, tokens, targets, valid):
        ref = maths.logits(cfg, params, tokens, partial(_mm, "float32"))
        best = jnp.max(ref, axis=-1)
        if control:
            targets = jnp.argmax(maths.logits(
                cfg, params, tokens, partial(_mm, "float8")), -1)
        got = jnp.take_along_axis(ref, targets[..., None], axis=-1)[..., 0]
        return jnp.max(jnp.where(valid, best - got, -jnp.inf), axis=-1)

    out = []
    for i in range(0, len(seqs), rows):
        block = seqs[i:i + rows]
        tokens = np.zeros((rows, S), np.int32)
        targets = np.zeros((rows, S), np.int32)
        valid = np.zeros((rows, S), bool)
        for r, (prompt, served) in enumerate(block):
            seq = np.concatenate([prompt, served])
            n, P = len(seq) - 1, len(prompt)
            if n > S:
                raise ValueError(f"sequence of {n} tokens > context {S}")
            tokens[r, :n] = seq[:-1]
            targets[r, :n] = seq[1:]
            valid[r, P - 1:n] = True      # the positions that served
        out.extend(float(g) for g in
                   np.asarray(gaps(params, tokens, targets, valid))[
                       :len(block)])
    return out
