"""Plain GPT-2 in float32: the mathematics of the ``gpt2`` family's
reference.

It imports nothing of the program and takes nothing the program made.
``init`` draws the weights from the seed by the initialisation the
configuration states (truncated normal within 3 standard deviations:
std 1/sqrt(fan-in) for projections, 0.02 for the embeddings; zero
biases, unit layer-norm scales), with the program's key splits, so that
both sides start from the same numbers.  ``logits`` takes every matrix
product through ``mm(spec, a, b)``, which ``chipbench/reference.py``
runs at ``Precision.HIGHEST``, or with its operands rounded for the
control.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


def dims(cfg: Dict[str, Any]) -> Tuple[int, int, int, int, int, int]:
    d, h = cfg["n_embd"], cfg["n_head"]
    return (cfg["n_layer"], d, h, d // h, cfg["n_inner"],
            cfg["vocab_size"])


def init(cfg: Dict[str, Any], key) -> Dict[str, Any]:
    """Float32 weights drawn from ``jax.random.key(seed)`` (call under
    ``jit``)."""
    L, d, H, hd, f, V = dims(cfg)
    tn = partial(jax.random.truncated_normal, lower=-3.0, upper=3.0)

    def dense(k, shape, fan_in):
        return (1.0 / math.sqrt(fan_in)) * tn(k, shape=shape)

    def norm():
        return {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}

    def block(k):
        r = jax.random.split(k, 4)
        m = jax.random.split(r[2], 3)
        a = jax.random.split(r[3], 4)
        return {
            "norm1": norm(), "norm2": norm(),
            "mlp": {"w_up": dense(m[0], (d, f), d),
                    "b_up": jnp.zeros((f,)),
                    "w_down": dense(m[1], (f, d), f),
                    "b_down": jnp.zeros((d,))},
            "attn": {"wq": dense(a[0], (d, H, hd), d),
                     "wk": dense(a[1], (d, H, hd), d),
                     "wv": dense(a[2], (d, H, hd), d),
                     "wo": dense(a[3], (H, hd, d), H * hd),
                     "bq": jnp.zeros((H, hd)), "bk": jnp.zeros((H, hd)),
                     "bv": jnp.zeros((H, hd)), "bo": jnp.zeros((d,))},
        }

    r = jax.random.split(key, 8)
    return {
        "embed": {"table": 0.02 * tn(r[0], shape=(V, d))},
        "final_norm": norm(),
        "pos_embed": {"table": 0.02 * tn(r[3], shape=(cfg["n_positions"],
                                                       d))},
        "layers": jax.vmap(block)(jax.random.split(r[4], L)),
    }


def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def logits(cfg: Dict[str, Any], params, tokens, mm, remat: bool = False):
    """[b, S] token ids -> [b, S, V] float32 logits, causal attention."""
    L, d, H, hd, f, V = dims(cfg)
    eps = cfg["layer_norm_epsilon"]
    S = tokens.shape[1]
    x = params["embed"]["table"][tokens] + params["pos_embed"]["table"][:S]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, p):
        a = p["attn"]
        h = _ln(x, p["norm1"], eps)
        q = mm("bsd,dhk->bshk", h, a["wq"]) + a["bq"]
        k = mm("bsd,dhk->bshk", h, a["wk"]) + a["bk"]
        v = mm("bsd,dhk->bshk", h, a["wv"]) + a["bv"]
        s = mm("bshk,bthk->bhst", q, k) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        o = mm("bhst,bthk->bshk", jax.nn.softmax(s, axis=-1), v)
        x = x + mm("bshk,hkd->bsd", o, a["wo"]) + a["bo"]
        m = p["mlp"]
        h = _ln(x, p["norm2"], eps)
        u = _gelu(mm("bsd,df->bsf", h, m["w_up"]) + m["b_up"])
        return x + mm("bsf,fd->bsd", u, m["w_down"]) + m["b_down"], None

    x, _ = jax.lax.scan(jax.checkpoint(layer) if remat else layer, x,
                        params["layers"])
    x = _ln(x, params["final_norm"], eps)
    return mm("bsd,vd->bsv", x, params["embed"]["table"])


def loss_sums(cfg: Dict[str, Any], params, tokens, labels, mm):
    """Summed next-token cross-entropy and squared log-normaliser over
    the labels that count (>= 0)."""
    lg = logits(cfg, params, tokens, mm, remat=True)[:, :-1]
    lab = labels[:, 1:]
    mask = lab >= 0
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, jnp.maximum(lab, 0)[..., None],
                                 axis=-1)[..., 0]
    nll = jnp.sum(jnp.where(mask, lse - picked, 0.0))
    return nll, jnp.sum(jnp.where(mask, jnp.square(lse), 0.0))
