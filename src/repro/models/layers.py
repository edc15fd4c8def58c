"""Primitive layers shared by every architecture family.

All parameters live in plain nested dicts of jnp arrays so that sharding
rules (core/sharding.py) can match on key paths, layers can be stacked on a
leading ``[n_layers, ...]`` axis for ``lax.scan``, and ``jax.eval_shape``
can produce allocation-free ShapeDtypeStructs for the multi-pod dry-run.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro import tracing

# --------------------------------------------------------------------- #
# init helpers
# --------------------------------------------------------------------- #

def dense_init(rng, shape, in_dim: Optional[int] = None, dtype=jnp.float32,
               scale: float = 1.0):
    """Truncated-normal fan-in init (std = scale / sqrt(in_dim))."""
    if in_dim is None:
        in_dim = shape[0]
    std = scale / math.sqrt(max(in_dim, 1))
    return (std * jax.random.truncated_normal(rng, -3.0, 3.0, shape)).astype(dtype)


def embed_init(rng, shape, dtype=jnp.float32):
    return (0.02 * jax.random.truncated_normal(rng, -3.0, 3.0, shape)).astype(dtype)


def zeros(shape, dtype=jnp.float32):
    return jnp.zeros(shape, dtype)


def ones(shape, dtype=jnp.float32):
    return jnp.ones(shape, dtype)


# --------------------------------------------------------------------- #
# normalization
# --------------------------------------------------------------------- #

def rmsnorm(x, weight, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, weight, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def init_norm(rng, d: int, kind: str):
    del rng
    if kind == "rmsnorm":
        return {"scale": ones((d,))}
    return {"scale": ones((d,)), "bias": zeros((d,))}


def apply_norm(x, params, kind: str, eps: float):
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"], eps)
    return layernorm(x, params["scale"], params["bias"], eps)


# --------------------------------------------------------------------- #
# rotary position embeddings
# --------------------------------------------------------------------- #

def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, D] (rotate pairs (x[..2i], x[..2i+1]));
    positions: broadcastable to [..., S]."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # [d/2]
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., S, d/2]
    cos = jnp.cos(angles)[..., None, :]                # [..., S, 1, d/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- #
# activations / MLP
# --------------------------------------------------------------------- #

def init_mlp(rng, d: int, d_ff: int, activation: str):
    r = jax.random.split(rng, 3)
    if activation == "silu":  # SwiGLU: gate + up + down
        return {
            "w_gate": dense_init(r[0], (d, d_ff), d),
            "w_up": dense_init(r[1], (d, d_ff), d),
            "w_down": dense_init(r[2], (d_ff, d), d_ff),
        }
    return {  # plain GELU MLP (gpt2 / whisper)
        "w_up": dense_init(r[0], (d, d_ff), d),
        "b_up": zeros((d_ff,)),
        "w_down": dense_init(r[1], (d_ff, d), d_ff),
        "b_down": zeros((d,)),
    }


def apply_mlp(x, params, activation: str):
    with jax.named_scope(tracing.MLP):
        dt = x.dtype
        if activation == "silu":
            g = jnp.einsum("...d,df->...f", x, params["w_gate"].astype(dt))
            u = jnp.einsum("...d,df->...f", x, params["w_up"].astype(dt))
            h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
            return jnp.einsum("...f,fd->...d", h, params["w_down"].astype(dt))
        h = jnp.einsum("...d,df->...f", x, params["w_up"].astype(dt))
        h = h + params["b_up"].astype(dt)
        h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(dt)
        out = jnp.einsum("...f,fd->...d", h, params["w_down"].astype(dt))
        return out + params["b_down"].astype(dt)


# --------------------------------------------------------------------- #
# embeddings
# --------------------------------------------------------------------- #

def init_embedding(rng, vocab: int, d: int):
    return {"table": embed_init(rng, (vocab, d))}


def embed(tokens, params, dtype):
    return params["table"].astype(dtype)[tokens]


def unembed(x, params, dtype):
    """Project back to vocabulary; logits in fp32 for a stable softmax."""
    table = params["table"].astype(dtype)
    return jnp.einsum("...d,vd->...v", x, table).astype(jnp.float32)


def init_learned_positions(rng, max_seq: int, d: int):
    return {"table": embed_init(rng, (max_seq, d))}
