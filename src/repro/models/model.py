"""Model assembly: embeddings -> stacked layer scan -> head, for every
architecture family, with train / prefill / decode entry points.

Layer parameters are stacked on a leading ``[n_layers]`` axis and driven by
``lax.scan`` (compile time independent of depth; sliceable into Pipeshard
stages).  Decode carries a constant-shape cache pytree through the same scan.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import tracing
from repro.configs.base import ModelConfig
from repro.models import blocks
from repro.models import attention as attn_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    apply_norm, dense_init, embed, embed_init, init_embedding,
    init_learned_positions, init_norm, unembed,
)

Params = Dict[str, Any]


def _stack_init(fn, rng, n: int):
    return jax.vmap(fn)(jax.random.split(rng, n))


_BLOCK = {
    "dense": (blocks.init_dense_block, blocks.dense_block_forward,
              blocks.dense_block_prefill, blocks.dense_block_decode),
    "vlm": (blocks.init_dense_block, blocks.dense_block_forward,
            blocks.dense_block_prefill, blocks.dense_block_decode),
    "moe": (blocks.init_moe_block, blocks.moe_block_forward,
            blocks.moe_block_prefill, blocks.moe_block_decode),
    "ssm": (blocks.init_ssm_block, blocks.ssm_block_forward,
            blocks.ssm_block_prefill, blocks.ssm_block_decode),
    "hybrid": (blocks.init_mamba2_block, blocks.mamba2_block_forward,
               blocks.mamba2_block_prefill, blocks.mamba2_block_decode),
    "encdec": (blocks.init_encdec_block, blocks.encdec_block_forward,
               blocks.encdec_block_prefill, blocks.encdec_block_decode),
}


class Model:
    """Functional model wrapper around a ModelConfig."""

    def __init__(self, cfg: ModelConfig, *, use_pallas: bool = False):
        self.cfg = cfg
        self.use_pallas = use_pallas
        self.compute_dtype = jnp.dtype(cfg.dtype)
        # Optional PartitionSpec pinned on logits right after unembedding.
        # Set by the step builders under weight-sharding plans so the
        # [B, S, vocab] tensor (and its fp32 softmax temporaries) stays
        # vocab-sharded instead of being all-gathered per device.
        self.logits_pspec = None
        # Optional PartitionSpec pinned on the residual stream at each
        # layer boundary (FSDP plans): the remat-saved activations then
        # shard their d_model dim over the model axis instead of holding
        # a full [L, B_loc, S, d] copy per device (270 GB for llama3-405b).
        self.resid_pspec = None

    # ----------------------------------------------------------------- #
    # init
    # ----------------------------------------------------------------- #
    def init(self, rng) -> Params:
        cfg = self.cfg
        r = jax.random.split(rng, 8)
        init_block = _BLOCK[cfg.family][0]
        params: Params = {
            "embed": init_embedding(r[0], cfg.vocab_size, cfg.d_model),
            "final_norm": init_norm(r[1], cfg.d_model, cfg.norm),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = init_embedding(r[2], cfg.vocab_size,
                                               cfg.d_model)
        if not cfg.rope_theta and cfg.family != "ssm":
            params["pos_embed"] = init_learned_positions(
                r[3], cfg.max_seq_len, cfg.d_model)

        if cfg.family == "hybrid":
            k = cfg.hybrid_attn_every
            G = cfg.n_layers // k
            per_group = lambda rg: _stack_init(
                lambda rr: init_block(rr, cfg), rg, k)
            params["layers"] = {                     # [G, k, ...] + [G]
                "blocks": _stack_init(per_group, r[4], G),
                "gates": jnp.ones((G,)),
            }
            params["shared"] = blocks.init_dense_block(r[5], cfg)
        else:
            params["layers"] = _stack_init(
                lambda rr: init_block(rr, cfg), r[4], cfg.n_layers)

        if cfg.family == "encdec":
            params["encoder"] = {
                "layers": _stack_init(
                    lambda rr: blocks.init_encoder_block(rr, cfg), r[5],
                    cfg.n_enc_layers),
                "norm": init_norm(r[6], cfg.d_model, cfg.norm),
                "pos": init_learned_positions(
                    jax.random.fold_in(r[6], 1), cfg.enc_seq_len, cfg.d_model),
            }
        if cfg.family == "vlm":
            rs = jax.random.split(r[5], 2)
            params["projector"] = {
                "w1": dense_init(rs[0], (cfg.vision_dim, cfg.d_model),
                                 cfg.vision_dim),
                "w2": dense_init(rs[1], (cfg.d_model, cfg.d_model),
                                 cfg.d_model),
            }
        return params

    # ----------------------------------------------------------------- #
    # shared pieces
    # ----------------------------------------------------------------- #
    def _embed_inputs(self, params, batch) -> Tuple[jax.Array, jax.Array, int]:
        """Returns (x, positions, n_prefix) where n_prefix = non-text prefix
        length (VLM patches)."""
        cfg, dt = self.cfg, self.compute_dtype
        tokens = batch["tokens"]
        x = embed(tokens, params["embed"], dt)
        n_prefix = 0
        if cfg.family == "vlm":
            patches = batch["patch_embeds"].astype(dt)        # [B, P, vdim]
            p = jnp.einsum("bpv,vd->bpd", patches,
                           params["projector"]["w1"].astype(dt))
            p = jax.nn.gelu(p.astype(jnp.float32)).astype(dt)
            p = jnp.einsum("bpd,de->bpe", p,
                           params["projector"]["w2"].astype(dt))
            x = jnp.concatenate([p, x], axis=1)
            n_prefix = patches.shape[1]
        B, S = x.shape[0], x.shape[1]
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        if "pos_embed" in params:
            x = x + params["pos_embed"]["table"].astype(dt)[positions]
        return x, positions, n_prefix

    def _head(self, params, x) -> jax.Array:
        cfg = self.cfg
        with jax.named_scope(tracing.LOGITS):
            x = apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
            table = params["embed"] if cfg.tie_embeddings \
                else params["lm_head"]
            logits = unembed(x, table, self.compute_dtype)
            if self.logits_pspec is not None:
                logits = jax.lax.with_sharding_constraint(
                    logits, self.logits_pspec)
            return logits

    def _encode(self, params, batch) -> jax.Array:
        """Whisper encoder over precomputed frame embeddings (stub frontend)."""
        cfg, dt = self.cfg, self.compute_dtype
        frames = batch["frames"].astype(dt)                   # [B, F, d]
        B, F = frames.shape[0], frames.shape[1]
        pos = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32)[None], (B, F))
        x = frames + params["encoder"]["pos"]["table"].astype(dt)[pos]

        def body(h, layer_p):
            return blocks.encoder_block_forward(
                h, layer_p, cfg, positions=pos), None

        x, _ = jax.lax.scan(body, x, params["encoder"]["layers"])
        return apply_norm(x, params["encoder"]["norm"], cfg.norm, cfg.norm_eps)

    # ----------------------------------------------------------------- #
    # full-sequence forward (train)
    # ----------------------------------------------------------------- #
    def run_stack(self, stack, x, positions, *, shared=None, enc_out=None,
                  window: int = 0, remat: bool = True, layer_valid=None
                  ) -> Tuple[jax.Array, jax.Array]:
        """Run a (slice of the) stacked layer parameters over activations.

        ``stack`` is ``params["layers"]`` or a stage-local slice of it
        (Pipeshard); ``shared`` is the hybrid family's shared attention
        block (replicated across stages).  Returns (x, aux_sum).

        ``layer_valid``: optional boolean mask over the stack's leading
        axis (groups for hybrid).  Slots marked False are identity
        pass-throughs — the activations skip the layer unchanged and the
        slot contributes zero aux.  This is how Pipeshard realizes uneven
        per-stage layer counts: every stage's slice is padded to the
        longest stage and the padding is masked out here
        (core/pipeline.make_pipeline_loss).
        """
        cfg = self.cfg
        fwd = _BLOCK[cfg.family][1]

        def block_fn(h, layer_p):
            if self.resid_pspec is not None:
                h = jax.lax.with_sharding_constraint(h, self.resid_pspec)
            return fwd(h, layer_p, cfg, positions=positions, window=window,
                       use_pallas=self.use_pallas,
                       **({"enc_out": enc_out} if enc_out is not None else {}))

        if remat:
            block_fn = jax.checkpoint(block_fn)

        if cfg.family == "hybrid":
            def shared_block(h, gate):
                y, _ = blocks.dense_block_forward(
                    h, shared, cfg, positions=positions, window=window,
                    use_pallas=self.use_pallas)
                return h + gate.astype(h.dtype) * (y - h)

            if remat:
                shared_block = jax.checkpoint(shared_block)

            def group_fn(h, inp):
                layer_p, gate = inp
                h = shared_block(h, gate)
                h, auxs = jax.lax.scan(
                    lambda hh, lp: block_fn(hh, lp), h, layer_p)
                return h, jnp.sum(auxs)

            body, xs = group_fn, (stack["blocks"], stack["gates"])
        else:
            body, xs = block_fn, stack

        if layer_valid is None:
            x, auxs = jax.lax.scan(body, x, xs)
        else:
            def masked_body(h, inp):
                valid, real = inp
                out, aux = body(h, real)
                return (jnp.where(valid, out, h),
                        jnp.where(valid, aux, jnp.zeros_like(aux)))

            x, auxs = jax.lax.scan(masked_body, x, (layer_valid, xs))
        return x, jnp.sum(auxs)

    def forward(self, params, batch, *, window: int = 0,
                remat: bool = True) -> Tuple[jax.Array, jax.Array]:
        """Returns (logits [B, S, V], aux_loss)."""
        cfg = self.cfg
        x, positions, _ = self._embed_inputs(params, batch)
        if batch.get("positions") is None:
            positions = None        # the plain arange: a plain causal mask
        enc_out = self._encode(params, batch) if cfg.family == "encdec" else None
        x, aux = self.run_stack(params["layers"], x, positions,
                                shared=params.get("shared"), enc_out=enc_out,
                                window=window, remat=remat)
        return self._head(params, x), aux

    # ----------------------------------------------------------------- #
    # loss
    # ----------------------------------------------------------------- #
    def loss(self, params, batch, *, remat: bool = True
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        logits, aux = self.forward(params, batch, remat=remat)
        with jax.named_scope(tracing.LOGITS):
            return lm_loss(self.cfg, logits, batch, aux)

    # ----------------------------------------------------------------- #
    # caches
    # ----------------------------------------------------------------- #
    def init_cache(self, batch: int, capacity: int, *,
                   window: int = 0, kv_dtype: str = "fp32") -> Any:
        """Decode cache pytree, leaves stacked on the layer axis.
        ``capacity`` is the KV length to materialize; a nonzero ``window``
        bounds it (ring buffer) for the long-context decode variant.
        ``kv_dtype='int8'`` (plain-GQA attention families only) swaps in
        the quantized ``QuantKVCache`` — decode then runs through the
        int8-KV Pallas kernel (docs/quantization.md)."""
        cfg, dt = self.cfg, self.compute_dtype
        cap = min(capacity, window) if window else capacity
        if kv_dtype not in ("fp32", "int8"):
            raise ValueError(f"unknown kv_dtype {kv_dtype!r}; "
                             f"expected 'fp32' or 'int8'")
        if kv_dtype == "int8" and (
                cfg.family not in ("dense", "vlm", "moe")
                or cfg.mla is not None):
            raise ValueError(
                "kv_dtype='int8' needs a plain-GQA attention cache; "
                f"family {cfg.family!r}"
                + (" with MLA" if cfg.mla is not None else "")
                + " stores no quantizable k/v tensors")

        def stack(make, n):
            return jax.tree.map(
                lambda *xs: jnp.stack(xs), *[make() for _ in range(n)])

        if cfg.family in ("dense", "vlm", "moe"):
            if cfg.mla is not None:
                make = lambda: attn_mod.init_mla_cache(batch, cap, cfg.mla, dt)
            elif kv_dtype == "int8":
                make = lambda: attn_mod.init_quant_kv_cache(
                    batch, cap, cfg.n_kv_heads, cfg.head_dim, cfg.head_dim)
            else:
                make = lambda: attn_mod.init_kv_cache(
                    batch, cap, cfg.n_kv_heads, cfg.head_dim, cfg.head_dim, dt)
            return stack(make, cfg.n_layers)
        if cfg.family == "ssm":
            return stack(lambda: ssm_mod.init_ssm_state(cfg, batch, dt),
                         cfg.n_layers)
        if cfg.family == "hybrid":
            k = cfg.hybrid_attn_every
            G = cfg.n_layers // k
            ssm_one = lambda: stack(
                lambda: ssm_mod.init_ssm_state(cfg, batch, dt), k)
            return {
                "ssm": stack(ssm_one, G),                       # [G, k, ...]
                "attn": stack(lambda: attn_mod.init_kv_cache(
                    batch, cap, cfg.n_kv_heads, cfg.head_dim,
                    cfg.head_dim, dt), G),
            }
        if cfg.family == "encdec":
            F = cfg.enc_seq_len
            make = lambda: {
                "self": attn_mod.init_kv_cache(
                    batch, cap, cfg.n_kv_heads, cfg.head_dim, cfg.head_dim, dt),
                "cross_k": jnp.zeros((batch, F, cfg.n_heads, cfg.head_dim), dt),
                "cross_v": jnp.zeros((batch, F, cfg.n_heads, cfg.head_dim), dt),
            }
            return stack(make, cfg.n_layers)
        raise ValueError(cfg.family)

    def init_slot_cache(self, batch: int, capacity: int, *,
                        window: int = 0, kv_dtype: str = "fp32") -> Any:
        """Per-slot decode cache for continuous batching: ``init_cache``
        with every ring ``index`` leaf widened by a trailing ``[batch]``
        axis, so each slot tracks its own fill position and can hold a
        different request (``serve.engine.ContinuousEngine``).  SSM state
        carries no index and is shared unchanged."""
        cache = self.init_cache(batch, capacity, window=window,
                                kv_dtype=kv_dtype)

        def widen(path, leaf):
            if any(getattr(p, "name", "") == "index" for p in path):
                return jnp.zeros(leaf.shape + (batch,), leaf.dtype)
            return leaf

        return jax.tree_util.tree_map_with_path(widen, cache)

    # ----------------------------------------------------------------- #
    # prefill: full forward that also fills the cache
    # ----------------------------------------------------------------- #
    def prefill(self, params, batch, cache, *, window: int = 0,
                last_pos=None) -> Tuple[jax.Array, Any]:
        """Returns (last-position logits [B, V], filled cache).

        ``last_pos``: optional traced int32 scalar — read the logits at
        this sequence position instead of the final one.  This is how a
        bucket-padded prefill (continuous batching) reads the true
        prompt's last token while the pad tail stays causally invisible.
        """
        cfg = self.cfg
        x, positions, _ = self._embed_inputs(params, batch)
        pre = _BLOCK[cfg.family][2]
        enc_out = self._encode(params, batch) if cfg.family == "encdec" else None

        def block_fn(h, inp):
            layer_p, layer_c = inp
            h, c, _ = pre(h, layer_p, cfg, positions=positions, cache=layer_c,
                          window=window,
                          **({"enc_out": enc_out} if enc_out is not None else {}))
            return h, c

        if cfg.family == "hybrid":
            shared = params["shared"]

            def group_fn(h, inp):
                layer_p, gate, g_cache = inp
                y, ac, _ = blocks.dense_block_prefill(
                    h, shared, cfg, positions=positions,
                    cache=g_cache["attn"], window=window)
                h = h + gate.astype(h.dtype) * (y - h)
                h, sc = jax.lax.scan(
                    lambda hh, i: (lambda r: (r[0], r[1]))(
                        blocks.mamba2_block_prefill(
                            hh, i[0], cfg, cache=i[1])[:2]),
                    h, (layer_p, g_cache["ssm"]))
                return h, {"attn": ac, "ssm": sc}

            x, new_cache = jax.lax.scan(
                group_fn, x,
                (params["layers"]["blocks"], params["layers"]["gates"],
                 {"attn": cache["attn"], "ssm": cache["ssm"]}))
        else:
            x, new_cache = jax.lax.scan(block_fn, x,
                                        (params["layers"], cache))
        x_last = x[:, -1:] if last_pos is None else \
            jax.lax.dynamic_slice_in_dim(x, last_pos, 1, 1)
        logits = self._head(params, x_last)[:, 0]
        return logits, new_cache

    # ----------------------------------------------------------------- #
    # decode: one token through the stack
    # ----------------------------------------------------------------- #
    def decode_step(self, params, cache, tokens, *, window: int = 0
                    ) -> Tuple[jax.Array, Any]:
        """tokens: [B, 1] -> (logits [B, V], new cache)."""
        cfg, dt = self.cfg, self.compute_dtype
        dec = _BLOCK[cfg.family][3]
        x = embed(tokens, params["embed"], dt)
        if "pos_embed" in params:
            pos = self._cache_index(cache)
            pe = params["pos_embed"]["table"].astype(dt)[
                jnp.clip(pos, 0, cfg.max_seq_len - 1)]
            x = x + (pe[None, None] if pos.ndim == 0 else pe[:, None])

        def block_fn(h, inp):
            layer_p, layer_c = inp
            h, c, _ = dec(h, layer_p, cfg, cache=layer_c, window=window)
            return h, c

        if cfg.family == "hybrid":
            shared = params["shared"]

            def group_fn(h, inp):
                layer_p, gate, g_cache = inp
                y, ac, _ = blocks.dense_block_decode(
                    h, shared, cfg, cache=g_cache["attn"], window=window)
                h = h + gate.astype(h.dtype) * (y - h)
                h, sc = jax.lax.scan(
                    lambda hh, i: (lambda r: (r[0], r[1]))(
                        blocks.mamba2_block_decode(hh, i[0], cfg,
                                                   cache=i[1])[:2]),
                    h, (layer_p, g_cache["ssm"]))
                return h, {"attn": ac, "ssm": sc}

            x, new_cache = jax.lax.scan(
                group_fn, x,
                (params["layers"]["blocks"], params["layers"]["gates"],
                 {"attn": cache["attn"], "ssm": cache["ssm"]}))
        else:
            x, new_cache = jax.lax.scan(block_fn, x,
                                        (params["layers"], cache))
        return self._head(params, x)[:, 0], new_cache

    # ----------------------------------------------------------------- #
    @staticmethod
    def _cache_index(cache) -> jax.Array:
        """Current absolute position from any cache pytree (first leaf
        named 'index'; stacked => take layer 0).  Scalar for the shared
        -index caches of ``init_cache``; ``[batch]`` for the per-slot
        caches of ``init_slot_cache`` (continuous batching)."""
        idx = None

        def find(path, leaf):
            nonlocal idx
            if idx is None and any(
                    getattr(p, "name", "") == "index" for p in path):
                idx = leaf
            return leaf

        jax.tree_util.tree_map_with_path(find, cache)
        if idx is None:
            return jnp.zeros((), jnp.int32)
        return idx[0]


def lm_loss(cfg: ModelConfig, logits, batch, aux
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Causal-LM objective: shifted xent + z-loss + (MoE) aux loss.
    Shared by the plain and pipelined (core/pipeline.py) paths."""
    labels = batch["labels"]
    if cfg.family == "vlm":
        # text token i is predicted by position P + i - 1 of the
        # concatenated [patches; text] sequence
        Pn = batch["patch_embeds"].shape[1]
        logits = logits[:, Pn - 1:-1]
    else:
        logits = logits[:, :-1]
        labels = labels[:, 1:]
    mask = labels >= 0
    labels_safe = jnp.where(mask, labels, 0)
    # NB: no take_along_axis/log_softmax here — those force XLA to
    # all-gather the fp32 [B, S, vocab] logits per device when the vocab
    # dim is model-sharded.  logsumexp + a one-hot contraction partition
    # cleanly over the sharded vocab axis instead.
    logits32 = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits32, axis=-1)
    onehot = jax.nn.one_hot(labels_safe, logits.shape[-1],
                            dtype=logits32.dtype)
    label_logit = jnp.einsum("...v,...v->...", logits32, onehot)
    nll = lse - label_logit
    denom = jnp.maximum(jnp.sum(mask), 1)
    ce = jnp.sum(jnp.where(mask, nll, 0.0)) / denom
    # z-loss keeps the softmax normalizer in check (PaLM-style)
    zl = jnp.sum(jnp.where(mask, jnp.square(lse), 0.0)) / denom
    loss = ce + 1e-4 * zl + aux
    acc = jnp.sum(jnp.where(
        mask, (jnp.argmax(logits, -1) == labels_safe), False)) / denom
    return loss, {"ce": ce, "aux": aux, "zloss": zl, "accuracy": acc,
                  "tokens": denom.astype(jnp.float32)}


def cast_params(params, dtype):
    """Cast floating-point leaves (bf16 deployment of fp32-initialized params)."""
    def cast(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree.map(cast, params)
