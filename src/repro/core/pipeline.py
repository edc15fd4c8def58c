"""Pipeshard: inter-operator (pipeline) parallelism over a ``stage`` mesh
axis, combined with intra-operator (Shard) parallelism inside each stage.

This is the TPU-native mapping of Alpa's pipeshard plans (paper §III-B):

  * the layer stack (already stacked ``[L, ...]`` for ``lax.scan``) is cut
    into ``n_stages`` contiguous slices by sharding the stack axis over the
    ``stage`` mesh axis with a partial-manual ``jax.shard_map``;
  * the global batch is split into microbatches; the classic GPipe schedule
    runs ``n_micro + n_stages - 1`` ticks, each stage processing microbatch
    ``t - stage_id`` and handing activations to its successor with
    ``jax.lax.ppermute`` — the point-to-point communication that makes the
    paper's Pipeshard latency-tolerant (Table II);
  * inside the body, the ``data``/``model`` mesh axes stay *auto*, so GSPMD
    still applies the Shard rules (tensor parallelism) per stage;
  * embedding / head / loss run outside the manual region in auto-SPMD land
    and the backward schedule falls out of differentiating through the scan
    and the ppermute.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro.core.costmodel import parse_schedule
from repro.core.plans import Plan, STAGE_AXIS


def pipeline_mesh(devices_mesh: Mesh, n_stages: int,
                  stage_order=None, stage_layers=None,
                  schedule: str = "gpipe") -> Mesh:
    """Reshape a (pod?, data, model) mesh into (stage, data, model).

    The stage axis absorbs the pod axis first (inter-stage point-to-point is
    exactly the traffic that tolerates the slow inter-pod link — the paper's
    geo-distributed finding), then splits the data axis if more stages are
    requested.

    Args:
        devices_mesh: the (pod?, data, model) source mesh.
        n_stages: pipeline stages to carve out of (pod x data).
        stage_order: permutation of the pod blocks (one block per site,
            see ``core.plans.Placement.pod_permutation``) giving the
            stage→site assignment from the plan search — stage k runs on
            pod block ``stage_order[k]``, so the pipeline crosses the
            topology's links in the order the search priced, not in raw
            site numbering.
        stage_layers: per-stage layer counts from the TFLOP-weighted
            balancer (``core.plans.Placement.stage_layers``).  The device
            mesh itself does not depend on how layers are split, so this
            only validates the split's shape (one positive entry per
            stage — per *chunk* under an interleaved ``schedule``); the
            split — even or uneven — is realized by
            ``make_pipeline_loss`` (pad-and-mask, see
            ``validate_stages``).
        schedule: pipeline tick-order schedule the split belongs to
            (``core.costmodel.SCHEDULES``); interleaved schedules expect
            ``n_stages * v`` chunk entries in ``stage_layers``.  The
            device mesh itself is schedule-independent.

    Returns:
        A ``(stage, data, model)`` mesh.
    """
    _, virt = parse_schedule(schedule)
    if stage_layers is not None:
        layers = tuple(stage_layers)
        if len(layers) != n_stages * virt:
            raise ValueError(
                f"stage_layers {layers} has {len(layers)} entries for "
                f"n_stages={n_stages} x {virt} virtual ({schedule})")
        if any(l < 1 for l in layers):
            raise ValueError(f"every stage needs >= 1 layer, "
                             f"got {layers}")
    names = devices_mesh.axis_names
    shape = dict(zip(names, devices_mesh.devices.shape))
    pod = shape.get("pod", 1)
    data = shape.get("data", 1)
    model = shape.get("model", 1)
    if n_stages % pod != 0 and pod % n_stages != 0:
        raise ValueError(f"n_stages={n_stages} incompatible with pod={pod}")
    rest = n_stages // pod if n_stages >= pod else 1
    if data % rest != 0:
        raise ValueError(
            f"cannot split data={data} into {rest} pipeline sub-stages")
    devices = devices_mesh.devices
    if stage_order is not None:
        order = tuple(stage_order)
        if sorted(order) != list(range(pod)):
            raise ValueError(
                f"stage_order {order} is not a permutation of the "
                f"{pod} pod blocks")
        if "pod" in names:
            import numpy as np
            devices = np.take(devices, order, axis=names.index("pod"))
        elif order != (0,):
            raise ValueError("stage_order given but mesh has no pod axis")
    devs = devices.reshape(n_stages, (pod * data) // n_stages, model)
    return Mesh(devs, (STAGE_AXIS, "data", "model"),
                axis_types=(AxisType.Auto,) * 3)


def stack_length(cfg, stack) -> int:
    """Length of the stacked layer axis (scan *groups* for hybrid).

    Args:
        cfg: model config (unused; kept for signature stability).
        stack: the stacked ``[L, ...]`` layer params pytree.

    Returns:
        The leading-axis length of the stack's leaves.
    """
    leaf = jax.tree.leaves(stack)[0]
    return leaf.shape[0]


def validate_stages(cfg, stack, n_stages: int,
                    stage_layers=None,
                    schedule: str = "gpipe") -> Optional[tuple]:
    """Check the layer stack can be cut into the schedule's chunks.

    GPipe/1F1B cut the stack into ``n_stages`` contiguous slices; an
    interleaved schedule with v virtual stages per device cuts it into
    ``n_stages * v`` chunks (chunk c running on stage ``c % n_stages``).

    Args:
        cfg: model config (names the stack in error messages).
        stack: the stacked ``[L, ...]`` layer params (groups for hybrid).
        n_stages: number of pipeline stages.
        stage_layers: optional per-chunk layer counts (a TFLOP-weighted
            split from ``core.costmodel.balanced_stage_layers``).  Must
            partition the stack; *uneven* splits are fine — they execute
            via the pad-and-mask stage construction in
            ``make_pipeline_loss`` (docs/topology-and-search.md
            §Balancing).
        schedule: pipeline tick-order schedule
            (``core.costmodel.SCHEDULES``) — fixes the chunk count.

    Returns:
        The normalized per-chunk split as a tuple when ``stage_layers``
        is given, else ``None`` for the single-chunk equal-block fast
        path (GPipe/1F1B even split) or the explicit even per-chunk
        tuple for interleaved schedules (whose chunks are non-contiguous
        on a stage, so they always take the gather path).
    """
    _, virt = parse_schedule(schedule)
    n_chunks = n_stages * virt
    L = stack_length(cfg, stack)
    if stage_layers is not None:
        layers = tuple(int(l) for l in stage_layers)
        if len(layers) != n_chunks or sum(layers) != L \
                or any(l < 1 for l in layers):
            raise ValueError(
                f"{cfg.name}: stage_layers {layers} does not partition the "
                f"{L}-entry stack into {n_chunks} {schedule} chunks")
        return layers
    if L % n_chunks != 0:
        raise ValueError(
            f"{cfg.name}: stack length {L} (groups for hybrid) not divisible "
            f"by {n_chunks} ({n_stages} stages, {schedule}) — pick a divisor "
            f"or pass an explicit stage_layers split (see DESIGN.md §4)")
    return None if virt == 1 else (L // n_chunks,) * n_chunks


def stage_gather_index(split, n_stages: int, virt: int = 1):
    """Gather index + validity mask realizing a per-chunk layer split.

    This is THE pad-and-mask convention: stage s holds its chunks
    (chunk ``c = k * n_stages + s``, ``k < virt``) back to back, each
    padded to the longest chunk by repeating its last layer; padded
    slots are identity-masked via the validity mask.  Both the pipeline
    runtime (``make_pipeline_loss``) and cross-plan checkpoint
    resharding (``repro.train.reshard.stage_view``) apply exactly this
    index, so a resharded layout is bit-for-bit what the runtime would
    have gathered.

    Args:
        split: per-chunk layer counts (``n_stages * virt`` entries, each
            >= 1, summing to the stack length).
        n_stages: pipeline stages.
        virt: virtual stages per device (interleaved schedules).

    Returns:
        ``(idx, layer_valid)`` numpy arrays of length
        ``n_stages * virt * max(split)``: the stack-row gather index in
        stage-major chunk order, and whether each padded slot holds a
        real (unrepeated) layer.
    """
    split = tuple(int(l) for l in split)
    if len(split) != n_stages * virt:
        raise ValueError(f"split {split} has {len(split)} entries for "
                         f"{n_stages} stages x {virt} virtual")
    max_l = max(split)
    offs = np.concatenate(([0], np.cumsum(split)))
    chunk_of = [k * n_stages + s
                for s in range(n_stages) for k in range(virt)]
    idx = np.concatenate([
        offs[c] + np.minimum(np.arange(max_l), split[c] - 1)
        for c in chunk_of]).astype(np.int32)
    layer_valid = np.concatenate(
        [np.arange(max_l) < split[c] for c in chunk_of])
    return idx, layer_valid


def banked_slot(stage: int, chunk: int, n_stages: int,
                virt: int = 1) -> bool:
    """Whether ``stage``'s output for local ``chunk`` is banked (kept as
    a finished microbatch) instead of sent on the ring — true only for
    the last stage's last chunk.  Shared by ``schedule_tables``'s
    arrival construction and the schedule race detector
    (``repro.analysis.schedlint``) so both sides agree on which sends
    must pair with receives.
    """
    return stage == n_stages - 1 and chunk == virt - 1


def schedule_tables(schedule: str, n_stages: int,
                    n_micro: int) -> Dict[str, np.ndarray]:
    """Static forward-slot tables driving the scheduled pipeline runner.

    Every schedule is a tick order: at tick t, stage s either runs the
    forward of one (chunk, microbatch) work item or idles (a slot the
    real schedule spends on a backward, which reverse-mode AD replays
    for us when the loss is differentiated — see docs/schedules.md).
    The tables are plain numpy (shape ``[n_stages, T]``), computed once
    at trace time:

      * GPipe: ``T = m + S - 1`` — stage s runs microbatch ``t - s``.
      * 1F1B (PipeDream-Flush): ``T = 2m + S - 2`` — stage s warms up
        with ``S - s`` forwards, then alternates forward/backward
        slots: forward i lands at ``t = s + i + max(0, i - (S-1-s))``.
      * interleaved: greedy list scheduling of the ``v * m`` per-stage
        work items (chunk c of microbatch i is ready one tick after
        chunk c-1 finished on the previous ring stage), priority
        ``(i + c, c)`` — earliest wave first, earlier chunk on ties.

    Args:
        schedule: schedule name (``core.costmodel.parse_schedule``).
        n_stages: pipeline stages S.
        n_micro: microbatches m.

    Returns:
        Dict of ``[S, T]`` arrays: ``active`` (bool — stage runs a
        forward this tick), ``chunk``/``mb`` (int32 — the local chunk
        index and microbatch of that forward), and the arrival tables
        ``arr_valid``/``arr_chunk``/``arr_mb`` describing the payload
        each stage's ppermute delivered at the *start* of tick t (sent
        by its ring predecessor at t-1): whether it is real, and which
        (local chunk, microbatch) inbox slot it fills.
    """
    kind, virt = parse_schedule(schedule)
    T_MAX = 1 << 30                         # "never done" sentinel
    S, m = n_stages, n_micro
    if kind == "gpipe":
        T = m + S - 1
        slots = [{s: (0, t - s) for s in range(S) if 0 <= t - s < m}
                 for t in range(T)]
    elif kind == "1f1b":
        T = 2 * m + S - 2
        slots = [dict() for _ in range(T)]
        for s in range(S):
            for i in range(m):
                t = s + i + max(0, i - (S - 1 - s))
                slots[t][s] = (0, i)
    else:                                   # interleaved, v >= 2
        done: Dict[tuple, int] = {}
        pending = {s: [(k, i) for k in range(virt) for i in range(m)]
                   for s in range(S)}
        slots = []
        t, left = 0, S * virt * m
        while left:
            row = {}
            for s in range(S):
                ready = []
                for k, i in pending[s]:
                    c = k * S + s
                    if c == 0 or done.get((c - 1, i), T_MAX) < t:
                        ready.append((i + c, c, k, i))
                if ready:
                    _, c, k, i = min(ready)
                    row[s] = (k, i)
                    done[(c, i)] = t
                    pending[s].remove((k, i))
                    left -= 1
            slots.append(row)
            t += 1
        T = len(slots)
    active = np.zeros((S, T), bool)
    chunk = np.zeros((S, T), np.int32)
    mb = np.zeros((S, T), np.int32)
    for t, row in enumerate(slots):
        for s, (k, i) in row.items():
            active[s, t], chunk[s, t], mb[s, t] = True, k, i
    # arrivals: what stage s's ppermute hands it at tick t is whatever
    # its ring predecessor computed (and did not bank) at tick t-1
    arr_valid = np.zeros((S, T), bool)
    arr_chunk = np.zeros((S, T), np.int32)
    arr_mb = np.zeros((S, T), np.int32)
    for s in range(S):
        prev = (s - 1) % S
        for t in range(1, T):
            if not active[prev, t - 1]:
                continue
            k, i = int(chunk[prev, t - 1]), int(mb[prev, t - 1])
            if banked_slot(prev, k, S, virt):
                continue                    # last chunk: banked, not sent
            arr_valid[s, t] = True
            arr_chunk[s, t] = k + (1 if prev == S - 1 else 0)
            arr_mb[s, t] = i
    return {"active": active, "chunk": chunk, "mb": mb,
            "arr_valid": arr_valid, "arr_chunk": arr_chunk,
            "arr_mb": arr_mb}


def make_pipeline_loss(model, mesh: Mesh, n_micro: int, *,
                       remat: bool = True, carrier_dtype=jnp.float32,
                       stage_layers=None, schedule: str = "gpipe"):
    """Build loss(params, batch) running the stacked layers as a
    pipelined forward over the mesh's ``stage`` axis.

    Schedules reorder work; they must not change math — every schedule
    runs the same layers on the same microbatches and the losses/grads
    agree bit-for-bit with the GPipe path and the unsharded reference
    (``tests/test_pipeline_schedules.py``).

    Args:
        model: the ``repro.models.Model`` whose stacked layers run
            staged; embedding/head/loss stay outside the manual region.
        mesh: a ``(stage, data, model)`` mesh from ``pipeline_mesh``.
        n_micro: microbatches the global batch is split into.
        remat: checkpoint each layer block (activation rematerialization).
        carrier_dtype: dtype of the inter-stage activation carriers
            (scan state / ppermute payload / bank buffer).  Defaults to
            fp32 because the XLA *CPU* SPMD partitioner CHECK-fails
            ("Invalid binary instruction opcode copy") when transposing
            the pipeline with bf16 carriers; the stage compute itself
            still runs in the model dtype.  On real TPU this can be set
            to bf16 to halve inter-stage ppermute bytes.
        stage_layers: optional per-stage (per-chunk under interleaved)
            layer counts from a ``core.plans.Placement`` — validated
            against the stack (see ``validate_stages``).  Uneven splits
            execute via pad-and-mask: every chunk's layer slice is
            gathered and padded to the longest chunk and the padded
            slots are identity-masked inside ``model.run_stack`` (zero
            aux, activations pass through unchanged), so a
            TFLOP-weighted heterogeneous split runs with the same
            equal-block stage sharding.
        schedule: pipeline tick order (``core.costmodel.SCHEDULES``,
            docs/schedules.md).  ``"gpipe"`` keeps the classic
            ``n_micro + n_stages - 1``-tick path; ``"1f1b"`` and
            ``"interleaved"`` run the generalized scheduled runner —
            the same ppermute ring driven by ``schedule_tables``, with
            a per-(chunk, microbatch) inbox holding activations across
            the slots the real schedule spends on backwards (which
            reverse-mode AD replays here).

    Returns:
        ``loss_fn(params, batch) -> (loss, metrics)``.
    """
    cfg = model.cfg
    n_stages = mesh.shape[STAGE_AXIS]
    kind, virt = parse_schedule(schedule)
    # the stage axis is the only manual axis of the pipeline region;
    # data/model stay auto so GSPMD applies the Shard rules per stage
    manual = {STAGE_AXIS}

    def loss_fn(params, batch):
        x, positions, _ = model._embed_inputs(params, batch)
        enc_out = model._encode(params, batch) if cfg.family == "encdec" \
            else None
        B, S, d = x.shape
        assert B % n_micro == 0, (B, n_micro)
        mb = B // n_micro
        xm = x.reshape(n_micro, mb, S, d).astype(carrier_dtype)
        xm = jax.lax.with_sharding_constraint(
            xm, P(None, "data", None, None))
        # every microbatch keeps its own position rows (packed/ragged
        # batches have per-example positions, so slicing the first
        # microbatch's rows for all of them would be wrong)
        pos_m = positions.reshape(n_micro, mb, S)
        enc_mb = jnp.zeros((), x.dtype) if enc_out is None else \
            enc_out.reshape(n_micro, mb, *enc_out.shape[1:])
        stack = params["layers"]
        split = validate_stages(cfg, stack, n_stages, stage_layers,
                                schedule=schedule)
        layer_valid = None
        if split is not None:
            # per-chunk gather realizing Placement.stage_layers
            # (stage_gather_index — the shared pad-and-mask convention):
            # padded slots are masked to identity (and zero aux) inside
            # run_stack, so the where() never sees uninitialized params.
            # virt == 1 is PR 3's per-stage gather unchanged.
            idx, valid = stage_gather_index(split, n_stages, virt)
            stack = jax.tree.map(
                lambda leaf: jnp.take(leaf, jnp.asarray(idx), axis=0),
                stack)
            layer_valid = jnp.asarray(valid)
        shared = params.get("shared")
        if shared is None:
            shared = jnp.zeros(())

        # in_specs: only the manual (stage) axis is mentioned; data/model
        # sharding of the same arrays stays in auto-SPMD land.
        stack_spec = jax.tree.map(lambda _: P(STAGE_AXIS), stack)
        mask_args = () if layer_valid is None else (layer_valid,)
        mask_specs = () if layer_valid is None else (P(STAGE_AXIS),)
        # stage id as a stage-sharded input: one row per stage
        stage_ids = jnp.arange(n_stages, dtype=jnp.int32)
        # per-stage local chunk length (layers a single run_stack call
        # scans): the padded chunk under a gather, the equal block else
        chunk_len = max(split) if split is not None \
            else stack_length(cfg, params["layers"]) // n_stages

        @partial(jax.shard_map, mesh=mesh, axis_names=manual,
                 in_specs=(P(STAGE_AXIS), stack_spec, *mask_specs,
                           P(), P(), P(), P()),
                 out_specs=P(STAGE_AXIS), check_vma=False)
        def run_pipeline(stage_ids, stack_local, *rest):
            if layer_valid is None:
                valid_local = None
                xm, pos_m, enc_mb, shared = rest
            else:
                valid_local, xm, pos_m, enc_mb, shared = rest
            stage = stage_ids[0]
            T = n_micro + n_stages - 1
            state0 = jnp.zeros_like(xm[0])
            buf0 = jnp.zeros_like(xm)

            def run_stage(inp, pos, mb_idx):
                kwargs = {}
                if cfg.family == "encdec":
                    kwargs["enc_out"] = enc_mb[mb_idx]
                out, aux = model.run_stack(
                    stack_local, inp.astype(model.compute_dtype), pos,
                    shared=(shared if cfg.family == "hybrid" else None),
                    remat=remat, layer_valid=valid_local, **kwargs)
                return out.astype(carrier_dtype), aux.astype(jnp.float32)

            def tick(carry, t):
                state, buf = carry
                mb_idx = jnp.clip(t - stage, 0, n_micro - 1)
                # a stage only holds a real microbatch for the ticks
                # t in [stage, stage + n_micro): warm-up and drain ticks
                # skip the stack entirely instead of burning a full
                # forward on a stale microbatch and polluting the aux sum
                active = jnp.logical_and(t >= stage, t - stage < n_micro)
                inp = jnp.where(stage == 0, xm[mb_idx], state)
                out, aux = jax.lax.cond(
                    active,
                    lambda op: run_stage(*op),
                    lambda op: (op[0], jnp.float32(0.0)),
                    (inp, pos_m[mb_idx], mb_idx))
                # last stage banks its finished microbatch t-(S-1)
                done_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
                valid = (t - (n_stages - 1) >= 0)
                slot = jax.lax.dynamic_update_index_in_dim(
                    buf, out.astype(buf.dtype), done_idx, 0)
                buf = jnp.where(valid, slot, buf)
                # hand activations to the next stage (p2p, ring)
                perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
                state = jax.lax.ppermute(out, STAGE_AXIS, perm)
                return (state, buf), aux

            (_, buf), auxs = jax.lax.scan(
                tick, (state0, buf0), jnp.arange(T))
            # leading (length-1 per shard) stage axis; caller slices [-1]
            return buf[None], jnp.sum(auxs)[None]

        # 1F1B / interleaved: the generalized scheduled runner.  Same
        # ppermute ring, but the tick order comes from static
        # schedule_tables and arrivals land in a per-(chunk, microbatch)
        # inbox — a stage may consume an activation several ticks after
        # it arrived (the slots the real schedule spends on backwards).
        tables = None
        if not (kind == "gpipe" and virt == 1):
            tables = {name: jnp.asarray(arr) for name, arr in
                      schedule_tables(schedule, n_stages, n_micro).items()}
        tbl_args = () if tables is None else (
            tables["active"], tables["chunk"], tables["mb"],
            tables["arr_valid"], tables["arr_chunk"], tables["arr_mb"])
        tbl_specs = tuple(P(STAGE_AXIS) for _ in tbl_args)

        @partial(jax.shard_map, mesh=mesh, axis_names=manual,
                 in_specs=(P(STAGE_AXIS), stack_spec, *mask_specs,
                           *tbl_specs, P(), P(), P(), P()),
                 out_specs=P(STAGE_AXIS), check_vma=False)
        def run_scheduled(stage_ids, stack_local, *rest):
            if layer_valid is None:
                valid_local = None
            else:
                valid_local, rest = rest[0], rest[1:]
            (active_t, chunk_t, mb_t, arrv_t, arrk_t, arri_t,
             xm, pos_m, enc_mb, shared) = rest
            stage = stage_ids[0]
            # stage-sharded [1, T] table rows -> local [T]
            active_t, chunk_t, mb_t = active_t[0], chunk_t[0], mb_t[0]
            arrv_t, arrk_t, arri_t = arrv_t[0], arrk_t[0], arri_t[0]
            T = active_t.shape[0]
            state0 = jnp.zeros_like(xm[0])
            inbox0 = jnp.zeros((virt,) + xm.shape, xm.dtype)
            buf0 = jnp.zeros_like(xm)

            def run_chunk(inp, pos, mb_idx, k):
                sl = lambda leaf: jax.lax.dynamic_slice_in_dim(
                    leaf, k * chunk_len, chunk_len, 0)
                stack_k = jax.tree.map(sl, stack_local)
                valid_k = None if valid_local is None else sl(valid_local)
                kwargs = {}
                if cfg.family == "encdec":
                    kwargs["enc_out"] = enc_mb[mb_idx]
                out, aux = model.run_stack(
                    stack_k, inp.astype(model.compute_dtype), pos,
                    shared=(shared if cfg.family == "hybrid" else None),
                    remat=remat, layer_valid=valid_k, **kwargs)
                return out.astype(carrier_dtype), aux.astype(jnp.float32)

            def tick(carry, t):
                recv, inbox, buf = carry
                # 1. stash the ppermute payload that arrived this tick
                #    (its (chunk, microbatch) slot is static knowledge —
                #    the arrival tables mirror the sender's slot tables)
                stash = jax.lax.dynamic_update_slice(
                    inbox, recv[None, None].astype(inbox.dtype),
                    (arrk_t[t], arri_t[t]) + (0,) * recv.ndim)
                inbox = jnp.where(arrv_t[t], stash, inbox)
                # 2. this tick's work item, if any
                k, i, active = chunk_t[t], mb_t[t], active_t[t]
                first_chunk = jnp.logical_and(stage == 0, k == 0)
                inbox_in = jax.lax.dynamic_slice(
                    inbox, (k, i) + (0,) * state0.ndim,
                    (1, 1) + state0.shape)[0, 0]
                inp = jnp.where(first_chunk, xm[i], inbox_in)
                out, aux = jax.lax.cond(
                    active,
                    lambda op: run_chunk(*op),
                    lambda op: (op[0], jnp.float32(0.0)),
                    (inp, pos_m[i], i, k))
                # 3. last chunk of the last stage banks its microbatch
                done = jnp.logical_and(
                    active, jnp.logical_and(stage == n_stages - 1,
                                            k == virt - 1))
                slot = jax.lax.dynamic_update_index_in_dim(
                    buf, out.astype(buf.dtype), i, 0)
                buf = jnp.where(done, slot, buf)
                # 4. ring handoff (receivers ignore ticks their arrival
                #    table marks invalid)
                perm = [(a, (a + 1) % n_stages) for a in range(n_stages)]
                recv = jax.lax.ppermute(out, STAGE_AXIS, perm)
                return (recv, inbox, buf), aux

            (_, _, buf), auxs = jax.lax.scan(
                tick, (state0, inbox0, buf0), jnp.arange(T))
            return buf[None], jnp.sum(auxs)[None]

        if tables is None:
            buf_staged, aux_staged = run_pipeline(
                stage_ids, stack, *mask_args, xm, pos_m, enc_mb, shared)
        else:
            buf_staged, aux_staged = run_scheduled(
                stage_ids, stack, *mask_args, *tbl_args,
                xm, pos_m, enc_mb, shared)
        hidden = buf_staged[-1].reshape(B, S, d).astype(model.compute_dtype)
        # every stage owns distinct layers, so the model's aux (MoE
        # load-balance) sums over stages; each stage accumulated one
        # batch-invariant aux per microbatch, so the microbatch mean is
        # what matches the reference full-batch aux
        aux = jnp.sum(aux_staged) / n_micro
        logits = model._head(params, hidden)
        from repro.models.model import lm_loss
        return lm_loss(cfg, logits, batch, aux)

    return loss_fn
