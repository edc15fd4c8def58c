"""Continuous-batching serving tests (ISSUE 10): the ServeStats unit
fix, SlotScheduler/OutputQueue invariants under random traces, the
replica-placement pass, the trace simulator, and the slow end-to-end
bit-exactness gate (continuous vs fixed-batch greedy tokens)."""
import dataclasses

import numpy as np
import pytest

from repro.serve import (ContinuousStats, OutputQueue, Request,
                         ServeStats, SlotScheduler)


# ------------------------------------------------------------------ #
# ServeStats units (the satellite regression)
def test_tokens_per_s_units():
    """One decode step emits one token per live slot: tokens/s must be
    steps/s * n_slots, not the bare step rate (the pre-PR-10 bug)."""
    st = ServeStats(decode_s=[0.1, 0.1, 0.1], n_slots=4)
    assert st.steps_per_s == pytest.approx(10.0)
    assert st.tokens_per_s == pytest.approx(40.0)
    # single-slot serving is unchanged by the fix
    assert ServeStats(decode_s=[0.1], n_slots=1).tokens_per_s == \
        pytest.approx(10.0)


def test_serve_stats_wall_clock_fallback():
    """timing=False records no per-step times; the loop wall clock and
    step count must still yield a rate."""
    st = ServeStats(decode_s=[], n_slots=2, total_decode_s=2.0, n_steps=10)
    assert st.steps_per_s == pytest.approx(5.0)
    assert st.tokens_per_s == pytest.approx(10.0)
    assert ServeStats().tokens_per_s == 0.0


def test_continuous_stats_goodput():
    st = ContinuousStats(n_slots=3, n_tokens=30, total_s=2.0,
                         occupancy=[3, 3, 2, 2])
    assert st.tokens_per_s == pytest.approx(15.0)
    assert st.mean_occupancy == pytest.approx(2.5)
    assert ContinuousStats().tokens_per_s == 0.0


# ------------------------------------------------------------------ #
# SlotScheduler invariants
def test_slot_scheduler_basics():
    s = SlotScheduler(2)
    a = s.admit(10, max_new=2)
    b = s.admit(11, max_new=1)
    assert not s.has_free() and s.occupancy == 2
    with pytest.raises(RuntimeError):
        s.admit(12, max_new=1)
    assert s.record_token(b) is True        # hit its budget of 1
    assert s.evict(b) == 11
    assert s.record_token(a) is False
    with pytest.raises(KeyError):
        s.record_token(b)                   # freed slot is unreadable
    with pytest.raises(KeyError):
        s.evict(b)
    c = s.admit(12, max_new=1)
    assert c == b                           # freed slot recycled
    s.check()


def test_slot_scheduler_rejects_bad_args():
    with pytest.raises(ValueError):
        SlotScheduler(0)
    with pytest.raises(ValueError):
        SlotScheduler(1).admit(0, max_new=0)


def test_slot_scheduler_random_trace(rng):
    """Property test: across random admit/generate/evict traces the slot
    invariants hold at every step — no slot is both free and live, no
    live slot is overwritten by backfill, occupancy is conserved."""
    for trial in range(20):
        n_slots = int(rng.integers(1, 6))
        s = SlotScheduler(n_slots)
        uid = 0
        live = {}                           # slot -> uid, shadow copy
        for _ in range(200):
            if s.has_free() and rng.random() < 0.5:
                slot = s.admit(uid, max_new=int(rng.integers(1, 5)))
                assert slot not in live     # backfill never clobbers
                live[slot] = uid
                uid += 1
            elif live:
                slot = int(rng.choice(sorted(live)))
                if s.record_token(slot):
                    assert s.evict(slot) == live.pop(slot)
            s.check()
            assert s.occupancy == len(live)
            assert sorted(live) == s.live_slots()
            for slot, u in live.items():
                assert s.uid_of(slot) == u


def test_output_queue_detokenizes_on_drain():
    calls = []

    def detok(ids):
        calls.append(ids)
        return "".join(chr(65 + i) for i in ids)

    q = OutputQueue(detok)
    q.put(7, [0, 1])
    q.put(3, [2])
    assert len(q) == 2 and not calls        # put never detokenizes
    assert q.drain() == [(7, "AB"), (3, "C")]
    assert len(calls) == 2 and len(q) == 0
    assert q.drain() == []
    # without a detokenizer, raw ids pass through
    q2 = OutputQueue()
    q2.put(1, [5])
    assert q2.drain() == [(1, [5])]


# ------------------------------------------------------------------ #
# replica placement (pure cost model, no jax compute)
def test_partitions_bell_numbers():
    from repro.serve.placement import partitions
    for n, bell in ((0, 1), (1, 1), (2, 2), (3, 5), (4, 15)):
        parts = list(partitions(range(n)))
        assert len(parts) == bell
        for p in parts:                     # each is an exact cover
            got = sorted(x for g in p for x in g)
            assert got == list(range(n))


def test_place_replicas_rejects_rate_mismatch():
    from repro.configs import get_config
    from repro.core.search import PlanSearch
    from repro.core.topology import two_site
    from repro.serve.placement import decode_workload, place_replicas

    topo = two_site("pair", ("A30",), ("A30",), 0.2)
    search = PlanSearch(decode_workload(get_config("gpt2m"), slots=4),
                        topo)
    with pytest.raises(ValueError, match="rates"):
        place_replicas(search, [1.0], slots=4)


def test_disconnected_group_is_infeasible():
    """Cutting the middle site out of a line leaves {0,2} with no link:
    that group must price as None, not crash or get a free lunch."""
    from repro.configs import get_config
    from repro.core.search import PlanSearch
    from repro.core.topology import Link, Site, line
    from repro.serve.placement import _price_group, decode_workload

    topo = line("l3", [Site(("A30",)) for _ in range(3)],
                [Link(1e-3, 10.0), Link(1e-3, 10.0)])
    search = PlanSearch(decode_workload(get_config("gpt2m"), slots=4),
                        topo)
    assert _price_group(search, topo, [0, 2], [1.0, 0.0, 1.0],
                        slots=4, prompt_len=64, gen_len=8) is None
    priced = _price_group(search, topo, [0, 1], [1.0, 1.0, 0.0],
                          slots=4, prompt_len=64, gen_len=8)
    assert priced is not None


def test_placement_winner_map_gate():
    """The pinned BENCH_10 scenario: at 50% single-site load the far
    (80 ms) site must keep its own local replica while the 0.2 ms LAN
    pair shares one — the ISSUE's acceptance winner map."""
    from benchmarks.serving_bench import PROMPT_LEN, SLOTS, pinned_scenario
    from repro.serve.placement import _price_group, place_replicas

    search = pinned_scenario()
    single, _ = _price_group(search, search.topology, [0],
                             [0.0, 0.0, 0.0], slots=SLOTS,
                             prompt_len=PROMPT_LEN, gen_len=64)
    capacity_rps = SLOTS / (single.prefill_s + 64 * single.decode_step_s)
    plan = place_replicas(search, [0.5 * capacity_rps] * 3, slots=SLOTS,
                          prompt_len=PROMPT_LEN, gen_len=64)
    assert (2,) in plan.groups, plan.groups
    assert any(0 in g and 1 in g for g in plan.groups), plan.groups
    # saturating one site must still be feasible pooled: rates at 90%
    # of one site's capacity only fit when the LAN pair shares
    hot = place_replicas(search, [0.9 * capacity_rps] * 3, slots=SLOTS,
                         prompt_len=PROMPT_LEN, gen_len=64)
    assert hot is not None
    for r in hot.replicas:
        assert r.rho < 0.95


# ------------------------------------------------------------------ #
# the trace simulator behind BENCH_10
def test_trace_is_deterministic():
    from benchmarks.serving_bench import make_trace
    a1, g1 = make_trace(1000, 5.0)
    a2, g2 = make_trace(1000, 5.0)
    np.testing.assert_array_equal(a1, a2)
    np.testing.assert_array_equal(g1, g2)
    assert np.all(np.diff(a1) > 0) and a1.shape == (1000,)


def test_continuous_beats_fixed_on_mixed_trace():
    """The goodput mechanism itself: with a long-tail generation mix and
    saturating arrivals, per-slot freeing must beat hold-for-longest."""
    from benchmarks.serving_bench import (make_trace, sim_continuous,
                                          sim_fixed)
    step_s, prefill_s = 2e-3, 60e-3
    arrivals_s, gen_len = make_trace(4000, 60.0)
    cont = sim_continuous(arrivals_s, gen_len, step_s=step_s,
                          prefill_s=prefill_s, slots=8)
    fixed = sim_fixed(arrivals_s, gen_len, step_s=step_s,
                      prefill_s=prefill_s, batch=8)
    assert cont["goodput_tok_s"] > 2.0 * fixed["goodput_tok_s"]
    assert np.all(cont["ttft_s"] >= 0) and np.all(fixed["ttft_s"] >= 0)
    assert 0.0 < cont["occupancy"] <= 1.0


def test_uniform_trace_no_continuous_advantage():
    """Control: when every request generates the same length, fixed
    batching wastes nothing and the two engines converge (<10% apart) —
    the 2x gate really is about the length mix."""
    from benchmarks.serving_bench import sim_continuous, sim_fixed
    rng = np.random.default_rng(0)
    arrivals_s = np.cumsum(rng.exponential(1 / 50.0, 4000))
    gen_len = np.full(4000, 64, dtype=np.int64)
    cont = sim_continuous(arrivals_s, gen_len, step_s=2e-3,
                          prefill_s=60e-3, slots=8)
    fixed = sim_fixed(arrivals_s, gen_len, step_s=2e-3,
                      prefill_s=60e-3, batch=8)
    ratio = cont["goodput_tok_s"] / fixed["goodput_tok_s"]
    assert ratio < 1.1


# ------------------------------------------------------------------ #
# slot-cache plumbing
def test_init_slot_cache_widens_index_leaves():
    """Per-slot caches carry one ring index per batch row: every index
    leaf gains a trailing [B] axis, data leaves keep their train shape."""
    import jax
    from repro.configs import get_config
    from repro.models import Model

    cfg = get_config("llama3.2-3b").reduced()
    model = Model(cfg)
    base = model.init_cache(3, 32)
    slot = model.init_slot_cache(3, 32)

    def leaves_by_path(tree):
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        return {jax.tree_util.keystr(p): v for p, v in flat}

    b, s = leaves_by_path(base), leaves_by_path(slot)
    assert b.keys() == s.keys()
    n_index = 0
    for k in b:
        if "index" in k:
            n_index += 1
            assert s[k].shape == b[k].shape + (3,)
            assert s[k].dtype == b[k].dtype
        else:
            assert s[k].shape == b[k].shape
    assert n_index >= 1


def test_ring_valid_per_slot_masks():
    import jax.numpy as jnp
    from repro.models.attention import _ring_valid

    scalar = _ring_valid(jnp.asarray(2, jnp.int32), 3, 4)
    assert scalar.shape == (3, 4)
    np.testing.assert_array_equal(np.asarray(scalar[0]),
                                  [True, True, False, False])
    per_slot = _ring_valid(jnp.asarray([0, 2, 4], jnp.int32), 3, 4)
    np.testing.assert_array_equal(
        np.asarray(per_slot),
        [[False] * 4, [True, True, False, False], [True] * 4])


# ------------------------------------------------------------------ #
# end-to-end: continuous == fixed, bit for bit
@pytest.fixture(scope="module")
def serve_setup():
    import jax
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models import Model

    cfg = dataclasses.replace(get_config("llama3.2-3b").reduced(),
                              vocab_size=512)
    model = Model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        params = model.init(jax.random.key(0))
    return model, mesh, params


@pytest.mark.slow
def test_continuous_bit_exact_vs_fixed(serve_setup):
    """The ISSUE's pinned gate: per-request greedy tokens from the
    continuous engine are bit-identical to the fixed-batch Engine's,
    across mixed prompt lengths, slot churn, and bucketed prefill."""
    from repro.core.plans import get_plan
    from repro.serve import ContinuousEngine, Engine, Request

    model, mesh, params = serve_setup
    rng = np.random.default_rng(3)
    lens = [5, 9, 9, 13, 5, 7]
    prompts = [np.asarray(rng.integers(4, 400, (n,)), np.int32)
               for n in lens]
    plan, max_new = get_plan("data"), 6

    ref, bylen = {}, {}
    for i, p in enumerate(prompts):
        bylen.setdefault(len(p), []).append(i)
    for n, idxs in bylen.items():
        eng = Engine(model, plan, mesh, batch_size=len(idxs), max_len=64)
        out = eng.generate(
            params, {"tokens": np.stack([prompts[i] for i in idxs])},
            n_tokens=max_new)
        for row, i in enumerate(idxs):
            ref[i] = out["tokens"][row]

    ce = ContinuousEngine(model, plan, mesh, slots=3, max_len=64,
                          buckets=(8, 16, 32))
    res = ce.run(params, [Request(i, p) for i, p in enumerate(prompts)],
                 max_new=max_new)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(res["outputs"][i], ref[i],
                                      err_msg=f"request {i} diverged")
    st = res["stats"]
    assert st.n_tokens == max_new * len(prompts)
    assert 0 < st.mean_occupancy <= 3
    assert len(st.ttft_s) == len(prompts)
    assert all(t >= 0 for t in st.ttft_s.values())


@pytest.mark.slow
def test_continuous_ssm_exact_prefill_bit_exact():
    """SSM families integrate pad tokens into their recurrent state, so
    the engine must route them through exact-length prefill — and still
    match the fixed-batch engine bit for bit."""
    import jax
    from repro.configs import get_config
    from repro.core.plans import get_plan
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    from repro.serve import ContinuousEngine, Engine, Request

    cfg = get_config("falcon-mamba-7b").reduced()
    model = Model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        params = model.init(jax.random.key(1))
    rng = np.random.default_rng(11)
    prompts = [np.asarray(
        rng.integers(4, min(cfg.vocab_size, 400), (n,)), np.int32)
        for n in (4, 6, 4)]
    plan, max_new = get_plan("data"), 4
    eng = Engine(model, plan, mesh, batch_size=1, max_len=32)
    ref = [eng.generate(params, {"tokens": p[None]},
                        n_tokens=max_new)["tokens"][0] for p in prompts]
    ce = ContinuousEngine(model, plan, mesh, slots=2, max_len=32)
    assert ce.exact_prefill      # the ssm family must take this path
    res = ce.run(params,
                 [Request(i, p) for i, p in enumerate(prompts)],
                 max_new=max_new)
    for i, want in enumerate(ref):
        np.testing.assert_array_equal(res["outputs"][i], want,
                                      err_msg=f"request {i} diverged")


@pytest.mark.slow
def test_continuous_int8_kv_bit_exact(serve_setup):
    """--kv-dtype int8 keeps working continuously: the quantized ring
    cache scatters through insert and stays bit-identical to the
    fixed-batch int8 engine."""
    from repro.core.plans import get_plan
    from repro.serve import ContinuousEngine, Engine, Request

    model, mesh, params = serve_setup
    rng = np.random.default_rng(7)
    prompts = [np.asarray(rng.integers(4, 400, (n,)), np.int32)
               for n in (5, 9, 7, 9)]
    plan, max_new = get_plan("data"), 5
    eng = Engine(model, plan, mesh, batch_size=1, max_len=64,
                 kv_dtype="int8")
    ref = [eng.generate(params, {"tokens": p[None]},
                        n_tokens=max_new)["tokens"][0] for p in prompts]
    ce = ContinuousEngine(model, plan, mesh, slots=2, max_len=64,
                          buckets=(8, 16), kv_dtype="int8")
    res = ce.run(params,
                 [Request(i, p) for i, p in enumerate(prompts)],
                 max_new=max_new)
    for i, want in enumerate(ref):
        np.testing.assert_array_equal(res["outputs"][i], want,
                                      err_msg=f"request {i} diverged")


@pytest.mark.slow
def test_engine_timing_flag(serve_setup):
    """timing=False must skip per-step device syncs but return the same
    tokens and still produce a wall-clock rate."""
    from repro.core.plans import get_plan
    from repro.serve import Engine

    model, mesh, params = serve_setup
    eng = Engine(model, get_plan("data"), mesh, batch_size=2, max_len=64)
    batch = {"tokens": np.asarray(
        np.random.default_rng(5).integers(4, 400, (2, 8)), np.int32)}
    timed = eng.generate(params, batch, n_tokens=4, timing=True)
    fast = eng.generate(params, batch, n_tokens=4, timing=False)
    np.testing.assert_array_equal(timed["tokens"], fast["tokens"])
    # the first token comes out of prefill; decode runs n_tokens-1 steps
    assert len(timed["stats"].decode_s) == 3
    assert fast["stats"].decode_s == []
    assert fast["stats"].n_steps == 3
    assert fast["stats"].total_decode_s > 0
    assert fast["stats"].tokens_per_s > 0
