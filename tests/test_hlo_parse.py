"""HLO collective-parse tests: trip-count handling on synthetic HLO and on
a real compiled module."""
import textwrap

from repro.launch.hlo_parse import (collective_bytes_with_trips,
                                    parse_computations)

SYNTH = textwrap.dedent("""\
    HloModule test

    %add (a: f32[], b: f32[]) -> f32[] {
      ROOT %r = f32[] add(%a, %b)
    }

    %body.1 (p: (s32[], f32[128])) -> (s32[], f32[128]) {
      %ar = f32[128]{0} all-reduce(%x), replica_groups={}, to_apply=%add
      ROOT %t = (s32[], f32[128]) tuple(%i, %ar)
    }

    %cond.1 (p: (s32[], f32[128])) -> pred[] {
      %c = s32[] constant(12)
      ROOT %cmp = pred[] compare(%i, %c), direction=LT
    }

    ENTRY %main (a: f32[256]) -> f32[256] {
      %ag = f32[256]{0} all-gather(%a), replica_groups={}
      %w = (s32[], f32[128]) while(%init), condition=%cond.1, body=%body.1
      ROOT %out = f32[256]{0} copy(%ag)
    }
    """)


def test_synthetic_trip_counts():
    res = collective_bytes_with_trips(SYNTH)
    # all-gather outside the loop: 256*4 bytes, once
    assert res["all-gather"] == 256 * 4
    # all-reduce inside the 12-trip while: 128*4*12
    assert res["all-reduce"] == 128 * 4 * 12


def test_parse_computations_structure():
    comps, entry = parse_computations(SYNTH)
    assert entry == "%main"
    assert comps["%cond.1"].max_const == 12
    assert comps["%main"].whiles == [("%cond.1", "%body.1")]


def test_real_module_scaling_with_depth():
    """Collective bytes must scale ~linearly with scan length."""
    import jax
    import jax.numpy as jnp
    from functools import partial
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("model",))

    def make(n):
        def f(w, x):
            def body(h, wl):
                y = jnp.tanh(h @ wl)
                y = jax.lax.with_sharding_constraint(y, P())
                return y, None
            return jnp.sum(jax.lax.scan(body, x, w)[0])
        return f

    sizes = {}
    with jax.set_mesh(mesh):
        for n in (4, 8):
            c = jax.jit(make(n)).lower(
                jax.ShapeDtypeStruct((n, 64, 64), jnp.float32),
                jax.ShapeDtypeStruct((8, 64), jnp.float32)).compile()
            res = collective_bytes_with_trips(c.as_text())
            sizes[n] = sum(v for k, v in res.items()
                           if not k.startswith("_"))
    # single-device: no collectives — but the parser must not crash and
    # totals must be consistent (0 == 0)
    assert sizes[4] == sizes[8] == 0
