"""Tests for the §Perf tooling: shard hints, HLO cross-pod classification,
and the beyond-paper router-entropy acquisition on a real MoE."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_analysis import _is_cross_pod, analyze
from repro.nn.shard_hints import hint, hint_heads

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------- hints
def _auto_mesh():
    # hint-style sharding (with_sharding_constraint under jax.set_mesh)
    # needs Auto axes; jax.make_mesh defaults to Explicit
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def test_hint_noop_without_mesh():
    x = jnp.ones((4, 8))
    y = hint(x, "data", "model")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    z = hint_heads(jnp.ones((2, 4, 8, 16)))
    assert z.shape == (2, 4, 8, 16)


def test_hint_inside_mesh_context():
    mesh = _auto_mesh()

    # axis size 1 divides everything; just verify it traces and is identity
    x = jnp.arange(12.0).reshape(4, 3)
    with jax.set_mesh(mesh):
        y = jax.jit(lambda v: hint(v, "data", None))(x)
    np.testing.assert_allclose(np.asarray(x), np.asarray(y))


def test_hint_applies_constraint_inside_mesh_context():
    """The hint must actually lower to a sharding constraint (not silently
    no-op) when a mesh is active."""
    mesh = _auto_mesh()
    x = jnp.arange(12.0).reshape(4, 3)
    with jax.set_mesh(mesh):
        from repro.nn.shard_hints import _active_mesh
        assert _active_mesh() is not None
        txt = jax.jit(lambda v: hint(v, "data", None)).lower(x).as_text()
    assert "sharding" in txt.lower()
    assert _active_mesh() is None  # context exited → hints back to no-ops


def test_hint_skips_nondividing_axis():
    mesh = _auto_mesh()
    with jax.set_mesh(mesh):
        # 7 is not divisible by anything > 1; with axis size 1 it IS
        # divisible — the guard path is exercised via absent axis name
        y = jax.jit(lambda v: hint(v, "absent_axis", None))(jnp.ones((7, 3)))
    assert y.shape == (7, 3)


# ------------------------------------------------------- cross-pod classifier
def test_cross_pod_explicit_groups():
    # groups {0..255} / {256..511}: intra-pod at pod_size=256
    rest = "x), replica_groups={{0,1,2},{256,257,258}}, to_apply=%add"
    assert not _is_cross_pod(rest, 256)
    rest2 = "x), replica_groups={{0,256}}, to_apply=%add"
    assert _is_cross_pod(rest2, 256)


def test_cross_pod_iota_groups():
    # contiguous 32 groups of 16: all intra-pod
    rest = "x), replica_groups=[32,16]<=[512], to_apply=%add"
    assert not _is_cross_pod(rest, 256)
    # 2 groups of 256: group 0 = pod 0, group 1 = pod 1 → intra
    rest2 = "x), replica_groups=[2,256]<=[512], to_apply=%add"
    assert not _is_cross_pod(rest2, 256)
    # 256 groups of 2 with transpose mixing pods: [2,256]T(1,0) pairs (i, i+256)
    rest3 = "x), replica_groups=[256,2]<=[2,256]T(1,0), to_apply=%add"
    assert _is_cross_pod(rest3, 256)


def test_analyze_multiplies_loop_collectives():
    """Hand-written HLO: a while loop (trip count 5) whose body holds one
    all-reduce of 1 KiB → analyzer must report 5 all-reduces / 5 KiB."""
    hlo = """
%body (p: (s32[], f32[256])) -> (s32[], f32[256]) {
  %p = (s32[], f32[256]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[256] get-tuple-element(%p), index=1
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  %ar = f32[256]{0} all-reduce(%x), replica_groups={{0,1}}, to_apply=%sum
  ROOT %t = (s32[], f32[256]) tuple(%ni, %ar)
}

%cond (p2: (s32[], f32[256])) -> pred[] {
  %p2 = (s32[], f32[256]) parameter(0)
  %i2 = s32[] get-tuple-element(%p2), index=0
  %trip = s32[] constant(5)
  ROOT %lt = pred[] compare(%i2, %trip), direction=LT
}

ENTRY %main (arg: f32[256]) -> f32[256] {
  %arg = f32[256] parameter(0)
  %zero = s32[] constant(0)
  %tup = (s32[], f32[256]) tuple(%zero, %arg)
  %w = (s32[], f32[256]) while(%tup), condition=%cond, body=%body
  ROOT %out = f32[256] get-tuple-element(%w), index=1
}
"""
    st = analyze(hlo, entry="main")
    assert st.collective_counts.get("all-reduce", 0) == 5
    assert st.collective_bytes == 5 * 256 * 4


# ------------------------------------------------------- router entropy
def test_router_entropy_on_reduced_moe():
    from repro.configs import get_config
    from repro.nn.moe import moe_init, moe_router_entropy

    cfg = get_config("deepseek-v2-236b").reduced()
    params = moe_init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 8, cfg.d_model))
    ent = moe_router_entropy(params, x)
    assert ent.shape == (2, 8)
    assert float(jnp.min(ent)) >= 0.0
    assert float(jnp.max(ent)) <= np.log(cfg.n_experts) + 1e-5


def test_moe_sort_dispatch_matches_dense_oracle():
    """Sort-based capacity dispatch == dense all-experts oracle when capacity
    is unconstrained."""
    from dataclasses import replace
    from repro.configs import get_config
    from repro.nn.moe import moe_apply, moe_init

    cfg = replace(get_config("arctic-480b").reduced(),
                  router_capacity_factor=16.0)
    params = moe_init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model)) * 0.3
    y_sort, _ = moe_apply(params, x, cfg=cfg, impl="sort")
    y_dense, _ = moe_apply(params, x, cfg=cfg, impl="dense")
    np.testing.assert_allclose(np.asarray(y_sort), np.asarray(y_dense),
                               atol=2e-4, rtol=1e-3)
