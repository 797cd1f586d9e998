"""Sharding-rule and launch-layer tests (no 512-device init — pure spec
logic plus a tiny 1-device mesh)."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch.sharding import cache_pspec, param_pspecs, spec_for_path
from repro.launch.steps import (cascade_shift, federated_sync,
                                federated_sync_weighted, make_train_step,
                                softmax_cross_entropy)
from repro.models import build_model
from repro.optim import adamw

jax.config.update("jax_platform_name", "cpu")


def test_spec_rules_basic():
    assert spec_for_path("units/0/attn/wq/kernel", 3) == P(None, None, "model")
    assert spec_for_path("units/0/moe/experts/wi_gate", 4) == P(None, "model", None, "data")
    assert spec_for_path("embed/embedding", 2) == P("model", None)
    assert spec_for_path("units/0/attn_norm/scale", 2) == P(None, None)
    assert spec_for_path("units/0/mamba/in_proj/kernel", 3) == P(None, None, "model")
    assert spec_for_path("head_layers/0/mlp/wo/kernel", 2) == P("model", None)


def test_adafactor_state_specs():
    # vr drops the last dim of the param spec; vc drops the second-to-last
    assert spec_for_path("v/units/0/mlp/wi_gate/kernel/vr", 2) == P(None, None)
    assert spec_for_path("v/units/0/mlp/wi_gate/kernel/vc", 2) == P(None, "model")


def test_param_pspecs_cover_reduced_model():
    cfg = get_config("deepseek-v2-236b").reduced()
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    specs = param_pspecs(shapes)
    leaves_s = jax.tree_util.tree_leaves(shapes)
    leaves_p = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves_s) == len(leaves_p)
    for s, p in zip(leaves_s, leaves_p):
        assert len(p) == s.ndim


def test_cache_pspec_modes():
    # decode_32k: batch-sharded attention cache [B, S, Hkv, hd]
    assert cache_pspec("units/0/k", 5, batch_sharded=True) == \
        P(None, "data", None, None, "model")
    # long_500k: seq-sharded
    assert cache_pspec("units/0/k", 5, batch_sharded=False) == \
        P(None, None, "data", None, "model")
    assert cache_pspec("units/0/ckv", 4, batch_sharded=False) == \
        P(None, None, "data", "model")
    assert cache_pspec("units/0/state", 5, batch_sharded=True) == \
        P(None, "data", "model", None, None)
    assert cache_pspec("units/0/pos", 2, batch_sharded=True) == P(None, None)


def test_softmax_cross_entropy_matches_naive():
    logits = jax.random.normal(jax.random.key(0), (4, 7, 11))
    targets = jax.random.randint(jax.random.key(1), (4, 7), 0, 11)
    ce = softmax_cross_entropy(logits, targets, z_loss=0.0)
    logp = jax.nn.log_softmax(logits, -1)
    naive = -np.take_along_axis(np.asarray(logp), np.asarray(targets)[..., None],
                                axis=-1)[..., 0]
    np.testing.assert_allclose(np.asarray(ce), naive, rtol=1e-5)


def test_federated_sync_uniform():
    params_g = {"w": jnp.stack([jnp.ones((3,)), 3 * jnp.ones((3,))])}
    out = federated_sync(params_g)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.full((2, 3), 2.0), rtol=1e-6)


def test_federated_sync_weighted():
    params_g = {"w": jnp.stack([jnp.zeros((2,)), jnp.ones((2,))])}
    out = federated_sync_weighted(params_g, jnp.asarray([1.0, 3.0]))
    np.testing.assert_allclose(np.asarray(out["w"]), np.full((2, 2), 0.75),
                               rtol=1e-6)


def test_cascade_shift_is_ring():
    params_g = {"w": jnp.asarray([[0.0], [1.0], [2.0]])}
    out = cascade_shift(params_g)
    np.testing.assert_array_equal(np.asarray(out["w"])[:, 0], [2.0, 0.0, 1.0])


def test_microbatched_step_matches_single_batch_loss():
    """Gradient accumulation must give (near-)identical parameters to the
    full-batch step for a deterministic model."""
    cfg = get_config("gemma-7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    opt = adamw(1e-3)
    toks = jax.random.randint(jax.random.key(1), (4, 17), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :16], "targets": toks[:, 1:]}
    s1 = make_train_step(model, opt)
    s2 = make_train_step(model, opt, num_microbatches=2)
    p1, _, m1 = s1(params, opt.init(params), batch, jnp.zeros((), jnp.int32))
    p2, _, m2 = s2(params, opt.init(params), batch, jnp.zeros((), jnp.int32))
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-4)


def test_hlo_analysis_scan_vs_unroll():
    from repro.launch.hlo_analysis import analyze

    def f_scan(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    def f_unroll(x, w):
        for _ in range(7):
            x = jnp.tanh(x @ w)
        return x

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    fl = []
    for f in (f_scan, f_unroll):
        st = analyze(jax.jit(f).lower(x, w).compile().as_text())
        fl.append(st.flops)
    assert fl[0] == fl[1] == 7 * 2 * 128**3


def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the helper sets no other path."""
    from repro.launch.compile_cache import ENV_VAR, enable_compile_cache

    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_and_tpu_only(monkeypatch):
    """Unset, the cache path is one fixed, git-ignored directory of the
    checkout, and off-TPU the helper leaves the cache off."""
    import os

    from repro.launch.compile_cache import (DEFAULT_DIR, ENV_VAR,
                                            enable_compile_cache)

    monkeypatch.delenv(ENV_VAR, raising=False)
    root = DEFAULT_DIR.parent
    assert DEFAULT_DIR.name == ".jax_cache"
    assert os.path.exists(root / "pyproject.toml")
    with open(root / ".gitignore") as f:
        assert ".jax_cache/" in f.read().split()
    if jax.default_backend() != "tpu":
        before = jax.config.jax_compilation_cache_dir
        assert enable_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == before
