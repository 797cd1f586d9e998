"""Sharded (shard_map) engine vs vmap engine equivalence.

The in-process tests build a device mesh over whatever host devices exist —
1 in a plain run (the shard_map code path still executes, collectives over a
size-1 axis), 8 in the CI job that sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before Python starts.
The ``slow`` subprocess test forces 8 fake host devices regardless of the
parent's XLA configuration, so the genuinely-sharded path is always covered
somewhere.
"""
import os
import subprocess
import sys
from dataclasses import replace

import jax
import numpy as np
import pytest

from repro.core.engine import EdgeEngine
from repro.core.federated import FederatedALConfig, Trainer
from repro.data.digits import make_digit_dataset
from repro.data.federated_split import federated_split
from repro.launch.mesh import make_device_mesh, make_fog_mesh
from repro.launch.sharding import shard_engine_state

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def setup():
    cfg = FederatedALConfig(num_devices=8, acquisitions=2, mc_samples=4,
                            k_per_acquisition=3, pool_window=16,
                            train_steps_per_acq=3, initial_train=10,
                            initial_train_steps=5, seed=5)
    full = make_digit_dataset(160, seed=1)
    test = make_digit_dataset(40, seed=2)
    seed_set = make_digit_dataset(cfg.initial_train, seed=3)
    shards = federated_split(full, cfg.num_devices, seed=4)
    return cfg, shards, seed_set, test


def _leaves_close(a, b, atol=1e-5):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=atol)


def test_sharded_round_matches_vmap(setup):
    cfg, shards, seed_set, test = setup
    trainer = Trainer(cfg)
    params0 = trainer.init_params(jax.random.key(0))

    ev = EdgeEngine(trainer, cfg, shards, seed_set, test)
    sv, rv = ev.run_round(ev.init_state(params0))

    em = EdgeEngine(trainer, cfg, shards, seed_set, test,
                    mesh=make_device_mesh())
    sm, rm = em.run_round(em.init_state(params0))

    _leaves_close(sv.params, sm.params)
    np.testing.assert_array_equal(np.asarray(rv["selected"]),
                                  np.asarray(rm["selected"]))
    np.testing.assert_allclose(np.asarray(rv["test_acc"]),
                               np.asarray(rm["test_acc"]), atol=1e-5)


def test_sharded_fused_rounds_match_vmap(setup):
    cfg, shards, seed_set, test = setup
    rounds, D = 2, cfg.num_devices
    total = cfg.acquisitions * rounds
    trainer = Trainer(replace(cfg, acquisitions=total))
    params0 = trainer.init_params(jax.random.key(1))
    mask = np.ones((rounds, D), np.float32)
    mask[0, ::2] = 0.0                       # partial participation round 0

    ev = EdgeEngine(trainer, cfg, shards, seed_set, test,
                    total_acquisitions=total)
    _, rv, fv = ev.run_rounds_fused(ev.init_state(params0), rounds,
                                    upload_mask=mask, aggregation="weighted")
    em = EdgeEngine(trainer, cfg, shards, seed_set, test,
                    total_acquisitions=total, mesh=make_device_mesh())
    _, rm, fm = em.run_rounds_fused(em.init_state(params0), rounds,
                                    upload_mask=mask, aggregation="weighted")

    _leaves_close(fv, fm)
    np.testing.assert_allclose(np.asarray(rv["weights"]),
                               np.asarray(rm["weights"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(rv["agg_acc"]),
                               np.asarray(rm["agg_acc"]), atol=1e-5)
    # masked-out devices carry zero aggregation weight on both paths
    assert np.all(np.asarray(rm["weights"])[0][mask[0] == 0.0] == 0.0)


def test_mesh_requires_divisible_fleet(setup):
    cfg, shards, seed_set, test = setup
    if jax.device_count() == 1:
        pytest.skip("needs >1 host device to make D indivisible")
    trainer = Trainer(cfg)
    with pytest.raises(ValueError, match="divide"):
        EdgeEngine(trainer, cfg, shards[:jax.device_count() - 1], seed_set,
                   mesh=make_device_mesh())


def test_shard_engine_state_places_leading_axis(setup):
    cfg, shards, seed_set, test = setup
    trainer = Trainer(cfg)
    eng = EdgeEngine(trainer, cfg, shards, seed_set)
    state = eng.init_state(trainer.init_params(jax.random.key(2)))
    mesh = make_device_mesh()
    sharded = shard_engine_state(mesh, state)
    leaf = jax.tree_util.tree_leaves(sharded.params)[0]
    assert leaf.sharding.mesh.shape["device"] == jax.device_count()


@pytest.mark.parametrize("make", [make_device_mesh, make_fog_mesh])
def test_fleet_meshes_use_auto_axes(make):
    """The fleet meshes carry Auto axes: the engines place data through
    shard_map specs, and an Explicit mesh axis breaks single-device programs
    that follow a sharded run (exercised in the forced-8-device check)."""
    mesh = make()
    assert set(mesh.axis_types) == {jax.sharding.AxisType.Auto}


# --------------------------------------------------- forced-8-device check
_FORCED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
import jax, numpy as np
from dataclasses import replace
from repro.core.engine import EdgeEngine
from repro.core.federated import FederatedALConfig, Trainer
from repro.data.digits import make_digit_dataset
from repro.data.federated_split import federated_split
from repro.launch.mesh import make_device_mesh

assert jax.device_count() == 8, jax.device_count()
cfg = FederatedALConfig(num_devices=8, acquisitions=1, mc_samples=2,
                        k_per_acquisition=2, pool_window=8,
                        train_steps_per_acq=2, initial_train=6,
                        initial_train_steps=2, seed=5)
full = make_digit_dataset(96, seed=1)
test = make_digit_dataset(24, seed=2)
seed_set = make_digit_dataset(cfg.initial_train, seed=3)
shards = federated_split(full, cfg.num_devices, seed=4)
trainer = Trainer(cfg)
params0 = trainer.init_params(jax.random.key(0))
ev = EdgeEngine(trainer, cfg, shards, seed_set, test)
_, _, fv = ev.run_rounds_fused(ev.init_state(params0), 1)
em = EdgeEngine(trainer, cfg, shards, seed_set, test, mesh=make_device_mesh())
_, _, fm = em.run_rounds_fused(em.init_state(params0), 1)
for a, b in zip(jax.tree_util.tree_leaves(fv), jax.tree_util.tree_leaves(fm)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
# a single-device program over the sharded run's fog model still lowers
fm1 = jax.device_put(fm, jax.devices()[0])
x = jax.numpy.asarray(np.stack([s.images[:8] for s in shards]))
keys = jax.random.split(jax.random.key(1), cfg.num_devices)
jax.block_until_ready(jax.jit(jax.vmap(
    lambda xd, kd: trainer.score_logprobs_raw(fm1, xd, kd, 2)))(x, keys))
print("OK")
"""


@pytest.mark.slow
def test_sharded_engine_on_forced_8_host_devices(setup):
    """End-to-end genuinely-sharded check: a subprocess forces 8 fake host
    devices (XLA_FLAGS must be set before jax initializes, hence the
    subprocess) and asserts shard_map == vmap on the fused round."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORM_NAME", "cpu")
    out = subprocess.run([sys.executable, "-c", _FORCED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
