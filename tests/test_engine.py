"""Vectorized engine tests: vpool invariants, vmapped-engine vs legacy
per-device-loop equivalence, and Pallas-scored vs jnp-scored parity inside
the AL hot loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation as agg_mod
from repro.core import engine as engine_mod
from repro.core import vpool
from repro.core.engine import EdgeEngine, stack_device_data
from repro.core.federated import (FederatedALConfig, Trainer,
                                  run_federated_round, run_federated_rounds)
from repro.data.digits import make_digit_dataset
from repro.data.federated_split import federated_split

jax.config.update("jax_platform_name", "cpu")


# ------------------------------------------------------------------ vpool
def test_vpool_draw_excludes_labeled_and_padding():
    valid = jnp.asarray(np.array([True] * 8 + [False] * 4))
    pool = vpool.vpool_init(valid, capacity=6)
    idx, ok = vpool.draw_window(pool, jax.random.key(0), 8)
    assert bool(jnp.all(ok))                       # 8 unlabeled remain
    assert bool(jnp.all(idx < 8))                  # never a padding slot
    assert len(set(np.asarray(idx).tolist())) == 8  # without replacement

    pool = vpool.acquire(pool, idx, jnp.asarray([0, 1, 2]),
                         jnp.asarray([True, True, True]))
    assert int(vpool.n_labeled(pool)) == 3
    idx2, ok2 = vpool.draw_window(pool, jax.random.key(1), 8)
    taken = set(np.asarray(idx)[np.array([0, 1, 2])].tolist())
    drawn_valid = set(np.asarray(idx2)[np.asarray(ok2)].tolist())
    assert not (taken & drawn_valid)               # labeled never re-drawn
    assert int(jnp.sum(ok2)) == 5                  # only 5 unlabeled remain


def test_vpool_depletion_marks_invalid():
    valid = jnp.ones((4,), bool)
    pool = vpool.vpool_init(valid, capacity=8)
    idx, ok = vpool.draw_window(pool, jax.random.key(0), 6)
    assert int(jnp.sum(ok)) == 4                   # window > unlabeled
    pool = vpool.acquire(pool, idx, jnp.arange(6), ok)
    assert int(vpool.n_labeled(pool)) == 4         # invalid picks masked out
    _, ok2 = vpool.draw_window(pool, jax.random.key(1), 6)
    assert int(jnp.sum(ok2)) == 0                  # pool exhausted


def test_stack_device_data_pads_ragged_shards():
    a = make_digit_dataset(10, seed=0)
    b = make_digit_dataset(7, seed=1)
    images, labels, valid = stack_device_data([a, b])
    assert images.shape == (2, 10, 28, 28, 1)
    assert bool(jnp.all(valid[0])) and int(jnp.sum(valid[1])) == 7
    np.testing.assert_array_equal(np.asarray(labels[1][:7]), b.labels)


# ------------------------------------------------------------- equivalence
@pytest.fixture(scope="module")
def setup():
    cfg = FederatedALConfig(num_devices=2, acquisitions=2, mc_samples=4,
                            k_per_acquisition=4, pool_window=24,
                            train_steps_per_acq=4, initial_train=12,
                            initial_train_steps=8, seed=7)
    full = make_digit_dataset(120, seed=1)
    test = make_digit_dataset(60, seed=2)
    seed_set = make_digit_dataset(cfg.initial_train, seed=3)
    shards = federated_split(full, cfg.num_devices, seed=4)
    return cfg, shards, seed_set, test


@pytest.mark.slow
def test_vmapped_engine_matches_legacy_loop(setup):
    """The tentpole's correctness contract: one vmapped dispatch computes
    exactly what the per-device Python loop computes — same selected pool
    indices, same final aggregated accuracy."""
    cfg, shards, seed_set, test = setup
    _, rep_v = run_federated_round(cfg, shards, seed_set, test, engine="vmap")
    _, rep_l = run_federated_round(cfg, shards, seed_set, test, engine="legacy")

    for hv, hl in zip(rep_v["device_histories"], rep_l["device_histories"]):
        for rv, rl in zip(hv, hl):
            assert rv["selected"] == rl["selected"]
            assert rv["n_labeled"] == rl["n_labeled"]
            assert abs(rv["test_acc"] - rl["test_acc"]) <= 1e-5
    assert abs(rep_v["aggregated_acc"] - rep_l["aggregated_acc"]) <= 1e-5
    assert rep_v["aggregation"]["strategy"] == rep_l["aggregation"]["strategy"]


@pytest.mark.parametrize("resolve", [engine_mod.resolve_scorer,
                                     agg_mod.resolve_aggregate_impl],
                         ids=["scorer", "aggregate_impl"])
def test_explicit_pallas_refuses_non_tpu_backend(resolve):
    """An explicit 'pallas' must not quietly fall back to interpret mode
    off-TPU; 'pallas_interpret' stays the CPU path."""
    with pytest.raises(ValueError, match="compiles for TPU"):
        resolve("pallas")
    assert resolve("pallas_interpret") == "pallas_interpret"


@pytest.mark.slow
def test_pallas_scored_engine_matches_jnp_oracle(setup):
    """Routing the hot loop's scoring through the fused Pallas kernel
    (interpret mode on CPU) must not change what gets acquired."""
    cfg, shards, seed_set, test = setup
    from dataclasses import replace
    cfg_p = replace(cfg, scorer="pallas_interpret")
    cfg_j = replace(cfg, scorer="jnp")
    _, rep_p = run_federated_round(cfg_p, shards, seed_set, test, engine="vmap")
    _, rep_j = run_federated_round(cfg_j, shards, seed_set, test, engine="vmap")

    for hp, hj in zip(rep_p["device_histories"], rep_j["device_histories"]):
        for rp, rj in zip(hp, hj):
            assert rp["selected"] == rj["selected"]
            assert abs(rp["test_acc"] - rj["test_acc"]) <= 1e-5
    assert abs(rep_p["aggregated_acc"] - rep_j["aggregated_acc"]) <= 1e-5


@pytest.mark.slow
def test_engine_multi_round_accumulates_labels(setup):
    cfg, shards, seed_set, test = setup
    params, reports = run_federated_rounds(cfg, shards, seed_set, test,
                                           rounds=2, engine="vmap")
    assert len(reports) == 2
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree_util.tree_leaves(params))
    for rep in reports:
        assert 0.0 <= rep["aggregated_acc"] <= 1.0


def test_engine_one_dispatch_per_round(setup):
    cfg, shards, seed_set, test = setup
    from repro.core import counters
    trainer = Trainer(cfg)
    params0 = trainer.init_params(jax.random.key(0))
    eng = EdgeEngine(trainer, cfg, shards, seed_set)
    state = eng.init_state(params0)
    counters.reset_dispatches()
    state, _ = eng.run_round(state, record_curves=False)
    assert counters.dispatch_count() == 1
    assert state.params["conv1"]["kernel"].shape[0] == cfg.num_devices


def test_engine_refuses_round_past_capacity(setup):
    """A second round on a single-round-capacity pool must raise, not
    silently clobber labeled slots (dynamic_update_slice clamps)."""
    cfg, shards, seed_set, test = setup
    trainer = Trainer(cfg)
    eng = EdgeEngine(trainer, cfg, shards, seed_set)
    state = eng.init_state(trainer.init_params(jax.random.key(0)))
    state, _ = eng.run_round(state, record_curves=False)
    with pytest.raises(ValueError, match="capacity"):
        eng.run_round(state, record_curves=False)


def test_random_acquisition_engine(setup):
    cfg, shards, seed_set, test = setup
    from dataclasses import replace
    cfg_r = replace(cfg, acquisition_fn="random")
    _, rep = run_federated_round(cfg_r, shards, seed_set, test, engine="vmap",
                                 record_curves=False)
    for hist in rep["device_histories"]:
        assert [h["n_labeled"] for h in hist] == [4, 8]
