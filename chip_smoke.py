"""Drive the federated-AL main path once on a TPU chip and check what comes out.

    python chip_smoke.py              # phases A, B, C on one chip
    python chip_smoke.py --chips 4    # phase B over a 4-way device mesh vs one chip

Phase A, the paper round: ``run_federated_round(FederatedALConfig(),
engine="vmap")`` at the config defaults (D=4, pool_window=200, mc_samples=16,
train_steps_per_acq=30) with the paper's LeNet-5 (``repro.configs.lenet``).
Phase B, the massive fleet: the ``massive`` scenario at D=256, two fused rounds
with the in-compile Eq. 1 reduce.  Phase C, the async loop: the ``async``
scenario at D=64, four aggregation events.  Phases B and C build their data as
``run_experiment`` does and call ``run_federated_rounds`` itself, the function
``run_experiment`` wraps, so that the final fog model comes back.

Each phase runs its entry point twice (cold, then warm) with buffer donation
live, and checks that
  * the compiled engine program carries the Pallas kernels as Mosaic custom
    calls (``tpu_custom_call`` with the kernel's name, read from the module
    JAX hands the compiler), and the engine resolved ``scorer`` and
    ``aggregate_impl`` to ``pallas``;
  * the kernels match their jnp oracles on the chip at the engine's own
    shapes: Eq. 1 within the tolerance the interpret-mode tests pin, the
    MC scores within the bound the chip's exp/log precision sets
    (``SCORE_ATOL``);
  * the fog model is finite and its test accuracy is above chance.
A failed check raises, so the script exits non-zero.  Per-phase numbers go on
JSON lines; the last line is ``{"ok": true, "device": {...}}`` and nothing else.
The script exits non-zero before any phase when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import time

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
IR_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke_ir")

# kernel-vs-oracle tolerances.  Eq. 1: what tests/test_fused_aggregation.py
# pins in interpret mode.  MC scores: tests/test_kernels.py pins 1e-5 on the
# CPU, but a v5e's f32 exp and log (Mosaic and XLA alike) carry a relative
# error of about 5.3e-6, and the kernel (log of the MC-mean probability) and
# the oracle (logsumexp) take them of different values; with log-probs down
# to log(1e-10) = -23 that bounds the gap by 2 * 5.3e-6 * (23 + 1) = 2.5e-4.
SCORE_ATOL = 2.5e-4
AGG_ATOL, AGG_RTOL = 1e-6, 1e-5
# sharded vs one-chip phase B.  The two programs sum in different orders
# (psum of four partials, 64 vs 256 vmapped slots), and Adam turns a
# reassociation difference in a near-zero gradient into up to one step of
# lr = 1e-3 per parameter, so the fog model is held to that step; the
# per-round test accuracies (1000 samples) may differ by five predictions.
SHARD_ATOL = 1e-3
SHARD_ACC_ATOL = 0.005
CHANCE = 0.1  # ten digit classes

# (scenario, num_devices, rounds or events) of phases B and C
MASSIVE = ("massive", 256, 2)
ASYNC = ("async", 64, 4)


def _emit(**row):
    print(json.dumps(row), flush=True)


def _require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found platform {dev.platform!r} "
                 f"({dev.device_kind}); this script needs a TPU")
    return dev


class _CompileClock:
    """Seconds JAX spent handing programs to the compiler (cache hits
    included), and how many of those were persistent-cache hits."""

    def __init__(self):
        self.seconds, self.hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def reset(self):
        self.seconds, self.hits = 0.0, 0


def _timed_twice(clock, fn):
    """Cold call (compile + run), then a warm call of the same entry point,
    which reuses the engine's compiled program and the donated-state path;
    returns the warm result and the timings."""

    clock.reset()
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    cold = time.perf_counter() - t0
    compile_s, hits = clock.seconds, clock.hits
    clock.reset()
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    warm = time.perf_counter() - t0
    return out, {"compile_s": compile_s, "cache_hits": hits,
                 "cold_call_s": cold, "run_s": warm,
                 "warm_compile_s": clock.seconds}


def _kernels_in(phase_dir, program, kernels):
    """Names of ``kernels`` that appear as Mosaic custom calls in the
    module(s) of ``program`` dumped under ``phase_dir``; ends the dump."""
    jax.config.update("jax_dump_ir_to", "")
    files = glob.glob(os.path.join(phase_dir, f"*jit_{program}_compile.mlir"))
    if not files:
        raise AssertionError(f"no {program} module was compiled")
    found = set()
    for path in files:
        with open(path) as f:
            text = f.read()
        if "@tpu_custom_call" not in text:
            continue
        found |= {k for k in kernels if f'kernel_name = "{k}"' in text}
    missing = sorted(set(kernels) - found)
    if missing:
        raise AssertionError(
            f"{program}: no Mosaic custom call for kernel(s) {missing}")
    shutil.rmtree(phase_dir)
    return sorted(found)


def _require_pallas(scorer, aggregate_impl):
    """The engine's ``auto`` kernel choice resolves to Mosaic, not to the
    jnp reference or to interpret mode."""
    from repro.core import aggregation as agg
    from repro.core import engine as engine_mod

    if engine_mod.resolve_scorer(scorer) != "pallas":
        raise AssertionError("scorer did not resolve to the Pallas kernel")
    if agg.resolve_aggregate_impl(aggregate_impl) != "pallas":
        raise AssertionError("aggregate_impl did not resolve to Pallas")


def _phase_ir_dir(name):
    path = os.path.join(IR_DIR, name)
    os.makedirs(path, exist_ok=True)
    for f in glob.glob(os.path.join(path, "*")):
        os.remove(f)
    jax.config.update("jax_dump_ir_to", path)
    return path


def _scenario_data(cfg, split, seed, n_train):
    """The data ``run_experiment`` builds for one repeat."""
    from repro.core.federated import HETERO_DIRICHLET_ALPHA
    from repro.data.digits import make_digit_dataset
    from repro.data.federated_split import dirichlet_split, federated_split

    full = make_digit_dataset(n_train, seed=seed)
    test = make_digit_dataset(1000, seed=seed + 5)
    seed_set = make_digit_dataset(cfg.initial_train, seed=seed + 11)
    if split == "dirichlet":
        shards = dirichlet_split(full, cfg.num_devices,
                                 alpha=HETERO_DIRICHLET_ALPHA, seed=seed)
    else:
        shards = federated_split(full, cfg.num_devices, seed=seed)
    return shards, seed_set, test


def _check_model(params, acc):
    leaves = jax.tree_util.tree_leaves(params)
    if not all(bool(jnp.all(jnp.isfinite(l))) for l in leaves):
        raise AssertionError("fog model has non-finite parameters")
    if not acc > CHANCE:
        raise AssertionError(f"accuracy {acc} is not above chance {CHANCE}")


def _assert_close(name, got, want, atol, rtol=0.0):
    """Max abs error between two pytrees; raises, with every leaf's error,
    if any element is outside ``atol + rtol * |want|``."""
    errs, ok = [], True
    for x, y in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
        errs.append(float(np.max(np.abs(x - y))))
        ok &= bool(np.all(np.abs(x - y) <= atol + rtol * np.abs(y)))
    if not ok:
        raise AssertionError(f"{name}: max abs error per leaf {errs} is "
                             f"outside atol={atol}, rtol={rtol}")
    return max(errs)


def _score_oracle_check(trainer, params, shards, window, T, key):
    """MC-scoring kernel vs its jnp oracle on LeNet log-probs of one pool
    window per device, vmapped over devices as the engine calls it."""

    from repro.core.engine import stack_device_data
    from repro.kernels import ref
    from repro.kernels.acquisition_scores import acquisition_scores_fused

    images = stack_device_data(shards)[0]
    x = images[:, :min(window, images.shape[1])]
    keys = jax.random.split(key, len(shards))
    logp = jax.jit(jax.vmap(
        lambda xd, kd: trainer.score_logprobs_raw(params, xd, kd, T)))(x, keys)
    kern = jax.jit(jax.vmap(
        lambda lp: acquisition_scores_fused(lp, interpret=False)))(logp)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(ref.acquisition_scores_ref))(logp)
    return _assert_close("acquisition_scores", kern, want, SCORE_ATOL)


def _agg_oracle_check(D, key):
    """Eq. 1 kernel vs its jnp oracle on a LeNet-shaped ``[D, ...]`` fleet:
    the engines' preweighted f32 form, int8 codes with per-tensor scales,
    and the G=4 segment form."""

    from repro.core import aggregation as agg
    from repro.kernels import ref
    from repro.kernels.fused_aggregation import fused_aggregate
    from repro.nn.lenet import LeNet

    from repro.configs import lenet

    k_init, k_w, k_q, k_s = jax.random.split(key, 4)
    stacked = jax.vmap(lambda k: LeNet.init(k, lenet.config()))(
        jax.random.split(k_init, D))
    w = jax.random.uniform(k_w, (D,), minval=0.1, maxval=1.0)
    w = w / jnp.sum(w)
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    q = jax.tree_util.tree_unflatten(treedef, [
        jax.random.randint(k, l.shape, -127, 128, jnp.int32).astype(jnp.int8)
        for k, l in zip(jax.random.split(k_q, len(leaves)), leaves)])
    scales = jax.tree_util.tree_unflatten(treedef, [
        jax.random.uniform(k, (D,), minval=1e-4, maxval=1e-2)
        for k in jax.random.split(k_s, len(leaves))])
    ids = jnp.arange(D, dtype=jnp.int32) % 4

    pallas = jax.jit(lambda t, v: agg.aggregate_stacked(t, v, impl="pallas"))
    pallas_seg = jax.jit(lambda t, v: agg.aggregate_stacked(
        t, v, impl="pallas", segment_ids=ids, num_segments=4))
    pallas_q = jax.jit(lambda t, v, s: fused_aggregate(
        t, v, scales=s, normalize=False, interpret=False))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda t, v: agg.aggregate_stacked(t, v, impl="ref"))(
            stacked, w)
        want_seg = jax.jit(lambda t, v: agg.aggregate_stacked(
            t, v, impl="ref", segment_ids=ids, num_segments=4))(stacked, w)
        want_q = jax.jit(lambda t, v, s: ref.fused_agg_ref(
            t, v, scales=s, normalize=False))(q, w, scales)
    return {
        "f32": _assert_close("fused_aggregation f32", pallas(stacked, w),
                             want, AGG_ATOL, AGG_RTOL),
        "segment_G4": _assert_close("fused_aggregation segment",
                                    pallas_seg(stacked, w), want_seg,
                                    AGG_ATOL, AGG_RTOL),
        "int8": _assert_close("fused_aggregation int8",
                              pallas_q(q, w, scales), want_q,
                              AGG_ATOL, AGG_RTOL),
    }


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_a(dev, clock):
    """The paper round on the vmapped engine."""

    from repro.configs import lenet
    from repro.core.federated import (FederatedALConfig, Trainer,
                                      run_federated_round)
    from repro.nn.lenet import LeNetConfig

    cfg = FederatedALConfig()
    if LeNetConfig() != lenet.config():
        raise AssertionError("engine default LeNet differs from the paper's")
    _require_pallas(cfg.scorer, cfg.aggregate_impl)
    shards, seed_set, test = _scenario_data(cfg, "uniform", cfg.seed, 4000)
    trainer = Trainer(cfg)
    ir = _phase_ir_dir("A")
    (params, report), t = _timed_twice(clock, lambda: run_federated_round(
        cfg, shards, seed_set, test, trainer=trainer, engine="vmap"))
    kernels = _kernels_in(ir, "round_all", ["acquisition_scores"])
    acc = report["aggregated_acc"]
    _check_model(params, acc)
    err = _score_oracle_check(trainer, params, shards, cfg.pool_window,
                              cfg.mc_samples, jax.random.key(1))
    _emit(phase="A", entry="run_federated_round", engine="vmap",
          num_devices=cfg.num_devices, pool_window=cfg.pool_window,
          mc_samples=cfg.mc_samples,
          train_steps_per_acq=cfg.train_steps_per_acq, kernels=kernels,
          score_max_abs_err=err, initial_acc=report["initial_acc"],
          aggregated_acc=acc, device_kind=dev.device_kind,
          peak_bytes_in_use=_peak_bytes(dev), **t)


def _scenario_run(scenario, num_devices, rounds, mesh=None):
    """One repeat of ``run_experiment(scenario=..., num_devices=...,
    rounds=...)``: returns (cfg, shards, run), where ``run()`` gives the
    fog model and the round reports."""
    from repro.core.federated import SCENARIOS, run_federated_rounds

    scn = SCENARIOS[scenario]
    cfg = scn.config(num_devices)
    shards, seed_set, test = _scenario_data(
        cfg, scn.split, cfg.seed, 40 * num_devices)

    def run():
        return run_federated_rounds(cfg, shards, seed_set, test,
                                    rounds=rounds, engine=scn.engine,
                                    mesh=mesh, fleet=scn.dynamics(cfg))
    return cfg, shards, run


def _fleet_phase(dev, clock, name, scenario, num_devices, rounds, program,
                 mesh=None):
    from repro.core.federated import Trainer

    cfg, shards, run = _scenario_run(scenario, num_devices, rounds, mesh)
    _require_pallas(cfg.scorer, cfg.aggregate_impl)
    ir = _phase_ir_dir(name)
    (params, reports), t = _timed_twice(clock, run)
    kernels = _kernels_in(ir, program,
                          ["acquisition_scores", "fused_aggregation"])
    accs = [r["aggregated_acc"] for r in reports]
    _check_model(params, accs[-1])
    # the oracle checks run on one device; a mesh run returns the fog model
    # replicated over the mesh
    score_err = _score_oracle_check(Trainer(cfg),
                                    jax.device_put(params, dev), shards,
                                    cfg.pool_window, cfg.mc_samples,
                                    jax.random.key(2))
    agg_err = _agg_oracle_check(num_devices, jax.random.key(3))
    row = dict(phase=name, scenario=scenario, entry="run_federated_rounds",
               num_devices=num_devices, rounds=rounds, kernels=kernels,
               score_max_abs_err=score_err, agg_max_abs_err=agg_err,
               aggregated_acc=accs, device_kind=dev.device_kind,
               peak_bytes_in_use=_peak_bytes(dev), **t)
    return params, row


def phase_b(dev, clock, mesh=None, name="B"):
    params, row = _fleet_phase(dev, clock, name, *MASSIVE, "rounds_all",
                               mesh=mesh)
    _emit(**row)
    return params, row["aggregated_acc"]


def phase_c(dev, clock):
    _, row = _fleet_phase(dev, clock, "C", *ASYNC, "events_all")
    _emit(**row)


def _check_spread(mesh, num_devices):
    """The sharded engine's fleet data and state hold D/4 rows on each of
    the mesh's devices, none piled onto device 0."""

    from repro.core.engine import EdgeEngine
    from repro.core.federated import SCENARIOS, Trainer

    cfg = SCENARIOS["massive"].config(num_devices)
    shards, seed_set, test = _scenario_data(cfg, "uniform", cfg.seed,
                                            40 * num_devices)
    trainer = Trainer(cfg)
    eng = EdgeEngine(trainer, cfg, shards, seed_set, test, mesh=mesh)
    state = eng.init_state(trainer.init_params(jax.random.key(0)))
    n = len(mesh.devices.flat)
    per = num_devices // n
    for what, arr in [("images", eng.images), ("labels", eng.labels)] + [
            (f"state leaf {i}", a) for i, a in
            enumerate(jax.tree_util.tree_leaves(state))]:
        rows = {s.device.id: s.data.shape[0] for s in arr.addressable_shards}
        if len(rows) != n or set(rows.values()) != {per}:
            raise AssertionError(f"{what} is not spread {per} rows per "
                                 f"device over {n} devices: {rows}")
    return per


def four_chip(dev, clock):
    """Phase B over a 4-way device mesh, then the same run on one chip."""

    from repro.launch.mesh import make_device_mesh

    if len(jax.devices()) != 4:
        raise AssertionError(f"--chips 4 needs 4 devices, JAX found "
                             f"{len(jax.devices())}")
    mesh = make_device_mesh()
    per = _check_spread(mesh, MASSIVE[1])
    sharded = phase_b(dev, clock, mesh=mesh, name="B_mesh4")
    single = phase_b(dev, clock, name="B_one_chip")
    err = _assert_close("sharded vs one-chip fog model", sharded[0],
                        single[0], SHARD_ATOL)
    acc_err = _assert_close("sharded vs one-chip accuracy", sharded[1],
                            single[1], SHARD_ACC_ATOL)
    _emit(phase="B_compare", slots_per_chip=per,
          fog_model_max_abs_diff=err, atol=SHARD_ATOL,
          acc_max_abs_diff=acc_err, acc_atol=SHARD_ACC_ATOL,
          peak_bytes_in_use=[_peak_bytes(d) for d in jax.devices()])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: phase B sharded over a 4-way device mesh and "
                         "compared with the same run on one chip")
    args = ap.parse_args(argv)

    dev = _require_tpu()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    clock = _CompileClock()
    if args.chips == 4:
        four_chip(dev, clock)
    else:
        phase_a(dev, clock)
        phase_b(dev, clock)
        phase_c(dev, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
