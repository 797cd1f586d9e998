"""Fault-tolerant federated AL: a churning fleet with crashes, dropped and
corrupted uploads, and label noise — survived in ONE compiled dispatch
(``core.faults`` + ``EdgeEngine.run_rounds_fused``).

Three runs over the same non-IID fleet: fault-free, faulted with the fog's
norm/finiteness guards armed (clip-or-drop before Eq. 1), and the same
fault trace unguarded — the degradation the guards exist to stop.  The
script finishes with a mid-experiment checkpoint/resume round-trip
(``repro.checkpoint.save_engine_state``): the resumed half must reproduce
the uninterrupted run, fault trace included.

    PYTHONPATH=src python examples/churn_fleet.py [--quick]

``--quick`` shrinks to an 8-device 2-round fleet (CI smoke-test sizing,
tests/test_examples.py).
"""
import argparse
import os
import tempfile

import numpy as np

import jax

from repro.checkpoint import load_engine_state, save_engine_state
from repro.core import counters
from repro.core import faults as faults_mod
from repro.core.engine import EdgeEngine
from repro.core.federated import (DEFAULT_FAULTS, DEFAULT_GUARDS,
                                  HETERO_DIRICHLET_ALPHA,
                                  MASSIVE_SAMPLES_PER_DEVICE, FogNode,
                                  Trainer, churn_config)
from repro.data.digits import make_digit_dataset
from repro.data.federated_split import dirichlet_split
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--quick", action="store_true",
                    help="tiny fleet/budgets (CI smoke-test sizing)")
    args = ap.parse_args(argv)
    if args.quick:
        args.devices, args.rounds = 8, 2

    cfg = churn_config(args.devices, seed=0)
    full = make_digit_dataset(MASSIVE_SAMPLES_PER_DEVICE * cfg.num_devices,
                              seed=0)
    test = make_digit_dataset(100 if args.quick else 400, seed=1)
    seed_set = make_digit_dataset(cfg.initial_train, seed=2)
    shards = dirichlet_split(full, cfg.num_devices,
                             alpha=HETERO_DIRICHLET_ALPHA, seed=3)
    print(f"devices={cfg.num_devices} non-IID dirichlet shards, "
          f"{args.rounds} rounds; faults: "
          f"death={DEFAULT_FAULTS.death_rate} birth={DEFAULT_FAULTS.birth_rate} "
          f"crash={DEFAULT_FAULTS.crash_rate} drop={DEFAULT_FAULTS.drop_rate} "
          f"corrupt={DEFAULT_FAULTS.corrupt_rate}"
          f"(x{DEFAULT_FAULTS.corrupt_scale:.0f})")

    trainer = Trainer(cfg)
    fog = FogNode(trainer, cfg, seed_set)
    eng = EdgeEngine(trainer, cfg, shards, seed_set, test,
                     total_acquisitions=cfg.acquisitions * args.rounds)
    params0 = fog.initial_model()
    print(f"fog-node seed model accuracy : "
          f"{trainer.accuracy(params0, test.images, test.labels):.3f}")

    for label, faults, guards in [
        ("fault-free        ", None, None),
        ("faulted + guards  ", DEFAULT_FAULTS, DEFAULT_GUARDS),
        ("faulted, UNGUARDED", DEFAULT_FAULTS, None),
    ]:
        counters.reset_dispatches()
        _, recs, final = eng.run_rounds_fused(
            eng.init_state(params0), args.rounds, faults=faults,
            guards=guards)
        acc = float(np.asarray(recs["agg_acc"])[-1])
        finite = all(np.isfinite(np.asarray(l)).all()
                     for l in jax.tree_util.tree_leaves(final))
        tel = faults_mod.summarize_faults(recs)
        live = tel.get("mean_live_fraction", 1.0)
        print(f"{label}: final acc {acc:.3f}, fog finite={finite}, "
              f"live {live:.2f}, "
              f"crashed {tel.get('crashed_total', 0)}, "
              f"dropped {tel.get('dropped_total', 0)}, "
              f"corrupted {tel.get('corrupted_total', 0)}, "
              f"rejected {tel.get('rejected_total', 0)} "
              f"({counters.dispatch_count()} host dispatch)")

    # ------------------------------------------- checkpoint / resume demo
    half = max(1, args.rounds // 2)
    rest = args.rounds - half
    _, _, final_full = eng.run_rounds_fused(
        eng.init_state(params0), args.rounds, faults=DEFAULT_FAULTS,
        guards=DEFAULT_GUARDS)
    st, _, _ = eng.run_rounds_fused(
        eng.init_state(params0), half, faults=DEFAULT_FAULTS,
        guards=DEFAULT_GUARDS)
    path = os.path.join(tempfile.mkdtemp(prefix="churn_ckpt_"),
                        "mid_experiment.msgpack")
    save_engine_state(path, st, metadata={"next_round": half})
    st2, meta = load_engine_state(path)
    st2 = eng.resume_state(st2, next_round=meta["next_round"])
    _, _, final_res = eng.run_rounds_fused(
        st2, rest, start_round=half, faults=DEFAULT_FAULTS,
        guards=DEFAULT_GUARDS)
    drift = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
                for a, b in zip(jax.tree_util.tree_leaves(final_full),
                                jax.tree_util.tree_leaves(final_res)))
    assert drift <= 1e-5, f"resume drifted from uninterrupted run: {drift}"
    print(f"checkpoint at round {half} -> restore -> {rest} more rounds: "
          f"max |drift| vs uninterrupted = {drift:.2e} (fault trace "
          f"replayed from absolute round indices)")


if __name__ == "__main__":
    main()
