"""Language-model fleet: the SSM adapter through the fused engine
(``scenario="lm"`` / ``core.model_adapter.SSMAdapter``).

A single-block Mamba-2 LM federates over token shards exactly the way the
paper's LeNet federates over digit shards — edge MC-dropout acquisition on
the unlabeled pool, fog Eq. 1 aggregation, re-dispatch — T rounds in ONE
compiled dispatch.  The adapter's ``aggregate_mask`` names its carried
recurrent state (``recurrent/state``), so the engine keeps each device's
copy OUT of the Eq. 1 average: recurrent state is per-device context, and
averaging it across devices would destroy it (the ``exclude`` stub in
``core.aggregation``, now threaded through the fused program).

The run compares score-driven acquisition against a random-selection
control at the SAME label budget — the paper's active-vs-random claim on
tokens (the BENCH_lm gate).

    PYTHONPATH=src python examples/lm_fleet.py [--quick]

``--quick`` shrinks to a 4-device 2-round fleet (CI smoke-test sizing,
tests/test_examples.py).
"""
import argparse
from dataclasses import replace

import jax
import numpy as np

from repro.core import counters
from repro.core.engine import EdgeEngine
from repro.core.federated import (LM_SEQ_LEN, LM_VOCAB, FogNode, Trainer,
                                  lm_config)
from repro.core.model_adapter import excluded_paths
from repro.data.lm import lm_federated_split, make_lm_dataset
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--quick", action="store_true",
                    help="tiny fleet/budgets (CI smoke-test sizing)")
    args = ap.parse_args(argv)
    if args.quick:
        args.devices, args.rounds = 4, 2

    cfg = lm_config(args.devices, seed=0)
    shards = lm_federated_split(cfg.num_devices, 40, seq_len=LM_SEQ_LEN,
                                vocab=LM_VOCAB, seed=0)
    test = make_lm_dataset(64 if args.quick else 256, seq_len=LM_SEQ_LEN,
                           vocab=LM_VOCAB, seed=5, stream_seed=0)
    seed_set = make_lm_dataset(cfg.initial_train, seq_len=LM_SEQ_LEN,
                               vocab=LM_VOCAB, seed=11, stream_seed=0)

    excl = excluded_paths(cfg.adapter, cfg.adapter.init(jax.random.key(0)))
    print(f"devices={cfg.num_devices} LM shards (seq={LM_SEQ_LEN}, "
          f"vocab={LM_VOCAB}), {args.rounds} fused rounds; leaves excluded "
          f"from Eq. 1: {list(excl)}")

    for label, acq in [("active (MC-dropout)", cfg.acquisition_fn),
                       ("random control     ", "random")]:
        cfg_arm = replace(cfg, acquisition_fn=acq)
        trainer = Trainer(cfg_arm)
        fog = FogNode(trainer, cfg_arm, seed_set)
        eng = EdgeEngine(trainer, cfg_arm, shards, seed_set, test,
                         total_acquisitions=cfg_arm.acquisitions
                         * args.rounds)
        state = eng.init_state(fog.initial_model())
        counters.reset_dispatches()
        _, recs, _ = eng.run_rounds_fused(state, args.rounds)
        accs = [float(a) for a in recs["agg_acc"]]
        labeled = float(np.asarray(recs["n_labeled"][-1]).sum())
        print(f"{label}: final next-token acc {accs[-1]:.3f} "
              f"(trajectory {['%.3f' % a for a in accs]}), "
              f"{labeled:.0f} labels total, "
              f"{counters.dispatch_count()} host dispatch")


if __name__ == "__main__":
    main()
