"""Massive-distribution regime (paper §IV-D): many devices with few images
each, federated averaging collapses, and cascading recovers accuracy.
Includes the beyond-paper pipelined cascade schedule.

    PYTHONPATH=src python examples/massive_cascade.py [--devices 12] [--quick]
"""
import argparse

import jax
import numpy as np

from repro.core.cascade import (cascade_train, pipelined_cascade_schedule,
                                pipelined_cascade_speedup)
from repro.core.federated import (EdgeDevice, FederatedALConfig, FogNode,
                                  Trainer, run_federated_round)
from repro.data.digits import make_digit_dataset
from repro.data.federated_split import federated_split
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=10)
    ap.add_argument("--images-per-device", type=int, default=40)
    ap.add_argument("--quick", action="store_true",
                    help="tiny fleet/budgets (CI smoke-test sizing)")
    args = ap.parse_args(argv)
    if args.quick:
        args.devices, args.images_per_device = 4, 20

    R = args.images_per_device // 10
    cfg = FederatedALConfig(num_devices=args.devices, acquisitions=R,
                            mc_samples=8, train_steps_per_acq=12,
                            pool_window=100, seed=0)
    trainer = Trainer(cfg)
    full = make_digit_dataset(3 * args.devices * args.images_per_device, seed=0)
    test = make_digit_dataset(400, seed=1)
    seed_set = make_digit_dataset(20, seed=2)
    shards = federated_split(full, args.devices, seed=3)

    _, rep = run_federated_round(cfg, shards, seed_set, test, trainer=trainer,
                                 record_curves=False)
    print(f"[massive] {args.devices} devices x {args.images_per_device} imgs "
          f"-> fedavg acc {rep['aggregated_acc']:.3f}")

    fog = FogNode(trainer, cfg, seed_set)
    params0 = fog.initial_model(jax.random.key(0))
    for chain_len in (2, 4):
        devices = [EdgeDevice(i, shards[i], trainer, cfg, seed_data=seed_set)
                   for i in range(chain_len)]
        p, _ = cascade_train(params0, devices, acquisitions_per_link=R)
        acc = trainer.accuracy(p, test.images, test.labels)
        sp = pipelined_cascade_speedup(chain_len, R)
        print(f"[cascade {chain_len}] chain acc {acc:.3f} "
              f"(paper slowdown {chain_len}x; pipelined recovers {sp:.2f}x)")

    sched = pipelined_cascade_schedule(4, R)
    print(f"[pipeline] chain=4, micro-rounds={R}: "
          f"{4 * R} blocking steps -> {len(sched)} pipelined steps")


if __name__ == "__main__":
    main()
