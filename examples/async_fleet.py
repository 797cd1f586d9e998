"""Rounds-free async federated AL: a skewed-latency fleet aggregated by a
FedBuff quorum / safety timer instead of a round barrier, in ONE compiled
dispatch (``EdgeEngine.run_async`` / ``core.async_engine``).

Each device draws a completion latency per local round (exponential around
a 10x slow/fast skew profile); the fog node aggregates whenever a quorum
of uploads has buffered or the timer fires, mixing arrivals with
staleness-decayed Eq. 1 weights.  The virtual clock is SIMULATED seconds —
compare the quorum loop's time-to-accuracy against the full barrier, which
must wait for the slowest device every round.

    PYTHONPATH=src python examples/async_fleet.py [--quick]

``--quick`` shrinks to an 8-device 2-event fleet (CI smoke-test sizing,
tests/test_examples.py).
"""
import argparse

import numpy as np

import jax

from repro.core import counters
from repro.core.async_engine import AsyncConfig, async_telemetry
from repro.core.engine import EdgeEngine
from repro.core.federated import (HETERO_DIRICHLET_ALPHA,
                                  MASSIVE_SAMPLES_PER_DEVICE, FogNode,
                                  Trainer, async_config)
from repro.data.digits import make_digit_dataset
from repro.data.federated_split import dirichlet_split
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=32)
    ap.add_argument("--events", type=int, default=4,
                    help="fog aggregation events to simulate")
    ap.add_argument("--quick", action="store_true",
                    help="tiny fleet/budgets (CI smoke-test sizing)")
    args = ap.parse_args(argv)
    if args.quick:
        args.devices, args.events = 8, 2

    cfg = async_config(args.devices, seed=0)
    full = make_digit_dataset(MASSIVE_SAMPLES_PER_DEVICE * cfg.num_devices,
                              seed=0)
    test = make_digit_dataset(100 if args.quick else 400, seed=1)
    seed_set = make_digit_dataset(cfg.initial_train, seed=2)
    shards = dirichlet_split(full, cfg.num_devices,
                             alpha=HETERO_DIRICHLET_ALPHA, seed=3)
    print(f"devices={cfg.num_devices} non-IID dirichlet shards, "
          f"{args.events} aggregation events")

    trainer = Trainer(cfg)
    fog = FogNode(trainer, cfg, seed_set)
    eng = EdgeEngine(trainer, cfg, shards, seed_set, test,
                     total_acquisitions=cfg.acquisitions * args.events)
    params0 = fog.initial_model()
    print(f"fog-node seed model accuracy : "
          f"{trainer.accuracy(params0, test.images, test.labels):.3f}")

    quorum = max(1, cfg.num_devices // 4)
    for label, acfg in [
        ("full barrier (quorum=D)  ",
         AsyncConfig(quorum=cfg.num_devices, dist="exp", mean_latency=1.0,
                     latency_skew=10.0)),
        (f"FedBuff (quorum={quorum}, timer)",
         AsyncConfig(quorum=quorum, timer=4.0, dist="exp", mean_latency=1.0,
                     latency_skew=10.0, decay="poly", decay_rate=0.5)),
    ]:
        counters.reset_dispatches()
        _, recs, _ = eng.run_async(eng.init_state(params0), args.events,
                                   async_cfg=acfg)
        tel = async_telemetry(recs)
        arrivals = np.asarray(recs["arrivals"], np.int64)
        print(f"{label}: {tel['sim_seconds_total']:7.2f} simulated s for "
              f"{args.events} events, final acc {tel['final_acc']:.3f}, "
              f"arrivals/event {arrivals.tolist()}, "
              f"stale mean {tel['staleness']['mean']:.2f} "
              f"({counters.dispatch_count()} host dispatch)")


if __name__ == "__main__":
    main()
