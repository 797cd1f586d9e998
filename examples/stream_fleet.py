"""Streaming active learning on live traffic: unlabeled requests ARRIVE
on the async event loop's virtual clock (``scenario="stream"`` /
``core.stream``), instead of sitting in a static pool.

Each device receives Poisson traffic with temporal label drift (the
favored class rotates through the label space), scores its bounded
request queue with the acquisition scorer, and a selection cascade
decides per event: confident requests are SERVED locally by the edge
model, the top-``escalate_k`` most informative are ESCALATED to the fog
(labeled + added to the training pool — active learning on traffic), the
rest wait until backpressure drops them.  The whole thing — arrivals,
queues, cascade, training, aggregation — is still ONE compiled dispatch,
configured through the unified ``FleetConfig`` bundle.

The run compares score-driven escalation against a random-selection
control at the SAME escalation budget — the streaming version of the
paper's active-vs-random claim.

    PYTHONPATH=src python examples/stream_fleet.py [--quick]

``--quick`` shrinks to an 8-device 2-event fleet (CI smoke-test sizing,
tests/test_examples.py).
"""
import argparse
from dataclasses import replace

from repro.core import counters
from repro.core.async_engine import async_telemetry
from repro.core.engine import EdgeEngine
from repro.core.federated import (HETERO_DIRICHLET_ALPHA,
                                  MASSIVE_SAMPLES_PER_DEVICE, FogNode,
                                  Trainer, default_async, default_stream,
                                  stream_config)
from repro.core.fleet import FleetConfig
from repro.core.stream import stream_telemetry
from repro.data.digits import make_digit_dataset
from repro.data.federated_split import dirichlet_split
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=32)
    ap.add_argument("--events", type=int, default=6,
                    help="fog aggregation events to simulate")
    ap.add_argument("--quick", action="store_true",
                    help="tiny fleet/budgets (CI smoke-test sizing)")
    args = ap.parse_args(argv)
    if args.quick:
        args.devices, args.events = 8, 2

    cfg = stream_config(args.devices, seed=0)
    full = make_digit_dataset(MASSIVE_SAMPLES_PER_DEVICE * cfg.num_devices,
                              seed=0)
    test = make_digit_dataset(100 if args.quick else 400, seed=1)
    seed_set = make_digit_dataset(cfg.initial_train, seed=2)
    shards = dirichlet_split(full, cfg.num_devices,
                             alpha=HETERO_DIRICHLET_ALPHA, seed=3)

    # every queued request is an escalation candidate: both arms below
    # spend the same min(escalate_k, queue) budget per event
    base = replace(default_stream(cfg.num_devices), escalate_threshold=0.0)
    extra = base.escalate_k * args.events
    trainer = Trainer(replace(
        cfg, acquisitions=cfg.acquisitions * args.events + extra))
    fog = FogNode(trainer, cfg, seed_set)
    eng = EdgeEngine(trainer, cfg, shards, seed_set, test,
                     total_acquisitions=cfg.acquisitions * args.events
                     + extra)
    params0 = fog.initial_model()
    print(f"devices={cfg.num_devices} non-IID dirichlet shards, "
          f"{args.events} events, traffic ~{base.arrival_rate:g} req/s/dev "
          f"(skew {base.rate_skew:g}x), drift period "
          f"{base.drift_period:g}s, escalation budget "
          f"{base.escalate_k}/device/event")
    print(f"fog-node seed model accuracy : "
          f"{trainer.accuracy(params0, test.images, test.labels):.3f}")

    for label, selection in [("active (score-ranked)", "score"),
                             ("random control       ", "random")]:
        fleet = FleetConfig(async_cfg=default_async(cfg.num_devices),
                            stream=replace(base, selection=selection))
        counters.reset_dispatches()
        _, recs, _ = eng.run_async(eng.init_state(params0), args.events,
                                   fleet=fleet)
        atel = async_telemetry(recs)
        stel = stream_telemetry(recs, image_shape=test.images.shape[1:])
        print(f"{label}: offered {stel['offered_total']}, served "
              f"{stel['served_total']} (serve acc "
              f"{stel['serve_accuracy']:.3f}), escalated "
              f"{stel['escalated_total']} "
              f"({stel['escalation_uplink_bytes']} uplink B), dropped "
              f"{stel['dropped_total']}, final acc {atel['final_acc']:.3f} "
              f"({counters.dispatch_count()} host dispatch)")


if __name__ == "__main__":
    main()
