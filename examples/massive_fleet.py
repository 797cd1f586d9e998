"""Massively-distributed federated AL: a 64-device fleet, whole rounds —
device AL + fog-node Eq. 1 aggregation + re-dispatch — fused into ONE
compiled dispatch (``EdgeEngine.run_rounds_fused``), with size-aware
``fedavg_n`` weighting, partial participation (paper §III-B's
asynchronization tolerance), int8-quantized uploads with error feedback
(``core.comms``), and byte-exact uplink/downlink accounting.

Optionally shards the device axis across a JAX mesh: run with

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/massive_fleet.py

and each of the 8 fake host devices simulates 8 edge devices; the fused
aggregation turns into an all_gather of per-device scalars plus one psum.

    PYTHONPATH=src python examples/massive_fleet.py [--quick]

``--quick`` shrinks to an 8-device single-round fleet (CI smoke-test
sizing, tests/test_examples.py).
"""
import argparse

import numpy as np

import jax

from repro.core import counters
from repro.core.comms import CommsConfig, comms_report
from repro.core.engine import EdgeEngine
from repro.core.federated import (FogNode, Trainer, massive_config,
                                  MASSIVE_SAMPLES_PER_DEVICE)
from repro.data.digits import make_digit_dataset
from repro.data.federated_split import federated_split
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_device_mesh


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny fleet/budgets (CI smoke-test sizing)")
    args = ap.parse_args(argv)
    rounds = 1 if args.quick else 2
    cfg = massive_config(num_devices=8 if args.quick else 64, seed=0)
    full = make_digit_dataset(MASSIVE_SAMPLES_PER_DEVICE * cfg.num_devices,
                              seed=0)
    test = make_digit_dataset(100 if args.quick else 400, seed=1)
    seed_set = make_digit_dataset(cfg.initial_train, seed=2)
    shards = federated_split(full, cfg.num_devices, seed=3)
    print(f"devices={cfg.num_devices} "
          f"shard sizes min/max={min(map(len, shards))}/{max(map(len, shards))}")

    mesh = None
    if jax.device_count() > 1 and cfg.num_devices % jax.device_count() == 0:
        mesh = make_device_mesh()
        print(f"sharding the device axis over {jax.device_count()} devices")

    trainer = Trainer(cfg)
    fog = FogNode(trainer, cfg, seed_set)
    eng = EdgeEngine(trainer, cfg, shards, seed_set, test,
                     total_acquisitions=cfg.acquisitions * rounds, mesh=mesh)
    params0 = fog.initial_model()
    print(f"fog-node seed model accuracy : "
          f"{trainer.accuracy(params0, test.images, test.labels):.3f}")

    comms = CommsConfig(compression="int8")  # ~4x smaller uplink, EF on
    counters.reset_dispatches()
    state, recs, agg = eng.run_rounds_fused(
        eng.init_state(params0), rounds,
        upload_fraction=0.75,            # 25% of devices skip each round
        aggregation="fedavg_n",          # Eq. 1 with alpha_i ~ n_i
        comms=comms)
    agg_accs = np.asarray(recs["agg_acc"])
    masks = np.asarray(recs["upload_mask"])
    report = comms_report(comms, params0, recs["upload_mask"],
                          agg_accs=recs["agg_acc"],
                          n_labeled=recs["n_labeled"],
                          image_shape=shards[0].images.shape[1:])
    for t in range(rounds):
        rec = report["rounds"][t]
        print(f"round {t}: aggregated acc {agg_accs[t]:.3f}  "
              f"({int(masks[t].sum())}/{cfg.num_devices} devices uploaded, "
              f"uplink {rec['uplink_bytes'] / 1e6:.2f} MB)")
    print(f"host->device dispatches for {rounds} full rounds "
          f"(AL + aggregation): {counters.dispatch_count()}")
    print(f"uplink total {report['uplink_mb_total']:.2f} MB at "
          f"{report['compression_ratio']:.1f}x compression "
          f"(float32 would be "
          f"{report['uplink_mb_total'] * report['compression_ratio']:.2f} MB)")


if __name__ == "__main__":
    main()
