"""Hierarchical fog topology: two-tier edge→fog→cloud federated AL in ONE
compiled dispatch (``core.topology`` + ``EdgeEngine.run_rounds_fused``).

Three runs over the same non-IID fleet: flat federation (every upload
straight to the cloud), the same fleet under a G=1 fog topology (must
reproduce the flat run bitwise — the reduction contract), and a real
G-group topology syncing to the cloud only every ``local_steps``-th
round.  The script closes with the per-tier byte ledger
(``comms.tier_report``): between syncs NOTHING crosses the fog→cloud
tier, which is the hierarchy's entire bandwidth case.

    PYTHONPATH=src python examples/fog_fleet.py [--quick]

``--quick`` shrinks to an 8-device 2-group 4-round fleet (CI smoke-test
sizing, tests/test_examples.py).
"""
import argparse

import numpy as np

import jax

from repro.core import comms as comms_mod
from repro.core import counters
from repro.core.engine import EdgeEngine
from repro.core.federated import (HETERO_DIRICHLET_ALPHA,
                                  MASSIVE_SAMPLES_PER_DEVICE, FogNode,
                                  Trainer, fog_config)
from repro.core.topology import uniform_topology
from repro.data.digits import make_digit_dataset
from repro.data.federated_split import dirichlet_split
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--groups", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=2,
                    help="cloud sync cadence (rounds per fog→cloud sync)")
    ap.add_argument("--quick", action="store_true",
                    help="tiny fleet/budgets (CI smoke-test sizing)")
    args = ap.parse_args(argv)
    if args.quick:
        args.devices, args.rounds, args.groups = 8, 4, 2

    cfg = fog_config(args.devices, seed=0)
    full = make_digit_dataset(MASSIVE_SAMPLES_PER_DEVICE * cfg.num_devices,
                              seed=0)
    test = make_digit_dataset(100 if args.quick else 400, seed=1)
    seed_set = make_digit_dataset(cfg.initial_train, seed=2)
    shards = dirichlet_split(full, cfg.num_devices,
                             alpha=HETERO_DIRICHLET_ALPHA, seed=3)
    print(f"devices={cfg.num_devices} non-IID dirichlet shards, "
          f"{args.rounds} rounds; fog tier: G={args.groups} groups, "
          f"cloud sync every {args.local_steps} rounds")

    trainer = Trainer(cfg)
    fog = FogNode(trainer, cfg, seed_set)
    eng = EdgeEngine(trainer, cfg, shards, seed_set, test,
                     total_acquisitions=cfg.acquisitions * args.rounds)
    params0 = fog.initial_model()
    print(f"fog-node seed model accuracy : "
          f"{trainer.accuracy(params0, test.images, test.labels):.3f}")

    topo1 = uniform_topology(cfg.num_devices, 1, local_steps=1)
    topo = uniform_topology(cfg.num_devices, args.groups,
                            local_steps=args.local_steps)

    runs = {}
    for label, topology in [("flat federation ", None),
                            ("fog tier, G=1   ", topo1),
                            (f"fog tier, G={args.groups:<2}  ", topo)]:
        counters.reset_dispatches()
        _, recs, final = eng.run_rounds_fused(
            eng.init_state(params0), args.rounds, topology=topology)
        acc = float(np.asarray(recs["agg_acc"])[-1])
        runs[label] = (recs, final)
        extra = ""
        if topology is not None:
            syncs = int(np.asarray(recs["fog_sync"]).sum())
            extra = f", cloud syncs {syncs}/{args.rounds}"
        print(f"{label}: final acc {acc:.3f}"
              f"{extra} ({counters.dispatch_count()} host dispatch)")

    # G=1 is the degenerate hierarchy: one fog group holding the whole
    # fleet, syncing every round — it must reproduce flat federation
    flat_final = runs["flat federation "][1]
    g1_final = runs["fog tier, G=1   "][1]
    drift = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
                for a, b in zip(jax.tree_util.tree_leaves(flat_final),
                                jax.tree_util.tree_leaves(g1_final)))
    assert drift <= 1e-5, f"G=1 drifted from flat federation: {drift}"
    print(f"G=1 vs flat: max |drift| = {drift:.2e} "
          f"(degenerate hierarchy reduces to Eq. 1)")

    # ------------------------------------------------ per-tier byte ledger
    recs, final = runs[f"fog tier, G={args.groups:<2}  "]
    tiers = comms_mod.tier_report(None, final,
                                  np.asarray(recs["upload_mask"]), topo)
    mb = 1 / 2**20
    print(f"edge→fog uplink : {tiers['edge_fog_bytes_total'] * mb:8.2f} MiB "
          f"(every round, every uploading device)")
    print(f"fog→cloud uplink: {tiers['fog_cloud_bytes_total'] * mb:8.2f} MiB "
          f"({tiers['sync_rounds']} sync rounds x {args.groups} groups)")
    print(f"flat would ship : "
          f"{tiers['flat_cross_tier_uplink_bytes'] * mb:8.2f} MiB "
          f"across the upper tier")
    print(f"cross-tier uplink cut: {tiers['cross_tier_reduction']:.1f}x")


if __name__ == "__main__":
    main()
