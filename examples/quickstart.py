"""Quickstart: one fog/edge federated active-learning round (the paper's
non-massive setting, scaled to run in ~1 minute on CPU).

The round executes on the compile-once vectorized engine by default: all
devices × acquisitions × train steps run as ONE compiled program (see
README "The compile-once edge engine"). Pass ``engine="classic"`` to
``run_federated_round`` for the original per-device numpy-pool loop.

    PYTHONPATH=src python examples/quickstart.py [--quick]

``--quick`` shrinks everything (2 devices, 1 acquisition, tiny pools) so
the CI example smoke test (tests/test_examples.py) can run the same entry
point in seconds.
"""
import argparse

from repro.core import counters
from repro.core.federated import FederatedALConfig, run_federated_round, Trainer
from repro.data.digits import make_digit_dataset
from repro.data.federated_split import federated_split
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny fleet/budgets (CI smoke-test sizing)")
    args = ap.parse_args(argv)
    quick = args.quick
    cfg = FederatedALConfig(
        num_devices=2 if quick else 4,   # paper: E1..E4
        initial_train=20,         # paper: m = 20 seed images at the fog node
        acquisitions=1 if quick else 3,  # paper experiments use 10-40
        k_per_acquisition=10,
        pool_window=50 if quick else 200,
        mc_samples=4 if quick else 8,    # T in MC-dropout (Eq. 13)
        acquisition_fn="entropy", # or: bald | vr | random | margin
        aggregation="average",    # paper Eq. 1 (or: optimal | weighted)
        train_steps_per_acq=5 if quick else 15,
        seed=0,
    )
    full = make_digit_dataset(300 if quick else 1200, seed=0)
    test = make_digit_dataset(100 if quick else 400, seed=1)
    seed_set = make_digit_dataset(cfg.initial_train, seed=2)
    shards = federated_split(full, cfg.num_devices, seed=3)

    print(f"devices={cfg.num_devices} shard sizes={[len(s) for s in shards]}")
    counters.reset_dispatches()
    params, report = run_federated_round(cfg, shards, seed_set, test,
                                         trainer=Trainer(cfg), engine="vmap")
    print(f"fog-node seed model accuracy : {report['initial_acc']:.3f}")
    for d, hist in enumerate(report["device_histories"]):
        curve = " -> ".join(f"{h['test_acc']:.2f}" for h in hist)
        print(f"device {d}: {curve}")
    print(f"aggregated ({cfg.aggregation})    : {report['aggregated_acc']:.3f}")
    print(f"device accs at upload        : "
          f"{[round(a, 3) for a in report['aggregation']['device_accs']]}")
    print(f"host->device dispatches      : {counters.dispatch_count()} "
          f"(incl. fog-node seed fit + evals; the AL loop itself is 1)")


if __name__ == "__main__":
    main()
