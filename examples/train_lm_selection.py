"""LM-scale federated active learning THROUGH the fused engine: a decoder
LM (``models.decoder`` via ``core.model_adapter.DecoderLMAdapter``) runs
the paper's Algorithm 1 — edge MC-dropout acquisition, fog Eq. 1
aggregation, re-dispatch — as ONE compiled dispatch per
``EdgeEngine.run_rounds_fused`` call, with the ``kernels.flash_attention``
Pallas core inside the fused AL hot loop (``--impl pallas``; interpret
mode on CPU).

This used to be a hand-rolled host loop over ``launch.steps``; the
ModelAdapter layer makes the engine model-agnostic, so the LM now takes
the exact code path LeNet does — selection, federation, checkpointing and
all.  ``lm_100m()`` keeps the ~100M-param config as the scale target; the
driver default is its ``reduced()`` cut so the fused program compiles in
CPU-CI time.

    PYTHONPATH=src python examples/train_lm_selection.py --rounds 3

``--quick`` shrinks to a 2-device 1-round fleet on a 1-layer model (CI
smoke-test sizing, tests/test_examples.py).
"""
import argparse
from dataclasses import replace

import jax
import numpy as np

from repro.checkpoint import save_round
from repro.core import counters
from repro.core.engine import EdgeEngine
from repro.core.federated import FogNode, Trainer, lm_config
from repro.core.model_adapter import DecoderLMAdapter
from repro.data.lm import lm_federated_split, make_lm_dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.models import ModelConfig


def lm_100m() -> ModelConfig:
    """~100M decoder (gemma-style) — the scale target this driver reduces."""
    return ModelConfig(
        name="lm-100m", family="decoder", n_layers=12, d_model=640,
        n_heads=10, n_kv_heads=5, head_dim=64, d_ff=2560, vocab_size=32768,
        attn_pattern=("S",), tie_embeddings=True, dropout_rate=0.1,
        max_seq_len=512)


def small_decoder(*, vocab: int, seq_len: int, n_layers: int = 2) -> ModelConfig:
    """CPU-sized cut of ``lm_100m`` with MC-dropout kept on (Eq. 13 needs
    ``dropout_rate > 0`` for the posterior samples to vary)."""
    cfg = lm_100m().reduced(n_layers=n_layers, vocab_size=vocab,
                            max_seq_len=seq_len)
    return replace(cfg, dropout_rate=0.1)


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--select", default="entropy",
                    choices=["entropy", "bald", "variation_ratio", "random"])
    ap.add_argument("--impl", default="pallas",
                    help="attention core for the no-grad forwards: "
                         "pallas (flash_attention, interpret on CPU) | "
                         "naive | blockwise | auto")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    ap.add_argument("--quick", action="store_true",
                    help="2-device 1-round 1-layer fleet (CI smoke-test "
                         "sizing, tests/test_examples.py)")
    args = ap.parse_args(argv)
    n_layers = 2
    if args.quick:
        args.devices, args.rounds = 2, 1
        args.seq, args.vocab, n_layers = 16, 128, 1

    model = small_decoder(vocab=args.vocab, seq_len=args.seq,
                          n_layers=n_layers)
    adapter = DecoderLMAdapter(model, impl=args.impl)
    cfg = lm_config(args.devices, adapter=adapter,
                    acquisition_fn=args.select)
    n_params = sum(
        int(np.prod(s.shape)) for s in
        jax.tree_util.tree_leaves(jax.eval_shape(adapter.init,
                                                 jax.random.key(0))))
    print(f"model: reduced {model.name} {n_params / 1e6:.2f}M params, "
          f"attention impl={args.impl}")

    # one shared Markov chain; per-device temperature ramp = the paper's
    # "same distribution, different proportions" regime on tokens
    shards = lm_federated_split(cfg.num_devices, 40, seq_len=args.seq,
                                vocab=args.vocab, seed=0)
    test = make_lm_dataset(64 if args.quick else 256, seq_len=args.seq,
                           vocab=args.vocab, seed=5, stream_seed=0)
    seed_set = make_lm_dataset(cfg.initial_train, seq_len=args.seq,
                               vocab=args.vocab, seed=11, stream_seed=0)

    trainer = Trainer(cfg)
    fog = FogNode(trainer, cfg, seed_set)
    eng = EdgeEngine(trainer, cfg, shards, seed_set, test,
                     total_acquisitions=cfg.acquisitions * args.rounds)
    params0 = fog.initial_model()
    print(f"devices={cfg.num_devices} LM shards (seq={args.seq}, "
          f"vocab={args.vocab}), {args.rounds} fused rounds, "
          f"selection={args.select}")

    counters.reset_dispatches()
    state, recs, agg = eng.run_rounds_fused(eng.init_state(params0),
                                            args.rounds)
    for r in range(args.rounds):
        print(f"round {r}: next-token acc {float(recs['agg_acc'][r]):.3f}  "
              f"labeled/device {np.asarray(recs['n_labeled'][r]).mean():.1f}")
    print(f"{args.rounds} rounds = {counters.dispatch_count()} host dispatch")
    save_round(args.ckpt_dir, args.rounds, fog_model=agg,
               metadata={"rounds": args.rounds, "select": args.select,
                         "impl": args.impl})
    print(f"checkpoint in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
