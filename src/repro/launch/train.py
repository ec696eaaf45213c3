"""End-to-end FL-LM training driver (deliverable b).

Trains an assigned-architecture LM with FedDif over Dirichlet-non-IID client
shards of a synthetic corpus, charging communication to the wireless ledger,
and checkpointing the global model.

    PYTHONPATH=src python -m repro.launch.train --arch smollm_360m --smoke \
        --rounds 8 --clients 4 --steps-per-round 8

``--smoke`` uses the reduced same-family config (CPU-friendly); omit it on
real hardware to train the full config.  ``chip_smoke.py`` runs the full
smollm_360m (361.8M params) with ``--engine fleet --clients 2`` on one
TPU v5e chip.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.data.partitioner import dirichlet_partition
from repro.data.synthetic import class_labels_for_lm, lm_corpus
from repro.fl.server import FLConfig, run_federated
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.train import save_checkpoint


def main(argv: list[str] | None = None):
    """Parse ``argv`` (default: the command line), train, return the
    :class:`~repro.fl.engine.RunResult`."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="smollm_360m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU)")
    ap.add_argument("--strategy", default="feddif",
                    choices=["feddif", "fedavg", "fedswap", "stc"])
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--steps-per-round", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--engine", default="host",
                    help="EngineSpec preset (host/fleet/sharded/auto/async/"
                         "async_barrier)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.frontend is not None:
        raise SystemExit(f"{args.arch} needs frontend embeddings; use the "
                         "dry-run for this arch or a text arch here.")
    model = build_model(cfg)
    print(f"arch={cfg.name} params~{cfg.param_count()/1e6:.1f}M "
          f"(config geometry)")

    # --- data: synthetic corpus, Dirichlet-partitioned by pseudo-class ---
    rng = np.random.default_rng(args.seed)
    corpus = lm_corpus(400_000, vocab=cfg.vocab_size, seed=args.seed)
    n_docs = len(corpus) // args.seq_len
    docs = corpus[:n_docs * args.seq_len].reshape(n_docs, args.seq_len)
    labels = class_labels_for_lm(corpus, 10, args.seq_len)
    held = docs[: max(8, args.batch)]
    docs, labels = docs[len(held):], labels[len(held):]
    part = dirichlet_partition(labels, args.clients, args.alpha, rng)

    def client_epoch(i):
        ix = part.indices[i]

        def gen():
            sel = rng.choice(ix, size=min(len(ix),
                                          args.steps_per_round * args.batch),
                             replace=len(ix) < args.steps_per_round
                             * args.batch)
            out = []
            for s in range(0, len(sel), args.batch):
                chunk = docs[sel[s:s + args.batch]]
                if len(chunk) < args.batch:
                    break
                out.append({
                    "tokens": jnp.asarray(chunk[:, :-1]),
                    "labels": jnp.asarray(chunk[:, 1:]),
                })
            return out
        return gen

    batches = [client_epoch(i) for i in range(args.clients)]
    eval_batch = {"tokens": jnp.asarray(held[:, :-1]),
                  "labels": jnp.asarray(held[:, 1:])}

    @jax.jit
    def _eval_loss(params):
        return model.loss(params, eval_batch, remat=False)

    def eval_fn(params):
        l = float(_eval_loss(params))
        return float(np.exp(-l)), l   # "accuracy" = exp(-loss) proxy

    fl = FLConfig(strategy=args.strategy, num_clients=args.clients,
                  num_models=args.clients, rounds=args.rounds, lr=args.lr,
                  seed=args.seed, engine=args.engine)

    def loss_fn(params, batch):
        return model.loss(params, batch, remat=False)

    t0 = time.time()
    result = run_federated(lambda k: model.init(k), loss_fn, batches,
                           part.dsi, part.data_sizes, eval_fn, fl)
    for i, (a, l) in enumerate(zip(result.accuracy, result.loss)):
        print(f"round {i+1}: eval_loss={l:.4f} "
              f"dif_rounds={result.diffusion_rounds[i]}")
    print(f"ledger: subframes={result.ledger.subframes} "
          f"models={result.ledger.transmitted_models} "
          f"bits={result.ledger.transmitted_bits:.3e} "
          f"({time.time()-t0:.0f}s)")
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.rounds, result.final_params,
                        {"arch": cfg.name, "strategy": args.strategy,
                         "loss_history": result.loss})
        print(f"global model checkpointed to {args.ckpt_dir}")
    return result


if __name__ == "__main__":
    main()
