"""SPMD FedDif runtime — a thin CLI over the RoundSchedule/Executor layer.

The FedDif *scheduler* (``repro.fl.schedulers.schedule_feddif``) plans each
communication round on host — auctions, DoL bookkeeping, wire accounting
[PUCCH] — and this driver replays the resulting
:class:`~repro.core.schedule.RoundSchedule` on an LM fleet with
``repro.distributed.fedshard``'s jitted data plane: vmapped local update per
``TrainOp``, collective-permute + masked train per ``PermuteOp``, Eq.-11
weighted aggregation from the schedule's chain weights [PUSCH].  The ledger
is charged by :func:`~repro.core.schedule.charge_schedule` — the same
function the host simulator uses, so fleet runs report the same Table-II
metrics.

On a pod, the client axis is a real mesh axis (``data`` on-pod for
paper-scale fleets, ``pod`` across pods — see fedshard); on this CPU host
it runs on the 1-device mesh, which is the same program.  With
``--shard-clients`` the stacked TrainState and batches are placed with
``NamedSharding`` over a ``("clients",)`` mesh
(:func:`repro.launch.mesh.make_clients_mesh` /
:func:`repro.distributed.sharding.client_shardings`) so GSPMD partitions
every jitted step across devices — the same client-sharded plane the
``sharded`` FL executor uses, here on LM fleets.

    PYTHONPATH=src python -m repro.launch.fl_spmd --clients 4 --rounds 3
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      PYTHONPATH=src python -m repro.launch.fl_spmd --clients 4 --shard-clients
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.channels.fading import ChannelModel
from repro.channels.resources import GAMMA_FLOOR, ResourceLedger
from repro.channels.topology import CellTopology
from repro.configs import ARCH_IDS, get_smoke_config
from repro.core import aggregation as agg
from repro.core.auction import AuctionConfig
from repro.core.diffusion import DiffusionPlanner
from repro.core.schedule import PermuteOp, TrainOp, charge_schedule
from repro.data.partitioner import dirichlet_partition
from repro.data.synthetic import class_labels_for_lm, lm_corpus
from repro.distributed.fedshard import (fleet_aggregate,
                                        make_diffusion_step,
                                        make_fleet_train_step)
from repro.fl.schedulers import RoundContext, schedule_feddif
from repro.fl.server import FLConfig, _uplink_gamma
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.train import optimizer as opt_lib
from repro.train.trainstep import TrainState

__all__ = ["run_spmd_feddif"]


def _stack_states(model, opt, key, n):
    """One model replica per client slot (BS clones the global model)."""
    params = model.init(key)
    one = TrainState(params=params, opt_state=opt.init(params),
                     step=jnp.zeros((), jnp.int32))
    return jax.tree.map(lambda x: jnp.broadcast_to(
        x, (n,) + x.shape).copy(), one)


def run_spmd_feddif(arch: str = "smollm_360m", clients: int = 4,
                    rounds: int = 3, alpha: float = 0.5, seq_len: int = 64,
                    batch: int = 4, lr: float = 0.01, epsilon: float = 0.04,
                    seed: int = 0, shard_clients: bool = False, log=print):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    opt = opt_lib.sgd()
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)

    client_sharding = None
    if shard_clients:
        from repro.distributed.sharding import client_shardings
        from repro.launch.mesh import make_clients_mesh
        mesh = make_clients_mesh(clients)
        client_sharding = lambda tree: client_shardings(mesh, tree)  # noqa: E731
        log(f"client mesh: {mesh} "
            f"({clients // mesh.shape['clients']} clients/device)")

    # --- non-IID client corpora -------------------------------------
    corpus = lm_corpus(200_000, vocab=cfg.vocab_size, seed=seed)
    n_docs = len(corpus) // seq_len
    docs = corpus[:n_docs * seq_len].reshape(n_docs, seq_len)
    labels = class_labels_for_lm(corpus, 10, seq_len)
    part = dirichlet_partition(labels, clients, alpha, rng)

    def client_batch(c):
        ix = rng.choice(part.indices[c], size=batch,
                        replace=len(part.indices[c]) < batch)
        chunk = docs[ix]
        return {"tokens": jnp.asarray(chunk[:, :-1]),
                "labels": jnp.asarray(chunk[:, 1:])}

    def fleet_batch():
        per = [client_batch(c) for c in range(clients)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
        if client_sharding is not None:
            stacked = jax.device_put(stacked, client_sharding(stacked))
        return stacked

    # --- jitted data plane ------------------------------------------
    fleet_step = jax.jit(make_fleet_train_step(model, opt, lr, remat=False))
    diff_step = jax.jit(make_diffusion_step(model, opt, lr, remat=False))
    aggregate = jax.jit(fleet_aggregate)

    # --- host control plane (shared with the FL simulator) ----------
    fl_cfg = FLConfig(strategy="feddif", num_clients=clients,
                      num_models=clients, rounds=rounds, lr=lr,
                      epsilon=epsilon, seed=seed)
    topology = CellTopology(num_pues=clients)
    channel = ChannelModel()
    auction = AuctionConfig(gamma_min=fl_cfg.gamma_min)
    planner = DiffusionPlanner(topology, channel, auction, epsilon=epsilon)
    state = _stack_states(model, opt, key, clients)
    if client_sharding is not None:
        state = jax.device_put(state, client_sharding(state))
    model_bits = agg.model_bits(state.params)
    auction.model_bits = model_bits
    ledger = ResourceLedger()
    history = []

    for t in range(rounds):
        t0 = time.time()
        pos = topology.sample_positions(rng, clients)
        up_gamma = np.maximum(_uplink_gamma(channel, pos, rng),
                              GAMMA_FLOOR)
        ctx = RoundContext(cfg=fl_cfg, t=t, dsi=part.dsi,
                           data_sizes=part.data_sizes, pos=pos, rng=rng,
                           up_gamma=up_gamma, topology=topology,
                           channel=channel, planner=planner,
                           model_bits=model_bits, param_template=None)
        schedule = schedule_feddif(ctx)
        charge_schedule(ledger, schedule)

        metrics = {"loss": jnp.zeros((clients,))}
        for op in schedule.ops:
            if isinstance(op, TrainOp):          # initial fleet update
                state, metrics = fleet_step(state, fleet_batch())
            elif isinstance(op, PermuteOp):      # one diffusion round
                state, metrics = diff_step(state, fleet_batch(),
                                           jnp.asarray(op.src_of_dst),
                                           jnp.asarray(op.train_mask), None)
        # Eq.-11 aggregation + broadcast, chain-data-size weighted.
        weights = jnp.asarray(schedule.slot_weights(), jnp.float32)
        state = TrainState(params=aggregate(state.params, weights),
                           opt_state=state.opt_state, step=state.step)
        loss = float(jnp.mean(metrics["loss"]))
        history.append(loss)
        log(f"round {t + 1}: diffusion_rounds={schedule.diffusion_rounds} "
            f"mean_client_loss={loss:.4f} "
            f"final_iid={schedule.mean_iid:.4f} "
            f"subframes={ledger.subframes} "
            f"({time.time() - t0:.1f}s)")
    return state, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="smollm_360m")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--shard-clients", action="store_true",
                    help="shard the client axis over a ('clients',) mesh "
                         "(use XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=K for a multi-device CPU mesh)")
    args = ap.parse_args()
    enable_compile_cache()
    _, hist = run_spmd_feddif(args.arch, args.clients, args.rounds,
                              args.alpha, args.seq_len, args.batch,
                              shard_clients=args.shard_clients)
    print("loss history:", [round(h, 3) for h in hist])


if __name__ == "__main__":
    main()
