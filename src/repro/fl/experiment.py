"""High-level experiment harness: dataset → partition → run_federated.

One call reproduces one cell of the paper's figures/tables; the benchmark
scripts sweep it over α (Fig. 3), ε (Fig. 4), γ_min (Fig. 5), ML task
(Fig. 6 / Table I) and strategy (Table II).
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.data.partitioner import dirichlet_partition
from repro.data.pipeline import make_client_loaders
from repro.data.synthetic import (ImageDataset, class_labels_for_lm,
                                  gaussian_image_dataset, lm_corpus)
from repro.fl.models import TASK_MODELS, build_task_model
from repro.fl.server import FLConfig, FLResult, run_federated

__all__ = ["ExperimentSpec", "run_experiment", "load_experiment_data",
           "spec_model_bits", "spec_adapter_bits"]


@dataclasses.dataclass
class ExperimentSpec:
    task: str = "fcn"                  # one of repro.fl.models.TASK_MODELS
    alpha: float = 1.0                 # Dirichlet concentration
    num_samples: int = 12_000
    num_classes: int = 10
    dim: int = 64                      # feature dim; seq_len for task="lm"
    test_frac: float = 0.2
    fl: FLConfig = dataclasses.field(default_factory=FLConfig)
    data_seed: int = 0
    adapter_hops: bool = True          # hop the trainable-adapter view when
                                       # the task has one (TaskModel.split);
                                       # full-params tasks are untouched
                                       # (identity view, bit-identical runs)

    def __post_init__(self):
        # Validate at construction — a bad task/dim otherwise surfaces as
        # a shape error deep inside the round loop.
        if self.task not in TASK_MODELS:
            raise ValueError(f"unknown task {self.task!r}; expected one of "
                             f"{TASK_MODELS}")
        if self.task == "cnn":
            side = int(self.dim ** 0.5)
            if side * side != self.dim:
                raise ValueError(f"task='cnn' needs a square feature dim "
                                 f"(got dim={self.dim})")
        if self.task == "lstm" and self.dim % 8 != 0:
            raise ValueError(f"task='lstm' needs dim divisible by 8 "
                             f"(got dim={self.dim})")


def load_experiment_data(spec: ExperimentSpec, with_loaders: bool = True):
    """Dataset → split → Dirichlet partition → loaders for one cell.

    The single definition of the ``data_seed`` RNG consumption order, shared
    by :func:`run_experiment`, the replicate engines and the sweep
    pre-planner — so a cell's DSIs (and therefore its plan-cache keys) are
    identical no matter which engine computes them.

    Returns ``(train, test, part, loaders)``.  ``with_loaders=False`` skips
    loader construction (loaders draw from their own seed, so the partition
    is unaffected) — the sweep pre-planner only needs ``part``.
    """
    rng = np.random.default_rng(spec.data_seed)
    if spec.task == "lm":
        # Token rows: spec.dim is the sequence length, one "sample" is one
        # document; labels are the dominant-token buckets that drive the
        # Dirichlet partition (non-IID unigram shards per client).
        from repro.fl.models import LM_VOCAB
        tokens = lm_corpus(spec.num_samples * spec.dim, vocab=LM_VOCAB,
                           seed=spec.data_seed)
        y = class_labels_for_lm(tokens, spec.num_classes, spec.dim)
        x = np.asarray(tokens[:len(y) * spec.dim]).reshape(len(y), spec.dim)
        ds = ImageDataset(x.astype(np.int32), y, spec.num_classes)
    else:
        ds = gaussian_image_dataset(spec.num_samples, spec.num_classes,
                                    spec.dim, seed=spec.data_seed)
    test, train = ds.split(spec.test_frac, rng)
    part = dirichlet_partition(train.y, spec.fl.num_clients, spec.alpha, rng)
    loaders = (make_client_loaders(train, part, spec.fl.batch_size,
                                   seed=spec.data_seed)
               if with_loaders else None)
    return train, test, part, loaders


def spec_model_bits(spec: ExperimentSpec) -> float:
    """S (Eq. 15) for a cell's task model without materializing weights —
    shapes come from ``jax.eval_shape`` on the model's init."""
    from repro.core.aggregation import model_bits
    model = build_task_model(spec.task, spec.dim, spec.num_classes)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return model_bits(shapes, spec.fl.bits_per_param)


def spec_adapter_bits(spec: ExperimentSpec) -> float:
    """S (Eq. 15) of one *D2D hop* for a cell — the companion of
    :func:`spec_model_bits` (which stays the full-model figure).

    The hop payload is the trainable-adapter view when the task has one and
    ``spec.adapter_hops`` is set, and it crosses the wire int8-packed
    (8 bits/element + one fp32 scale per row-block) when
    ``spec.fl.hop_quant == "int8"``; full-params fp32 cells return exactly
    :func:`spec_model_bits`."""
    from repro.core.aggregation import model_bits
    from repro.fl.adapters import packed_bits
    model = build_task_model(spec.task, spec.dim, spec.num_classes)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if spec.adapter_hops and model.split is not None:
        _, shapes = model.split(shapes)
    if spec.fl.hop_quant == "int8":
        return packed_bits(shapes)
    return model_bits(shapes, spec.fl.bits_per_param)


def run_experiment(spec: ExperimentSpec, plan_cache=None,
                   checkpoint_dir: str | None = None) -> FLResult:
    """Run one cell of a paper figure/table.

    ``plan_cache`` (a :class:`repro.core.diffusion.PlanCache`) is forwarded
    to the FL runtime; combined with ``spec.fl.topology_seed`` it lets the
    sweep orchestrator replay host-side diffusion plans across replicate
    seeds instead of re-running the auction loop per seed.
    ``spec.fl.engine`` selects the data plane (``"host"`` per-slot
    reference loop, ``"fleet"`` client-stacked vmap, …) — schedules and
    ledger charges are identical either way.

    ``checkpoint_dir`` + ``spec.fl.checkpoint_every > 0`` makes the cell
    durable: a :class:`~repro.fl.resume.RoundCheckpointer` serializes round
    state every R rounds (including the per-client loader shuffle cursors,
    so a resumed run replays the exact same batch order) and resumes from
    the latest readable checkpoint in that directory.
    """
    train, test, part, loaders = load_experiment_data(spec)
    model = build_task_model(spec.task, spec.dim, spec.num_classes)
    # The executors train/hop the view's payload tree: the trainable
    # adapter for split tasks, the full params (identity view — unwrapped
    # model.init/model.loss, bit-identical traces) otherwise.
    from repro.fl.adapters import make_adapter_view
    view = make_adapter_view(model, spec.fl, adapter_hops=spec.adapter_hops)

    checkpointer = None
    if checkpoint_dir is not None and spec.fl.checkpoint_every > 0:
        from repro.fl.resume import RoundCheckpointer

        def _capture():
            return {"loader_epochs": [ld.epochs_drawn for ld in loaders]}

        def _restore(extra):
            for ld, e in zip(loaders, extra["loader_epochs"]):
                ld.seek(int(e))

        checkpointer = RoundCheckpointer(checkpoint_dir,
                                         every=spec.fl.checkpoint_every,
                                         capture_extra=_capture,
                                         restore_extra=_restore)

    def client_epoch(i):
        return lambda: list(loaders[i].epoch())

    batches = [client_epoch(i) for i in range(spec.fl.num_clients)]

    @jax.jit
    def _eval(params):
        full = view.merge_fn(params)
        acc = model.accuracy(full, test.x, test.y)
        loss = model.loss(full, {"x": test.x, "y": test.y})
        return acc, loss

    def eval_fn(params):
        a, l = _eval(params)
        return float(a), float(l)

    value_fn = None
    if spec.fl.uncertainty_weight > 0.0:
        # Learning-value probe: a fixed 32-sample draw from each client's
        # shard (np.resize wraps small shards); the value is the global
        # model's mean predictive entropy on it, normalized to [0, 1] by
        # log of the class count.  High entropy = data the model is still
        # uncertain about = a shard worth routing models toward — the
        # signal the planner fuses into its bids (kernels.bid_value_fuse).
        import jax.numpy as jnp
        probe = np.stack([train.x[np.resize(idx, 32)]
                          for idx in part.indices])

        @jax.jit
        def _values(params):
            full = view.merge_fn(params)

            def one(x):
                lg = model.logits(full, x)
                logp = jax.nn.log_softmax(lg, axis=-1)
                ent = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
                return jnp.mean(ent) / jnp.log(lg.shape[-1])

            return jax.vmap(one)(probe)

        def value_fn(params):
            return np.asarray(_values(params))

    return run_federated(view.init_fn, view.loss_fn, batches, part.dsi,
                         part.data_sizes, eval_fn, spec.fl,
                         plan_cache=plan_cache, checkpointer=checkpointer,
                         base_bits=view.base_bits, value_fn=value_fn)
