"""The one traffic generator.  A traffic mix is a data file beside this one
(``<mix>.json``: strategy, fleet size, engine, batch, learning rate, rows
per client, Dirichlet alpha, ...); a configuration's file says what a row
is: an image of ``dim`` features (``data.kind`` ``"image"``), or
``seq + 1`` token ids from a vocabulary of ``vocab`` (``"tokens"``).  From
``--seed`` this module makes every array the program receives: the training
rows split over the clients, and the held-out test rows.

The image generator and the partition are copied from the program
(``repro.data.synthetic`` and ``repro.data.partitioner``) so that a change
to the program cannot move the yardstick; the token generator is the
benchmark's own.  One departure, for steady work from seed to seed: every
client holds exactly ``rows_per_client`` rows.  The Dirichlet(alpha) draw
sets each client's class mixture, and the counts are its largest-remainder
rounding; the rows of each class are then generated, not drawn from a
shared pool, so no class runs short.
"""
from __future__ import annotations

import dataclasses
import json
import os
from types import SimpleNamespace

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# Named sub-streams of one run's seed.  Each is a 31-bit integer, so the
# program may add small offsets to it (its loaders use seed + 1000 i + k).
# A stream is only ever appended: ``generate_state`` keeps the words of the
# earlier streams as its prefix, so their values never change.
STREAMS = ("data", "loader", "topology", "init", "frozen")


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, f"{name}.json")) as f:
        return json.load(f)


def sub_seeds(seed: int) -> dict:
    words = np.random.SeedSequence(int(seed)).generate_state(len(STREAMS))
    return {k: int(w) >> 2 for k, w in zip(STREAMS, words)}


@dataclasses.dataclass
class Traffic:
    train: SimpleNamespace      # x, y: the rows the loaders slice
    test_x: np.ndarray
    test_y: np.ndarray
    part: SimpleNamespace       # indices, dsi, data_sizes (per client)
    counts: np.ndarray          # (N, C) rows of each class per client


def client_class_counts(n_clients: int, classes: int, rows: int,
                        alpha: float, rng: np.random.Generator) -> np.ndarray:
    """(N, C) integer class counts, each row summing to ``rows``: a
    Dirichlet(alpha) mixture per client, rounded by largest remainder."""
    mix = rng.dirichlet(np.full(classes, alpha), size=n_clients)
    want = mix * rows
    counts = np.floor(want).astype(np.int64)
    short = rows - counts.sum(axis=1)
    order = np.argsort(-(want - counts), axis=1, kind="stable")
    for i in range(n_clients):
        counts[i, order[i, :short[i]]] += 1
    return counts


# ----------------------------------------------------------------- images

def gaussian_image_model(classes: int, dim: int, separation: float,
                         rng: np.random.Generator):
    """Class means and the shared nonlinear warp of
    ``repro.data.synthetic.gaussian_image_dataset``."""
    means = rng.normal(size=(classes, dim)) * separation
    warp = rng.normal(size=(dim, dim)) / np.sqrt(dim)
    return means, warp


def gaussian_image_rows(labels: np.ndarray, means: np.ndarray,
                        warp: np.ndarray, noise: float,
                        rng: np.random.Generator) -> np.ndarray:
    x = means[labels] + rng.normal(size=(len(labels), means.shape[1])) * noise
    x = np.tanh(x @ warp) + 0.1 * x
    return x.astype(np.float32)


# ----------------------------------------------------------------- tokens

# Zipf exponent of a topic's noise and the share of tokens that follow its
# rule: those of the program's ``repro.data.synthetic.lm_corpus``.
ZIPF, FOLLOW = 1.1, 0.5


def topic_model(classes: int, vocab: int, rng: np.random.Generator):
    """Per topic (class): a successor of every id, the first-order rule,
    and a ranking of the vocabulary that its Zipf noise follows; and the
    noise's cumulative distribution over ranks."""
    succ = np.stack([rng.permutation(vocab) for _ in range(classes)])
    rank = np.stack([rng.permutation(vocab) for _ in range(classes)])
    cdf = np.cumsum(1.0 / np.arange(1, vocab + 1) ** ZIPF)
    return succ, rank, cdf / cdf[-1]


def token_rows(labels: np.ndarray, succ: np.ndarray, rank: np.ndarray,
               cdf: np.ndarray, seq: int,
               rng: np.random.Generator) -> np.ndarray:
    """(rows, seq + 1) int32 ids: each row's first id is its topic's Zipf
    noise; each next one follows the topic's rule with probability
    ``FOLLOW``, and is fresh noise otherwise."""
    n = len(labels)
    ranks = np.minimum(np.searchsorted(cdf, rng.random((n, seq + 1)),
                                       side="right"), len(cdf) - 1)
    noise = rank[labels[:, None], ranks]
    keep = rng.random((n, seq)) < FOLLOW
    out = np.empty((n, seq + 1), np.int32)
    out[:, 0] = noise[:, 0]
    for t in range(1, seq + 1):
        out[:, t] = np.where(keep[:, t - 1], succ[labels, out[:, t - 1]],
                             noise[:, t])
    return out


def row_width(data: dict) -> int:
    """Features (images) or token ids (tokens) in one row."""
    return int(data["dim"]) if data["kind"] == "image" else \
        int(data["seq"]) + 1


# ------------------------------------------------------------------- mix

def _labels_by_client(counts: np.ndarray, rng: np.random.Generator):
    labels = []
    for row in counts:
        lab = np.repeat(np.arange(len(row)), row)
        labels.append(lab[rng.permutation(len(lab))])
    return labels


def _balanced_labels(n: int, classes: int,
                     rng: np.random.Generator) -> np.ndarray:
    lab = np.arange(n) % classes
    return lab[rng.permutation(n)]


def make_traffic(mix: dict, data: dict, seed: int) -> Traffic:
    """Every array of one run, from ``seed`` alone."""
    rng = np.random.default_rng(sub_seeds(seed)["data"])
    n, rows = int(mix["clients"]), int(mix["rows_per_client"])
    classes = int(data["classes"])
    counts = client_class_counts(n, classes, rows, float(mix["alpha"]), rng)
    client_labels = _labels_by_client(counts, rng)
    train_y = np.concatenate(client_labels).astype(np.int64)
    test_y = _balanced_labels(int(mix["test_rows"]), classes,
                              rng).astype(np.int64)
    if data["kind"] == "image":
        means, warp = gaussian_image_model(classes, int(data["dim"]),
                                           float(data["separation"]), rng)
        noise = float(data["noise"])
        train_x = gaussian_image_rows(train_y, means, warp, noise, rng)
        test_x = gaussian_image_rows(test_y, means, warp, noise, rng)
    elif data["kind"] == "tokens":
        succ, rank, cdf = topic_model(classes, int(data["vocab"]), rng)
        seq = int(data["seq"])
        train_x = token_rows(train_y, succ, rank, cdf, seq, rng)
        test_x = token_rows(test_y, succ, rank, cdf, seq, rng)
    else:
        raise ValueError(f"unknown data kind {data['kind']!r}")
    indices = [np.arange(i * rows, (i + 1) * rows) for i in range(n)]
    part = SimpleNamespace(indices=indices,
                           dsi=(counts / rows).astype(np.float32),
                           data_sizes=np.full(n, float(rows)))
    return Traffic(train=SimpleNamespace(x=train_x, y=train_y),
                   test_x=test_x, test_y=test_y, part=part, counts=counts)
