"""The traffic generator: token rows from the seed alone, heavy-tailed and
skewed by each client's class mix; and the image mixes and the earlier
sub-seeds exactly as they were before token rows and the ``"frozen"``
stream came."""
import hashlib
import json
import os

import numpy as np
import pytest

from perf.traffic.generate import STREAMS, load_mix, make_traffic, sub_seeds

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2 ** 31 + 2718          # more than 32 signed bits hold
TOKENS = {"kind": "tokens", "vocab": 128, "seq": 16, "classes": 4}
MIX = {"clients": 8, "rows_per_client": 256, "alpha": 0.1, "test_rows": 64}
# Both image mixes at SEED, and the sub-seeds at SEED, as the generator
# drew them before it knew token rows.
IMAGE_DIGEST = \
    "9b75ddaab9c33522c6cd40c0f06620d6fc08e83343a7d1c3452b5fe5fcfdb59c"
SUB_SEEDS = {"data": 265795134, "loader": 325071553, "topology": 745597393,
             "init": 1071669865}


def _digest(t) -> str:
    h = hashlib.sha256()
    for a in (t.train.x, t.train.y, t.test_x, t.test_y, t.part.dsi,
              t.part.data_sizes, t.counts, *t.part.indices):
        a = np.ascontiguousarray(a)
        h.update(str((a.dtype.str, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _freqs(rows: np.ndarray, vocab: int) -> np.ndarray:
    return np.bincount(rows.ravel(), minlength=vocab) / rows.size


def test_token_rows_repeat_with_the_seed():
    a, b = make_traffic(MIX, TOKENS, SEED), make_traffic(MIX, TOKENS, SEED)
    assert _digest(a) == _digest(b)
    assert _digest(make_traffic(MIX, TOKENS, SEED + 1)) != _digest(a)
    assert a.train.x.dtype == np.int32
    assert a.train.x.shape == (8 * 256, 17) and a.test_x.shape == (64, 17)
    assert a.train.y.shape == (8 * 256,)
    assert (a.counts.sum(axis=1) == 256).all()


def test_token_ids_cover_the_vocabulary():
    t = make_traffic(MIX, TOKENS, SEED)
    ids = np.concatenate([t.train.x.ravel(), t.test_x.ravel()])
    assert ids.min() == 0 and ids.max() == 127
    assert len(np.unique(ids)) == 128
    # Heavy-tailed: a topic's commonest id is far above the uniform 1/128.
    topic = t.train.x[t.train.y == t.train.y[0]]
    assert _freqs(topic, 128).max() > 8 / 128


def test_clients_of_different_class_mixes_differ_in_token_frequencies():
    t = make_traffic(MIX, TOKENS, SEED)
    rows = [t.train.x[ix] for ix in t.part.indices]
    freqs = [_freqs(r, 128) for r in rows]
    mix_gap = np.abs(t.part.dsi[:, None] - t.part.dsi[None]).sum(-1) / 2
    i, j = np.unravel_index(np.argmax(mix_gap), mix_gap.shape)
    assert mix_gap[i, j] > 0.9
    apart = np.abs(freqs[i] - freqs[j]).sum() / 2
    # Two halves of one client's rows differ by sampling noise alone.
    halves = [np.abs(_freqs(r[:128], 128) - _freqs(r[128:], 128)).sum() / 2
              for r in rows]
    assert apart > 2 * max(halves), (apart, halves)


@pytest.mark.parametrize("mix", ["feddif_n256", "fedavg_n256"])
def test_image_mixes_draw_what_they_drew_before_token_rows(mix):
    with open(os.path.join(HERE, "configs", "cnn_fmnist.json")) as f:
        data = json.load(f)["data"]
    assert _digest(make_traffic(load_mix(mix), data, SEED)) == IMAGE_DIGEST


def test_sub_seeds_keep_their_values():
    got = sub_seeds(SEED)
    assert STREAMS[:len(SUB_SEEDS)] == tuple(SUB_SEEDS)
    assert {k: got[k] for k in SUB_SEEDS} == SUB_SEEDS
    assert set(got) == set(STREAMS)
    assert len(set(got.values())) == len(STREAMS)
    assert all(0 <= v < 2 ** 31 for v in got.values())
