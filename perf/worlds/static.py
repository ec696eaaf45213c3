"""The static scenario's channels (Sec. VI-A): every round places the N
clients uniformly on a disc of radius 250 m around the base station, then
draws Rayleigh fading for the uplinks and for each D2D channel draw,
from ``default_rng([topology_seed, t])`` in that order."""
from __future__ import annotations

import numpy as np

from perf import reference as R

RADIUS_M = 250.0


def round_channels(topology_seed: int, t: int, n: int, d2d_rounds: int):
    """Round ``t``'s uplink efficiencies (floored) and ``d2d_rounds``
    (N, N) D2D efficiency draws, in the order the round draws them."""
    rng = np.random.default_rng([topology_seed, t])
    r = RADIUS_M * np.sqrt(rng.uniform(size=n))
    theta = rng.uniform(0.0, 2 * np.pi, size=n)
    pos = np.stack([r * np.cos(theta), r * np.sin(theta)], axis=-1)
    d_up = np.maximum(np.linalg.norm(pos, axis=-1), 1.0)
    up = np.maximum(R.efficiency(R.pathloss(d_up)
                                 * rng.exponential(1.0, size=n)),
                    R.GAMMA_FLOOR)
    dist = np.linalg.norm(pos[:, None, :] - pos[None, :, :], axis=-1)
    np.fill_diagonal(dist, 1.0)
    d2d = [R.efficiency(R.pathloss(dist)
                        * rng.exponential(1.0, size=dist.shape))
           for _ in range(d2d_rounds)]
    return up, d2d
