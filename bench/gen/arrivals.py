"""Seeded Poisson arrival counts, copied from the program's
``repro.serving.traces.poisson_trace`` so that a later change there does not
move the benchmark's traffic. The program receives ``Trace`` objects only."""
from __future__ import annotations

import numpy as np

_POISSON_TAG = 101


def poisson_counts(shares, *, rate: float, horizon: int, seed: int) -> np.ndarray:
    """``counts[t, c] ~ Poisson(rate * share[c])``: ``rate`` is the fleet's
    mean arrivals per logical cycle, split by the hospitals' shares."""
    w = np.asarray(shares, np.float64)
    lam = rate * w / w.sum()
    rng = np.random.default_rng((int(seed), _POISSON_TAG))
    return rng.poisson(lam[None, :], size=(horizon, len(w)))


def shuffled_counts(shares, *, rate: float, horizon: int, counts_seed: int,
                    seed: int) -> np.ndarray:
    """The same ``horizon`` cycles of Poisson counts for every ``seed``,
    drawn once from ``counts_seed``, in an order drawn from ``seed``: every
    run and every call serves the same requests, in another order."""
    counts = poisson_counts(shares, rate=rate, horizon=horizon, seed=counts_seed)
    order = np.random.default_rng((int(seed), _POISSON_TAG)).permutation(horizon)
    return counts[order]


def requests(counts: np.ndarray):
    """``[(req_id, client, cycle), ...]`` in (cycle, client, draw) order."""
    out, rid = [], 0
    for t in range(counts.shape[0]):
        for c in range(counts.shape[1]):
            for _ in range(int(counts[t, c])):
                out.append((rid, c, t))
                rid += 1
    return out
