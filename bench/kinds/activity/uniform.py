"""Which (key, tick) cells carry an event: a share ``p``, placed uniformly.

``{"kind": "uniform", "p"}``: every pool chunk holds exactly
``round(p * keys * span)`` events at random cells, so every seed offers
the same work and only its placement differs."""
import numpy as np


def _chunk(rng, keys: int, span: int, p: float) -> np.ndarray:
    n_cells = keys * span
    n = int(round(p * n_cells))
    if n >= n_cells:
        return np.ones((keys, span), bool)
    r = rng.random(n_cells)
    if n == 0:
        return np.zeros((keys, span), bool)
    cut = np.partition(r, n)[n]
    return (r < cut).reshape(keys, span)


def draw(rng, spec: dict, shape) -> np.ndarray:
    P, K, S = shape
    return np.stack([_chunk(rng, K, S, spec["p"]) for _ in range(P)])
