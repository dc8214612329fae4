"""Prices: one random walk per key, continuous through every pool chunk.

``{"kind": "random_walk", "start", "step_sigma"}``: the walk starts at
``start`` and moves by a normal step of ``step_sigma`` a tick."""
import numpy as np


def draw(rng, spec: dict, shape) -> np.ndarray:
    P, K, S = shape
    steps = rng.standard_normal((K, P * S), dtype=np.float32)
    steps *= np.float32(spec["step_sigma"])
    path = np.float32(spec["start"]) + np.cumsum(steps, axis=1,
                                                 dtype=np.float64)
    return np.ascontiguousarray(
        path.astype(np.float32).reshape(K, P, S).transpose(1, 0, 2))
