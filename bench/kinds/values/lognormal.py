"""Transaction amounts: lognormal, with a share of injected outliers.

``{"kind": "lognormal", "mu", "sigma", "spike_p", "spike_mult"}``: each
value is ``exp(mu + sigma * z)``; with probability ``spike_p`` it is
multiplied by ``spike_mult``."""
import numpy as np


def draw(rng, spec: dict, shape) -> np.ndarray:
    z = rng.standard_normal(shape, dtype=np.float32)
    amt = np.exp(np.float32(spec["mu"]) + np.float32(spec["sigma"]) * z)
    spike = rng.random(shape, dtype=np.float32) < spec["spike_p"]
    amt[spike] *= np.float32(spec["spike_mult"])
    return amt
