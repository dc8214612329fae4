"""Pallas TPU kernels (window reductions, fused change detection), their
jnp oracles (:mod:`.ref`) and the backend dispatch (:mod:`.ops`)."""
