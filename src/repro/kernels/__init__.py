"""Pallas TPU kernels for the serving hot spots (paged decode, flash
prefill, the RG-LRU scan), their pure-jnp oracles (``ref``) and the
shape-routed dispatch layer (``ops``)."""

import jax


def default_interpret() -> bool:
    """Kernels run compiled on a TPU and in interpret mode elsewhere."""
    return jax.default_backend() != "tpu"
