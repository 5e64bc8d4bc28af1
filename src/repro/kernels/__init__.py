"""Pallas TPU kernels of the serving path, each with a jnp reference."""
