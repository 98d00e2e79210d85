"""Near miss: the same request under jax.enable_x64."""
import jax
import jax.numpy as jnp


def widen(x):
    with jax.enable_x64(True):
        return jnp.asarray(x, dtype=jnp.float64)
