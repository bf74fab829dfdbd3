"""Inputs whose float32 matmuls are exact in any summation order."""
import jax
import jax.numpy as jnp


def grid_normal(key, shape):
    """Normals rounded to a 1/8 grid: every dot product of such vectors
    is exact in float32, so tiled, chunked and dense matmuls agree bit
    for bit whatever order they sum in — id/tie-order comparisons then
    test selection, not summation order."""
    return jnp.round(jax.random.normal(key, shape) * 8) / 8
