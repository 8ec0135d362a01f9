"""Plain-jnp oracle for the conv filter-gradient kernel."""
import jax.numpy as jnp


def conv_dw_ref(p: jnp.ndarray, dy: jnp.ndarray) -> jnp.ndarray:
    """p: (B, K, N), dy: (B, N, O) -> sum_b p[b] @ dy[b], (K, O)."""
    return jnp.einsum("bkn,bno->ko", p, dy)
