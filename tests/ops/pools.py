"""KV page pools for the attention tests."""

import jax.numpy as jnp


def stacked_pool(pool, layer, num_layers=3):
    """The paged decode paths' operand: `pool` ([P, PS, K, D] values, [P, PS,
    K] scales, or a quantized {"q", "s"} pair of them) as layer `layer` of a
    pool stacked over layers. Every other layer is poison — NaN values,
    saturated int8 values, 1e30 scales — so reading the wrong layer cannot
    pass a parity check."""
    if isinstance(pool, dict):
        return {name: stacked_pool(member, layer, num_layers)
                for name, member in pool.items()}
    if pool.dtype == jnp.int8:
        poison = 127
    else:
        poison = 1e30 if pool.ndim == 3 else jnp.nan
    layers = [jnp.full_like(pool, poison)] * num_layers
    layers[layer] = pool
    return jnp.stack(layers)
