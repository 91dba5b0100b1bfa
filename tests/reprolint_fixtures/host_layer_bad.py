"""Violating: JAX imports in a host-layer (scheduler-shaped) module."""
import jax                       # EXPECT: host-layer-jax
import jax.numpy as jnp          # EXPECT: host-layer-jax
from jax import lax              # EXPECT: host-layer-jax


def nested():
    from jax import shard_map  # EXPECT: host-layer-jax
    return shard_map


def decide(queue):
    return jnp.argmin(jax.numpy.asarray(queue)), lax
