"""XLA programs compiled or loaded from the persistent cache inside the
window, counted by a jax.monitoring listener."""

from kdebench import layers


def read(ctx):
    return layers.compiles(ctx)
