"""Mean admit-to-dispatch wait in the front end's queue, from its
frontend.queue_wait_s histogram over the window (ms)."""

from kdebench import layers


def read(ctx):
    return layers.hist_mean(ctx, "frontend.queue_wait_s", 1e3)
