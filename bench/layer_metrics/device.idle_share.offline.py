"""Share of the window in which no operation ran on the device (%)."""

from kdebench import layers


def read(ctx):
    return layers.idle_share(ctx)
