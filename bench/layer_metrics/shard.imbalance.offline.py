"""Slowest chip's pruned kernel device time over the chips' mean, less one,
per sharded pass, averaged over the window's passes (%)."""

from kdebench import sharding


def read(ctx):
    return sharding.imbalance(ctx)
