"""Score kernel (flash_score, flash_pruned): least time for the pairs it
evaluated over its device time, naming the bound (%)."""

from kdebench import layers


def read(ctx):
    return layers.kernel_roofline(ctx, layers.SCORE)
