"""Column tiles visited over total, from the occupancy of the program's
kernels.pruned_* spans, weighted by rows (%)."""

from kdebench import layers


def read(ctx):
    return layers.visit_fraction(ctx)
